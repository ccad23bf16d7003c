"""One run of one cell: set-up, the measured or the traced window, the
verdict, the last line.

The loop is the one GluonNLP's pretraining script runs: dispatch a step
for each batch as fast as the program takes them, and every
``log_interval`` steps read the losses back, which is the only place the
host waits for the device and the only place the clock is looked at.
"""
import glob
import json
import math
import os
import shutil
import sys
import time
import types

from chipbench import hlo, manifest, program, tokens, xplane

TAG = '[chipbench]'
# the traced run profiles this many log intervals, so one read and the
# refill after it lie inside the traced window
TRACED_INTERVALS = 2


def say(msg):
    print(f"{TAG} {msg}", flush=True)


class CompileCounter:
    """Backend compile requests jax itself reports, served from the
    persistent cache or not, whoever made them."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.n += 1


class Loop:
    """Steps dispatched and losses read so far, counted from the first
    step of the process."""

    def __init__(self, step, ring, log_interval):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.step, self.ring, self.log_interval = step, ring, log_interval
        self.losses = []            # float per step, in order
        self.failed = 0
        self.error = None
        self._pending = []

    @property
    def dispatched(self):
        return len(self.losses) + len(self._pending)

    def _read(self):
        with self._annotate('chipbench.read_loss'):
            values = [float(x.asscalar()) for x in self._pending]
        self._pending = []
        self.failed += sum(not math.isfinite(v) for v in values)
        self.losses.extend(values)

    def interval(self, steps=None):
        """Dispatch ``steps`` (default: one log interval) and read them
        back. False when a step raised; the run then stops."""
        try:
            for _ in range(steps or self.log_interval):
                with self._annotate('chipbench.feed'):
                    inputs, labels = self.ring[self.dispatched
                                               % len(self.ring)]
                with self._annotate('chipbench.dispatch'):
                    self._pending.append(self.step(inputs, labels))
            self._read()
        except Exception as e:    # a step that raises is a failed step
            self.error = f"{type(e).__name__}: {e}"
            self.failed += 1
            say(f"step {self.dispatched} raised {self.error[:2000]}")
            return False
        return True


def peaks_for(kind):
    table = manifest.read_json(os.path.join(manifest.HERE, 'peaks.json'))
    if kind not in table:
        raise KeyError(
            f"device_kind {kind!r} is not in chipbench/peaks.json; add it "
            f"with its source before measuring on it")
    return table[kind]


def route_matches(declared, counted):
    return all(counted.get(k, 0) > 0 if want == 'positive'
               else counted.get(k, 0) == want
               for k, want in declared.items())


def memory_peak(devices):
    """(peak bytes, memory_stats) of the fullest chip. On this runtime
    ``peak_bytes_in_use`` counts the arrays a process holds and not what a
    running program takes for its temporaries; that is reserved apart and
    reported as ``peak_bytes_reserved`` (my chip run, PR 24: 2.40 and
    12.70 GB in bert_base.t512, whose step XLA plans with 12.77 GB of
    temporaries). The peak is their sum."""
    def peak(stats):
        return int(stats.get('peak_bytes_in_use', 0)
                   + stats.get('peak_bytes_reserved', 0))
    fullest = max((d.memory_stats() or {} for d in devices), key=peak)
    return peak(fullest), fullest


def byte_plan(compiled):
    """XLA's byte plan of a compiled program on one chip; the total is
    arguments + outputs - aliased + temporaries."""
    m = compiled.memory_analysis()
    plan = {k: int(getattr(m, k + '_size_in_bytes'))
            for k in ('argument', 'output', 'alias', 'temp',
                      'generated_code')}
    plan['total'] = plan['argument'] + plan['output'] - plan['alias'] \
        + plan['temp']
    return plan


def step_program(step):
    """(optimized HLO text, byte plan) of the step as the backend built it."""
    compiled = step.compiled_program()
    return compiled.as_text(), byte_plan(compiled)


def profiled_window(loop, out_dir):
    """Profile ``TRACED_INTERVALS`` log intervals; the newest .xplane.pb."""
    import jax
    trace_dir = os.path.join(out_dir, 'trace')
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # TraceAnnotation needs only the host tracer
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(TRACED_INTERVALS):
            if not loop.interval():
                break
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')),
        key=os.path.getmtime)
    return found[-1] if found else None


def set_up(args, cell, config, traffic, devices, out_dir, t0):
    """Everything before the window: telemetry, model, ring, reference
    check, the step compiled and warmed, and in a traced run the step's
    HLO text and byte plan. The seconds it took are ``setup_s``."""
    import jax
    family = cell.family

    def clock():
        return time.perf_counter() - t0

    cache_dir = program.start_telemetry()
    compiles = CompileCounter()
    routes_before = program.route_counts()
    program.seed(args.seed)
    model, loss_fn = family.build(config)
    model.hybridize()
    global_batch = traffic['per_chip_batch'] * cell.chips
    ring = tokens.make_ring(family, config, traffic, args.seed, global_batch)
    say(f"model built and ring of {len(ring)} batches of {global_batch} "
        f"made at {clock():.1f} s; compile cache {cache_dir}")

    reference = family.reference_check(
        model, program.weights_of(model), config, traffic,
        *tokens.source(family, config, traffic, args.seed, 0xC4EC))
    say(f"reference check at {clock():.1f} s: {json.dumps(reference)}")

    # the step is handed the model as examples/pretrain_bert.py hands it:
    # not hybridized (hybridized, tracing the step takes twice as long)
    model.hybridize(False)
    step = program.make_step(model, loss_fn, config, traffic, devices)
    loop = Loop(step, ring, traffic['log_interval'])
    alive = loop.interval(traffic['warmup_steps'])
    say(f"warm-up of {traffic['warmup_steps']} steps done at {clock():.1f} "
        f"s: losses " + ' '.join(f"{v:.4f}" for v in loop.losses))
    for e in program.compile_ledger():
        if e['site'] == program.STEP_SITE:
            say(f"step program compile: {json.dumps(e['seconds'])}, cache "
                f"{json.dumps(e.get('cache'))}")
    text = plan = None
    if args.trace and alive:
        text, plan = step_program(step)
        with open(os.path.join(out_dir, 'step_program.hlo.txt'), 'w') as f:
            f.write(text)
        say(f"XLA's byte plan a chip: {json.dumps(plan)}")
    routes = {k: v - routes_before[k]
              for k, v in program.route_counts().items()}
    jax.block_until_ready([program.payload(p.data())
                           for p in model.collect_params().values()])
    return types.SimpleNamespace(
        loop=loop, alive=alive, reference=reference, routes=routes,
        compiles=compiles, text=text, plan=plan, global_batch=global_batch,
        ledger_open=len(program.compile_ledger()), compiles_open=compiles.n,
        warm=loop.dispatched, setup_s=clock())


def measured_window(ready, seconds, last_loss_step):
    """Run until ``seconds`` have passed and the last step whose loss is
    reported has been read; the window's length on the host clock."""
    loop = ready.loop
    t_open = time.perf_counter()
    while ready.alive and (time.perf_counter() - t_open < seconds
                           or loop.dispatched <= last_loss_step):
        ready.alive = loop.interval()
    return time.perf_counter() - t_open


def traced_window(ready, out_dir):
    """(the trace as loaded, the step's hlo.Program, the reduced trace),
    each None where there is none: no trace file, or a trace with no
    device op or no execution of the step in it."""
    if not ready.alive:
        return None, None, None
    path = profiled_window(ready.loop, out_dir)
    ready.alive = ready.loop.error is None
    if not path:
        return None, None, None
    say(f"trace written to {path}")
    events = xplane.load(path)
    for name, lines in events['planes']:
        say(f"trace plane {name}: lines {lines}")
    step = hlo.Program(ready.text)
    reduced = xplane.reduce(events, step)
    if reduced is None:
        say(f"the trace reduces to nothing: no op on a device plane, or no "
            f"execution of {step.module!r} among its XLA Modules")
    return events, step, reduced


def run(args, t0):
    cell = manifest.resolve(args.workload)
    family, config, traffic = cell.family, cell.config, cell.traffic
    rehearsal = args.rehearse
    say(f"cell {cell.name}: config {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']}, {cell.chips} chip(s), seed {args.seed}, "
        f"{args.seconds:g} s, trace {args.trace}"
        + (' REHEARSAL (CPU, toy size: counts only, no metric)'
           if rehearsal else ''))

    import jax
    devices = jax.devices()
    say(f"jax imported and devices listed at "
        f"{time.perf_counter() - t0:.1f} s")
    platform, kind = devices[0].platform, devices[0].device_kind
    want = 'cpu' if rehearsal else 'tpu'
    if platform != want or len(devices) != cell.chips:
        print(f"{TAG} jax reports {len(devices)} {platform!r} device(s) "
              f"({kind}); this run needs {cell.chips} of platform "
              f"{want!r}. Nothing was built.", file=sys.stderr)
        return 2
    peaks = None if rehearsal else peaks_for(kind)
    if rehearsal:
        config = family.tiny(config)
        traffic = dict(traffic, per_chip_batch=2,
                       seq_len=min(traffic['seq_len'], 128),
                       loss_margin=None)
    out_dir = args.out or os.path.join(manifest.ROOT, 'chiprun_out',
                                       cell.name)
    if args.trace:      # only a traced run leaves files
        os.makedirs(out_dir, exist_ok=True)
    first, last = traffic['loss_steps']

    ready = set_up(args, cell, config, traffic, devices, out_dir, t0)
    loop = ready.loop
    events = step = reduced = None
    if args.trace:
        events, step, reduced = traced_window(ready, out_dir)
        # then on, untraced, until the loss the run is judged by is read
        measured_window(ready, 0.0, last)
    else:
        window_s = measured_window(ready, args.seconds, last)
    attempted = loop.dispatched - ready.warm

    # ---- the verdict ------------------------------------------------------
    ledger_grew = len(program.compile_ledger()) - ready.ledger_open
    compiles_grew = ready.compiles.n - ready.compiles_open
    losses = loop.losses
    loss = sum(losses[first:last + 1]) / (last + 1 - first) \
        if len(losses) > last else None
    margin = traffic['loss_margin']
    fell = loss is not None and loss < losses[0] - (margin or 0.0)
    route_ok = rehearsal or route_matches(traffic['route'], ready.routes)
    correct = bool(
        ready.alive and loop.failed == 0 and ready.reference['ok'] and fell
        and route_ok and ledger_grew == 0 and compiles_grew == 0
        and (not args.trace or rehearsal or reduced is not None))
    say("losses read: " + ' '.join(f"{v:.4f}" for v in losses))
    say(f"mean loss of steps {first} to {last}: {loss}; of step {last} "
        f"alone: {losses[last] if loss is not None else None}")
    say(f"verdict: finite {loop.failed == 0}, reference "
        f"{ready.reference['ok']}, loss fell by the margin ({margin}) "
        f"{fell}, route {ready.routes} as declared {route_ok}, compilations "
        f"inside the window: ledger {ledger_grew}, jax {compiles_grew}")
    if margin is None:
        say("NOTE: this traffic file fixes no loss_margin yet; the loss "
            "only had to fall")
    peak, stats = memory_peak(devices)
    say(f"memory_stats of the fullest chip: {json.dumps(stats)}")
    cache = program.cache_stats()
    say(f"compile cache: {cache['hits']} hits, {cache['misses']} misses; "
        f"set-up {ready.setup_s:.2f} s")

    # ---- the last line ----------------------------------------------------
    result = {'correct': correct, 'attempted': attempted,
              'failed': loop.failed, 'metrics': {},
              'device': {'platform': platform, 'kind': kind,
                         'count': len(devices),
                         'memory_peak_bytes': peak}}
    if rehearsal:
        result['rehearsal'] = True
        result['counts'] = {
            'steps': loop.dispatched, 'losses_read': len(losses),
            'ring_batches': len(loop.ring), 'route': ready.routes,
            'ledger_entries': ready.ledger_open,
            'trace_file': events is not None,
            'device_planes_in_trace': reduced['chips'] if reduced else 0}
        print(json.dumps(result), flush=True)
        return 0
    if args.trace:
        seen = types.SimpleNamespace(
            cell=cell, config=config, traffic=traffic, family=family,
            peaks=peaks, trace=reduced, events=events, program=step,
            plan=ready.plan, ledger=program.compile_ledger(), cache=cache)
        values = {}
        for m in cell.per_layer:
            reader = manifest.load_module('layer_metrics', m['name'])
            values[m['name']] = reader.read(seen)
            if hasattr(reader, 'note'):     # what a number cannot say
                say(f"{m['name']}: {reader.note(seen)}")
        chosen = cell.per_layer
        if reduced:
            result['device']['busy_s'] = reduced['busy_s']
            result['device']['window_s'] = reduced['window_s']
            result['breakdown'] = reduced['breakdown']
            say("reduced trace: " + json.dumps(
                {k: v for k, v in reduced.items()
                 if k not in ('breakdown', 'per_chip')}))
    else:
        flops = family.flops_per_sample(config, traffic)
        rate = attempted * ready.global_batch / window_s / cell.chips
        say(f"{attempted} steps of {ready.global_batch} in {window_s:.3f} "
            f"s: {window_s / max(attempted, 1) * 1e3:.2f} ms a step, MFU "
            f"{100 * rate * flops / peaks['bf16_flops_per_s']:.2f} % "
            f"({rate:.2f} samples/s/chip x {flops / 1e9:.3f} GFLOP a "
            f"sample over {peaks['bf16_flops_per_s'] / 1e12:g} TFLOP/s)")
        values = {'samples_per_s_per_chip': rate, 'setup_s': ready.setup_s,
                  f'loss_steps_{first}_{last}': loss}
        chosen = cell.end_to_end
    for m in chosen:
        if values.get(m['name']) is not None:
            result['metrics'][m['name']] = {'value': values[m['name']],
                                            'unit': m['unit']}
    print(json.dumps(result), flush=True)
    return 0
