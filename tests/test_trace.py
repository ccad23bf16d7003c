"""Step-span tracing, per-step attribution and the crash-time flight
recorder (mxnet_tpu.telemetry.{trace,flight,attribution}).

Every dump produced here is validated by the same tools/check_trace.py
contract the driver runs standalone: one traceEvents array, balanced
B/E pairs per (pid, tid), sane timestamps — so chrome://tracing and
Perfetto render exactly what was measured.
"""
import json
import os
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, checkpoint, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import trace, flight, attribution
from mxnet_tpu.resilience import StepWatchdog, faults

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                'tools'))
import check_trace  # noqa: E402  (the standalone validator, imported)


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.disable()
    trace.set_ring_capacity(None)
    trace.clear()
    flight.get().clear()
    faults.disarm()
    yield
    trace.disable()
    trace.set_ring_capacity(None)
    trace.clear()
    flight.get().clear()
    faults.disarm()


def _names(events):
    return [e['name'] for e in events]


# ---------------------------------------------------------------------------
# span basics: nesting, balance, export validity
# ---------------------------------------------------------------------------

def test_nested_spans_export_balanced_chrome_events():
    trace.enable()
    with trace.span('io.batch'):
        with trace.span('io.decode', records=8):
            pass
        with trace.span('h2d.device_put'):
            pass
    evs = trace.chrome_events(metadata=True)
    assert check_trace.check_events(evs) == []
    bs = [e for e in evs if e['ph'] == 'B']
    assert _names(bs) == ['io.batch', 'io.decode', 'h2d.device_put']
    assert bs[1]['args'] == {'records': 8}
    # every event stamped with pid + the small sequential tid
    assert all(e['pid'] == os.getpid() for e in bs)
    assert all(e['tid'] == 1 for e in bs)
    meta = [e for e in evs if e['ph'] == 'M']
    assert meta and meta[0]['args']['name'] == 'MainThread'


def test_instant_and_complete_events():
    trace.enable()
    trace.instant('comm.all_gather', bytes=4096, count=2)
    trace.complete('xprof.matmul', ts_us=10.0, dur_us=5.0)
    evs = trace.chrome_events()
    assert check_trace.check_events(evs) == []
    phs = {e['name']: e['ph'] for e in evs}
    assert phs == {'comm.all_gather': 'i', 'xprof.matmul': 'X'}


def test_dump_is_loadable_standalone_trace(tmp_path):
    trace.enable()
    with trace.span('step.dispatch'):
        pass
    path = trace.dump(str(tmp_path / 'trace.json'))
    assert check_trace.check_file(path) == []
    doc = json.loads(open(path).read())
    assert isinstance(doc['traceEvents'], list)


def test_env_gates_declared():
    for var in ('MXTPU_TRACE', 'MXTPU_TRACE_RING', 'MXTPU_FLIGHT_STEPS',
                'MXTPU_FLIGHT_PATH'):
        assert var in mx.config.list_vars()


# ---------------------------------------------------------------------------
# disarmed cost: shared no-op, nothing allocated, nothing recorded
# ---------------------------------------------------------------------------

def test_disarmed_span_records_nothing_and_costs_under_5us():
    """Disarmed, a span is the profiler annotation alone: nothing of it
    reaches a ring or an aggregate, and with no profile running a use
    costs well under the 5 us the step's four spans may take."""
    assert not trace.enabled()

    def hot_loop(n):
        for i in range(n):
            with trace.span('hot.path', step=i):
                pass
    hot_loop(64)                       # warm any lazy interpreter state
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    hot_loop(2000)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, 'filename')
                if d.size_diff > 0)
    # nothing survives the loop: no events, no rings, no per-call litter
    assert grown < 4096, f"disarmed span path leaked {grown} bytes"
    assert trace.stats() == {'spans_total': 0, 'dropped_spans_total': 0,
                             'ring_depth': 0, 'threads': 0}
    assert trace.chrome_events() == []
    assert trace.drain_aggregates() == {}
    # the best of five: a loaded machine slows every one of them, so a
    # single reading would say more of the machine than of the span
    n = 20000
    best = min(_seconds(hot_loop, n) for _ in range(5))
    assert best / n < 5e-6, f"{best / n * 1e9:.0f} ns a disarmed span"


def _seconds(fn, *args):
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def _host_spans(trace_dir):
    """{line name: [(name, start_ns, end_ns)]} of the ``mxtpu.`` events
    on the host plane of the one profile under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(
        str(trace_dir), 'plugins', 'profile', '*', '*.xplane.pb'))
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    assert '/host:CPU' in planes, sorted(planes)
    out = {}
    for line in planes['/host:CPU'].lines:
        found = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in line.events if e.name.startswith('mxtpu.')]
        if found:
            out[line.name] = sorted(found, key=lambda e: e[1])
    return out


@pytest.mark.parametrize('armed', [False, True], ids=['off', 'on'])
def test_a_profile_holds_the_programs_spans_nested(tmp_path, armed):
    """A ``jax.profiler`` trace taken round two steps holds the step's
    own spans on the host plane, on the profile's clock, nested as the
    code nests them, whether or not this module's rings record."""
    import jax
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    net = nn.Dense(4, in_units=6)
    net.initialize(mx.init.Xavier())
    step = ShardedTrainStep(net, gluon.loss.L2Loss(), 'sgd',
                            {'learning_rate': 0.1},
                            mesh=make_mesh((1,), ('dp',)))
    batch = ([nd.array(onp.ones((2, 6), onp.float32))],
             [nd.array(onp.ones((2, 4), onp.float32))])
    step(*batch)                        # the compile stays outside
    if armed:
        trace.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        step(*batch)
        step(*batch).asscalar()
    finally:
        jax.profiler.stop_trace()
    lines = _host_spans(tmp_path)
    (spans,) = [v for v in lines.values()
                if any(n == 'mxtpu.step.dispatch' for n, _s, _e in v)]
    outer = [e for e in spans if e[0] == 'mxtpu.step.dispatch']
    assert len(outer) == 2
    for _name, start, end in outer:
        inside = {n for n, s, e in spans if start <= s and e <= end}
        assert {'mxtpu.h2d.batch_put', 'mxtpu.step.compiled',
                'mxtpu.step.gather'} <= inside, inside
    # and the rings hold them only when armed
    assert (trace.stats()['spans_total'] > 0) is armed


def test_disarmed_flight_recorder_is_noop(tmp_path):
    flight.record_step(1, loss=3.0)
    flight.note('fault', site='io.decode')
    assert flight.get().steps() == []
    assert flight.dump(path=str(tmp_path / 'f.json')) is None
    assert not (tmp_path / 'f.json').exists()


# ---------------------------------------------------------------------------
# ring overwrite: whole spans dropped, export stays balanced + counted
# ---------------------------------------------------------------------------

def test_ring_overwrite_drops_spans_but_export_stays_balanced():
    trace.set_ring_capacity(16)
    trace.clear()
    trace.enable()
    for i in range(100):
        with trace.span('step.dispatch', step=i):
            pass
    st = trace.stats()
    assert st['spans_total'] == 100
    assert st['dropped_spans_total'] > 0
    assert st['ring_depth'] <= 16
    evs = trace.chrome_events()
    assert check_trace.check_events(evs) == []
    # the surviving events are the NEWEST ones
    steps = [e['args']['step'] for e in evs
             if e['ph'] == 'B' and 'args' in e]
    assert steps and min(steps) > 80


def test_open_span_flushes_with_synthetic_close():
    trace.enable()
    span = trace.span('step.compiled')
    span.__enter__()                   # crash while inside the program
    evs = trace.chrome_events(flush_open=True)
    assert check_trace.check_events(evs) == []
    closes = [e for e in evs if e['ph'] == 'E'
              and e.get('args', {}).get('flushed')]
    assert len(closes) == 1 and closes[0]['name'] == 'step.compiled'
    assert trace.open_spans()[0]['name'] == 'step.compiled'
    span.__exit__(None, None, None)


# ---------------------------------------------------------------------------
# cross-thread interleaving: per-thread rings, deterministic merge
# ---------------------------------------------------------------------------

def test_dataloader_workers_and_checkpoint_writer_interleave(tmp_path):
    trace.enable()
    X = onp.random.RandomState(0).rand(64, 5).astype(onp.float32)
    dataset = gluon.data.ArrayDataset(nd.array(X), nd.array(X[:, 0]))
    loader = gluon.data.DataLoader(dataset, batch_size=8, num_workers=3)
    net = nn.Dense(2, in_units=5)
    net.initialize()
    mgr = checkpoint.CheckpointManager(str(tmp_path), params=net,
                                       async_save=True)
    for step, _batch in enumerate(loader):     # workers span io.worker_fetch
        mgr.save(step)                         # writer spans checkpoint.write
    mgr.wait()
    loader.close()

    evs = trace.chrome_events(metadata=True)
    assert check_trace.check_events(evs) == [], \
        "cross-thread spans corrupted the merged stream"
    by_thread = {}
    for e in evs:
        if e['ph'] in ('B', 'E'):
            by_thread.setdefault(e['tid'], []).append(e)
    assert len(by_thread) >= 3          # consumer + workers + ckpt writer
    for tid, tevs in by_thread.items():
        assert check_trace.check_events(tevs) == [], \
            f"per-thread stream for tid {tid} unbalanced"
    names = set(_names(evs))
    assert 'io.worker_fetch' in names
    assert 'checkpoint.write' in names and 'checkpoint.snapshot' in names
    # deterministic merge: exporting twice yields the identical stream
    assert evs == trace.chrome_events(metadata=True)
    # every traced thread got a thread_name metadata row
    meta_tids = {e['tid'] for e in evs if e['ph'] == 'M'}
    assert set(by_thread) <= meta_tids


def test_tids_are_small_sequential_and_stable():
    trace.enable()
    seen = {}
    barrier = threading.Barrier(4)      # all alive at once: no ident reuse

    def work(k):
        barrier.wait(timeout=10)
        with trace.span('t.span'):
            seen[k] = trace.tid_for_current_thread()
        barrier.wait(timeout=10)
    ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with trace.span('t.span'):
        main_tid = trace.tid_for_current_thread()
    tids = set(seen.values()) | {main_tid}
    assert len(tids) == 5               # one per thread
    assert tids <= set(range(1, 32))    # small ints, not raw idents
    assert main_tid == trace.tid_for_current_thread()  # stable


# ---------------------------------------------------------------------------
# telemetry contract: mxnet_tpu_trace_* metrics
# ---------------------------------------------------------------------------

def test_trace_metrics_contract(tmp_path):
    telemetry.enable()
    telemetry.reset()
    try:
        trace.set_ring_capacity(16)
        trace.clear()
        trace.enable()
        for i in range(40):
            with trace.span('step.dispatch'):
                pass
        flight.record_step(1)
        flight.record_step(2)
        assert flight.dump(path=str(tmp_path / 'f.json')) is not None
        trace.chrome_events()
        assert telemetry.value('mxnet_tpu_trace_spans_total') == 40
        assert telemetry.value('mxnet_tpu_trace_dropped_spans_total') > 0
        assert telemetry.value('mxnet_tpu_trace_ring_depth') <= 16
        assert telemetry.value('mxnet_tpu_trace_flight_dumps_total') == 1
        # counters are monotonic across repeated syncs (deltas, not sets)
        trace.chrome_events()
        assert telemetry.value('mxnet_tpu_trace_spans_total') == 40
    finally:
        telemetry.reset()
        telemetry.disable()


# ---------------------------------------------------------------------------
# flight recorder: step records, deferred loss, dumps
# ---------------------------------------------------------------------------

class _Loss:
    """A device scalar as the recorder sees one: it says whether its
    program has finished, and a read of it before that would wait."""

    def __init__(self, value, ready=False):
        self.value, self.ready, self.reads = value, ready, 0

    def is_ready(self):
        return self.ready

    def __float__(self):
        self.reads += 1
        if not self.ready:
            raise AssertionError('read while its program was running')
        return self.value


def test_flight_records_spans_losses_and_deferred_reads():
    """The recorder never reads a loss whose program is still running: it
    keeps the pair and reads it at the first record_step that finds it
    ready. A plain number is ready at once."""
    trace.enable()
    with trace.span('step.dispatch'):
        pass
    flight.record_step(1, loss=onp.float32(2.5))
    slow, quick = _Loss(1.5), _Loss(0.5)
    with trace.span('step.dispatch'):
        pass
    flight.record_step(2, loss=slow)
    flight.record_step(3, loss=nd.array(onp.float32(0.75)))
    flight.record_step(4, loss=quick)
    steps = flight.get().steps()
    assert [r['step'] for r in steps] == [1, 2, 3, 4]
    assert steps[0]['loss'] == 2.5       # a numpy scalar: read at once
    assert steps[1]['loss'] is None and slow.reads == 0
    assert steps[3]['loss'] is None and quick.reads == 0
    assert 'step.dispatch' in steps[0]['spans_ms']
    assert steps[1]['interval_ms'] >= 0
    # the later step's program finishes first (another stream, say): its
    # loss is in its record by the next call, the other still waits
    quick.ready = True
    flight.record_step(5)
    steps = flight.get().steps()
    assert steps[3]['loss'] == 0.5 and steps[1]['loss'] is None
    assert steps[2]['loss'] == 0.75      # an NDArray, read once ready
    assert slow.reads == 0
    # a crash-time snapshot does not wait either; one that may, does
    assert flight.get().snapshot()['steps'][1]['loss'] is None
    slow.ready = True
    assert flight.get().snapshot(
        resolve_loss=True)['steps'][1]['loss'] == 1.5
    assert (slow.reads, quick.reads) == (1, 1)
    flight.annotate_last(guard_ok=False)
    assert flight.get().steps()[-1]['guard_ok'] is False


def test_flight_pending_losses_are_bounded_and_a_bad_one_reads_none():
    """A device that never finishes must not grow the queue past the
    ring, and a loss that cannot say whether it is ready (a deleted
    array) is not waited for: it reads as None."""
    class Gone:
        def is_ready(self):
            raise RuntimeError('Array has been deleted.')

        def __float__(self):
            raise RuntimeError('Array has been deleted.')

    trace.enable()
    rec = flight.FlightRecorder(capacity=4)
    stuck = [_Loss(float(i)) for i in range(10)]
    for i, loss in enumerate(stuck):
        rec.record_step(i, loss=loss)
    assert len(rec._pending) == 4 and all(x.reads == 0 for x in stuck)
    rec.record_step(10, loss=Gone())
    assert rec.steps()[-1]['loss'] is None
    # it did not stay queued
    assert all(isinstance(loss, _Loss) for _rec, loss in rec._pending)


def test_flight_dump_survives_a_held_lock(tmp_path):
    """Crash-time contract: a dump must never deadlock on the
    recorder's own lock — a fatal-signal handler can fire while THIS
    thread holds it mid-append, and a wedged holder must not wedge the
    watchdog's report. After a bounded wait the dump proceeds
    lock-free."""
    trace.enable()
    rec = flight.get()
    rec.record_step(1)
    rec._lock.acquire()                  # simulate the interrupted holder
    try:
        t0 = time.monotonic()
        with rec._locked_for_dump(timeout=0.2):
            steps = [dict(r) for r in rec._steps]
        assert time.monotonic() - t0 < 2.0
        assert steps and steps[0]['step'] == 1
    finally:
        rec._lock.release()


def test_flight_ring_is_bounded():
    trace.enable()
    rec = flight.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record_step(i)
    steps = rec.steps()
    assert len(steps) == 4 and steps[0]['step'] == 6


def test_flight_dump_document_shape(tmp_path, monkeypatch):
    monkeypatch.setenv('MXTPU_FLIGHT_PATH', str(tmp_path / 'black_box.json'))
    trace.enable()
    with trace.span('io.batch'):
        pass
    flight.record_step(7, guard_ok=True)
    flight.note('fault', site='io.decode', fault_kind='corrupt')
    path = flight.dump(reason='unit')
    assert path == str(tmp_path / 'black_box.json')
    doc = json.loads(open(path).read())
    assert doc['reason'] == 'unit'
    assert doc['steps'][0]['step'] == 7
    assert doc['events'][0]['kind'] == 'fault'
    assert doc['trace_stats']['spans_total'] == 1
    # the embedded stream is itself a valid chrome trace
    assert check_trace.check_doc(doc) == []


def test_watchdog_stall_on_injected_hang_dumps_flight(tmp_path, monkeypatch):
    """The acceptance scenario: a step wedges (injected
    step.dispatch:hang), the watchdog notices the missing heartbeat and
    dumps the flight recorder — the post-mortem JSON names the faulting
    step's spans, including the still-OPEN step.dispatch scope."""
    monkeypatch.setenv('MXTPU_FAULT_HANG_SECONDS', '6.0')
    monkeypatch.setenv('MXTPU_FLIGHT_PATH', str(tmp_path / 'flight.json'))
    trace.enable()

    net = nn.Dense(1, in_units=3)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    x = nd.array(onp.ones((2, 3), onp.float32))
    from mxnet_tpu import autograd
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(2)                     # one healthy recorded step
    faults.arm('step.dispatch', 'hang')

    def hung_step():
        with autograd.record():
            l2 = (net(x) ** 2).sum()
        l2.backward()
        trainer.step(2)                 # sleeps inside span step.dispatch

    reports = []
    t = threading.Thread(target=hung_step, daemon=True)
    wd = StepWatchdog(deadline_seconds=0.2, poll_seconds=0.05,
                      on_stall=reports.append)
    with wd:
        wd.beat(1)
        t.start()
        # Feed the watchdog until the worker is provably wedged inside the
        # step.dispatch span, so the stall clock only starts ticking while
        # the hang window is open (a loaded machine can otherwise delay the
        # worker past the deadline before it even reaches the span).
        entered = time.monotonic() + 15.0
        while time.monotonic() < entered and not any(
                s['name'] == 'step.dispatch' for s in trace.open_spans()):
            wd.beat(1)
            time.sleep(0.02)
        assert any(s['name'] == 'step.dispatch'
                   for s in trace.open_spans()), \
            "worker never entered the step.dispatch span"
        deadline = time.monotonic() + 15.0
        while not reports and time.monotonic() < deadline:
            time.sleep(0.02)
    t.join(timeout=20.0)
    assert reports, "watchdog never fired on the hung step"
    path = tmp_path / 'flight.json'
    assert path.exists(), "stall did not dump the flight recorder"
    doc = json.loads(path.read_text())
    assert doc['reason'] == 'watchdog_stall'
    assert check_trace.check_doc(doc) == []
    # the dump names the wedged scope (open at dump time) and the fault
    open_names = {s['name'] for s in doc['open_spans']}
    assert 'step.dispatch' in open_names
    assert any(e['kind'] == 'fault' and e['site'] == 'step.dispatch'
               for e in doc['events'])
    assert any(e['kind'] == 'watchdog.stall' for e in doc['events'])
    # the healthy step's span summary rode along
    assert any('step.dispatch' in r['spans_ms'] for r in doc['steps'])
    # and the human-readable report embeds the flight summary + path
    assert 'flight recorder' in reports[0]
    assert str(path) in reports[0]


# ---------------------------------------------------------------------------
# profiler merge: op rows + 'C' counters + spans in ONE valid stream
# ---------------------------------------------------------------------------

def test_profiler_dump_merges_spans_and_counters(tmp_path):
    from mxnet_tpu import profiler
    telemetry.enable()
    telemetry.reset()
    try:
        trace.enable()
        profiler.set_config(filename=str(tmp_path / 'profile.json'),
                            profile_imperative=True)
        profiler.set_state('run')
        with trace.span('step.dispatch'):
            (nd.ones((4, 4)) * 2).wait_to_read()
        profiler.set_state('stop')
        profiler.dump()
        path = str(tmp_path / 'profile.json')
        assert check_trace.check_file(path) == []
        doc = json.loads(open(path).read())
        evs = doc['traceEvents']
        phs = {e['ph'] for e in evs}
        assert 'X' in phs               # profiler op rows
        assert 'C' in phs               # telemetry counter track
        assert 'B' in phs and 'E' in phs  # step spans
        assert 'step.dispatch' in _names(evs)
        # ONE coherent tid space: op rows use the same small tids as spans
        xt = {e['tid'] for e in evs if e['ph'] == 'X'}
        bt = {e['tid'] for e in evs if e['ph'] == 'B'}
        assert xt & bt
    finally:
        profiler.set_config(filename='profile.json',
                            profile_imperative=False)
        telemetry.reset()
        telemetry.disable()


# ---------------------------------------------------------------------------
# the standalone validator itself
# ---------------------------------------------------------------------------

def test_check_trace_flags_violations():
    ok = [{'name': 'a', 'ph': 'B', 'ts': 1.0, 'pid': 1, 'tid': 1},
          {'name': 'a', 'ph': 'E', 'ts': 2.0, 'pid': 1, 'tid': 1}]
    assert check_trace.check_events(ok) == []
    orphan = [{'name': 'a', 'ph': 'E', 'ts': 2.0, 'pid': 1, 'tid': 1}]
    assert any('orphan' in e for e in check_trace.check_events(orphan))
    unclosed = [{'name': 'a', 'ph': 'B', 'ts': 1.0, 'pid': 1, 'tid': 1}]
    assert any('unclosed' in e for e in check_trace.check_events(unclosed))
    crossed = ok[:1] + [
        {'name': 'b', 'ph': 'B', 'ts': 1.5, 'pid': 1, 'tid': 1},
        {'name': 'a', 'ph': 'E', 'ts': 2.0, 'pid': 1, 'tid': 1}]
    assert any('interleaved' in e for e in check_trace.check_events(crossed))
    backwards = [{'name': 'a', 'ph': 'B', 'ts': 5.0, 'pid': 1, 'tid': 1},
                 {'name': 'a', 'ph': 'E', 'ts': 1.0, 'pid': 1, 'tid': 1}]
    assert any('precedes' in e for e in check_trace.check_events(backwards))
    no_ts = [{'name': 'a', 'ph': 'B', 'pid': 1, 'tid': 1}]
    assert any('ts' in e for e in check_trace.check_events(no_ts))
    assert check_trace.check_doc({'no_events': 1})
    assert check_trace.check_doc(3.14)


def test_check_trace_cli_on_real_dump(tmp_path):
    trace.enable()
    with trace.span('io.batch'):
        pass
    path = trace.dump(str(tmp_path / 't.json'))
    tool = os.path.join(os.path.dirname(__file__), os.pardir,
                        'tools', 'check_trace.py')
    res = subprocess.run([sys.executable, tool, path],
                         capture_output=True, text=True)
    assert res.returncode == 0, res.stderr
    assert 'OK' in res.stdout
    bad = tmp_path / 'bad.json'
    bad.write_text(json.dumps({'traceEvents': [
        {'name': 'a', 'ph': 'B', 'ts': 1.0, 'pid': 1, 'tid': 1}]}))
    res = subprocess.run([sys.executable, tool, str(bad)],
                         capture_output=True, text=True)
    assert res.returncode == 1
    assert 'unclosed' in res.stderr


def test_balance_events_repairs_crash_streams():
    raw = [{'name': 'outer', 'ph': 'B', 'ts': 1.0, 'pid': 1, 'tid': 1},
           {'name': 'gone', 'ph': 'E', 'ts': 1.5, 'pid': 1, 'tid': 2},
           {'name': 'inner', 'ph': 'B', 'ts': 2.0, 'pid': 1, 'tid': 1}]
    fixed = trace.balance_events(raw, close_ts=9.0)
    assert check_trace.check_events(fixed) == []
    closes = [e for e in fixed if e['ph'] == 'E']
    assert [e['name'] for e in closes] == ['inner', 'outer']
    assert all(e['ts'] == 9.0 and e['args']['flushed'] for e in closes)


# ---------------------------------------------------------------------------
# attribution: bucket math, residual honesty, cost_analysis join
# ---------------------------------------------------------------------------

def _mkstep(step, interval_ms, spans):
    return {'step': step, 'interval_ms': interval_ms,
            'spans_ms': {n: {'count': 1, 'total_ms': ms, 'self_ms': ms}
                         for n, ms in spans.items()}, 'loss': 2.0 - step}


def test_attribution_buckets_sum_to_wall():
    steps = [_mkstep(0, 100.0, {})] + [
        _mkstep(i, 40.0, {'io.batch': 6.0, 'io.prefetch_wait': 2.0,
                          'h2d.device_put': 4.0, 'comm.allreduce': 8.0,
                          'sync.lease_drain': 1.0,
                          'io.worker_fetch': 30.0,     # overlapped thread
                          'optimizer.fused': 15.0})
        for i in range(1, 5)]
    rep = attribution.report(steps, flops_per_step=1e9, peak_flops=1e12)
    assert rep['steps_used'] == 4
    assert rep['wall_ms_per_step'] == 40.0
    b = rep['buckets_ms']
    assert b['input'] == 8.0            # io.* minus overlapped worker
    assert b['h2d'] == 4.0
    assert b['collective'] == 8.0
    assert b['host_sync'] == 1.0
    # compute is the residual: bucket sum reconstructs wall EXACTLY
    assert abs(sum(b.values()) - rep['wall_ms_per_step']) < 1e-6
    assert abs(sum(rep['bucket_fractions'].values()) - 1.0) < 1e-3
    assert rep['measured_fraction'] == round(21.0 / 40.0, 4)
    # overlapped spans still appear in the span table, unbucketed
    assert 'io.worker_fetch' in rep['spans_ms_per_step']
    # the calls column is per-step, matching the per-step ms columns
    assert rep['spans_ms_per_step']['io.batch']['count'] == 1.0
    assert rep['mfu_percent'] == round(100 * 1e9 / (0.040 * 1e12), 2)
    assert rep['loss_last'] == 2.0 - 4
    table = attribution.format_table(rep)
    for token in ('input', 'compute', 'honest MFU', 'io.batch'):
        assert token in table
    assert attribution.report([])['error']


def test_attribution_subsystem_coverage_helper():
    assert attribution.subsystems(
        ['io.batch', 'io.decode', 'h2d.pin', 'step.dispatch',
         'comm.all_gather', 'optimizer.fused', 'checkpoint.write',
         'nodot']) == ['checkpoint', 'comm', 'h2d', 'io', 'optimizer',
                       'step']


def test_xla_cost_from_compiled_step():
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda a, b: (a @ b).sum())
    compiled = fn.lower(jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    cost = attribution.xla_cost(compiled)
    assert cost is not None and cost['flops'] >= 2 * 8 * 8 * 8 * 0.5
    assert attribution.xla_cost(object()) is None


# ---------------------------------------------------------------------------
# e2e: a traced tiny train step covers the step lifecycle subsystems
# ---------------------------------------------------------------------------

def test_e2e_traced_step_lifecycle_subsystems(tmp_path):
    from mxnet_tpu import io as mio
    from mxnet_tpu.io.io import _device_put_batch
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
    import jax
    trace.enable()
    mesh = make_mesh((1,), ('dp',), devices=jax.devices()[:1])
    net = nn.Dense(1, in_units=6)
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    step = ShardedTrainStep(net, loss_fn, 'adam', {'learning_rate': 0.01},
                            mesh=mesh)
    X = onp.random.RandomState(0).rand(32, 6).astype(onp.float32)
    Y = onp.random.RandomState(1).rand(32, 1).astype(onp.float32)
    it = mio.NDArrayIter(X, Y, batch_size=8)
    mgr = checkpoint.CheckpointManager(str(tmp_path), params=net,
                                       async_save=False)
    i = 0
    for batch in it:
        batch = _device_put_batch(batch)          # h2d span
        step(batch.data[0], batch.label[0])
        flight.record_step(i)
        i += 1
    mgr.save(i)
    mgr.restore_latest()
    evs = trace.chrome_events(metadata=True)
    assert check_trace.check_events(evs) == []
    subs = attribution.subsystems(set(_names(evs)))
    for sub in ('io', 'h2d', 'step', 'optimizer', 'checkpoint'):
        assert sub in subs, f"no {sub}.* span in traced step lifecycle"
    # attribution over those steps reconstructs the wall time
    rep = attribution.report(flight.get().steps())
    assert 'error' not in rep
    assert abs(rep['bucket_sum_over_wall'] - 1.0) < 0.05
