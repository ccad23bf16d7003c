"""Elastic-training drill: kill a worker, watch the survivor re-form.

The CI-testable half of the elastic story (ISSUE 8 / ROADMAP item 4):
``run_drill`` spawns two real worker processes under ``JAX_PLATFORMS=cpu``
(each with its own jax.distributed rank, membership heartbeat sender and
CheckpointManager), SIGKILLs one mid-run, and asserts the survivor

1. detects the loss on the membership side channel within the peer
   deadline,
2. commits a checkpoint at its last completed step,
3. tears down jax.distributed (bounded — the runtime's shutdown barrier
   would wait for the corpse) and re-forms its mesh at world size 1,
4. resumes from the committed step with a trajectory **bit-identical**
   to a clean single-process run restored from the same checkpoint
   (verified by a third reference process).

It returns the measured MTTR phases (detect / commit / teardown /
restore / first-resumed-step), which ``__graft_entry__.dryrun_multichip``
records each MULTICHIP round and ``tests/test_elastic.py`` asserts in
CI. Workers train on process-LOCAL meshes (this jaxlib's CPU backend
has no cross-process collectives — the same capability gap
tests/test_interop_tools.py skips on); the membership, commit, teardown
and re-form machinery is exactly the multi-host path.

Run a worker by hand::

    python -m mxnet_tpu.resilience.drill --worker --workdir /tmp/d ...
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time as _time

__all__ = ['run_drill', 'run_churn_drill', 'run_fleet_drill',
           'run_oom_drill', 'run_serving_drill']


def _free_port():
    with socket.socket() as s:
        s.bind(('', 0))
        return s.getsockname()[1]


def _free_port_base(n=2, tries=32):
    """A base port with ``n`` consecutive free ports (the replica
    servers listen on base + rank)."""
    for _ in range(tries):
        base = _free_port()
        ok = True
        for off in range(n):
            try:
                with socket.socket() as s:
                    s.bind(('', base + off))
            except OSError:
                ok = False
                break
        if ok:
            return base
    raise RuntimeError("no consecutive free port range found")


def _data_for(step, batch=16, dim=8):
    """Deterministic per-step batch: the same step index produces the
    same bytes in every process — the precondition for bit-identical
    resume parity."""
    import numpy as onp
    rng = onp.random.RandomState(10_000 + int(step))
    x = rng.randn(batch, dim).astype(onp.float32)
    y = (x.sum(axis=1) > 0).astype(onp.float32)
    return x, y


def _build(workdir, rank, mesh, autosave_steps=None, replication=False,
           ckpt_dir=None):
    """Model + compiled step + checkpoint manager for one worker.
    Explicit prefixes: every process (workers, the reference run) must
    produce identical parameter names for the states payload to apply.
    ``ckpt_dir`` overrides the per-rank default — the churn drill runs
    every incarnation against ONE shared directory (single-writer: only
    rank 0 commits)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu import checkpoint as _checkpoint
    from mxnet_tpu.parallel import ShardedTrainStep

    mx.random.seed(7)
    net = gluon.nn.HybridSequential(prefix='drill_')
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation='relu', prefix='fc1_'),
                gluon.nn.Dense(2, prefix='fc2_'))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss_fn, 'adam',
                            {'learning_rate': 0.05}, mesh=mesh)
    mgr = _checkpoint.CheckpointManager(
        ckpt_dir or os.path.join(workdir, f'ckpt-rank{rank}'),
        params=net, trainer=step, async_save=False,
        autosave_steps=autosave_steps,
        replication=None if replication else False)
    return net, step, mgr


def _run_step(step, i):
    from mxnet_tpu import nd
    x, y = _data_for(i)
    return float(step(nd.array(x), nd.array(y)).asnumpy())


def _worker(args):
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import faulthandler
    faulthandler.register(signal.SIGUSR1)   # stacks on demand in CI
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass
    from mxnet_tpu.parallel import dist, make_mesh
    from mxnet_tpu.resilience import ElasticController

    from .. import config as _config
    rank = max(0, _config.get('MXNET_TPU_PROC_ID'))
    progress = os.path.join(args.workdir, f'progress-rank{rank}.txt')
    dist.init()
    ms = dist.start_membership(port=args.port,
                               heartbeat_seconds=args.heartbeat,
                               deadline_seconds=args.deadline)
    mesh = make_mesh(devices=jax.local_devices())
    # disk-loss mode: ONE rank owns the checkpoint directory (the
    # standard multi-host pattern — payloads are host-gathered, one
    # writer suffices) and commits every step; every rank runs the
    # replica server, so the owner's commits land on its peers
    owner = args.ckpt_owner if args.disk_loss else None
    is_owner = owner is None or rank == owner
    net, step, mgr = _build(
        args.workdir, rank, mesh,
        autosave_steps=1 if (args.disk_loss and is_owner) else None,
        replication=args.disk_loss)
    ctl = ElasticController(manager=mgr, membership=ms, step=step,
                            commit_on_reform=is_owner)
    ctl.start_monitor()

    marks = {'rank': rank, 'start_wall': _time.time()}
    losses, post = {}, {}
    i = 0
    while i < args.steps:
        resumed = ctl.pre_step()
        if resumed is not None:
            marks['reform'] = ctl.last_reform
            marks['reform_done_wall'] = _time.time()
            marks['resumed_step'] = resumed
            marks['restore_source'] = mgr.last_restore_source
            i = int(resumed)
            continue
        t0 = _time.perf_counter()
        loss = _run_step(step, i + 1)
        dt = _time.perf_counter() - t0
        i += 1
        ctl.beat(i)
        if args.disk_loss and is_owner:
            mgr.maybe_save(i)
            if mgr.replica is not None:
                mgr.replica.wait(timeout=10.0)   # drill determinism only
        losses[i] = float(loss).hex()
        if 'reform' in marks:
            post[i] = float(loss).hex()
            marks.setdefault('first_resumed_step_seconds', dt)
            marks.setdefault('first_resumed_step_wall', _time.time())
        with open(progress, 'w') as f:
            f.write(str(i))
        if args.step_sleep:
            _time.sleep(args.step_sleep)
    ctl.stop_monitor()
    mgr.close()
    out = {'marks': marks, 'losses': losses, 'post': post,
           'world': ms.world_size(), 'reforms': ctl.reforms,
           'peer_losses': ctl.peer_losses}
    with open(os.path.join(args.workdir, f'result-rank{rank}.json'),
              'w') as f:
        json.dump(out, f, indent=1)
    ms.stop()


def _fleet_worker(args):
    """One rank of the fleet-observability drill (ISSUE 13): trains
    with telemetry + tracing armed and the /metrics //healthz //flight
    endpoint up, heartbeats carrying per-step telemetry snapshots.
    After its steps it commits a checkpoint, beats once more (so the
    coordinator's fleet view holds the FINAL per-rank comm totals),
    dumps its rank trace for the stitcher, then holds the endpoints up
    until the parent releases it — the parent's scrape window."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass
    from mxnet_tpu.parallel import dist, make_mesh
    from mxnet_tpu.telemetry import fleet, server

    from .. import config as _config
    rank = max(0, _config.get('MXNET_TPU_PROC_ID'))
    progress = os.path.join(args.workdir, f'progress-rank{rank}.txt')
    dist.init()            # membership + fleet attach + endpoint arm
    ms = dist.membership()
    assert ms is not None, "fleet drill needs MXTPU_ELASTIC=1"
    mesh = make_mesh(devices=jax.local_devices())
    net, step, mgr = _build(args.workdir, rank, mesh)
    slow_s = args.slow_ms / 1e3 if rank == args.slow_rank else 0.0
    for i in range(args.steps):
        loss = _run_step(step, i + 1)
        ms.current_step = i + 1
        if slow_s:
            _time.sleep(slow_s)
        if args.step_sleep:
            _time.sleep(args.step_sleep)
        with open(progress, 'w') as f:
            f.write(str(i + 1))
    mgr.save_now(args.steps)          # /healthz last_committed_step
    ms.beat()                         # final snapshot: last step+totals
    fleet.dump_rank_trace(
        os.path.join(args.workdir, f'trace-rank{rank}.json'), ms)
    out = {'rank': rank, 'steps': args.steps, 'loss': float(loss),
           'metrics_port': server.get().port if server.get() else None,
           'snapshot_bytes': fleet.snapshot_bytes(),
           'comm_bytes': fleet.comm_bytes_by_axis(),
           'clock_offset': ms.clock_offset()}
    if rank == 0:
        # wait for the straggler detector to flag the slow rank, then
        # capture the watchdog's ACTUAL stall-report text — the drill
        # asserts the verdict names the rank, not just that a flag is up
        deadline = _time.monotonic() + 30.0
        flagged = None
        while _time.monotonic() < deadline:
            mon = fleet.monitor()
            flagged = mon.straggler() if mon is not None else None
            if flagged is not None:
                break
            _time.sleep(0.05)
        out['straggler'] = flagged
        from .watchdog import StepWatchdog
        wd = StepWatchdog(deadline_seconds=9999.0, membership=ms)
        report = wd._format_report(1.0, args.steps)
        out['watchdog_verdict'] = next(
            (ln for ln in report.split('\n')
             if ln.startswith('verdict:')), '')
        mon = fleet.monitor()
        out['fleet_view'] = mon.view() if mon is not None else None
        from mxnet_tpu.telemetry import flight
        out['flight_events'] = flight.get().events()
    with open(os.path.join(args.workdir, f'result-rank{rank}.json'),
              'w') as f:
        json.dump(out, f, indent=1, default=str)
    release = os.path.join(args.workdir, 'release')
    deadline = _time.monotonic() + 90.0
    while not os.path.exists(release) and _time.monotonic() < deadline:
        _time.sleep(0.05)
    mgr.close()
    ms.stop()


def _prom_value(text, name, **labels):
    """Sum of a metric's samples in Prometheus exposition ``text``
    whose labels are a superset of ``labels`` (None: never seen)."""
    import re as _re
    total, seen = 0.0, False
    for line in text.splitlines():
        if not line.startswith(name) or line.startswith('#'):
            continue
        m = _re.match(r'^([a-z0-9_]+)(?:\{([^}]*)\})?\s+(\S+)$', line)
        if not m or m.group(1) != name:
            continue
        got = dict(_re.findall(r'(\w+)="([^"]*)"', m.group(2) or ''))
        if all(got.get(k) == str(v) for k, v in labels.items()):
            total += float(m.group(3))
            seen = True
    return total if seen else None


def _http_get(url, timeout=5.0):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode()
    except urllib.error.HTTPError as e:
        # /healthz answers 503 when degraded — the body is the document
        return e.read().decode()


def run_fleet_drill(workdir, steps=8, heartbeat=0.2, step_sleep=0.1,
                    slow_rank=1, slow_ms=400, hang_seconds=1.0,
                    timeout=150.0):
    """Two-rank fleet-observability drill (ISSUE 13). Rank ``slow_rank``
    runs slower steps AND an armed ``dist.heartbeat:hang`` fault delays
    its beats, so both straggler signals (step-time skew, snapshot
    staleness) are live. Asserts:

    - /metrics, /healthz and /flight respond on BOTH ranks;
    - the coordinator's fleet view holds both ranks with per-rank skew;
    - the injected straggler is flagged (flight note + anomaly counter)
      and NAMED in the watchdog verdict line;
    - the coordinator's ``mxnet_tpu_fleet_comm_bytes`` gauge for the
      slow rank agrees EXACTLY with that rank's own per-hop
      ``mxnet_tpu_comm_collective_bytes_total`` scrape;
    - the two rank traces stitch (``tools/stitch_traces.py``) into one
      ``check_trace``-clean timeline.

    Returns the measured numbers for PERF_NOTES / dryrun_multichip."""
    os.makedirs(workdir, exist_ok=True)
    jax_port, side_port = _free_port(), _free_port()
    metrics_base = _free_port_base(2)
    env = dict(os.environ)
    env.update({
        'PYTHONPATH': os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))] +
            ([env['PYTHONPATH']] if env.get('PYTHONPATH') else [])),
        'JAX_PLATFORMS': 'cpu',
        'XLA_FLAGS': '--xla_force_host_platform_device_count=2',
        'MXNET_TPU_COORDINATOR': f'localhost:{jax_port}',
        'MXNET_TPU_NUM_PROCS': '2',
        'MXTPU_ELASTIC': '1',
        'MXTPU_ELASTIC_PORT': str(side_port),
        'MXTPU_HEARTBEAT_SECONDS': str(heartbeat),
        # deadline far above the beat-delay fault: the slow rank must
        # look STALE to the fleet detectors, never LOST to membership
        'MXTPU_PEER_DEADLINE_SECONDS': '60',
        'MXNET_TPU_TELEMETRY': '1',
        'MXTPU_TRACE': '1',
        'MXTPU_METRICS_PORT': str(metrics_base),
        'MXTPU_FLIGHT_DIR': workdir,
    })
    base = [sys.executable, '-m', 'mxnet_tpu.resilience.drill',
            '--fleet', '--workdir', workdir, '--steps', str(steps),
            '--port', str(side_port), '--heartbeat', str(heartbeat),
            '--step-sleep', str(step_sleep),
            '--slow-rank', str(slow_rank), '--slow-ms', str(slow_ms)]
    procs, logs = [], []
    for r in range(2):
        e = dict(env)
        e['MXNET_TPU_PROC_ID'] = str(r)
        if r == slow_rank and hang_seconds:
            e['MXTPU_FAULT'] = 'dist.heartbeat:hang'
            e['MXTPU_FAULT_HANG_SECONDS'] = str(hang_seconds)
        log = open(os.path.join(workdir, f'worker-rank{r}.log'), 'wb')
        logs.append(log)
        procs.append(subprocess.Popen(
            base, env=e, stdout=log, stderr=subprocess.STDOUT))

    def _fail(msg):
        for p in procs:
            if p.poll() is None:
                p.kill()
        errs = []
        for i, log in enumerate(logs):
            log.flush()
            try:
                with open(log.name, 'rb') as f:
                    errs.append(f"-- rank {i} log --\n" +
                                f.read().decode(errors='replace')[-3000:])
            except OSError:
                pass
        raise AssertionError(msg + '\n' + '\n'.join(errs))

    try:
        # readiness: both result files exist (written AFTER the final
        # beat + trace dump, so the scrape window sees steady state)
        deadline = _time.monotonic() + timeout
        results = {}
        while _time.monotonic() < deadline and len(results) < 2:
            for r in range(2):
                if r in results:
                    continue
                p = os.path.join(workdir, f'result-rank{r}.json')
                if os.path.exists(p):
                    try:
                        with open(p) as f:
                            results[r] = json.load(f)
                    except (OSError, ValueError):
                        pass
            if any(p.poll() not in (None, 0) for p in procs):
                _fail("fleet drill: a worker died")
            _time.sleep(0.1)
        if len(results) < 2:
            _fail("fleet drill: workers never reached the scrape window")

        ports = {r: metrics_base + r for r in range(2)}
        # 1. every endpoint answers on both ranks
        scraped = {}
        for r in range(2):
            url = f'http://127.0.0.1:{ports[r]}'
            scraped[r] = {
                'metrics': _http_get(url + '/metrics'),
                'healthz': json.loads(_http_get(url + '/healthz')),
                'flight': json.loads(_http_get(url + '/flight')),
            }
            assert 'mxnet_tpu_comm_collective_bytes_total' in \
                scraped[r]['metrics'], (r, scraped[r]['metrics'][:400])
            assert scraped[r]['flight'].get('steps'), \
                f"rank {r} /flight has no step records"
            assert scraped[r]['healthz'].get('last_committed_step') \
                == steps, scraped[r]['healthz']
        # 2. the coordinator's fleet view holds both ranks + skew
        hz0 = scraped[0]['healthz']
        fleet_view = hz0.get('fleet') or {}
        ranks = {int(k) for k in (fleet_view.get('ranks') or {})}
        assert ranks == {0, 1}, fleet_view
        vr = fleet_view['ranks']
        v1 = vr.get(str(slow_rank), vr.get(slow_rank))
        assert v1['step'] == steps, v1
        assert v1.get('skew_ms') is not None and v1['skew_ms'] > 0, v1
        # 3. the injected straggler is flagged and NAMED in the verdict
        r0 = results[0]
        assert r0.get('straggler') and \
            int(r0['straggler']['rank']) == slow_rank, r0.get('straggler')
        assert r0['straggler'].get('snapshot_age_seconds') is not None
        assert f'STRAGGLER SUSPECTED: rank {slow_rank}' in \
            r0.get('watchdog_verdict', ''), r0.get('watchdog_verdict')
        notes = [e for e in r0.get('flight_events', [])
                 if e.get('kind') == 'fleet.straggler'
                 and int(e.get('rank', -1)) == slow_rank]
        assert notes, "no fleet.straggler flight note for the slow rank"
        anomalies = _prom_value(scraped[0]['metrics'],
                                'mxnet_tpu_fleet_anomalies_total',
                                kind='fleet.straggler', rank=slow_rank)
        assert anomalies and anomalies >= 1, anomalies
        # 4. fleet comm gauge == the rank's own per-hop counter scrape
        own = results[slow_rank]['comm_bytes']
        assert own, "slow rank reported no comm bytes"
        agreement = {}
        for axis, nbytes in own.items():
            fleet_val = _prom_value(scraped[0]['metrics'],
                                    'mxnet_tpu_fleet_comm_bytes',
                                    rank=slow_rank, axis=axis)
            own_scrape = _prom_value(
                scraped[slow_rank]['metrics'],
                'mxnet_tpu_comm_collective_bytes_total', axis=axis)
            assert fleet_val == nbytes == own_scrape, \
                (axis, fleet_val, nbytes, own_scrape)
            agreement[axis] = int(nbytes)
        # 5. stitch the two rank traces into one clean timeline
        stitched = os.path.join(workdir, 'fleet_trace.json')
        tools_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), 'tools')
        rc = subprocess.run(
            [sys.executable, os.path.join(tools_dir, 'stitch_traces.py'),
             '-o', stitched,
             os.path.join(workdir, 'trace-rank0.json'),
             os.path.join(workdir, 'trace-rank1.json')],
            capture_output=True, text=True, timeout=60)
        assert rc.returncode == 0, (rc.stdout, rc.stderr)
        rc2 = subprocess.run(
            [sys.executable, os.path.join(tools_dir, 'check_trace.py'),
             stitched],
            capture_output=True, text=True, timeout=60)
        assert rc2.returncode == 0, (rc2.stdout, rc2.stderr)
    finally:
        with open(os.path.join(workdir, 'release'), 'w') as f:
            f.write('done')
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in logs:
            log.close()
    return {
        'ok': True,
        'steps': steps,
        'slow_rank': slow_rank,
        'straggler': r0['straggler'],
        'watchdog_verdict': r0['watchdog_verdict'],
        'snapshot_bytes': {r: results[r]['snapshot_bytes']
                           for r in results},
        'comm_agreement': agreement,
        'skew_ms': v1['skew_ms'],
        'clock_offset': results[1].get('clock_offset'),
        'stitched': stitched,
        'healthz_status': {r: scraped[r]['healthz']['status']
                           for r in scraped},
    }


def run_oom_drill(workdir, steps_before=3):
    """OOM forensics drill (ISSUE 14) — no real 16 GB chip required.

    Trains the drill model a few steps with memory watermarking armed,
    then arms the deterministic ``alloc.oom`` fault so the NEXT pass
    through a guarded dispatch site raises a synthetic
    RESOURCE_EXHAUSTED through ``telemetry.memory.oom_guard``. Asserts
    the guard wrote exactly the post-mortem a real allocator
    exhaustion would:

    - the dump validates against the ``mxtpu_oom_v1`` schema,
    - it names the largest live tracked array (with shape/dtype/
      sharding) and carries the watermark ring + bucket analysis,
    - the ``memory.oom`` flight note landed.

    Returns the summary dict ``dryrun_multichip`` prints each
    MULTICHIP round. In-process (the fault is deterministic and the
    exception is caught here) — state is restored on exit."""
    import json

    from mxnet_tpu import config as _config
    from mxnet_tpu.telemetry import flight, memory, trace
    from . import faults

    prev_dir = _config.get('MXTPU_FLIGHT_DIR')
    was_mem, was_trace = memory.enabled(), trace.enabled()
    os.environ['MXTPU_FLIGHT_DIR'] = str(workdir)
    memory.clear()
    memory.enable()
    trace.enable()             # the memory.oom flight note needs the ring
    try:
        from mxnet_tpu.parallel.mesh import default_mesh
        _net, step, mgr = _build(str(workdir), 0, default_mesh())
        for i in range(steps_before):
            _run_step(step, i)
        analysis = step.memory_analysis()
        assert analysis is not None, "no memory_analysis after steps"
        # falsifiable: the buckets must measure THIS step's residency
        # (sum==peak alone holds by construction)
        assert analysis['buckets_bytes']['params'] \
            == step.param_bytes_per_device(), analysis
        assert analysis['buckets_bytes']['optimizer_state'] \
            == step.opt_state_bytes_per_device(), analysis
        faults.arm('alloc.oom', 'raise', window=1)
        err = None
        try:
            _run_step(step, steps_before)
        except faults.InjectedFault as e:
            err = e
        assert err is not None and err.site == 'alloc.oom', \
            "injected alloc.oom did not surface"
        path = memory.default_oom_path()
        assert os.path.exists(path), f"no forensics dump at {path}"
        with open(path) as f:
            doc = json.load(f)
        problems = memory.validate_oom_dump(doc)
        assert not problems, problems
        assert doc['top_arrays'], "dump names no live arrays"
        top = doc['top_arrays'][0]
        live = {}
        for pool in memory.pools().values():
            live.update(pool)
        biggest = max(memory.entry_nbytes(a) for a in live.values())
        peers = {n for n, a in live.items()
                 if memory.entry_nbytes(a) == biggest}
        # the dump's prime suspect IS the largest live allocation
        # (several arrays may tie at the same byte size)
        assert top['nbytes'] == biggest and top['name'] in peers, \
            (top, biggest, sorted(peers))
        notes = [e['kind'] for e in flight.get().events()
                 if e['kind'] == 'memory.oom']
        mgr.close()
        return {
            'ok': True,
            'path': path,
            'site': doc['site'],
            'top_array': {k: top[k] for k in
                          ('pool', 'name', 'nbytes') if k in top},
            'device_bytes': doc['device_bytes'],
            'peak_bytes': doc['peak_bytes'],
            'watermark_samples': len(doc['watermarks']),
            'hints': [h['action'] for h in doc['hints']],
            'flight_noted': bool(notes),
        }
    finally:
        faults.disarm('alloc.oom')
        memory.clear()
        (memory.enable if was_mem else memory.disable)()
        (trace.enable if was_trace else trace.disable)()
        if prev_dir:
            os.environ['MXTPU_FLIGHT_DIR'] = prev_dir
        else:
            os.environ.pop('MXTPU_FLIGHT_DIR', None)


def _reference(args):
    """Clean single-process resume: restore the survivor's committed
    checkpoint and train the remaining steps — the trajectory the
    survivor's post-re-form segment must match bit-for-bit."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass
    from mxnet_tpu.parallel import make_mesh

    mesh = make_mesh(devices=jax.local_devices())
    net, step, mgr = _build(args.workdir, args.ref_rank, mesh)
    start = mgr.restore_latest()
    losses = {}
    for i in range(int(start), args.steps):
        losses[i + 1] = float(_run_step(step, i + 1)).hex()
    with open(os.path.join(args.workdir, 'result-reference.json'),
              'w') as f:
        json.dump({'restored_step': start, 'losses': losses}, f, indent=1)
    mgr.close()


def _hosted_steps(nsdir):
    """Committed step numbers under one hosted-replica namespace dir."""
    try:
        import re
        return sorted(int(m.group(1)) for m in
                      (re.match(r'^step_(\d{10})$', n)
                       for n in os.listdir(nsdir)) if m)
    except OSError:
        return []


def _assert_dirs_bit_identical(a, b):
    """Every file under ``a`` must exist under ``b`` with identical
    bytes (and vice versa) — the replica-restore parity check."""
    def walk(root):
        out = {}
        for dirpath, _dirs, files in os.walk(root):
            for fn in files:
                p = os.path.join(dirpath, fn)
                out[os.path.relpath(p, root)] = p
        return out
    fa, fb = walk(a), walk(b)
    assert sorted(fa) == sorted(fb), (sorted(fa), sorted(fb))
    for rel in fa:
        with open(fa[rel], 'rb') as f1, open(fb[rel], 'rb') as f2:
            assert f1.read() == f2.read(), \
                f"{rel} differs between {a} and {b}"


def _wait_progress(path, target, timeout):
    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        try:
            with open(path) as f:
                if int(f.read().strip() or 0) >= target:
                    return True
        except (OSError, ValueError):
            pass
        _time.sleep(0.05)
    return False


def run_drill(workdir, steps=14, kill_at=3, heartbeat=0.2, deadline=1.2,
              step_sleep=0.35, timeout=180.0, victim_rank=1,
              disk_loss=False):
    """Run the two-worker SIGKILL drill. Returns a dict with the
    survivor's MTTR phase breakdown and the bit-parity verdict (raises
    AssertionError on any broken guarantee).

    ``disk_loss=True`` is the survivability variant (ISSUE 10): the
    victim rank OWNS the checkpoint directory (commits every step,
    replicated to the peer over the side channel) and its directory is
    **wiped before the SIGKILL** — so the survivor can only resume by
    fetching the newest replicated step from its own hosted replica,
    hash-verified, bit-identical to a clean local restore."""
    os.makedirs(workdir, exist_ok=True)
    jax_port, side_port = _free_port(), _free_port()
    replica_base = _free_port_base(2) if disk_loss else 0
    env = dict(os.environ)
    env.update({
        'PYTHONPATH': os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))] +
            ([env['PYTHONPATH']] if env.get('PYTHONPATH') else [])),
        'JAX_PLATFORMS': 'cpu',
        'XLA_FLAGS': '--xla_force_host_platform_device_count=2',
        'MXNET_TPU_COORDINATOR': f'localhost:{jax_port}',
        'MXNET_TPU_NUM_PROCS': '2',
        'MXTPU_ELASTIC': '1',
        # the membership knobs ride the env so dist.init()'s automatic
        # start_membership and the worker's explicit call agree
        'MXTPU_ELASTIC_PORT': str(side_port),
        'MXTPU_HEARTBEAT_SECONDS': str(heartbeat),
        'MXTPU_PEER_DEADLINE_SECONDS': str(deadline),
    })
    if disk_loss:
        env.update({
            # exercise the AUTO wiring: CheckpointManager attaches the
            # ReplicaManager itself off the membership world + env knobs
            'MXTPU_CHECKPOINT_REPLICAS': '1',
            'MXTPU_REPLICA_PORT_BASE': str(replica_base),
            'MXTPU_REPLICA_TIMEOUT_SECONDS': '5',
        })
    base = [sys.executable, '-m', 'mxnet_tpu.resilience.drill',
            '--workdir', workdir, '--steps', str(steps),
            '--port', str(side_port), '--heartbeat', str(heartbeat),
            '--deadline', str(deadline), '--step-sleep', str(step_sleep)]
    if disk_loss:
        base += ['--disk-loss', '--ckpt-owner', str(victim_rank)]
    procs, logs = [], []
    for r in range(2):
        e = dict(env)
        e['MXNET_TPU_PROC_ID'] = str(r)
        log = open(os.path.join(workdir, f'worker-rank{r}.log'), 'wb')
        logs.append(log)
        procs.append(subprocess.Popen(
            base + ['--worker'], env=e, stdout=log,
            stderr=subprocess.STDOUT))
    survivor_rank = 1 - victim_rank
    victim, survivor = procs[victim_rank], procs[survivor_rank]

    def _fail(msg):
        for p in procs:
            if p.poll() is None:
                p.kill()
        errs = []
        for i, log in enumerate(logs):
            log.flush()
            try:
                with open(log.name, 'rb') as f:
                    errs.append(f"-- rank {i} log --\n" +
                                f.read().decode(errors='replace')[-3000:])
            except OSError:
                pass
        raise AssertionError(msg + '\n' + '\n'.join(errs))

    # let both ranks make real progress before the kill
    for r in range(2):
        if not _wait_progress(
                os.path.join(workdir, f'progress-rank{r}.txt'),
                kill_at, timeout / 2):
            _fail(f"drill: rank {r} never reached step {kill_at}")
    victim_ckpt = os.path.join(workdir, f'ckpt-rank{victim_rank}')
    hosted = os.path.join(workdir, f'ckpt-rank{survivor_rank}',
                          '.replicas', f'rank{victim_rank}')
    if disk_loss:
        # the survivor must already hold a committed replica of the
        # owner's checkpoints before the disaster strikes
        deadline_t = _time.monotonic() + timeout / 2
        while _time.monotonic() < deadline_t:
            if _hosted_steps(hosted):
                break
            _time.sleep(0.05)
        else:
            _fail(f"drill: no committed replica under {hosted}")
        # the disaster: the preemption takes the owner's DISK with it —
        # wipe the whole checkpoint dir (local steps AND its replica
        # root), then SIGKILL. The survivor's only restore source is
        # now its own hosted replica.
        import shutil
        shutil.rmtree(victim_ckpt, ignore_errors=True)
    victim.kill()                       # SIGKILL: no goodbye, no flush
    kill_wall = _time.time()
    victim.wait()
    try:
        survivor.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _fail("drill: survivor did not exit (re-form wedged?)")
    if survivor.returncode != 0:
        _fail(f"drill: survivor exited rc={survivor.returncode}")
    for log in logs:
        log.close()
    with open(os.path.join(workdir,
                           f'result-rank{survivor_rank}.json')) as f:
        res = json.load(f)
    marks = res['marks']
    assert res['reforms'] == 1 and res['peer_losses'] == 1, res
    assert marks.get('reform', {}).get('world') == 1, marks
    assert res['post'], "survivor recorded no post-re-form steps"
    if disk_loss:
        # the restore bytes must have come through the replica path
        # (there is no other source: the owner's dir was wiped and the
        # survivor never committed) — and the fetched local step must
        # be bit-identical to the hosted replica copy it came from
        src = marks.get('restore_source')
        assert src and src.startswith(f'hosted:rank{victim_rank}'), (
            "survivor did not restore from a peer replica", marks)
        resumed = int(marks['resumed_step'])
        _assert_dirs_bit_identical(
            os.path.join(workdir, f'ckpt-rank{survivor_rank}',
                         f'step_{resumed:010d}'),
            os.path.join(hosted, f'step_{resumed:010d}'))

    # reference: clean restore of the SAME committed checkpoint
    ref_cmd = base + ['--reference', '--ref-rank', str(survivor_rank)]
    e = dict(env)
    e['MXNET_TPU_NUM_PROCS'] = '1'
    e['MXNET_TPU_PROC_ID'] = '0'
    r = subprocess.run(ref_cmd, env=e, capture_output=True, timeout=timeout)
    assert r.returncode == 0, r.stderr.decode(errors='replace')[-3000:]
    with open(os.path.join(workdir, 'result-reference.json')) as f:
        ref = json.load(f)
    assert ref['restored_step'] == marks['resumed_step'], (ref, marks)
    assert res['post'] == ref['losses'], (
        "post-re-form trajectory diverges from a clean restore of the "
        "same checkpoint", res['post'], ref['losses'])

    reform = marks['reform']
    detect_seconds = round(
        marks['reform_done_wall'] - reform['reform_seconds'] - kill_wall, 3)
    mttr = {
        'detect_seconds': detect_seconds,
        'commit_seconds': reform['commit_seconds'],
        'teardown_seconds': reform['teardown_seconds'],
        'restore_seconds': reform['restore_seconds'],
        'reform_seconds': reform['reform_seconds'],
        'first_resumed_step_seconds': round(
            marks.get('first_resumed_step_seconds', 0.0), 3),
        'total_seconds': round(
            marks.get('first_resumed_step_wall',
                      marks['reform_done_wall']) - kill_wall, 3),
    }
    assert detect_seconds <= deadline + max(
        4 * heartbeat, 1.0) + step_sleep + 1.0, (
        f"peer loss detected {detect_seconds}s after the kill — past "
        f"the {deadline}s deadline budget", mttr)
    return {
        'ok': True,
        'committed_step': marks['resumed_step'],
        'post_steps': len(res['post']),
        'bit_identical': True,
        'deadline_seconds': deadline,
        'disk_loss': bool(disk_loss),
        'restore_source': marks.get('restore_source'),
        'mttr': mttr,
    }


# ---------------------------------------------------------------------------
# churn-storm drill (elastic scale-UP): randomized kill/join cycles

_CHURN_SAMPLES = 64      # dataset size behind the ElasticShard
_CHURN_BATCH = 8         # GLOBAL batch — fixed across every world size
_CHURN_SEED = 11         # shard shuffle seed (shared by every process)


class _FileCapacityProvider:
    """The drill's ``CapacityProvider``: decisions land in a JSONL
    ledger the parent process — the drill's 'scheduler' — tails.
    Granted capacity arrives later as a fresh worker process announcing
    JOIN on the side channel, which closes the autoscaler's
    loss -> request -> join -> admit loop with real processes."""

    def __init__(self, path):
        self.path = path

    def request_capacity(self, count, reason):
        self._append({'count': int(count), 'reason': reason})

    def evict(self, rank, reason):
        self._append({'evict': int(rank), 'reason': reason})

    def _append(self, doc):
        doc['wall'] = _time.time()
        with open(self.path, 'a') as f:
            f.write(json.dumps(doc) + '\n')
            f.flush()
            os.fsync(f.fileno())


def _churn_sync(ms, ctl, target):
    """Emulate the collective's step barrier on the side channel: block
    until every OTHER alive rank reports ``target`` done (beats
    piggyback the step counter). Returns False — caller re-enters
    ``pre_step`` — the moment a peer is lost or a JOIN lands, exactly
    when a real collective would abort. Without this lockstep an
    unsynchronized survivor could commit a world-2 step whose partner
    half was never consumed: a silently dropped sample."""
    while True:
        if ms.lost_peers() or ctl._pending_joins(ms):
            return False
        view = ms.view() or {}
        steps = {int(r): int(s)
                 for r, s in (view.get('steps') or {}).items()}
        peers = [int(r) for r in view.get('alive', ())
                 if int(r) != ms.rank]
        if all(steps.get(r, 0) >= target for r in peers):
            return True
        _time.sleep(0.02)


def _churn_worker(args):
    """One churn-drill rank (founding member or JOIN incarnation).

    The data-plane discipline that makes exactly-once provable from the
    on-disk records: each rank appends (step, position, ids) to its
    sample ledger and fsyncs BEFORE beating the step — so a survivor
    can only have committed a world-2 step if the partner's consumption
    record for it is already on disk. A step whose barrier aborts (peer
    lost / JOIN pending) is rolled back (``last_step`` retreats to the
    last synced step) and re-run after the re-form; replaying the
    ledgers is last-record-wins per step."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass
    from mxnet_tpu.io import ElasticShard
    from mxnet_tpu.parallel import dist, make_mesh
    from mxnet_tpu.resilience import Autoscaler, ElasticController

    rank, tag = args.rank, args.tag
    ms = dist.Membership(rank, 2, port=args.port,
                         heartbeat_seconds=args.heartbeat,
                         deadline_seconds=args.deadline)
    mesh = make_mesh(devices=jax.local_devices())
    is_owner = rank == 0
    net, step, mgr = _build(args.workdir, rank, mesh,
                            autosave_steps=1 if is_owner else None,
                            ckpt_dir=os.path.join(args.workdir,
                                                  'ckpt-shared'))
    ctl = ElasticController(manager=mgr, membership=ms, step=step,
                            commit_on_reform=is_owner)
    holder = {'shard': ElasticShard(_CHURN_SAMPLES, _CHURN_BATCH,
                                    rank=rank, world=2,
                                    seed=_CHURN_SEED)}
    scaler = None
    if is_owner:
        # the commit manifest carries the data position: any later
        # incarnation reshards from it at its new (rank, world)
        mgr.bind_data_state(lambda: holder['shard'].state())
        scaler = Autoscaler(
            membership=ms,
            provider=_FileCapacityProvider(
                os.path.join(args.workdir, 'capacity-requests.jsonl')),
            target_world=2, cooldown_seconds=1.0, strikes=3)
    progress = os.path.join(args.workdir, f'progress-{tag}.txt')
    release = os.path.join(args.workdir, 'churn-release')
    samples = open(os.path.join(args.workdir, f'samples-{tag}.jsonl'),
                   'a')
    marks = {'tag': tag, 'rank': rank, 'start_wall': _time.time()}
    reforms, losses = [], {}

    def _reseed():
        meta = mgr.last_restored_metadata or {}
        assert meta.get('data'), \
            f"restored manifest carries no data position: {meta}"
        holder['shard'] = ElasticShard.from_state(
            meta['data'], rank=ctl.last_reform['rank'],
            world=ctl.last_reform['world'])

    def _note_progress(done):
        with open(progress, 'w') as f:
            f.write(str(done))

    i = 0
    if args.join:
        resumed = ctl.join()
        marks['admitted_wall'] = _time.time()
        reforms.append(dict(ctl.last_reform,
                            wall=marks['admitted_wall']))
        i = int(resumed or 0)
        _reseed()
        _note_progress(i)
        _atomic_json(os.path.join(args.workdir, f'admitted-{tag}.json'),
                     {'tag': tag, 'resumed': i,
                      'admitted_wall': marks['admitted_wall'],
                      'reform': dict(ctl.last_reform)})
    ctl.start_monitor()
    while True:
        if i >= args.steps:
            if not is_owner:
                break
            # tail guard: the owner keeps its coordinator seat (still
            # servicing admissions + the autoscaler loop) until the
            # parent releases it — a joiner spawned for a late kill
            # must find a live rendezvous even after training is done
            if os.path.exists(release):
                break
        if is_owner:
            scaler.observe()
            if ctl._pending_joins(ms):
                scaler.observe()    # a JOIN landed since the poll
                                    # above: ledger the admit decision
                                    # pre_step is about to honor
        resumed = ctl.pre_step()
        if resumed is not None:
            reforms.append(dict(ctl.last_reform, wall=_time.time()))
            i = int(resumed)
            _reseed()
            continue
        if i >= args.steps:
            _time.sleep(0.05)
            continue
        shard = holder['shard']
        pos = shard.position
        ids = [int(x) for x in shard.next_batch()]
        loss = _run_step(step, i + 1)
        # the consumption record must hit the disk BEFORE the beat that
        # publishes the step: a SIGKILL can then never yield a
        # committed step whose partner block went unrecorded
        samples.write(json.dumps({'step': i + 1, 'position': int(pos),
                                  'ids': ids, 'rank': shard.rank,
                                  'world': shard.world}) + '\n')
        samples.flush()
        os.fsync(samples.fileno())
        ctl.beat(i + 1)
        if not _churn_sync(ms, ctl, i + 1):
            # barrier aborted (peer lost / JOIN pending): the step is
            # NOT committed — retreat to the last synced step so the
            # re-form's commit + restore replays it
            ctl.last_step = i
            continue
        i += 1
        losses[i] = float(loss).hex()
        if is_owner:
            mgr.maybe_save(i)
        _note_progress(i)
        if args.step_sleep:
            _time.sleep(args.step_sleep)
    ctl.stop_monitor()
    samples.close()
    out = {'marks': marks, 'losses': losses, 'reforms': reforms,
           'world': ms.world_size(), 'peer_losses': ctl.peer_losses}
    if is_owner:
        out['autoscaler'] = scaler.decisions
    _atomic_json(os.path.join(args.workdir, f'result-{tag}.json'), out)
    mgr.close()
    ms.stop()


def _churn_baseline(args):
    """Fixed-world reference: one process, no churn, same model and
    per-step data — the trajectory every churn survivor must match
    bit-for-bit."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass
    from mxnet_tpu.parallel import make_mesh

    mesh = make_mesh(devices=jax.local_devices())
    bdir = os.path.join(args.workdir, 'baseline')
    os.makedirs(bdir, exist_ok=True)
    net, step, mgr = _build(bdir, 0, mesh)
    losses = {}
    for i in range(args.steps):
        losses[i + 1] = float(_run_step(step, i + 1)).hex()
    mgr.close()
    _atomic_json(os.path.join(args.workdir, 'result-baseline.json'),
                 {'losses': losses})


def run_churn_drill(workdir, steps=30, cycles=3, heartbeat=0.15,
                    deadline=1.2, step_sleep=0.2, seed=23,
                    timeout=420.0):
    """Churn storm (elastic scale-UP acceptance): ``cycles`` randomized
    SIGKILL + rejoin rounds against a two-rank elastic world, then
    prove the storm was harmless:

    1. the owner's loss trajectory is bit-identical to a fixed-world
       run that was never interrupted;
    2. data exactly-once: replaying every incarnation's consumption
       ledger (pruned to each cycle's committed rollback point) covers
       every global batch exactly once — no sample dropped, none seen
       twice — and every record's block matches the deterministic
       world-indexed assignment at its recorded position;
    3. the re-form ledger shows one shrink + one admission per cycle,
       and the autoscaler requested + admitted capacity each time.

    Kill steps are randomized-but-deterministic via the fault
    registry's hash stream (``faults._unit(seed, cycle)``). Returns
    per-cycle MTTR phases (detect / request / rendezvous / admission /
    full restore-world time) for PERF_NOTES."""
    from .faults import _unit
    os.makedirs(workdir, exist_ok=True)
    side_port = _free_port()
    env = dict(os.environ)
    env.update({
        'PYTHONPATH': os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))] +
            ([env['PYTHONPATH']] if env.get('PYTHONPATH') else [])),
        'JAX_PLATFORMS': 'cpu',
        'XLA_FLAGS': '--xla_force_host_platform_device_count=2',
        # process-local meshes by construction: the membership side
        # channel is the only cross-process link (no jax.distributed)
        'MXNET_TPU_NUM_PROCS': '1',
        'MXNET_TPU_PROC_ID': '0',
        'MXTPU_ELASTIC': '0',
    })
    env.pop('MXNET_TPU_COORDINATOR', None)
    base = [sys.executable, '-m', 'mxnet_tpu.resilience.drill',
            '--workdir', workdir, '--steps', str(steps),
            '--port', str(side_port), '--heartbeat', str(heartbeat),
            '--deadline', str(deadline),
            '--step-sleep', str(step_sleep)]
    req_path = os.path.join(workdir, 'capacity-requests.jsonl')
    procs, logs = {}, []

    def _spawn(tag, rank, join=False):
        log = open(os.path.join(workdir, f'worker-{tag}.log'), 'wb')
        logs.append(log)
        cmd = base + ['--churn-worker', '--rank', str(rank),
                      '--tag', tag] + (['--join'] if join else [])
        procs[tag] = subprocess.Popen(cmd, env=env, stdout=log,
                                      stderr=subprocess.STDOUT)

    def _fail(msg):
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        errs = []
        for log in logs:
            log.flush()
            try:
                with open(log.name, 'rb') as f:
                    errs.append(f"-- {os.path.basename(log.name)} --\n"
                                + f.read().decode(
                                    errors='replace')[-3000:])
            except OSError:
                pass
        raise AssertionError(msg + '\n' + '\n'.join(errs))

    def _requests():
        try:
            with open(req_path) as f:
                return [json.loads(ln) for ln in f if ln.strip()]
        except OSError:
            return []

    # randomized-but-deterministic kill schedule: cycle c kills inside
    # the c-th slice of the step budget so cycles never collide
    lo = 3
    span = max(1, (max(4, steps - 4) - lo) // cycles)
    kill_steps = [lo + c * span + int(_unit(seed, c) * span)
                  for c in range(cycles)]

    _spawn('r0', 0)
    _spawn('r1c0', 1)
    cycle_stats = []
    last_resumed = 0
    try:
        for c in range(cycles):
            victim = f'r1c{c}'
            target = min(steps - 2,
                         max(kill_steps[c], last_resumed + 2))
            for tag in ('r0', victim):
                if not _wait_progress(
                        os.path.join(workdir, f'progress-{tag}.txt'),
                        target, timeout / 2):
                    _fail(f"churn: {tag} never reached step {target} "
                          f"(cycle {c})")
            nreq = len(_requests())
            procs[victim].kill()        # SIGKILL mid-step, no flush
            kill_wall = _time.time()
            procs[victim].wait()
            # the autoscaler inside rank 0 must notice the shrink and
            # ask this parent — its capacity provider — for a new rank
            deadline_t = _time.monotonic() + timeout / 4
            while _time.monotonic() < deadline_t:
                if len(_requests()) > nreq:
                    break
                if procs['r0'].poll() is not None:
                    _fail(f"churn: rank 0 died during cycle {c}")
                _time.sleep(0.05)
            else:
                _fail(f"churn: autoscaler never requested capacity "
                      f"after kill {c}")
            request_wall = float(_requests()[-1]['wall'])
            joiner = f'r1c{c + 1}'
            spawn_wall = _time.time()
            _spawn(joiner, 1, join=True)
            admit_path = os.path.join(workdir,
                                      f'admitted-{joiner}.json')
            while _time.monotonic() < deadline_t:
                if os.path.exists(admit_path):
                    break
                if procs[joiner].poll() is not None:
                    _fail(f"churn: joiner {joiner} died before "
                          f"admission")
                _time.sleep(0.05)
            else:
                _fail(f"churn: {joiner} was never admitted")
            with open(admit_path) as f:
                admitted = json.load(f)
            last_resumed = int(admitted['resumed'])
            cycle_stats.append({
                'cycle': c, 'kill_step': target,
                'kill_wall': kill_wall,
                'request_wall': request_wall,
                'spawn_wall': spawn_wall,
                'admitted_wall': float(admitted['admitted_wall']),
                'resumed': last_resumed,
            })
        last_tag = f'r1c{cycles}'
        try:
            rc = procs[last_tag].wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _fail(f"churn: {last_tag} never finished")
        if rc != 0:
            _fail(f"churn: {last_tag} exited rc={rc}")
        # release the owner's tail guard now every joiner is through
        with open(os.path.join(workdir, 'churn-release'), 'w') as f:
            f.write('done')
        try:
            rc = procs['r0'].wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _fail("churn: rank 0 never finished")
        if rc != 0:
            _fail(f"churn: rank 0 exited rc={rc}")
        # fixed-world reference trajectory
        r = subprocess.run(
            base + ['--churn-baseline'], env=env,
            capture_output=True, timeout=timeout)
        if r.returncode != 0:
            _fail("churn: baseline run failed\n" +
                  r.stdout.decode(errors='replace')[-3000:] +
                  r.stderr.decode(errors='replace')[-3000:])
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()

    with open(os.path.join(workdir, 'result-r0.json')) as f:
        r0 = json.load(f)
    with open(os.path.join(workdir, 'result-baseline.json')) as f:
        ref = json.load(f)

    # 1. loss parity: the churned trajectory IS the fixed-world one
    assert r0['losses'] == ref['losses'], (
        "churned trajectory diverges from the fixed-world run",
        {k: (r0['losses'].get(k), ref['losses'].get(k))
         for k in set(r0['losses']) | set(ref['losses'])
         if r0['losses'].get(k) != ref['losses'].get(k)})

    # 2. the re-form ledger: one shrink + one admission per cycle
    shrinks = [rf for rf in r0['reforms'] if rf.get('lost')]
    grows = [rf for rf in r0['reforms'] if rf.get('grow')]
    assert len(shrinks) == cycles and len(grows) == cycles, \
        r0['reforms']

    # 3. exactly-once coverage replayed from the consumption ledgers
    from ..io.io import ElasticShard
    exp = ElasticShard(_CHURN_SAMPLES, _CHURN_BATCH, rank=0, world=1,
                       seed=_CHURN_SEED)

    def _records(tag):
        out = {}
        try:
            with open(os.path.join(workdir,
                                   f'samples-{tag}.jsonl')) as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue   # torn final line of a SIGKILL
                    out[int(rec['step'])] = rec   # last record wins
        except OSError:
            pass
        return out

    recs, prune = {'r0': _records('r0')}, {}
    for c in range(cycles + 1):
        tag = f'r1c{c}'
        recs[tag] = _records(tag)
        if c < cycles:
            # a dead incarnation's records past the shrink re-form's
            # committed rollback point were never part of the
            # trajectory: the survivor re-ran those steps itself
            prune[tag] = int(shrinks[c]['resumed_step'])
    for s in range(1, steps + 1):
        base_pos = (s - 1) * _CHURN_BATCH
        want = [int(exp.sample_at(base_pos + j))
                for j in range(_CHURN_BATCH)]
        got = []
        for tag, rs in sorted(recs.items()):
            rec = rs.get(s)
            if rec is None or (tag in prune and s > prune[tag]):
                continue
            per = _CHURN_BATCH // int(rec['world'])
            blk = int(rec['rank']) * per
            assert rec['ids'] == want[blk:blk + per], (
                f"step {s}: {tag} consumed the wrong block", rec, want)
            assert int(rec['position']) == base_pos, (s, rec)
            got.extend(rec['ids'])
        assert sorted(got) == sorted(want), (
            f"step {s}: global batch not covered exactly once",
            {'missing': sorted(set(want) - set(got)),
             'extra': sorted({x for x in got if got.count(x) > 1})})

    # 4. the autoscaler drove every recovery
    ledger = r0.get('autoscaler') or []
    n_req = sum(1 for d in ledger if d['kind'] == 'request_capacity')
    n_adm = sum(1 for d in ledger if d['kind'] == 'admit')
    assert n_req >= cycles and n_adm >= cycles, ledger

    mttr = []
    for c, st in enumerate(cycle_stats):
        shrink, grow = shrinks[c], grows[c]
        mttr.append({
            'cycle': c, 'kill_step': st['kill_step'],
            'detect_seconds': round(
                shrink['wall'] - shrink['reform_seconds']
                - st['kill_wall'], 3),
            'shrink_reform_seconds': shrink['reform_seconds'],
            'request_seconds': round(
                st['request_wall'] - st['kill_wall'], 3),
            'spawn_seconds': round(
                st['spawn_wall'] - st['kill_wall'], 3),
            'rendezvous_seconds': grow['rendezvous_seconds'],
            'admission_seconds': grow['admission_seconds'],
            'restored_world_seconds': round(
                st['admitted_wall'] - st['kill_wall'], 3),
        })
    return {
        'ok': True, 'steps': steps, 'cycles': cycles,
        'kill_steps': [st['kill_step'] for st in cycle_stats],
        'loss_parity': True, 'coverage_exact': True,
        'autoscaler': {'requests': n_req, 'admits': n_adm,
                       'decisions': len(ledger)},
        'mttr': mttr,
    }


def _serve_model():
    """The drill's serving model: tiny token-in/logits-out block. Every
    process builds it identically (auto-named — the jit boundary is
    name-stable, PR 17 satellite), so a checkpoint pushed from one
    process loads into another's block by parameter name."""
    from mxnet_tpu.gluon import nn

    class TinyTok(nn.HybridBlock):
        def __init__(self, vocab=64, dim=8, classes=4, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(vocab, dim)
                self.proj = nn.Dense(classes, flatten=False)

        def forward(self, x):
            return self.proj(self.embed(x))

    net = TinyTok()
    net.initialize()
    return net


_SERVE_WORLD = 3      # rank 0 = the router/observer, ranks 1..2 serve


def _serving_worker(args):
    """One serving replica of the drain drill: membership rank
    ``args.rank`` of a 3-rank view (rank 0 is the parent's router),
    warmup through the SHARED persistent compile cache, then a
    PredictServer + hosted ReplicaServer until drained (SIGTERM or
    POST /drain)."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    from mxnet_tpu import serving
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.telemetry import compile as _compile

    rank = args.rank
    _compile.enable()
    ms = dist.Membership(rank, _SERVE_WORLD, port=args.port,
                         heartbeat_seconds=args.heartbeat,
                         deadline_seconds=args.deadline)
    net = _serve_model()
    engine = serving.InferenceEngine(
        serving.BlockRunner(net), seq_buckets='8,16',
        batch_buckets='1,2,4', deadline_ms=2.0)
    warm = serving.warmup(engine)
    ledger_after_warmup = len(_compile.ledger())
    store = os.path.join(args.workdir, f'store-rank{rank}')
    rs = dist.ReplicaServer(store, port=args.replica_base + rank)
    srv = serving.PredictServer(engine, port=args.serve_base + rank,
                                membership=ms, block=net,
                                replica_root=store)
    srv.install_sigterm()
    ready = {'rank': rank, 'serve_port': srv.port,
             'replica_port': args.replica_base + rank, 'warmup': warm}
    _atomic_json(os.path.join(args.workdir, f'ready-rank{rank}.json'),
                 ready)
    while not srv.draining.is_set():
        _time.sleep(0.05)
    # drain() flushed the engine + left the membership; wait for the
    # listener to retire (drain's final stop()) then report and exit
    deadline = _time.monotonic() + 30.0
    while srv._server is not None and _time.monotonic() < deadline:
        _time.sleep(0.05)
    out = {'rank': rank, 'stats': engine.stats(),
           'ledger_after_warmup': ledger_after_warmup,
           'ledger_final': len(_compile.ledger()),
           'reloaded_step': srv.reloaded_step}
    _atomic_json(os.path.join(args.workdir, f'result-rank{rank}.json'),
                 out)
    rs.stop()
    ms.stop()


def _atomic_json(path, doc):
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(doc, f, indent=1, default=str)
    os.replace(tmp, path)


def run_serving_drill(workdir, requests=90, kill_rank=1, heartbeat=0.1,
                      deadline=2.0, timeout=180.0):
    """Two-replica serving drain drill (ISSUE 17).

    Spawns 2 replica processes (membership ranks 1..2; this process is
    rank 0, the router's observer seat) sharing one persistent compile
    cache dir, storms the fleet through the ``serving.Router``, and
    ``SIGTERM``s rank ``kill_rank`` mid-storm. Asserts:

    - both replicas warmed their full bucket grid before the first
      request (and the SECOND replica's warmup rode the first's
      persistent cache);
    - the storm finishes with **zero failed requests** — predicts that
      hit the dying replica fail over inside the router;
    - zero steady-state recompiles on the survivor (compile ledger is
      flat after warmup);
    - the drained replica LEAVES the membership (a departure, not a
      loss) and the router's set drops it — MTTR is measured from the
      SIGTERM to the router no longer holding the dead rank;
    - a weight push (replica transport + POST /reload) lands on the
      survivor and its predictions flip to the pushed weights exactly.

    Returns the measured numbers for PERF_NOTES / dryrun_multichip."""
    import threading

    import numpy as onp

    from mxnet_tpu import nd, serving
    from mxnet_tpu.parallel import dist

    os.makedirs(workdir, exist_ok=True)
    side_port = _free_port()
    serve_base = _free_port_base(_SERVE_WORLD)
    replica_base = _free_port_base(_SERVE_WORLD)
    cache_dir = os.path.join(workdir, 'xla_cache')
    env = dict(os.environ)
    env.update({
        'PYTHONPATH': os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))] +
            ([env['PYTHONPATH']] if env.get('PYTHONPATH') else [])),
        'JAX_PLATFORMS': 'cpu',
        'MXNET_TPU_TELEMETRY': '1',
        'JAX_COMPILATION_CACHE_DIR': cache_dir,
        'MXTPU_FLIGHT_DIR': workdir,
    })
    ms = dist.Membership(0, _SERVE_WORLD, port=side_port,
                         heartbeat_seconds=heartbeat,
                         deadline_seconds=deadline)
    base = [sys.executable, '-m', 'mxnet_tpu.resilience.drill',
            '--serve', '--workdir', workdir, '--port', str(side_port),
            '--serve-base', str(serve_base),
            '--replica-base', str(replica_base),
            '--heartbeat', str(heartbeat), '--deadline', str(deadline)]
    procs, logs = {}, []

    def _spawn(r):
        log = open(os.path.join(workdir, f'serve-rank{r}.log'), 'wb')
        logs.append(log)
        procs[r] = subprocess.Popen(base + ['--rank', str(r)], env=env,
                                    stdout=log, stderr=subprocess.STDOUT)

    def _fail(msg):
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        errs = []
        for log in logs:
            log.flush()
            try:
                with open(log.name, 'rb') as f:
                    errs.append(f"-- {log.name} --\n" +
                                f.read().decode(errors='replace')[-3000:])
            except OSError:
                pass
        raise AssertionError(msg + '\n' + '\n'.join(errs))

    def _wait_ready(ready, r, t0):
        while _time.monotonic() - t0 < timeout and r not in ready:
            p = os.path.join(workdir, f'ready-rank{r}.json')
            if os.path.exists(p):
                try:
                    with open(p) as f:
                        ready[r] = json.load(f)
                    break
                except (OSError, ValueError):
                    pass
            if procs[r].poll() is not None:
                _fail(f"serving drill: rank {r} died before ready")
            _time.sleep(0.05)
        if r not in ready:
            _fail(f"serving drill: rank {r} never finished warmup")

    try:
        # replica 1 warms COLD (pays every XLA compile into the shared
        # cache dir), then replica 2 starts and warms WARM — the
        # persistent-cache startup win, measured
        ready, t0 = {}, _time.monotonic()
        _spawn(1)
        _wait_ready(ready, 1, t0)
        _spawn(2)
        _wait_ready(ready, 2, t0)
        for r in (1, 2):
            assert ready[r]['warmup']['buckets'], ready[r]
        assert ready[2]['warmup']['cache']['hits'] > 0, \
            f"warm replica never hit the persistent cache: {ready[2]}"
        survivor = 3 - kill_rank

        # storm through the router; SIGTERM kill_rank a third in
        router = serving.Router(membership=ms, serve_port_base=serve_base,
                                timeout=30.0)
        rng = onp.random.RandomState(7)
        storm = [[int(v) for v in rng.randint(0, 64, rng.randint(1, 17))]
                 for _ in range(requests)]
        failures, t_kill = [], [None]
        lock = threading.Lock()

        def _client(i, seq):
            if i == requests // 3 and t_kill[0] is None:
                with lock:
                    if t_kill[0] is None:
                        t_kill[0] = _time.monotonic()
                        procs[kill_rank].send_signal(signal.SIGTERM)
            try:
                out = router.predict(seq)
                assert len(out) == len(seq), (len(out), len(seq))
            except Exception as e:                    # noqa: BLE001
                failures.append((i, repr(e)))

        threads = [threading.Thread(target=_client, args=(i, s))
                   for i, s in enumerate(storm)]
        for i, t in enumerate(threads):
            t.start()
            if i % 8 == 7:
                _time.sleep(0.02)      # a storm, not one thundering herd
        for t in threads:
            t.join(timeout=60)
        assert not failures, \
            f"{len(failures)} predicts failed: {failures[:5]}"
        assert t_kill[0] is not None, "the kill point never fired"

        # MTTR: SIGTERM -> router no longer holds the drained rank
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 30.0:
            router.refresh()
            with router._lock:
                gone = kill_rank not in router._replicas
            if gone:
                break
            _time.sleep(0.02)
        assert gone, "router never dropped the drained replica"
        mttr = _time.monotonic() - t_kill[0]
        view = ms.view()
        assert kill_rank in (view.get('left') or []), \
            f"drained rank should be a DEPARTURE, view={view}"

        # weight push: new weights reach the survivor over the replica
        # transport and flip its predictions exactly
        # the replicas run on the CPU backend, so the expectation is
        # computed there too, whatever platform this process defaults to
        import jax
        with jax.default_device(jax.devices('cpu')[0]):
            net = _serve_model()
            probe = [1, 2, 3, 5, 7]
            want = onp.asarray(net(nd.array(
                onp.asarray([probe + [0] * 3], 'int32'))).asnumpy())[0, :5]
        push = serving.push_weights(
            net, step=7,
            replicas=[{'host': '127.0.0.1',
                       'replica_port': replica_base + survivor,
                       'serve_port': serve_base + survivor}])
        res = push[serve_base + survivor]
        assert res.get('status') == 200, push
        got = onp.asarray(router.predict(probe), onp.float64)
        assert onp.allclose(got, want, atol=1e-5), (got, want)

        # graceful drain of the survivor ends the exercise
        status, _doc = serving.http_json(
            '127.0.0.1', serve_base + survivor, '/drain', {})
        assert status == 200, status
        results = {}
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 60.0 and len(results) < 2:
            for r in (1, 2):
                if r in results:
                    continue
                p = os.path.join(workdir, f'result-rank{r}.json')
                if os.path.exists(p):
                    try:
                        with open(p) as f:
                            results[r] = json.load(f)
                    except (OSError, ValueError):
                        pass
            _time.sleep(0.05)
        if len(results) < 2:
            _fail("serving drill: replicas never wrote results")
        for r in (1, 2):
            assert results[r]['ledger_final'] == \
                results[r]['ledger_after_warmup'], \
                f"rank {r} recompiled post-warmup: {results[r]}"
        assert results[survivor]['reloaded_step'] == 7, results[survivor]
        for r, p in procs.items():
            try:
                rc = p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                _fail(f"serving drill: rank {r} never exited")
            assert rc == 0, f"rank {r} exited {rc}"
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()
        ms.stop()
    served = {r: results[r]['stats'] for r in results}
    return {
        'ok': True,
        'requests': requests,
        'failed': 0,
        'failovers': router.failovers,
        'mttr_seconds': round(mttr, 4),
        'warmup': {r: ready[r]['warmup'] for r in ready},
        'stats': served,
        'reloaded_step': results[survivor]['reloaded_step'],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--worker', action='store_true')
    ap.add_argument('--fleet', action='store_true')
    ap.add_argument('--serve', action='store_true')
    ap.add_argument('--rank', type=int, default=1)
    ap.add_argument('--serve-base', type=int, default=0)
    ap.add_argument('--replica-base', type=int, default=0)
    ap.add_argument('--slow-rank', type=int, default=1)
    ap.add_argument('--slow-ms', type=float, default=0.0)
    ap.add_argument('--reference', action='store_true')
    ap.add_argument('--workdir', required=True)
    ap.add_argument('--steps', type=int, default=10)
    ap.add_argument('--port', type=int, default=0)
    ap.add_argument('--heartbeat', type=float, default=0.2)
    ap.add_argument('--deadline', type=float, default=1.2)
    ap.add_argument('--step-sleep', type=float, default=0.35)
    ap.add_argument('--ref-rank', type=int, default=0)
    ap.add_argument('--disk-loss', action='store_true')
    ap.add_argument('--ckpt-owner', type=int, default=None)
    ap.add_argument('--churn-worker', action='store_true')
    ap.add_argument('--churn-baseline', action='store_true')
    ap.add_argument('--join', action='store_true')
    ap.add_argument('--tag', default='')
    args = ap.parse_args(argv)
    if args.serve:
        _serving_worker(args)
    elif args.churn_worker:
        _churn_worker(args)
    elif args.churn_baseline:
        _churn_baseline(args)
    elif args.fleet and args.worker is False and args.reference is False:
        _fleet_worker(args)
    elif args.worker:
        _worker(args)
    elif args.reference:
        _reference(args)
    else:
        print(json.dumps(run_drill(args.workdir, steps=args.steps,
                                   heartbeat=args.heartbeat,
                                   deadline=args.deadline,
                                   step_sleep=args.step_sleep,
                                   disk_loss=args.disk_loss), indent=1))
    return 0


if __name__ == '__main__':
    sys.exit(main())
