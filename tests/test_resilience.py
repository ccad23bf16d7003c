"""Training resilience layer: fault injection, non-finite guard with
auto-rollback, step watchdog, bounded retries, corrupt-record recovery.

Every recovery path here is exercised by REAL injected faults
(resilience.faults) with deterministic per-seed firing, so these tests
are exactly reproducible — tools/flakiness_checker.py runs a core one
3x in test_fault_injection_seeds_are_deterministic_3x to prove it.
"""
import io as _io
import os
import signal
import subprocess
import sys
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon, checkpoint, resilience, telemetry
from mxnet_tpu.base import DataError, MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import (InjectedFault, NonFiniteGuard,
                                  StepWatchdog, faults, retry_call)


@pytest.fixture(autouse=True)
def _clean_faults_and_telemetry():
    faults.disarm()
    telemetry.enable()
    telemetry.reset()
    yield
    faults.disarm()
    telemetry.reset()
    telemetry.disable()


# ---------------------------------------------------------------------------
# fault registry + grammar + determinism
# ---------------------------------------------------------------------------

def test_fault_sites_registered_and_unknown_site_raises():
    s = faults.sites()
    for name in ('io.decode', 'io.device_put', 'dataloader.worker',
                 'step.dispatch', 'checkpoint.write', 'checkpoint.read',
                 'collective.all_reduce', 'dist.file_put'):
        assert name in s
    with pytest.raises(MXNetError, match='unknown fault site'):
        faults.arm('io.decoed', 'raise')          # typo fails loudly
    with pytest.raises(MXNetError, match='unknown fault kind'):
        faults.arm('io.decode', 'explode')
    with pytest.raises(MXNetError, match='not meaningful'):
        faults.arm('io.device_put', 'nan')


def test_fault_env_grammar():
    n = faults.arm_from_env(
        'step.dispatch:nan:1:0:5-7, io.decode:corrupt:0.25:42;'
        'checkpoint.write:raise:1:9:3')
    assert n == 3
    spec = faults.active()
    assert spec['step.dispatch'] == {
        'kind': 'nan', 'prob': 1.0, 'seed': 0, 'first': 5, 'last': 7,
        'count': 0, 'fired': 0}
    assert spec['io.decode']['prob'] == 0.25
    assert spec['io.decode']['seed'] == 42
    assert spec['checkpoint.write']['first'] == 3
    assert spec['checkpoint.write']['last'] == 3
    assert faults.arm_from_env('') == 0
    assert faults.active() == {}
    with pytest.raises(MXNetError, match='expected'):
        faults.arm_from_env('justasite')
    # a malformed numeric field fails as loudly as a site/kind typo —
    # naming the env var and the grammar, not a bare ValueError at import
    with pytest.raises(MXNetError, match='MXTPU_FAULT.*bad numeric'):
        faults.arm_from_env('step.dispatch:nan:abc')
    with pytest.raises(MXNetError, match='MXTPU_FAULT.*bad numeric'):
        faults.arm_from_env('step.dispatch:nan:1:0:5-x')


def test_fault_window_and_prob_determinism():
    # window: fires exactly on occurrences 5..7, never elsewhere
    faults.arm('step.dispatch', 'nan', window=(5, 7))
    fired = [faults.fire('step.dispatch') for _ in range(10)]
    assert fired == [None] * 4 + ['nan'] * 3 + [None] * 3
    # probabilistic firing is a pure function of (seed, occurrence):
    # two fresh arms with the same seed produce the identical pattern
    patterns = []
    for _ in range(2):
        faults.arm('io.decode', 'corrupt', prob=0.5, seed=123)
        patterns.append(tuple(faults.fire('io.decode')
                              for _ in range(64)))
    assert patterns[0] == patterns[1]
    assert 10 < sum(k == 'corrupt' for k in patterns[0]) < 54
    # ... and a different seed produces a different pattern
    faults.arm('io.decode', 'corrupt', prob=0.5, seed=124)
    other = tuple(faults.fire('io.decode') for _ in range(64))
    assert other != patterns[0]


def test_fault_raise_and_corrupt_bytes():
    faults.arm('checkpoint.write', 'raise', window=2)
    assert faults.fire('checkpoint.write') is None
    with pytest.raises(InjectedFault) as ei:
        faults.fire('checkpoint.write')
    assert ei.value.site == 'checkpoint.write'
    assert ei.value.occurrence == 2
    data = b'\x89PNG' + bytes(range(200))
    c1 = faults.corrupt_bytes(data, occurrence=7)
    assert c1 == faults.corrupt_bytes(data, occurrence=7)  # deterministic
    assert c1 != data and len(c1) == len(data)
    assert c1[:4] != data[:4]                  # format magic destroyed
    assert faults.fire('io.decode') is None    # disarmed site: no-op


def test_fault_injection_counted_in_telemetry():
    faults.arm('step.dispatch', 'nan')
    faults.fire('step.dispatch')
    faults.fire('step.dispatch')
    assert telemetry.value('mxnet_tpu_resilience_faults_injected_total',
                           site='step.dispatch', kind='nan') == 2


# ---------------------------------------------------------------------------
# bounded retry helper
# ---------------------------------------------------------------------------

def test_retry_call_bounded_and_counted():
    calls = []

    def flaky(x):
        calls.append(x)
        if len(calls) < 3:
            raise OSError('transient')
        return x * 2

    assert retry_call(flaky, 21, retries=2, backoff_seconds=0,
                      site='unit.test') == 42
    assert len(calls) == 3
    assert telemetry.value('mxnet_tpu_resilience_retries_total',
                           site='unit.test') == 2
    # budget exhausted: the ORIGINAL error propagates
    calls.clear()
    with pytest.raises(OSError, match='transient'):
        retry_call(flaky, 1, retries=1, backoff_seconds=0, site='unit.test')
    assert len(calls) == 2
    # non-retryable exceptions propagate immediately
    calls.clear()
    with pytest.raises(ValueError):
        retry_call(lambda: (_ for _ in ()).throw(ValueError('no')),
                   retries=5, backoff_seconds=0)


# ---------------------------------------------------------------------------
# non-finite guard: on-device skip + policy ladder
# ---------------------------------------------------------------------------

def _toy_regression(n=64, d=4, seed=0):
    rng = onp.random.RandomState(seed)
    x = rng.randn(n, d).astype(onp.float32)
    w = rng.randn(d, 1).astype(onp.float32)
    return x, x.dot(w)


def test_guard_skips_nonfinite_steps_on_device():
    x, y = _toy_regression()
    net = nn.Dense(1, in_units=4)
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 0.05})
    guard = NonFiniteGuard(policy='skip', max_consecutive_bad=10)
    trainer.attach_guard(guard)
    faults.arm('step.dispatch', 'nan', window=(2, 3))
    weights = []
    for step in range(1, 6):
        with autograd.record():
            loss = loss_fn(net(nd.array(x)), nd.array(y))
        loss.backward()
        trainer.step(len(x))
        weights.append(net.weight.data().asnumpy().copy())
    assert all(onp.isfinite(w).all() for w in weights)
    # poisoned steps 2-3 were no-ops ON DEVICE (weights frozen at step 1)
    assert onp.array_equal(weights[0], weights[1])
    assert onp.array_equal(weights[1], weights[2])
    assert not onp.array_equal(weights[2], weights[3])
    assert guard.bad_steps == 2
    assert telemetry.value('mxnet_tpu_resilience_bad_steps_total') == 2
    # a skipped step is a TRUE no-op: the host-side adam update counts
    # were rewound, so 5 steps with 2 skipped advanced t only 3 times
    assert all(t == 3 for t in
               trainer._optimizer._index_update_count.values()), \
        trainer._optimizer._index_update_count


def test_guard_skip_matches_clean_run_bitwise():
    """5 guarded steps with steps 2-3 NaN-skipped must land on weights
    BIT-IDENTICAL to 3 clean steps — skipped steps leave no trace in
    weights, optimizer moments, or the adam t counter."""
    x, y = _toy_regression()

    def run(n_steps, fault=False):
        mx.random.seed(11)
        onp.random.seed(11)
        net = nn.Dense(1, in_units=4)
        net.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(net.collect_params(), 'adam',
                                {'learning_rate': 0.05})
        guard = NonFiniteGuard(policy='skip', max_consecutive_bad=10)
        trainer.attach_guard(guard)
        if fault:
            faults.arm('step.dispatch', 'nan', window=(2, 3))
        loss_fn = gluon.loss.L2Loss()
        for step in range(n_steps):
            with autograd.record():
                loss = loss_fn(net(nd.array(x)), nd.array(y))
            loss.backward()
            trainer.step(len(x))
        faults.disarm()
        return net

    net_a = run(5, fault=True)    # 5 steps, 2 skipped on device
    net_b = run(3, fault=False)   # 3 clean steps
    assert onp.array_equal(net_a.weight.data().asnumpy(),
                           net_b.weight.data().asnumpy())
    assert onp.array_equal(net_a.bias.data().asnumpy(),
                           net_b.bias.data().asnumpy())


def test_guard_covers_update_on_kvstore_path():
    """The kvstore-side update (sparse weights force it) cannot fuse the
    guard on device — the eager pre-push check must skip the push."""
    x, y = _toy_regression()
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1}, kvstore='device',
                            update_on_kvstore=True)
    trainer.attach_guard(NonFiniteGuard(policy='skip',
                                        max_consecutive_bad=10))
    loss_fn = gluon.loss.L2Loss()
    faults.arm('step.dispatch', 'nan', window=(2, 3))
    weights = []
    for step in range(5):
        with autograd.record():
            loss = loss_fn(net(nd.array(x)), nd.array(y))
        loss.backward()
        trainer.step(len(x))
        weights.append(net.weight.data().asnumpy().copy())
    assert all(onp.isfinite(w).all() for w in weights)
    assert onp.array_equal(weights[1], weights[2])   # poisoned: no push
    assert not onp.array_equal(weights[3], weights[4])


def test_guard_policy_raise():
    x, y = _toy_regression()
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    trainer.attach_guard(NonFiniteGuard(policy='raise',
                                        max_consecutive_bad=2))
    loss_fn = gluon.loss.L2Loss()
    faults.arm('step.dispatch', 'nan')
    with pytest.raises(MXNetError, match='consecutive non-finite'):
        for step in range(6):
            with autograd.record():
                loss = loss_fn(net(nd.array(x)), nd.array(y))
            loss.backward()
            trainer.step(len(x))


def test_guard_requires_manager_for_rollback_policy():
    with pytest.raises(MXNetError, match='CheckpointManager'):
        NonFiniteGuard(policy='rollback', manager=None)


def _guarded_run(ckpt_dir, total_steps, fault_spec=None, data_seed=0):
    """One gluon training run under guard supervision. Returns
    (net, trainer, per-step losses, guard)."""
    mx.random.seed(7)
    onp.random.seed(7)
    x, y = _toy_regression(seed=data_seed)
    net = nn.Dense(1, in_units=4)
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 0.1})
    mgr = checkpoint.CheckpointManager(
        ckpt_dir, params=net, trainer=trainer, keep_last_n=100,
        autosave_steps=1, async_save=False)
    guard = NonFiniteGuard(manager=mgr, max_consecutive_bad=3)
    trainer.attach_guard(guard)
    if fault_spec:
        faults.arm_from_env(fault_spec)
    losses = []
    for step in range(1, total_steps + 1):
        with autograd.record():
            loss = loss_fn(net(nd.array(x)), nd.array(y))
        loss.backward()
        trainer.step(len(x))
        guard.maybe_save(step)
        losses.append(float(loss.mean().asscalar()))
    faults.disarm()
    mgr.close()
    return net, trainer, losses, guard


def test_guard_rollback_e2e_nan_steps_5_to_7(tmp_path):
    """The ISSUE acceptance scenario: MXTPU_FAULT grammar forces NaN
    gradients on exactly steps 5-7; the guard skips each on device,
    counts 3 consecutive bad steps, auto-restores the step-4 checkpoint
    (params + optimizer state + RNG), and the run converges to the same
    final loss as an uninjected run with the same seeds. The resumed
    trajectory is bit-identical to a clean run restored from that same
    step-4 checkpoint."""
    total = 80
    net_a, trainer_a, losses_a, guard_a = _guarded_run(
        str(tmp_path / 'a'), total,
        fault_spec='step.dispatch:nan:1:0:5-7')
    # the ladder: 3 bad steps -> exactly one rollback, to step 4
    assert guard_a.bad_steps == 3
    assert guard_a.rollbacks == 1
    assert guard_a.last_rollback_step == 4
    assert telemetry.value('mxnet_tpu_resilience_rollbacks_total') == 1
    assert telemetry.value(
        'mxnet_tpu_resilience_last_rollback_step') == 4
    assert telemetry.value('mxnet_tpu_resilience_recovery_seconds')[0] == 1
    # no checkpoint captured a poisoned step (saves 5-7 were flag-gated;
    # step 8 is the post-rollback re-save of restored state)
    mgr_a = checkpoint.CheckpointManager(str(tmp_path / 'a'),
                                         keep_last_n=100)
    steps = mgr_a.all_steps()
    assert 4 in steps and total in steps
    assert not {5, 6, 7} & set(steps)

    # bit-identical resume: replay from the SAME step-4 checkpoint in a
    # fresh process-state (fresh net/trainer), applying the same
    # post-rollback updates (steps 9..total; step 8's update was
    # dropped), and land on byte-equal weights
    mx.random.seed(7)
    onp.random.seed(7)
    x, y = _toy_regression(seed=0)
    net_b = nn.Dense(1, in_units=4)
    net_b.initialize()
    trainer_b = gluon.Trainer(net_b.collect_params(), 'adam',
                              {'learning_rate': 0.1})
    mgr_b = checkpoint.CheckpointManager(str(tmp_path / 'a'),
                                         params=net_b, trainer=trainer_b,
                                         keep_last_n=100)
    assert mgr_b.restore(4) == 4
    # a guard on the control arm too: the comparison below is bit for
    # bit, so both arms have to run the same compiled update. Without
    # one the fused update is another program (no `where` on the
    # write-back), and after 72 steps one weight is an ulp apart.
    trainer_b.attach_guard(NonFiniteGuard(policy='skip'))
    loss_fn = gluon.loss.L2Loss()
    for step in range(9, total + 1):
        with autograd.record():
            loss = loss_fn(net_b(nd.array(x)), nd.array(y))
        loss.backward()
        trainer_b.step(len(x))
    assert onp.array_equal(net_a.weight.data().asnumpy(),
                           net_b.weight.data().asnumpy())
    assert onp.array_equal(net_a.bias.data().asnumpy(),
                           net_b.bias.data().asnumpy())

    # and an entirely uninjected run with the same seeds converges to
    # the same final loss (both are at the optimum by step 80)
    telemetry.reset()
    net_c, _, losses_c, guard_c = _guarded_run(str(tmp_path / 'c'), total)
    assert guard_c.bad_steps == 0 and guard_c.rollbacks == 0
    assert losses_a[-1] < 0.01 * losses_a[0]
    assert abs(losses_a[-1] - losses_c[-1]) < 5e-3


def test_guard_on_sharded_train_step():
    """The pjit path: the guard's isfinite reduction + on-device skip is
    fused into ShardedTrainStep's one compiled program."""
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
    mesh = make_mesh((8,), ('dp',))
    rng = onp.random.RandomState(0)
    x = rng.randn(32, 6).astype(onp.float32)
    y = rng.randn(32, 1).astype(onp.float32)
    net = nn.Dense(1, in_units=6)
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    guard = NonFiniteGuard(policy='skip', max_consecutive_bad=10)
    step = ShardedTrainStep(net, loss_fn, 'adam',
                            {'learning_rate': 0.05}, mesh=mesh,
                            guard=guard)
    faults.arm('step.dispatch', 'nan', window=(3, 4))
    weights = []
    for i in range(6):
        step(nd.array(x), nd.array(y))
        weights.append(net.weight.data().asnumpy().copy())
    assert all(onp.isfinite(w).all() for w in weights)
    assert onp.array_equal(weights[2], weights[3])   # poisoned: no-ops
    assert not onp.array_equal(weights[4], weights[5])
    assert guard.bad_steps == 2


# ---------------------------------------------------------------------------
# step watchdog
# ---------------------------------------------------------------------------

def test_watchdog_dumps_stacks_once_per_stall():
    reports = []
    wd = StepWatchdog(deadline_seconds=0.15, poll_seconds=0.03,
                      on_stall=reports.append)
    with wd:
        wd.beat(1)
        deadline = time.monotonic() + 3.0
        while not reports and time.monotonic() < deadline:
            time.sleep(0.02)          # stalled: no beats
        assert len(reports) == 1
        time.sleep(0.3)               # still stalled: NO second dump
        assert len(reports) == 1
        wd.beat(2)                    # progress re-arms the watchdog
        deadline = time.monotonic() + 3.0
        while len(reports) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(reports) == 2
    report = reports[0]
    assert 'no training-step heartbeat' in report
    assert 'last step 1' in report
    assert 'MainThread' in report          # all-thread stack dump
    assert 'test_watchdog_dumps_stacks_once_per_stall' in report
    assert wd.stalls == 2
    assert telemetry.value(
        'mxnet_tpu_resilience_watchdog_stalls_total') == 2


def test_watchdog_save_on_stall_commits_checkpoint(tmp_path):
    net = nn.Dense(2, in_units=3)
    net.initialize()
    mgr = checkpoint.CheckpointManager(str(tmp_path), params=net,
                                       async_save=False)
    mgr._current_step = 11
    done = []
    wd = StepWatchdog(deadline_seconds=0.1, poll_seconds=0.03,
                      manager=mgr, save_on_stall=True,
                      on_stall=done.append)
    with wd:
        deadline = time.monotonic() + 3.0
        while not done and time.monotonic() < deadline:
            time.sleep(0.02)
        deadline = time.monotonic() + 3.0
        while mgr.latest_step() != 11 and time.monotonic() < deadline:
            time.sleep(0.02)
    assert mgr.latest_step() == 11     # emergency save_now() committed


def test_watchdog_estimator_handler_beats(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import (Estimator,
                                                   WatchdogHandler)
    from mxnet_tpu.gluon.data import DataLoader, ArrayDataset
    x, y = _toy_regression(n=32)
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.01})
    est = Estimator(net, gluon.loss.L2Loss(), metrics=mx.metric.Loss(),
                    trainer=trainer, context=[mx.cpu()])
    handler = WatchdogHandler(deadline_seconds=60)
    est.fit(DataLoader(ArrayDataset(x, y), batch_size=16), epochs=2,
            event_handlers=[handler])
    assert handler.watchdog is None        # stopped at train_end
    assert handler._step == 4              # one beat per batch


# ---------------------------------------------------------------------------
# checkpoint write faults: transient retry + corrupt fallback
# ---------------------------------------------------------------------------

def test_checkpoint_write_transient_error_is_retried(tmp_path):
    net = nn.Dense(2, in_units=3)
    net.initialize()
    mgr = checkpoint.CheckpointManager(str(tmp_path), params=net,
                                       async_save=False)
    faults.arm('checkpoint.write', 'raise', window=1)   # first attempt only
    mgr.save(1)                                          # retried, commits
    assert mgr.latest_step() == 1
    assert mgr.restore_latest(apply=False).step == 1
    assert telemetry.value('mxnet_tpu_resilience_retries_total',
                           site='checkpoint.write') == 1


def test_checkpoint_write_corrupt_payload_falls_back(tmp_path):
    net = nn.Dense(2, in_units=3)
    net.initialize()
    mgr = checkpoint.CheckpointManager(str(tmp_path), params=net,
                                       async_save=False)
    mgr.save(1)
    faults.arm('checkpoint.write', 'corrupt', window=1)
    mgr.save(2)            # commits, but a payload's bytes are mangled
    assert mgr.all_steps() == [1, 2]
    with pytest.warns(RuntimeWarning, match='failed validation'):
        ck = mgr.restore_latest(apply=False)
    assert ck.step == 1    # hash mismatch on 2 -> previous step restored


# ---------------------------------------------------------------------------
# DataLoader worker respawn
# ---------------------------------------------------------------------------

def test_dataloader_worker_crash_respawns_bounded(tmp_path):
    from mxnet_tpu.gluon.data import DataLoader, ArrayDataset
    x = onp.arange(64, dtype=onp.float32).reshape(16, 4)
    y = onp.arange(16, dtype=onp.float32)
    loader = DataLoader(ArrayDataset(x, y), batch_size=4, num_workers=2,
                        worker_retries=2)
    faults.arm('dataloader.worker', 'raise', window=(1, 2))
    batches = list(loader)               # crashes respawned transparently
    assert len(batches) == 4
    got = onp.concatenate([b[0].asnumpy() for b in batches])
    assert onp.array_equal(onp.sort(got.ravel()), onp.sort(x.ravel()))
    assert telemetry.value(
        'mxnet_tpu_resilience_worker_respawns_total') == 2
    # budget exhausted -> a clear error naming the failing batch
    faults.arm('dataloader.worker', 'raise')     # every fetch crashes
    loader2 = DataLoader(ArrayDataset(x, y), batch_size=4, num_workers=2,
                         worker_retries=1)
    with pytest.raises(MXNetError, match=r'worker failed 2x on batch 0'):
        list(loader2)
    loader.close()
    loader2.close()


def test_dataloader_does_not_retry_data_errors(tmp_path):
    """Deterministic input corruption (DataError) must NOT be burned
    through the respawn budget and rewrapped — the index/offset context
    has to reach the caller intact."""
    from mxnet_tpu.gluon.data import DataLoader

    class CorruptAt:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i == 5:
                raise DataError('corrupt record 5 at offset 1234',
                                index=5, offset=1234, path='x.rec')
            return onp.float32(i)

    telemetry.reset()
    loader = DataLoader(CorruptAt(), batch_size=4, num_workers=2,
                        worker_retries=5)
    with pytest.raises(DataError) as ei:
        list(loader)
    assert ei.value.index == 5 and ei.value.offset == 1234
    assert telemetry.value(
        'mxnet_tpu_resilience_worker_respawns_total') is None
    loader.close()


def test_indexed_recordio_corrupt_read_idx_names_key(tmp_path,
                                                     monkeypatch):
    from mxnet_tpu import recordio, _native
    monkeypatch.setattr(_native, 'get_lib', lambda: None)
    rec_path = str(tmp_path / 'i.rec')
    idx_path = str(tmp_path / 'i.idx')
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, 'w')
    for k in range(4):
        w.write_idx(k, b'payload-%d' % k)
    w.close()
    r = recordio.MXIndexedRecordIO(idx_path, rec_path, 'r')
    pos = r.idx[2]
    r.close()
    with open(rec_path, 'r+b') as f:
        f.seek(pos)
        f.write(b'\xba\xad\xf0\x0d')        # destroy record 2's magic
    r = recordio.MXIndexedRecordIO(idx_path, rec_path, 'r')
    assert r.read_idx(1) == b'payload-1'
    with pytest.raises(DataError) as ei:
        r.read_idx(2)
    # random access reports the real record KEY, not a stale sequential
    # counter (seek() invalidates it)
    assert ei.value.index == 2
    assert ei.value.offset == pos
    assert r.read_idx(3) == b'payload-3'     # reader still usable
    r.close()


# ---------------------------------------------------------------------------
# corrupt / truncated records (recordio + ImageRecordIter)
# ---------------------------------------------------------------------------

def _write_image_rec(path, n=8, size=(16, 16)):
    """A tiny .rec of solid-color JPEGs; returns per-record offsets."""
    from PIL import Image
    from mxnet_tpu import recordio
    rec = recordio.MXRecordIO(path, 'w')
    for i in range(n):
        img = Image.new('RGB', size, (i * 20 % 255, 30, 40))
        buf = _io.BytesIO()
        img.save(buf, format='JPEG', quality=95)
        rec.write(recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    rec.close()


def test_recordio_truncated_file_names_record_and_offset(tmp_path,
                                                         monkeypatch):
    from mxnet_tpu import recordio, _native
    monkeypatch.setattr(_native, 'get_lib', lambda: None)  # python path
    path = str(tmp_path / 'data.rec')
    _write_image_rec(path, n=4)
    # truncate inside the third record's payload
    rec = recordio.MXRecordIO(path, 'r')
    rec.read()
    rec.read()
    third_at = rec.handle.tell()
    rec.close()
    with open(path, 'r+b') as f:
        f.truncate(third_at + 12)     # header + a few payload bytes
    rec = recordio.MXRecordIO(path, 'r')
    assert rec.read() is not None
    assert rec.read() is not None
    with pytest.raises(DataError) as ei:
        rec.read()
    assert ei.value.index == 2
    assert ei.value.offset == third_at
    assert str(third_at) in str(ei.value)
    rec.close()


def test_image_record_iter_corrupt_record_error_and_skip(tmp_path,
                                                         monkeypatch):
    from mxnet_tpu.io.io import ImageRecordIter, _NativePipeline
    # force the pure-python fallback so the per-record decode path runs
    monkeypatch.setattr(_NativePipeline, 'try_create',
                        classmethod(lambda cls, *a, **k: None))
    path = str(tmp_path / 'data.rec')
    _write_image_rec(path, n=8)
    it = ImageRecordIter(path, (3, 8, 8), batch_size=4,
                         preprocess_threads=1, transport='f32')
    # mangle record 5's image payload on disk (IRHeader stays valid,
    # the JPEG magic right after it is destroyed)
    pos, length = it._offsets[5]
    with open(path, 'r+b') as f:
        f.seek(pos + 28)              # past the 28-byte IRHeader
        f.write(b'\x00' * (length - 28))
    it.reset()
    it.next()                          # records 0-3 decode fine
    with pytest.raises(DataError) as ei:
        it.next()
    assert ei.value.index == 5
    assert ei.value.offset == pos
    assert f'offset {pos}' in str(ei.value)
    it.close()
    # error-policy surfaces the DataError and counts NOTHING — the
    # counter means "records silently substituted"
    assert telemetry.value('mxnet_tpu_io_corrupt_records_total') is None
    # policy-skip: the epoch completes, the bad record is substituted
    # and counted
    it2 = ImageRecordIter(path, (3, 8, 8), batch_size=4,
                          preprocess_threads=1, transport='f32',
                          corrupt_policy='skip')
    batches = 0
    while True:
        try:
            it2.next()
            batches += 1
        except StopIteration:
            break
    assert batches == 2
    assert telemetry.value('mxnet_tpu_io_corrupt_records_total') == 1
    it2.close()


def test_injected_decode_corruption_is_policy_skipped(tmp_path,
                                                      monkeypatch):
    """io.decode:corrupt mangles image bytes in flight — the skip policy
    must absorb it exactly like on-disk corruption."""
    from mxnet_tpu.io.io import ImageRecordIter, _NativePipeline
    monkeypatch.setattr(_NativePipeline, 'try_create',
                        classmethod(lambda cls, *a, **k: None))
    path = str(tmp_path / 'data.rec')
    _write_image_rec(path, n=8)
    faults.arm('io.decode', 'corrupt', window=3)
    it = ImageRecordIter(path, (3, 8, 8), batch_size=4,
                         preprocess_threads=1, transport='f32',
                         corrupt_policy='skip')
    batches = 0
    while True:
        try:
            it.next()
            batches += 1
        except StopIteration:
            break
    assert batches == 2
    assert telemetry.value('mxnet_tpu_io_corrupt_records_total') == 1
    it.close()


def test_injected_decode_corruption_deterministic_across_threads(
        tmp_path, monkeypatch):
    """io.decode firing is keyed by record index, not call order — the
    default multi-threaded decode pool must corrupt the SAME records in
    every run no matter how its threads interleave."""
    from mxnet_tpu.io.io import ImageRecordIter, _NativePipeline
    monkeypatch.setattr(_NativePipeline, 'try_create',
                        classmethod(lambda cls, *a, **k: None))
    path = str(tmp_path / 'data.rec')
    _write_image_rec(path, n=16)

    def run():
        faults.arm('io.decode', 'corrupt', prob=0.5, seed=11)
        it = ImageRecordIter(path, (3, 8, 8), batch_size=8,
                             preprocess_threads=4, transport='f32',
                             corrupt_policy='skip')
        out = []
        try:
            while True:
                out.append(it.next().data[0].asnumpy().copy())
        except StopIteration:
            pass
        it.close()
        skipped = telemetry.value('mxnet_tpu_io_corrupt_records_total')
        faults.disarm()
        telemetry.reset()
        return out, skipped

    a, skipped_a = run()
    b, skipped_b = run()
    assert skipped_a == skipped_b and skipped_a > 0
    assert len(a) == len(b) == 2
    for x, y in zip(a, b):
        onp.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# estimator / module.fit: interrupts exit cleanly + resumably
# ---------------------------------------------------------------------------

def _fit_estimator_with(tmp_path, interrupter):
    from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                                   Estimator)
    from mxnet_tpu.gluon.data import DataLoader, ArrayDataset
    x, y = _toy_regression(n=64)
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.01})
    est = Estimator(net, gluon.loss.L2Loss(), metrics=mx.metric.Loss(),
                    trainer=trainer, context=[mx.cpu()])
    handler = CheckpointHandler(str(tmp_path), epoch_period=None)
    est.fit(DataLoader(ArrayDataset(x, y), batch_size=16), epochs=50,
            event_handlers=[handler, interrupter])
    return handler


def test_estimator_keyboard_interrupt_saves_and_exits_cleanly(tmp_path,
                                                              caplog):
    from mxnet_tpu.gluon.contrib.estimator import BatchEnd

    class InterruptAt(BatchEnd):
        def __init__(self, at):
            self.n, self.at = 0, at

        def batch_end(self, estimator, *args, **kwargs):
            self.n += 1
            if self.n == self.at:
                raise KeyboardInterrupt

    import logging
    with caplog.at_level(logging.WARNING, logger='estimator'):
        handler = _fit_estimator_with(tmp_path, InterruptAt(3))
    # no traceback escaped; one checkpoint committed at the interrupt step
    mgr = checkpoint.CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == 3
    assert any('resumable from step 3' in r.message for r in caplog.records)


def test_estimator_sigterm_saves_and_exits_cleanly(tmp_path, caplog):
    from mxnet_tpu.gluon.contrib.estimator import BatchEnd, EpochEnd

    class SigtermAt(BatchEnd, EpochEnd):
        def __init__(self, at):
            self.n, self.at = 0, at
            self.epoch_ends = 0

        def batch_end(self, estimator, *args, **kwargs):
            self.n += 1
            if self.n == self.at:
                os.kill(os.getpid(), signal.SIGTERM)

        def epoch_end(self, estimator, *args, **kwargs):
            self.epoch_ends += 1

    import logging
    interrupter = SigtermAt(2)
    with caplog.at_level(logging.WARNING, logger='estimator'):
        handler = _fit_estimator_with(tmp_path, interrupter)
    mgr = checkpoint.CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == 2
    assert any('resumable from step 2' in r.message for r in caplog.records)
    # the preemption grace window is for the save, not epoch-end work
    # (a ValidationHandler would run a full eval pass there)
    assert interrupter.epoch_ends == 0
    # the preemption hook was uninstalled by manager.close() at train_end
    assert signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL,
                                                signal.default_int_handler)


def test_module_fit_keyboard_interrupt_saves_and_exits(tmp_path, caplog):
    import logging
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.module import Module
    x = onp.random.RandomState(0).randn(32, 6).astype(onp.float32)
    y = (x.sum(axis=1) > 0).astype(onp.float32)
    data = sym.Variable('data')
    out = sym.FullyConnected(data, num_hidden=2, name='fc')
    out = sym.SoftmaxOutput(out, sym.Variable('softmax_label'),
                            name='softmax')
    mod = Module(out, data_names=('data',), label_names=('softmax_label',))
    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=False)

    calls = {'n': 0}

    def interrupt_cb(param):
        calls['n'] += 1
        if calls['n'] == 3:
            raise KeyboardInterrupt

    logger = logging.getLogger('mxtpu.test.module')
    mod.logger = logger
    with caplog.at_level(logging.WARNING, logger=logger.name):
        mod.fit(NDArrayIter(x, y, batch_size=8), num_epoch=50,
                batch_end_callback=interrupt_cb, checkpoint_manager=mgr)
    assert mgr.latest_step() == 2          # saved at the last whole step
    assert any('resumable from step 2' in r.message
               for r in caplog.records)
    ck = mgr.restore_latest(apply=False)
    assert any(k.startswith('arg:') for k in ck.params)


def test_estimator_failing_handler_leaks_no_hook_or_watchdog(tmp_path):
    """A train_begin/batch error escaping fit must tear down the
    process-global SIGTERM hook and any watchdog thread — train_end
    never runs on that path."""
    import threading
    from mxnet_tpu.gluon.contrib.estimator import (BatchEnd,
                                                   CheckpointHandler,
                                                   Estimator,
                                                   WatchdogHandler)
    from mxnet_tpu.gluon.data import DataLoader, ArrayDataset
    x, y = _toy_regression(n=32)
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.01})
    est = Estimator(net, gluon.loss.L2Loss(), metrics=mx.metric.Loss(),
                    trainer=trainer, context=[mx.cpu()])

    class Boom(BatchEnd):
        def batch_end(self, estimator, *args, **kwargs):
            raise ValueError('boom')

    before = signal.getsignal(signal.SIGTERM)
    wd_handler = WatchdogHandler(deadline_seconds=60)
    with pytest.raises(ValueError, match='boom'):
        est.fit(DataLoader(ArrayDataset(x, y), batch_size=16), epochs=2,
                event_handlers=[CheckpointHandler(str(tmp_path)),
                                wd_handler, Boom()])
    assert signal.getsignal(signal.SIGTERM) == before
    assert wd_handler.watchdog is None
    assert not any(t.name == 'mxtpu-step-watchdog'
                   for t in threading.enumerate())


def test_estimator_interrupt_during_train_begin_leaks_no_hook(tmp_path):
    """Ctrl-C landing INSIDE CheckpointHandler.train_begin (e.g. during
    a slow restore_latest) leaves the handler out of the begun set, so
    its train_end — the normal uninstall path for the SIGTERM hook — is
    skipped; fit must still tear the hook down before returning."""
    from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                                   Estimator)
    from mxnet_tpu.gluon.data import DataLoader, ArrayDataset
    x, y = _toy_regression(n=32)
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.01})
    est = Estimator(net, gluon.loss.L2Loss(), metrics=mx.metric.Loss(),
                    trainer=trainer, context=[mx.cpu()])

    class InterruptedRestore(CheckpointHandler):
        def train_begin(self, estimator, *args, **kwargs):
            super().train_begin(estimator, *args, **kwargs)
            raise KeyboardInterrupt       # ctrl-C lands mid-train_begin

    before = signal.getsignal(signal.SIGTERM)
    est.fit(DataLoader(ArrayDataset(x, y), batch_size=16), epochs=1,
            event_handlers=[InterruptedRestore(str(tmp_path))])
    assert signal.getsignal(signal.SIGTERM) == before


def test_module_fit_autosave_commits_real_params(tmp_path):
    """The per-batch autosave cadence and the SIGTERM hook go through a
    params-UNBOUND manager on the Module path (module_checkpoint passes
    params per save) — fit must bind a provider so those checkpoints
    carry the real arg:/aux: arrays, and unbind it afterwards."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.module import Module
    x = onp.random.RandomState(0).randn(32, 6).astype(onp.float32)
    y = (x.sum(axis=1) > 0).astype(onp.float32)
    data = sym.Variable('data')
    out = sym.FullyConnected(data, num_hidden=2, name='fc')
    out = sym.SoftmaxOutput(out, sym.Variable('softmax_label'),
                            name='softmax')
    mod = Module(out, data_names=('data',), label_names=('softmax_label',))
    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=False,
                                       autosave_steps=2, keep_last_n=10)
    mod.fit(NDArrayIter(x, y, batch_size=8), num_epoch=1,
            checkpoint_manager=mgr)
    steps = mgr.all_steps()
    assert steps == [2, 4]                 # 4 batches, cadence every 2
    ck = mgr.restore_latest(apply=False)
    assert any(k.startswith('arg:') for k in ck.params)   # real params
    assert mgr._params is None             # provider unbound after fit


# ---------------------------------------------------------------------------
# collective fault site reaches the kvstore reduce path
# ---------------------------------------------------------------------------

def test_collective_fault_site_fires_in_kvstore_reduce():
    from mxnet_tpu.kvstore.kvstore import _reduce
    from mxnet_tpu.ndarray.ndarray import array
    faults.arm('collective.all_reduce', 'raise')
    with pytest.raises(InjectedFault, match='collective.all_reduce'):
        _reduce([array(onp.ones(3)), array(onp.ones(3))])


# ---------------------------------------------------------------------------
# CI determinism smoke: the fault seeds are exactly reproducible
# ---------------------------------------------------------------------------

def test_fault_injection_seeds_are_deterministic_3x():
    """Drives tools/flakiness_checker.py over a fault-injection test 3x
    (distinct MXNET_TEST_SEED per trial): the injected-fault pattern is a
    pure function of the MXTPU_FAULT seed, so every trial must pass."""
    tools = os.path.join(os.path.dirname(__file__), os.pardir, 'tools',
                         'flakiness_checker.py')
    res = subprocess.run(
        [sys.executable, tools,
         'tests/test_resilience.py::test_fault_window_and_prob_determinism',
         '-n', '3'],
        cwd=os.path.join(os.path.dirname(__file__), os.pardir),
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert '3/3 passed' in res.stdout
