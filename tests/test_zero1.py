"""ZeRO-1 sharded optimizer step (ISSUE 4): reduce-scatter grads,
shard-local AdamW, overlapped all-gather — parity vs the replicated
update on the 8-device CPU mesh, tp composition, layout-independent
checkpoints across dp degrees, and the comm telemetry contract."""
import os
import pickle

import numpy as onp
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, autograd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
from mxnet_tpu.parallel import compose_zero_spec


def _data(n=64, din=16, classes=8, seed=0):
    rng = onp.random.RandomState(seed)
    x = rng.randn(n, din).astype(onp.float32)
    y = rng.randint(0, classes, n).astype(onp.float32)
    return nd.array(x), nd.array(y)


def _net(din=16, hidden=32, classes=8):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation='relu', in_units=din))
    net.add(nn.Dense(classes, in_units=hidden))
    net.initialize(mx.init.Xavier())
    return net


def _run_step(optimizer, mesh, zero, steps=3, param_specs=None, net=None):
    net = net if net is not None else _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss_fn, optimizer,
                            {'learning_rate': 0.01}, mesh=mesh, zero=zero,
                            param_specs=param_specs)
    x, y = _data()
    losses = [float(step(x, y).asscalar()) for _ in range(steps)]
    return net, step, losses


# ---------------------------------------------------------------------------
# parity: ZeRO-1 must train the SAME model as the replicated update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('optimizer', ['adam', 'adamw', 'lamb'])
def test_zero1_parity_vs_replicated(optimizer):
    """dp=8: 3-step loss trajectory matches the replicated update to
    <=1e-6 in fp32 (acceptance criterion), and the updated weights agree
    too — the reduce-scatter/shard-update/all-gather decomposition is a
    pure layout change."""
    mesh = make_mesh((8,), ('dp',))
    net_z, step_z, loss_z = _run_step(optimizer, mesh, zero=True)
    net_r, step_r, loss_r = _run_step(optimizer, mesh, zero=False)
    assert step_z.zero and not step_r.zero
    for a, b in zip(loss_z, loss_r):
        assert abs(a - b) <= 1e-6, (optimizer, loss_z, loss_r)
    # in creation order: the two nets' auto-numbered names (dense9_,
    # dense10_) do not sort alike across a digit boundary
    for (n, pz), (_, pr) in zip(net_z.collect_params().items(),
                                net_r.collect_params().items()):
        d = float(onp.max(onp.abs(pz.data().asnumpy()
                                  - pr.data().asnumpy())))
        assert d <= 1e-6, (optimizer, n, d)


def test_zero1_state_is_sharded_one_over_dp():
    """Every shardable state tensor carries the dp axis, and ONE device
    holds ~1/dp of the replicated optimizer-state bytes (± the
    replicated step-count scalars)."""
    mesh = make_mesh((8,), ('dp',))
    _, step_z, _ = _run_step('adamw', mesh, zero=True)
    _, step_r, _ = _run_step('adamw', mesh, zero=False)
    assert all(spec is not None and 'dp' in str(spec)
               for spec in step_z.zero_specs.values())
    for n, st in step_z._opt_state.items():
        for s in st:
            if s.ndim:
                assert not s.sharding.is_fully_replicated, n
    zb = step_z.opt_state_bytes_per_device()
    rb = step_r.opt_state_bytes_per_device()
    assert rb / 8 <= zb <= rb / 4, (zb, rb)


def test_zero1_composes_with_tp():
    """ZeRO + tp=2 (acceptance): a tp-sharded weight's optimizer state
    shards over BOTH axes — the dp shard composes onto a dim tp does not
    already claim — and the trajectory still matches zero-off on the
    same mesh."""
    mesh = make_mesh((4, 2), ('dp', 'tp'))

    def run(zero):
        net = _net()   # fresh net: specs keyed by ITS auto-generated name
        return _run_step('adamw', mesh, zero, net=net,
                         param_specs={net[0].weight.name: P('tp', None)})

    net_z, step_z, loss_z = run(True)
    net_r, step_r, loss_r = run(False)
    for a, b in zip(loss_z, loss_r):
        assert abs(a - b) <= 1e-6, (loss_z, loss_r)
    wname = net_z[0].weight.name
    zspec = step_z.zero_specs[wname]
    assert 'tp' in str(zspec) and 'dp' in str(zspec), zspec
    # physically laid out over both axes
    m = step_z._opt_state[wname][0]
    assert not m.sharding.is_fully_replicated


def test_compose_zero_spec_rules():
    assert compose_zero_spec((32, 16), P('tp', None), 'dp', 4) == \
        P('tp', 'dp')
    # already dp-sharded (fsdp-style specs): never compose a duplicate
    # axis — the state inherits the param's own 1/dp layout instead
    assert compose_zero_spec((32, 16), P('dp', None), 'dp', 4) is None
    assert compose_zero_spec((32, 16), P(('tp', 'dp'), None), 'dp', 4) \
        is None
    assert compose_zero_spec((32, 16), P(None, 'tp'), 'dp', 4) == \
        P('dp', 'tp')
    assert compose_zero_spec((32,), P(), 'dp', 8) == P('dp')
    # too small to shard -> stays replicated (the ragged/padding slack)
    assert compose_zero_spec((3,), P(), 'dp', 8) is None
    # uneven-but-large dims no longer shard raggedly: this jax refuses
    # uneven NamedShardings, so they stay replicated here (ZeRO-3
    # recovers them via flatten+pad — see zero3_layout) ...
    assert compose_zero_spec((12,), P(), 'dp', 8) is None
    # ... and a spec that itself PROPOSES dp on a non-divisible dim is
    # rejected up front with a clear error instead of deferring to an
    # opaque XLA refusal at device_put time
    with pytest.raises(MXNetError, match='not divisible'):
        compose_zero_spec((12, 16), P('dp', None), 'dp', 8)
    assert compose_zero_spec((), P(), 'dp', 8) is None


def test_zero1_with_fsdp_style_dp_sharded_param():
    """A param ALREADY sharded over dp by param_specs must not crash the
    build with a duplicate-axis spec: its state simply inherits the
    param's own 1/dp layout, and training still matches zero-off."""
    mesh = make_mesh((8,), ('dp',))

    def run(zero):
        net = _net()
        return _run_step('adamw', mesh, zero, net=net,
                         param_specs={net[0].weight.name: P('dp', None)})

    net_z, step_z, loss_z = run(True)
    _, _, loss_r = run(False)
    for a, b in zip(loss_z, loss_r):
        assert abs(a - b) <= 1e-6, (loss_z, loss_r)
    wname = net_z[0].weight.name
    assert step_z.zero_specs[wname] is None   # no duplicate composition
    # the moments are still 1/dp-sharded — via the param's own spec
    m = step_z._opt_state[wname][0]
    assert not m.sharding.is_fully_replicated


def test_zero1_flag_gate(monkeypatch):
    """MXTPU_ZERO=0 forces the replicated update; the explicit zero=
    argument wins over the env; dp=1 meshes never enable ZeRO."""
    mesh = make_mesh((8,), ('dp',))
    monkeypatch.setenv('MXTPU_ZERO', '0')
    step = ShardedTrainStep(_net(), gluon.loss.SoftmaxCrossEntropyLoss(),
                            'adamw', mesh=mesh)
    assert not step.zero
    step = ShardedTrainStep(_net(), gluon.loss.SoftmaxCrossEntropyLoss(),
                            'adamw', mesh=mesh, zero=True)
    assert step.zero
    monkeypatch.delenv('MXTPU_ZERO')
    step = ShardedTrainStep(_net(), gluon.loss.SoftmaxCrossEntropyLoss(),
                            'adamw', mesh=mesh)
    assert step.zero   # default-on with a >1-device dp axis
    step = ShardedTrainStep(_net(), gluon.loss.SoftmaxCrossEntropyLoss(),
                            'adamw', mesh=make_mesh((1, 8), ('dp', 'tp')))
    assert not step.zero


# ---------------------------------------------------------------------------
# comm telemetry contract
# ---------------------------------------------------------------------------

def test_zero1_comm_telemetry_accounting():
    """ZeRO swaps the grad all-reduce for reduce-scatter + all-gather at
    UNCHANGED total wire bytes (ring accounting), and the per-device
    optimizer-state gauge shows the 1/dp footprint."""
    mesh = make_mesh((8,), ('dp',))
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        _, step_z, _ = _run_step('adamw', mesh, zero=True, steps=2)
        rs = telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                             kind='reduce_scatter', axis='dp',
                             stage='zero1')
        ag = telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                             kind='all_gather', axis='dp', stage='zero1')
        n_rs = telemetry.value('mxnet_tpu_comm_collectives_total',
                               kind='reduce_scatter', axis='dp',
                               stage='zero1')
        gauge_z = telemetry.value(
            'mxnet_tpu_comm_opt_state_bytes_per_device')
        assert rs and ag and rs == ag
        assert n_rs == 2 * len(step_z._t_names)   # 2 steps, one per param
        assert gauge_z == step_z.opt_state_bytes_per_device()

        telemetry.reset()
        _, step_r, _ = _run_step('adamw', mesh, zero=False, steps=2)
        ar = telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                             kind='all_reduce', axis='dp', stage='off')
        gauge_r = telemetry.value(
            'mxnet_tpu_comm_opt_state_bytes_per_device')
        assert telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                               kind='reduce_scatter', axis='dp',
                               stage='off') is None
        assert ar == rs + ag   # same total traffic, different decomposition
        assert gauge_r >= 4 * gauge_z   # ~8x minus replicated scalars
    finally:
        if not was_on:
            telemetry.disable()


# ---------------------------------------------------------------------------
# layout-independent checkpoints: save at dp=8 -> restore at dp=4 / no-ZeRO
# ---------------------------------------------------------------------------

def test_zero1_checkpoint_dp8_to_dp4_bit_parity(tmp_path):
    """Acceptance: a checkpoint written under ZeRO at dp=8 restores
    bit-identical through CheckpointManager into a dp=4 ZeRO step AND
    into a non-ZeRO (replicated) step — the states payload is gathered
    host fp32, never the sharded layout."""
    from mxnet_tpu.checkpoint import CheckpointManager
    net = _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data()
    step8 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((8,), ('dp',)), zero=True)
    for _ in range(3):
        step8(x, y)
    mgr = CheckpointManager(str(tmp_path), params=net, trainer=step8,
                            async_save=False)
    mgr.save(3)
    mgr.close()
    saved = pickle.loads(step8.get_states_bytes())
    assert saved['zero'] and saved['dp'] == 8

    # manifest records the layout the checkpoint was written under
    from mxnet_tpu.checkpoint import manifest as mf
    doc = mf.read_manifest(mgr.step_dir(3))
    layout = doc['metadata']['optimizer_state_layout']
    assert layout == {'format': 'gathered-host', 'zero1': True,
                      'stage': 1, 'dp': 8}

    # reference trajectory: one MORE step on the saving instance (before
    # any restore mutates the shared net's params)
    step8(x, y)
    ref = pickle.loads(step8.get_states_bytes())
    ref_params = {n: p.data().asnumpy().copy()
                  for n, p in net.collect_params().items()}

    for target_mesh, target_zero in ((make_mesh((4,), ('dp',)), True),
                                     (make_mesh((8,), ('dp',)), False)):
        step_t = ShardedTrainStep(net, loss_fn, 'adamw',
                                  {'learning_rate': 0.01},
                                  mesh=target_mesh, zero=target_zero)
        mgr_t = CheckpointManager(str(tmp_path), params=net,
                                  trainer=step_t, async_save=False)
        assert mgr_t.restore_latest() == 3   # params + states -> step 3
        # the pending restored states apply lazily at the first build;
        # after one step the target must sit exactly where the saving
        # trainer sat after ITS fourth step
        step_t(x, y)
        got = pickle.loads(step_t.get_states_bytes())
        for n in ref['opt_state']:
            for a, b in zip(ref['opt_state'][n], got['opt_state'][n]):
                assert onp.allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=0, atol=1e-6), (target_zero, n)
        for n, p in net.collect_params().items():
            d = float(onp.max(onp.abs(p.data().asnumpy() - ref_params[n])))
            assert d <= 1e-6, (target_zero, n, d)
        mgr_t.close()


def test_zero1_states_roundtrip_bit_identical():
    """get_states_bytes/set_states_bytes without the extra step: the
    gathered payload survives a zero(dp=8) -> replicated(dp=4) move
    bit-for-bit."""
    net = _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data()
    step8 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((8,), ('dp',)), zero=True)
    for _ in range(2):
        step8(x, y)
    blob = step8.get_states_bytes()
    step4 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((4,), ('dp',)), zero=False)
    step4(x, y)              # build (state now exists, will be overwritten)
    step4.set_states_bytes(blob)
    a = pickle.loads(blob)
    b = pickle.loads(step4.get_states_bytes())
    for n in a['opt_state']:
        for sa, sb in zip(a['opt_state'][n], b['opt_state'][n]):
            assert onp.array_equal(onp.asarray(sa), onp.asarray(sb)), n
    with pytest.raises(MXNetError, match='not a ShardedTrainStep'):
        step4.set_states_bytes(pickle.dumps({'format': 'bogus'}))
    # restore -> save BEFORE the first step (preemption window): the
    # pending payload is handed back unchanged instead of raising
    fresh = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((4,), ('dp',)))
    with pytest.raises(MXNetError, match='no optimizer state yet'):
        fresh.get_states_bytes()
    fresh.set_states_bytes(blob)
    got = pickle.loads(fresh.get_states_bytes())
    for n in a['opt_state']:
        for sa, sb in zip(a['opt_state'][n], got['opt_state'][n]):
            assert onp.array_equal(onp.asarray(sa), onp.asarray(sb)), n


# ---------------------------------------------------------------------------
# gluon.Trainer path: the traced fused update learns the sharded layout
# ---------------------------------------------------------------------------

def _put_mesh(arr, mesh):
    """Commit an NDArray to the mesh (replicated): eager ops reject a
    batch committed to one device against mesh-committed weights."""
    arr._data = jax.device_put(arr._data, NamedSharding(mesh, P()))
    return arr


def _mesh_trainer(mesh, steps, optimizer='adam'):
    net = _net()
    x, y = _data()
    net(x)
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        for p in net.collect_params().values():
            p.data()._data = jax.device_put(p.data()._data, repl)
        _put_mesh(x, mesh)
        _put_mesh(y, mesh)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            {'learning_rate': 0.01})
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
    return net, trainer


def test_trainer_zero1_parity_and_sharded_states():
    """Trainer over mesh-replicated params activates ZeRO in the fused
    multi-tensor update (default-on), shards the Adam moments 1/dp, and
    trains bit-for-bit like the single-device trainer."""
    mesh = make_mesh((8,), ('dp',))
    net_z, tr_z = _mesh_trainer(mesh, steps=3)
    net_r, tr_r = _mesh_trainer(None, steps=3)
    assert tr_z._zero_active and tr_z._zero_dp == 8
    assert not tr_r._zero_active
    # in creation order: the two nets' auto-numbered names (dense9_,
    # dense10_) do not sort alike across a digit boundary
    for (n, pz), (_, pr) in zip(net_z.collect_params().items(),
                                net_r.collect_params().items()):
        d = float(onp.max(onp.abs(pz.data().asnumpy()
                                  - pr.data().asnumpy())))
        assert d <= 1e-6, (n, d)
    # moments physically sharded
    some_sharded = False
    for st in tr_z._updater.states.values():
        for s in (st if isinstance(st, (list, tuple)) else [st]):
            if s is not None and s.ndim and hasattr(s._data, 'sharding'):
                some_sharded |= not s._data.sharding.is_fully_replicated
    assert some_sharded
    assert tr_z.opt_state_bytes_per_device() * 4 < \
        tr_r.opt_state_bytes_per_device()


def test_trainer_zero1_restore_into_non_zero_trainer():
    """Acceptance: states saved under ZeRO restore bit-identical into a
    non-ZeRO trainer (gathered-host payload), and the restored trainer
    re-scatters on its next fused step without diverging."""
    mesh = make_mesh((8,), ('dp',))
    net_z, tr_z = _mesh_trainer(mesh, steps=3)
    blob = tr_z.get_states_bytes()

    net_p, tr_p = _mesh_trainer(None, steps=3)   # plain, same trajectory
    tr_p.set_states_bytes(blob)
    a, b = pickle.loads(blob), pickle.loads(tr_p.get_states_bytes())

    def _leaves(s, out):
        if isinstance(s, (list, tuple)):
            for x in s:
                _leaves(x, out)
        elif s is not None:
            out.append(s)
        return out

    sa = a[0] if isinstance(a, tuple) else a
    sb = b[0] if isinstance(b, tuple) else b
    assert set(sa) == set(sb)
    for k in sa:
        for la, lb in zip(_leaves(sa[k], []), _leaves(sb[k], [])):
            assert onp.array_equal(onp.asarray(la), onp.asarray(lb)), k
    # and the zero trainer accepts its own payload back (re-scatter path)
    tr_z.set_states_bytes(blob)
    x, y = _data()
    _put_mesh(x, mesh)
    _put_mesh(y, mesh)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net_z(x), y)
    loss.backward()
    tr_z.step(x.shape[0])
    assert tr_z._zero_active


def test_trainer_zero1_flag_gate(monkeypatch):
    monkeypatch.setenv('MXTPU_ZERO', '0')
    mesh = make_mesh((8,), ('dp',))
    _, tr = _mesh_trainer(mesh, steps=2)
    assert not tr._zero_active
    # zero OFF with mesh weights still places the states on the mesh
    # (replicated) — a jit cannot mix committed device sets
    for st in tr._updater.states.values():
        for s in (st if isinstance(st, (list, tuple)) else [st]):
            if s is not None and s.ndim:
                sh = s._data.sharding
                assert sh.is_fully_replicated
                assert getattr(sh, 'mesh', None) is not None \
                    and sh.mesh.size == 8


def test_trainer_multi_ctx_broadcast_batched():
    """Satellite: the post-update broadcast to the other context copies
    is ONE batched multi-array device_put per step (counted once under
    the comm contract), and still leaves every copy identical."""
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        net = nn.Dense(4, in_units=8)
        net.initialize(mx.init.Xavier(), ctx=[mx.cpu(0), mx.cpu(1)])
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           {'learning_rate': 0.1})
        rng = onp.random.RandomState(0)
        for _ in range(2):
            with autograd.record():
                l0 = net(nd.array(rng.randn(8, 8).astype(onp.float32),
                                  ctx=mx.cpu(0))).sum()
                l1 = net(nd.array(rng.randn(8, 8).astype(onp.float32),
                                  ctx=mx.cpu(1))).sum()
            autograd.backward([l0, l1])
            tr.step(16)
        for p in net.collect_params().values():
            d0, d1 = [d.asnumpy() for d in p.list_data()]
            assert onp.array_equal(d0, d1), p.name
        # one broadcast per step, bytes = (weight + bias) x extra copies
        assert telemetry.value('mxnet_tpu_comm_collectives_total',
                               kind='broadcast', axis='ctx') == 2
        assert telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                               kind='broadcast', axis='ctx') == \
            2 * ((4 * 8 + 4) * 4)
    finally:
        if not was_on:
            telemetry.disable()


# ---------------------------------------------------------------------------
# gradient compression on the GSPMD path: routed for real (ISSUE 12) —
# the former rejection sites now apply the error-feedback codecs; only
# a genuinely unsupported ctype string still raises
# ---------------------------------------------------------------------------

def test_gradient_compression_routed_on_gspmd_paths():
    net = _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # ShardedTrainStep: accepted and active (the error-feedback
    # epilogue runs inside the compiled step — see test_compression.py
    # for the trajectory/wire assertions)
    step = ShardedTrainStep(net, loss_fn, 'adamw',
                            mesh=make_mesh((8,), ('dp',)),
                            compression_params={'type': '2bit'})
    assert step.compression['type'] == '2bit'
    # type='none' is accepted (explicitly no compression)
    step = ShardedTrainStep(net, loss_fn, 'adamw',
                            mesh=make_mesh((8,), ('dp',)),
                            compression_params={'type': 'none'})
    assert step.compression is None
    # unknown ctype: actionable error at construction
    with pytest.raises(MXNetError, match='not supported'):
        ShardedTrainStep(net, loss_fn, 'adamw',
                         mesh=make_mesh((8,), ('dp',)),
                         compression_params={'type': '3bit'})
    # Trainer single-copy path: the push that would compress is
    # skipped, so the codec applies to the single gradient copy in
    # place — the step RUNS and the gradient is quantized
    x, y = _data()
    net(x)
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1},
                            compression_params={'type': '2bit',
                                                'threshold': 0.05})
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(x.shape[0])
    g = next(iter(net.collect_params().values())).list_grad()[0].asnumpy()
    lvls = onp.array([-0.05, 0.0, 0.05], onp.float32)
    assert onp.all(onp.min(onp.abs(g[..., None] - lvls), axis=-1) < 1e-7), \
        "single-copy gradient was not 2bit-quantized in place"
    # Trainer without a kvstore: the trainer-local compressor applies
    # to the merged gradient in _update
    net2 = _net()
    net2(x)
    trainer = gluon.Trainer(net2.collect_params(), 'sgd',
                            {'learning_rate': 0.1}, kvstore=None,
                            compression_params={'type': '2bit'})
    with autograd.record():
        loss = loss_fn(net2(x), y)
    loss.backward()
    trainer.step(x.shape[0])
    assert trainer._local_gc is not None and trainer._local_gc._residual
    # unsupported ctype gets an actionable error, not an AssertionError
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    with pytest.raises(MXNetError, match="'1bit'"):
        GradientCompression('1bit')
    # fp16/int8 are REAL codecs on the kvstore path now
    for ctype in ('fp16', 'int8'):
        gc = GradientCompression(ctype)
        out = gc.compress_decompress(nd.array([0.30000001, -1.5]), 'k')
        assert onp.all(onp.isfinite(out.asnumpy()))
