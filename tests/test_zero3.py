"""ZeRO-3 / FSDP (ISSUE 7): persistent params + fp32 masters sharded
1/dp, per-layer prefetched all-gather-on-use inside the pjit step
(rematerialized for backward), gradient reduce-scatter into the
shard-local update — parity vs zero1/off on the 8-device CPU mesh, tp
composition, flatten+pad for ragged params, guard composition,
checkpoint layout-independence across stages, the gluon Trainer
stage-3 layout, and the comm telemetry stage/layer labels."""
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
from mxnet_tpu.parallel.collectives import (group_params_by_layer,
                                            ordered_barrier)
from mxnet_tpu.parallel import compose_zero_spec, zero3_layout


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


def _run_step(optimizer, mesh, zero, steps=3, param_specs=None, net=None,
              data=None):
    net = net if net is not None else _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss_fn, optimizer,
                            {'learning_rate': 0.01}, mesh=mesh, zero=zero,
                            param_specs=param_specs)
    x, y = data if data is not None else _data()
    losses = [float(step(x, y).asscalar()) for _ in range(steps)]
    return net, step, losses


# ---------------------------------------------------------------------------
# parity: the sharded-parameter decomposition is a pure layout change
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('optimizer', ['adam', 'adamw', 'lamb'])
def test_zero3_parity_vs_zero1_and_replicated(optimizer):
    """dp=8: the 3-step zero3 loss trajectory is BIT-IDENTICAL to zero1
    and to the replicated update (acceptance), and so are the updated
    weights — gather/reduce-scatter/slice are layout ops, the update
    arithmetic is elementwise on the same values."""
    mesh = make_mesh((8,), ('dp',))
    net3, step3, l3 = _run_step(optimizer, mesh, zero=3)
    net1, step1, l1 = _run_step(optimizer, mesh, zero=1)
    net0, step0, l0 = _run_step(optimizer, mesh, zero=0)
    assert step3.zero_stage == 3 and step1.zero_stage == 1 \
        and step0.zero_stage == 0
    assert l3 == l1 == l0, (optimizer, l3, l1, l0)
    for (n, p3), (_, p1), (_, p0) in zip(
            sorted(net3.collect_params().items()),
            sorted(net1.collect_params().items()),
            sorted(net0.collect_params().items())):
        a3, a1, a0 = (p.data().asnumpy() for p in (p3, p1, p0))
        # zero3 == zero1 bit-for-bit always; vs the REPLICATED update
        # adam/adamw are bitwise too (purely elementwise), while lamb's
        # trust-ratio norm reduces over the whole (sharded) param —
        # reduction-order slack, same 1e-6 bound as the zero1 suite
        assert onp.array_equal(a3, a1), (optimizer, n)
        if optimizer == 'lamb':
            assert onp.max(onp.abs(a3 - a0)) <= 1e-6, (optimizer, n)
        else:
            assert onp.array_equal(a3, a0), (optimizer, n)


def test_zero3_params_and_masters_live_sharded():
    """The PERSISTENT params are physically dp-sharded between steps
    (1/dp shard per device), and the per-device param residency drops
    >= 6x vs zero1 (acceptance: all dims here divide evenly, so it is
    exactly 8x)."""
    mesh = make_mesh((8,), ('dp',))
    _, step3, _ = _run_step('adamw', mesh, zero=3)
    _, step1, _ = _run_step('adamw', mesh, zero=1)
    for n, p in step3._trainable:
        d = p.data()._data
        assert not d.sharding.is_fully_replicated, n
        assert 'dp' in str(d.sharding.spec), n
        full = int(onp.prod(d.shape)) * d.dtype.itemsize
        assert d.addressable_shards[0].data.nbytes * 8 == full, n
    pb3, pb1 = step3.param_bytes_per_device(), \
        step1.param_bytes_per_device()
    assert pb1 >= 6 * pb3, (pb3, pb1)
    # optimizer state footprint matches zero1 (already 1/dp there)
    assert step3.opt_state_bytes_per_device() == \
        step1.opt_state_bytes_per_device()
    # zero1 keeps params replicated — the contrast that IS the feature
    for n, p in step1._trainable:
        assert p.data()._data.sharding.is_fully_replicated, n


def test_zero3_layer_groups_and_gather_plan():
    """Params bucket into per-layer gather groups in natural (numeric)
    order, and the analytic plan charges each dim-sharded param two
    ring all-gathers per step (forward use + backward regather)."""
    groups = group_params_by_layer(
        ['enc_layer10_w', 'enc_layer2_w', 'enc_layer2_b', 'embed_w',
         'head_w'])
    keys = [k for k, _ in groups]
    assert keys.index('enc_layer2') < keys.index('enc_layer10')
    assert dict(groups)['enc_layer2'] == ['enc_layer2_b', 'enc_layer2_w']

    mesh = make_mesh((8,), ('dp',))
    net, step3, _ = _run_step('adamw', mesh, zero=3, net=_net())
    # one group per Dense block (names are auto-numbered), in order
    expected = sorted({n.rsplit('_', 1)[0]
                       for n in net.collect_params()})
    assert [k for k, _ in step3._layer_groups] == expected
    ring = 7 / 8
    for (gname, names), (pname, nbytes, count) in zip(
            step3._layer_groups, step3._gather_plan):
        assert gname == pname and count == 2
        expect = 2 * ring * sum(
            int(onp.prod(step3._shapes[n])) * 4 for n in names)
        assert nbytes == expect, (gname, nbytes, expect)
    # the plan rolls up into the per-step comm accounting
    ag_bytes, ag_count = step3._comm_plan['all_gather']
    assert ag_bytes == sum(b for _, b, _ in step3._gather_plan)


def test_zero3_layout_rules():
    # exactly-divisible free dim -> dim mode, composed with tp
    lay = zero3_layout((32, 16), P('tp', None), 'dp', 4)
    assert lay['mode'] == 'dim' and lay['spec'] == P('tp', 'dp') \
        and lay['gather_spec'] == P('tp')
    lay = zero3_layout((32, 16), P(), 'dp', 8)
    assert lay['mode'] == 'dim' and lay['spec'] == P('dp', None) \
        and lay['gather_spec'] == P()
    # user-proposed dp shard (fsdp-style): kept, gather strips dp
    lay = zero3_layout((32, 16), P('dp', None), 'dp', 8)
    assert lay['mode'] == 'dim' and lay['spec'] == P('dp', None) \
        and lay['gather_spec'] == P()
    # ... but a non-divisible proposed dim is rejected up front
    with pytest.raises(MXNetError, match='not divisible'):
        zero3_layout((12, 16), P('dp', None), 'dp', 8)
    # ragged, un-tp'd, >= dp elements -> flatten + pad to a dp multiple
    lay = zero3_layout((13, 7), P(), 'dp', 8)
    assert lay['mode'] == 'flat' and (lay['size'], lay['padded']) == \
        (91, 96) and lay['pad'] == 5
    # ragged but tp-claimed: flattening would destroy tp -> replicated
    assert zero3_layout((13, 7), P('tp', None), 'dp', 8)['mode'] == 'repl'
    # too small -> replicated
    assert zero3_layout((3,), P(), 'dp', 8)['mode'] == 'repl'
    assert zero3_layout((), P(), 'dp', 8)['mode'] == 'repl'


def test_zero3_flat_pad_parity_and_accounting():
    """A net whose dims never divide by dp=8 falls back to flatten+pad:
    training still matches the replicated update bit-for-bit, the flat
    fp32 stores + moments shard 1/dp (padded), and the pad slack is
    reported."""
    def ragged_net():
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(19, activation='relu', in_units=13))
        net.add(nn.Dense(7, in_units=19))
        net.initialize(mx.init.Xavier())
        return net

    mesh = make_mesh((8,), ('dp',))
    data = _data(din=13, classes=7)
    net3, step3, l3 = _run_step('adamw', mesh, zero=3, net=ragged_net(),
                                data=data)
    net0, step0, l0 = _run_step('adamw', mesh, zero=0, net=ragged_net(),
                                data=data)
    assert l3 == l0, (l3, l0)
    for (n, p3), (_, p0) in zip(sorted(net3.collect_params().items()),
                                sorted(net0.collect_params().items())):
        assert onp.array_equal(p3.data().asnumpy(),
                               p0.data().asnumpy()), n
    modes = {n: v['mode'] for n, v in step3.zero3_layouts.items()}
    assert 'flat' in modes.values()
    for n, fz in step3._flat_meta.items():
        m = step3._master[n]
        assert m.shape == (fz['padded'],)
        assert not m.sharding.is_fully_replicated, n
        assert fz['padded'] % 8 == 0
    # physical state bytes include the pad; the slack is broken out
    sb = step3.opt_state_bytes_per_device()
    assert step3.opt_state_pad_bytes > 0
    assert sb < step0.opt_state_bytes_per_device()


def test_zero3_composes_with_tp():
    """zero3 + tp=2: a tp-sharded weight's persistent layout carries
    BOTH axes, the gather restores the tp layout (not full replication),
    and the trajectory still matches zero-off on the same mesh."""
    mesh = make_mesh((4, 2), ('dp', 'tp'))

    def run(zero):
        net = _net()
        return _run_step('adamw', mesh, zero, net=net,
                         param_specs={net[0].weight.name: P('tp', None)})

    net3, step3, l3 = run(3)
    _, _, l0 = run(0)
    for a, b in zip(l3, l0):
        assert abs(a - b) <= 1e-6, (l3, l0)
    wname = net3[0].weight.name
    lay = step3.zero3_layouts[wname]
    assert lay['mode'] == 'dim'
    assert 'tp' in str(lay['spec']) and 'dp' in str(lay['spec'])
    assert lay['gather_spec'] == P('tp')
    d = dict(step3._trainable)[wname].data()._data
    assert 'tp' in str(d.sharding.spec) and 'dp' in str(d.sharding.spec)


def test_zero3_ordered_barrier_differentiates():
    """ordered_barrier is an identity with a working VJP (the raw
    optimization_barrier has no differentiation rule in this jax) —
    the mechanism that chains layer k+1's gather to layer k's."""
    import jax.numpy as jnp
    a = jnp.arange(4.0)
    b = jnp.ones((2,))
    oa, ob = ordered_barrier(a, b)
    assert onp.array_equal(onp.asarray(oa), onp.asarray(a))

    def f(a, b):
        oa, ob = ordered_barrier(a * 2, b)
        return jnp.sum(oa) + 3 * jnp.sum(ob)

    ga, gb = jax.grad(f, argnums=(0, 1))(a, b)
    assert onp.allclose(onp.asarray(ga), 2.0)
    assert onp.allclose(onp.asarray(gb), 3.0)
    (single,) = ordered_barrier(a)
    assert onp.array_equal(onp.asarray(single), onp.asarray(a))


# ---------------------------------------------------------------------------
# guard composition: isfinite over the SHARDED grads, gated sharded masters
# ---------------------------------------------------------------------------

def test_zero3_guard_skips_bad_step_on_device():
    """NonFiniteGuard under zero3: a NaN batch's update is a device
    no-op (the where-gate writes back the old SHARDED params/masters/
    state), the deferred flag drains bad at the next step, and training
    continues from the unpoisoned weights."""
    from mxnet_tpu.resilience import NonFiniteGuard
    mesh = make_mesh((8,), ('dp',))
    net = _net()
    guard = NonFiniteGuard(policy='skip')
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            'adamw', {'learning_rate': 0.01}, mesh=mesh,
                            zero=3, guard=guard)
    x, y = _data()
    step(x, y)
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()}
    states_before = pickle.loads(step.get_states_bytes())
    xbad = nd.array(onp.full((64, 16), onp.nan, onp.float32))
    step(xbad, y)          # flag pushed (device), read at next pre_step
    for n, p in net.collect_params().items():
        assert onp.array_equal(p.data().asnumpy(), before[n]), n
        assert not p.data()._data.sharding.is_fully_replicated, n
    states_after = pickle.loads(step.get_states_bytes())
    for n in states_before['opt_state']:
        for a, b in zip(states_before['opt_state'][n],
                        states_after['opt_state'][n]):
            assert onp.array_equal(onp.asarray(a), onp.asarray(b)), n
    step(x, y)             # drains the bad flag, trains normally
    assert guard.bad_steps == 1 and guard.consecutive_bad == 1
    step(x, y)
    assert guard.consecutive_bad == 0   # good flag reset the ladder
    changed = any(
        not onp.array_equal(p.data().asnumpy(), before[n])
        for n, p in net.collect_params().items())
    assert changed


# ---------------------------------------------------------------------------
# checkpoint layout-independence across stages (acceptance)
# ---------------------------------------------------------------------------

def test_zero3_checkpoint_layout_independence(tmp_path):
    """Save at dp=8/zero3 through CheckpointManager -> restore into
    dp=4/zero1, dp=8/non-zero AND dp=4+tp=2/zero3. The same-mesh
    restore continues BIT-identically; the cross-degree restores match
    to 1e-6 (changing dp reorders the batch-reduction sums — same bound
    as the zero1 suite). The manifest records stage 3."""
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.checkpoint import manifest as mf
    net = _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data()
    step8 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((8,), ('dp',)), zero=3)
    for _ in range(3):
        step8(x, y)
    mgr = CheckpointManager(str(tmp_path), params=net, trainer=step8,
                            async_save=False)
    mgr.save(3)
    mgr.close()
    saved = pickle.loads(step8.get_states_bytes())
    assert saved['zero'] and saved['stage'] == 3 and saved['dp'] == 8
    # the states payload is layout-independent: every leaf logical-shape
    for n, st in saved['opt_state'].items():
        assert onp.asarray(st[0]).shape == \
            tuple(dict(step8._trainable)[n].data().shape), n

    layout = mf.read_manifest(mgr.step_dir(3))['metadata'][
        'optimizer_state_layout']
    assert layout == {'format': 'gathered-host', 'zero1': True,
                      'stage': 3, 'dp': 8}

    step8(x, y)   # reference 4th step BEFORE restores mutate the net
    ref = pickle.loads(step8.get_states_bytes())
    ref_params = {n: p.data().asnumpy().copy()
                  for n, p in net.collect_params().items()}

    targets = [
        ('dp8/off', make_mesh((8,), ('dp',)), 0, {}, 0.0),
        ('dp4/zero1', make_mesh((4,), ('dp',)), 1, {}, 1e-6),
        ('dp4tp2/zero3', make_mesh((4, 2), ('dp', 'tp')), 3,
         {net[0].weight.name: P('tp', None)}, 1e-6),
    ]
    for tag, mesh_t, zero_t, specs, tol in targets:
        step_t = ShardedTrainStep(net, loss_fn, 'adamw',
                                  {'learning_rate': 0.01}, mesh=mesh_t,
                                  zero=zero_t, param_specs=specs)
        mgr_t = CheckpointManager(str(tmp_path), params=net,
                                  trainer=step_t, async_save=False)
        assert mgr_t.restore_latest() == 3
        step_t(x, y)
        got = pickle.loads(step_t.get_states_bytes())
        for n in ref['opt_state']:
            for a, b in zip(ref['opt_state'][n], got['opt_state'][n]):
                a, b = onp.asarray(a), onp.asarray(b)
                if tol == 0.0:
                    assert onp.array_equal(a, b), (tag, n)
                else:
                    assert onp.allclose(a, b, rtol=0, atol=tol), (tag, n)
        for n, p in net.collect_params().items():
            d = float(onp.max(onp.abs(p.data().asnumpy()
                                      - ref_params[n])))
            assert d <= tol, (tag, n, d)
        mgr_t.close()


def test_zero3_states_blob_roundtrips_across_stages():
    """get_states_bytes/set_states_bytes: a zero3 payload lands
    bit-identically in a zero1 step and vice versa (flat stores
    un-flatten to logical shape on save, re-flatten+pad on restore)."""
    def ragged_net():
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(19, activation='relu', in_units=13))
        net.add(nn.Dense(7, in_units=19))
        net.initialize(mx.init.Xavier())
        return net

    net = ragged_net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data(din=13, classes=7)
    step3 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((8,), ('dp',)), zero=3)
    for _ in range(2):
        step3(x, y)
    blob = step3.get_states_bytes()
    a = pickle.loads(blob)
    # zero3 -> zero1 (different stage, same payload)
    step1 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((4,), ('dp',)), zero=1)
    step1(x, y)
    step1.set_states_bytes(blob)
    b = pickle.loads(step1.get_states_bytes())
    for n in a['opt_state']:
        for sa, sb in zip(a['opt_state'][n], b['opt_state'][n]):
            assert onp.array_equal(onp.asarray(sa), onp.asarray(sb)), n
    # zero1 -> zero3 (flat targets re-flatten; masters reseed from the
    # current params where the zero1 payload had none)
    blob1 = step1.get_states_bytes()
    step3b = ShardedTrainStep(net, loss_fn, 'adamw',
                              {'learning_rate': 0.01},
                              mesh=make_mesh((8,), ('dp',)), zero=3)
    step3b(x, y)
    step3b.set_states_bytes(blob1)
    c = pickle.loads(step3b.get_states_bytes())
    for n in a['opt_state']:
        for sa, sc in zip(a['opt_state'][n], c['opt_state'][n]):
            assert onp.array_equal(onp.asarray(sa), onp.asarray(sc)), n
    for n, fz in step3b._flat_meta.items():
        assert step3b._master[n].shape == (fz['padded'],), n


# ---------------------------------------------------------------------------
# flags / config
# ---------------------------------------------------------------------------

def test_zero3_flag_gate(monkeypatch):
    mesh = make_mesh((8,), ('dp',))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    monkeypatch.setenv('MXTPU_ZERO', '3')
    step = ShardedTrainStep(_net(), loss_fn, 'adamw', mesh=mesh)
    assert step.zero_stage == 3 and step.zero
    # explicit argument wins over the env
    step = ShardedTrainStep(_net(), loss_fn, 'adamw', mesh=mesh, zero=1)
    assert step.zero_stage == 1
    step = ShardedTrainStep(_net(), loss_fn, 'adamw', mesh=mesh,
                            zero=False)
    assert step.zero_stage == 0 and not step.zero
    # dp=1 never activates any stage
    step = ShardedTrainStep(_net(), loss_fn, 'adamw',
                            mesh=make_mesh((1, 8), ('dp', 'tp')), zero=3)
    assert step.zero_stage == 0
    # unsupported stages get an actionable error
    with pytest.raises(MXNetError, match='stage 2'):
        ShardedTrainStep(_net(), loss_fn, 'adamw', mesh=mesh, zero=2)
    monkeypatch.setenv('MXTPU_ZERO', '2')
    from mxnet_tpu import config as _config
    with pytest.raises(MXNetError, match='MXTPU_ZERO'):
        _config.get('MXTPU_ZERO')
    monkeypatch.setenv('MXTPU_ZERO', 'on')
    assert _config.get('MXTPU_ZERO') == 1
    monkeypatch.setenv('MXTPU_ZERO', '0')
    assert _config.get('MXTPU_ZERO') == 0


# ---------------------------------------------------------------------------
# comm telemetry: stage labels + per-layer gather bytes
# ---------------------------------------------------------------------------

def test_zero3_comm_telemetry_stage_labels():
    """zero3 counters carry stage='zero3'; the gather bytes equal the
    per-layer plan (2 gathers per dim param per step); the param-bytes
    gauge shows the 1/dp residency; and zero3 honestly reports MORE
    wire bytes than zero1 (the regather) — the delta is exactly one
    ring all-gather of the params."""
    mesh = make_mesh((8,), ('dp',))
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        _, step3, _ = _run_step('adamw', mesh, zero=3, steps=2)
        ag = telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                             kind='all_gather', axis='dp', stage='zero3')
        rs = telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                             kind='reduce_scatter', axis='dp',
                             stage='zero3')
        n_ag = telemetry.value('mxnet_tpu_comm_collectives_total',
                               kind='all_gather', axis='dp',
                               stage='zero3')
        pgauge = telemetry.value('mxnet_tpu_comm_param_bytes_per_device')
        assert ag == 2 * rs            # fwd gather + bwd regather vs one RS
        assert n_ag == 2 * 2 * len(step3._t_names)   # 2 steps x 2 gathers
        assert pgauge == step3.param_bytes_per_device()
        plan_ag = sum(b for _l, b, _c in step3._gather_plan)
        assert ag == 2 * plan_ag       # 2 steps of the per-layer plan
    finally:
        if not was_on:
            telemetry.disable()


# ---------------------------------------------------------------------------
# gluon.Trainer stage 3: sharded param NDArrays, unmodified user loop
# ---------------------------------------------------------------------------

def _put_mesh(arr, mesh):
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


def test_trainer_zero3_shards_params(monkeypatch):
    """MXTPU_ZERO=3 + weights on a dp mesh: the fused update re-places
    the weight NDArrays dp-sharded (8x residency drop), the eager
    forward/backward consume them unmodified, and training matches the
    single-device trainer to 1e-6 (the sharded eager matmul reorders
    one contraction — same bound as the zero1 trainer suite)."""
    monkeypatch.setenv('MXTPU_ZERO', '3')
    mesh = make_mesh((8,), ('dp',))
    net_z, tr_z = _mesh_trainer(mesh, steps=3)
    monkeypatch.setenv('MXTPU_ZERO', '0')
    net_r, tr_r = _mesh_trainer(None, steps=3)
    assert tr_z._zero_stage == 3 and tr_z._zero_active \
        and tr_z._zero_dp == 8
    assert tr_r._zero_stage == 0
    for (n, pz), (_, pr) in zip(sorted(net_z.collect_params().items()),
                                sorted(net_r.collect_params().items())):
        d = pz.data()._data
        assert not d.sharding.is_fully_replicated, n
        diff = float(onp.max(onp.abs(pz.data().asnumpy()
                                     - pr.data().asnumpy())))
        assert diff <= 1e-6, (n, diff)
    assert tr_r.param_bytes_per_device() >= \
        6 * tr_z.param_bytes_per_device()


def test_trainer_zero3_replaces_after_restore(monkeypatch):
    """A checkpoint restore rewrites params as host arrays; the next
    fused step re-adopts the remembered mesh and re-places them sharded
    (the 're-run after restore' contract), continuing from the restored
    values."""
    monkeypatch.setenv('MXTPU_ZERO', '3')
    mesh = make_mesh((8,), ('dp',))
    net, tr = _mesh_trainer(mesh, steps=3)
    blob = tr.get_states_bytes()
    vals = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    # simulate CheckpointManager._apply_params: host arrays via set_data
    for n, p in net.collect_params().items():
        p.set_data(nd.array(vals[n]))
    tr.set_states_bytes(blob)      # clears the fused cache
    assert tr._zero3_mesh is not None
    x, y = _data()
    _put_mesh(x, mesh)
    _put_mesh(y, mesh)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr.step(x.shape[0])
    assert tr._zero_stage == 3 and tr._zero_active
    for n, p in net.collect_params().items():
        assert not p.data()._data.sharding.is_fully_replicated, n


def test_trainer_zero3_stage1_unaffected(monkeypatch):
    """MXTPU_ZERO=1 (the default) must keep the PR-4 behavior: states
    shard, weights stay replicated — stage 3 is strictly opt-in."""
    monkeypatch.setenv('MXTPU_ZERO', '1')
    mesh = make_mesh((8,), ('dp',))
    net, tr = _mesh_trainer(mesh, steps=2)
    assert tr._zero_stage == 1 and tr._zero_active
    for n, p in net.collect_params().items():
        assert p.data()._data.sharding.is_fully_replicated, n
