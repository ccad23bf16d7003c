"""Error-feedback gradient compression + hierarchy-aware collectives
(ISSUE 12): the codec contracts, the in-step quantization epilogue with
per-param sharded residuals, the (cross-host, intra-host) dp
decomposition and its per-hop wire accounting, composition with
ZeRO-1/3 and the non-finite guard, and checkpoint round-trips of the
residual state across dp degrees and compression configs."""
import os
import pickle
import subprocess
import sys

import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, autograd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
from mxnet_tpu.parallel import compression as codecs
from mxnet_tpu.parallel import dist as pdist
from mxnet_tpu.resilience import NonFiniteGuard, faults


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


def _run(compression=None, hierarchy=None, zero=1, steps=3, dp=8,
         lr=0.01, net=None, optimizer='adamw'):
    net = net if net is not None else _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss_fn, optimizer,
                            {'learning_rate': lr},
                            mesh=make_mesh((dp,), ('dp',)), zero=zero,
                            compression_params=compression,
                            hierarchy=hierarchy)
    x, y = _data()
    losses = [float(step(x, y).asscalar()) for _ in range(steps)]
    return net, step, losses


# ---------------------------------------------------------------------------
# codec unit contracts
# ---------------------------------------------------------------------------

def test_codec_roundtrip_properties():
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 512).astype(onp.float32))
    # fp16: bounded relative error
    dec = codecs.encode_decode(x, 'fp16')
    err = onp.abs(onp.asarray(dec) - onp.asarray(x))
    assert float(onp.max(err / (onp.abs(onp.asarray(x)) + 1e-8))) < 1e-3
    # int8: error bounded by half a quantization step of the block max
    dec = codecs.encode_decode(x, 'int8', block=256)
    err = onp.abs(onp.asarray(dec) - onp.asarray(x))
    assert float(onp.max(err)) <= float(onp.max(onp.abs(x))) / 127.0
    # 2bit with block scale: exactly three levels per block {-ts, 0, ts}
    dec = onp.asarray(codecs.encode_decode(x, '2bit', threshold=0.5,
                                           block=256))
    blocks = dec.reshape(4, 2, 256)
    src = onp.asarray(x).reshape(4, 2, 256)
    for i in range(4):
        for b in range(2):
            t = 0.5 * onp.max(onp.abs(src[i, b]))
            allowed = onp.array([-t, 0.0, t], onp.float32)
            d = onp.min(onp.abs(blocks[i, b][:, None] - allowed), axis=-1)
            assert onp.all(d < 1e-6), (i, b, t)
    # 2bit block=0: the reference's ABSOLUTE threshold
    dec = onp.asarray(codecs.encode_decode(
        jnp.asarray([0.3, 0.7, -0.6, -0.2], jnp.float32), '2bit',
        threshold=0.5, block=0))
    assert onp.allclose(dec, [0.0, 0.5, -0.5, 0.0])


def test_codec_nan_propagates_to_decoded():
    """A comparison against NaN is False, so a naive quantizer maps a
    poisoned gradient to 0 and hides it from the guard — the codecs
    must re-inject non-finite inputs into the decoded output."""
    x = jnp.asarray([1.0, float('nan'), float('inf'), -2.0], jnp.float32)
    for ctype in ('fp16', 'int8', '2bit'):
        dec = onp.asarray(codecs.encode_decode(x, ctype))
        assert onp.isnan(dec[1]), ctype
        assert not onp.isfinite(dec[2]), ctype
        assert onp.isfinite(dec[0]) and onp.isfinite(dec[3]), ctype


def test_codec_wire_bytes_math():
    # fp16: 2 bytes/elem, no scales
    assert codecs.wire_bytes((4, 512), 'fp16') == 2 * 4 * 512
    # int8: 1 byte/elem + one fp32 scale per 256-block
    assert codecs.wire_bytes((4, 512), 'int8', 256) == \
        4 * 512 + 4 * (4 * 512 // 256)
    # 2bit: 2 bits/elem + scales
    assert codecs.wire_bytes((4, 512), '2bit', 256) == \
        (4 * 512 * 2 + 7) // 8 + 4 * (4 * 512 // 256)
    # 2bit absolute threshold (block=0): no scales on the wire
    assert codecs.wire_bytes((4, 512), '2bit', 0) == (4 * 512 * 2 + 7) // 8
    # ragged last dim: one per-tensor scale
    assert codecs.wire_bytes((7,), 'int8', 256) == 7 + 4
    assert codecs.wire_bytes((), 'fp16') == 2
    assert codecs.wire_bytes((4, 512), 'none') == 4 * 4 * 512
    assert codecs.compression_ratio((4, 512), '2bit', 0) > 15.9


def test_resolve_validates_and_reads_knobs(monkeypatch):
    assert codecs.resolve(None) is None
    assert codecs.resolve({'type': 'none'}) is None
    spec = codecs.resolve({'type': '2bit', 'threshold': 0.25,
                           'block_size': 128})
    assert spec == {'type': '2bit', 'threshold': 0.25, 'block': 128}
    with pytest.raises(MXNetError, match='not supported'):
        codecs.resolve({'type': '3bit'})
    with pytest.raises(MXNetError, match='threshold'):
        codecs.resolve({'type': '2bit', 'threshold': 0})
    monkeypatch.setenv('MXTPU_COMPRESSION', 'fp16')
    spec = codecs.resolve(None)
    assert spec['type'] == 'fp16'
    # the env default reaches the step too
    net, step, losses = _run(steps=1)
    assert step.compression is not None and \
        step.compression['type'] == 'fp16'
    monkeypatch.delenv('MXTPU_COMPRESSION')
    assert codecs.resolve(None) is None


def test_error_feedback_reconstruction_invariant():
    """acc = decoded + residual EXACTLY (the EF bookkeeping identity),
    and over repeated pushes of the same gradient the accumulated
    residual eventually releases sub-threshold mass (the Deep Gradient
    Compression property)."""
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    gc = GradientCompression('2bit', threshold=0.5)
    g = nd.array([0.3, 0.7, -0.6, -0.2])
    out1 = gc.compress_decompress(g, 'k')
    r1 = onp.asarray(gc._residual['k'])
    assert onp.allclose(out1.asnumpy() + r1, [0.3, 0.7, -0.6, -0.2])
    out2 = gc.compress_decompress(g, 'k').asnumpy()
    # 0.3 + 0.3 carried residual = 0.6 >= t -> released on push 2
    assert onp.allclose(out2, [0.5, 0.5, -0.5, 0.0])
    gc.reset()
    assert not gc._residual


def test_transient_nan_does_not_poison_eager_residual():
    """A single non-finite gradient on the eager compression paths
    (Trainer in-place / kvstore push / Module.update) must propagate to
    the DECODED value (so the guard/AMP scaler skips the step) but must
    NOT outlive the push in the carried residual — the same gated
    writeback the pjit step applies on device."""
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    gc = GradientCompression('2bit', threshold=0.5)
    gc.compress_decompress(nd.array([0.3, 0.7]), 'k')
    r_before = onp.asarray(gc._residual['k']).copy()
    bad = gc.compress_decompress(nd.array([float('nan'), 1.0]), 'k')
    assert not onp.all(onp.isfinite(bad.asnumpy()))   # caller sees it
    assert onp.array_equal(onp.asarray(gc._residual['k']), r_before)
    # recovery: the next finite push behaves as if the bad one never
    # happened
    out = gc.compress_decompress(nd.array([0.3, 0.7]), 'k').asnumpy()
    assert onp.all(onp.isfinite(out))


def test_gradient_compression_validates_block_size():
    """The kvstore wrapper shares resolve()'s validation: a negative
    block must fail actionably at construction, not as an opaque
    reshape error mid-training."""
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    with pytest.raises(MXNetError, match='block_size'):
        GradientCompression('int8', block_size=-64)
    with pytest.raises(MXNetError, match='threshold'):
        GradientCompression('2bit', threshold=-1.0)
    gc = GradientCompression('none', threshold=0.25)
    assert gc.type == 'none'


# ---------------------------------------------------------------------------
# host-topology query / hierarchy derivation
# ---------------------------------------------------------------------------

def test_dp_host_split_rules():
    import jax
    devs = jax.devices()[:8]
    # single-process CPU: auto-detect finds one host -> flat
    assert pdist.dp_host_split(devs, force=0) == (1, 8)
    assert pdist.dp_host_split(devs, force=1) == (1, 8)
    # forced synthetic split (CPU simulation)
    assert pdist.dp_host_split(devs, force=2) == (2, 4)
    assert pdist.dp_host_split(devs, force=4) == (4, 2)
    with pytest.raises(MXNetError, match='not divisible'):
        pdist.dp_host_split(devs[:6], force=4)
    groups = pdist.host_topology(devs)
    assert len(groups) == 1 and len(groups[0][1]) == 8


def test_hierarchy_rejects_dp_param_specs():
    from jax.sharding import PartitionSpec as P
    net = _net()
    with pytest.raises(MXNetError, match='hierarchical dp'):
        ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         'adamw', mesh=make_mesh((8,), ('dp',)),
                         hierarchy=2,
                         param_specs={net[0].weight.name: P('dp', None)})


# ---------------------------------------------------------------------------
# the uncompressed path is bit-unchanged; hierarchy alone is a pure
# layout change
# ---------------------------------------------------------------------------

def test_compression_off_paths_bit_identical():
    _, step_a, loss_a = _run(compression=None)
    _, step_b, loss_b = _run(compression={'type': 'none'})
    assert loss_a == loss_b
    assert step_a.compression is None and step_b.compression is None
    assert step_a.compression_report() is None
    # legacy accounting intact: zero1 reduce_scatter == all_gather bytes
    rs = step_a._comm_plan['reduce_scatter']
    ag = step_a._comm_plan['all_gather']
    assert rs[0] == ag[0] and rs[0] > 0
    assert step_a.comm_bytes_per_hop() == {'dp': int(rs[0] + ag[0])}


@pytest.mark.parametrize('H', [2, 4])
def test_hierarchy_parity_uncompressed(H):
    """Splitting dp into (cross, intra) sub-axes without compression is
    a pure layout change: the trajectory matches flat dp to <=1e-6 and
    the per-hop bytes decompose (intra param traffic + cross grad
    exchange)."""
    _, step_f, loss_f = _run(hierarchy=1)
    _, step_h, loss_h = _run(hierarchy=H)
    for a, b in zip(loss_f, loss_h):
        assert abs(a - b) <= 1e-6, (H, loss_f, loss_h)
    hops = step_h.comm_bytes_per_hop()
    assert set(hops) == {'dph', 'dpi'}
    assert hops['dph'] > 0 and hops['dpi'] > 0
    # ZeRO shard degree is the INTRA extent: states replicate across
    # host groups, so one device holds ~1/h (not 1/dp) of the state
    h = 8 // H
    _, step_flat_off, _ = _run(zero=0)
    rb = step_flat_off.opt_state_bytes_per_device()
    zb = step_h.opt_state_bytes_per_device()
    assert zb <= rb / h * 1.3 + 4096, (zb, rb, h)
    assert step_h._axes.shard_size == h and step_h._axes.cross_size == H
    assert tuple(step_h.mesh.axis_names) == ('dph', 'dpi')


# ---------------------------------------------------------------------------
# error-feedback compression in the compiled step
# ---------------------------------------------------------------------------

def test_fp16_compression_close_to_uncompressed():
    """fp16 EF truncation at lr=0.01 over 3 steps stays within a tight
    bound of the uncompressed trajectory, with the residual carried as
    SHARDED per-param fp32 state."""
    _, step_u, loss_u = _run()
    _, step_c, loss_c = _run(compression={'type': 'fp16'})
    for a, b in zip(loss_u, loss_c):
        assert abs(a - b) <= 5e-5, (loss_u, loss_c)
    rep = step_c.compression_report()
    assert rep['codec'] == 'fp16' and rep['ratio'] == 2.0
    assert rep['residual_bytes_per_device'] > 0
    # residuals shard with the grad layout (zero1: 1/dp per device)
    for n, r in step_c._residual.items():
        assert tuple(r.shape) == tuple(step_c._residual_shapes[n])
        if step_c.zero_specs[n] is not None:
            assert not r.sharding.is_fully_replicated, n


def test_2bit_compression_trains_and_is_deterministic():
    _, step_a, loss_a = _run(compression={'type': '2bit'}, steps=10)
    _, step_b, loss_b = _run(compression={'type': '2bit'}, steps=10)
    assert loss_a == loss_b          # same seed -> bit-identical
    assert all(onp.isfinite(l) for l in loss_a)
    assert loss_a[-1] < loss_a[0]    # still learns through the codec
    # the residual is genuinely nonzero (error is being carried)
    total = sum(float(onp.sum(onp.abs(onp.asarray(r))))
                for r in step_a._residual.values())
    assert total > 0


def test_hier_cross_hop_shrink_ratios():
    """The acceptance ratios: the cross-host gradient exchange carries
    the encoded payload — >=3x smaller for 2bit (and int8), >=1.9x for
    fp16 — while the intra hop stays full precision."""
    _, base, _ = _run(hierarchy=2, steps=1)
    before = base.comm_bytes_per_hop()
    for ctype, floor in (('2bit', 3.0), ('int8', 3.0), ('fp16', 1.9)):
        _, step, _ = _run(compression={'type': ctype}, hierarchy=2,
                          steps=1)
        after = step.comm_bytes_per_hop()
        assert after['dpi'] == before['dpi'], ctype   # ICI untouched
        shrink = before['dph'] / max(1, after['dph'])
        assert shrink >= floor, (ctype, before, after, shrink)
        rep = step.compression_report()
        assert rep['axis'] == 'dph'
        assert rep['ratio'] >= floor, (ctype, rep)


def test_zero_stages_compose_with_compression():
    """Compression fixed, ZeRO stage varied: the quantization epilogue
    sees the same mathematical gradient either way, so zero3 matches
    zero1 to <=1e-6 (the established reduction-reorder bound)."""
    _, s1, loss_1 = _run(compression={'type': 'fp16'}, zero=1)
    _, s3, loss_3 = _run(compression={'type': 'fp16'}, zero=3)
    for a, b in zip(loss_1, loss_3):
        assert abs(a - b) <= 1e-6, (loss_1, loss_3)
    # zero3 flat params carry flat padded residuals
    for n, fz in s3._flat_meta.items():
        assert s3._residual_shapes[n] == (fz['padded'],)


def test_guard_composes_with_compression():
    """An injected NaN step under 2bit compression: the codec must NOT
    silently quantize the NaN away — the guard (which reduces over the
    DECODED grads) skips the step on device and the gated residual
    writeback keeps the error state clean."""
    mesh = make_mesh((8,), ('dp',))
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(32, 6).astype(onp.float32))
    y = nd.array(rng.randn(32, 1).astype(onp.float32))
    net = nn.Dense(1, in_units=6)
    net.initialize()
    guard = NonFiniteGuard(policy='skip', max_consecutive_bad=10)
    step = ShardedTrainStep(net, gluon.loss.L2Loss(), 'adam',
                            {'learning_rate': 0.05}, mesh=mesh,
                            guard=guard,
                            compression_params={'type': '2bit'})
    faults.arm('step.dispatch', 'nan', window=(3, 4))
    weights = []
    try:
        for _ in range(6):
            step(x, y)
            weights.append(net.weight.data().asnumpy().copy())
    finally:
        faults.disarm()
    assert all(onp.isfinite(w).all() for w in weights)
    assert onp.array_equal(weights[2], weights[3])   # poisoned: no-op
    assert not onp.array_equal(weights[4], weights[5])
    assert guard.bad_steps == 2
    # the residual survived the poisoned steps finite
    for n, r in step._residual.items():
        assert onp.all(onp.isfinite(onp.asarray(r))), n


# ---------------------------------------------------------------------------
# checkpoint round-trips of the residual state (ISSUE 12 satellite)
# ---------------------------------------------------------------------------

def test_residuals_ride_states_payload_dp8_to_dp4(tmp_path):
    """Save under 2bit compression at dp=8, restore at dp=4 (same
    codec): the residuals re-scatter from the layout-independent
    payload and the continued trajectory matches the saving instance's
    to <=1e-6 (the established cross-dp-degree parity bound — the batch
    reduction order changes with the mesh)."""
    from mxnet_tpu.checkpoint import CheckpointManager
    net = _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data()
    comp = {'type': '2bit', 'threshold': 0.5}
    step8 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((8,), ('dp',)),
                             compression_params=comp)
    for _ in range(3):
        step8(x, y)
    blob = step8.get_states_bytes()
    doc = pickle.loads(blob)
    assert set(doc['residual']) == set(n for n, _ in step8._trainable)
    assert doc['compression']['type'] == '2bit'
    # manifest audit trail
    mgr = CheckpointManager(str(tmp_path), params=net, trainer=step8,
                            async_save=False)
    mgr.save(3)
    mgr.close()
    from mxnet_tpu.checkpoint import manifest as mf
    layout = mf.read_manifest(mgr.step_dir(3))['metadata'][
        'optimizer_state_layout']
    assert layout['compression']['type'] == '2bit'
    params_at_3 = {n: p.data().asnumpy().copy()
                   for n, p in net.collect_params().items()}
    # reference: two more steps on the saving instance
    ref_losses = [float(step8(x, y).asscalar()) for _ in range(2)]
    # restore into dp=4 with the same codec; rewind the params too
    for n, p in net.collect_params().items():
        p.set_data(nd.array(params_at_3[n]))
    step4 = ShardedTrainStep(net, loss_fn, 'adamw',
                             {'learning_rate': 0.01},
                             mesh=make_mesh((4,), ('dp',)),
                             compression_params=comp)
    step4.set_states_bytes(blob)
    got_losses = [float(step4(x, y).asscalar()) for _ in range(2)]
    for a, b in zip(got_losses, ref_losses):
        assert abs(a - b) <= 1e-6, (got_losses, ref_losses)
    # and the restored residuals round-trip bit-identically
    got = pickle.loads(step4.get_states_bytes())
    for n in doc['residual']:
        a = onp.asarray(doc['residual'][n])
        b = onp.asarray(got['residual'][n])
        assert a.shape == b.shape


def test_residual_restore_compression_off_and_reseed(tmp_path):
    """The cross-config matrix: a compressed payload restores into an
    UNCOMPRESSED step (residuals dropped — no error state to carry),
    and an uncompressed payload restores into a compressed step
    (residuals deterministically reseed to zero)."""
    net = _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data()
    comp = {'type': 'fp16'}
    step_c = ShardedTrainStep(net, loss_fn, 'adamw',
                              {'learning_rate': 0.01},
                              mesh=make_mesh((8,), ('dp',)),
                              compression_params=comp)
    for _ in range(2):
        step_c(x, y)
    blob_c = step_c.get_states_bytes()
    # compressed payload -> uncompressed step: runs, residuals dropped
    step_u = ShardedTrainStep(net, loss_fn, 'adamw',
                              {'learning_rate': 0.01},
                              mesh=make_mesh((4,), ('dp',)))
    step_u.set_states_bytes(blob_c)
    step_u(x, y)
    assert 'residual' not in pickle.loads(step_u.get_states_bytes())
    # uncompressed payload -> compressed step: zero reseed
    blob_u = step_u.get_states_bytes()
    step_c2 = ShardedTrainStep(net, loss_fn, 'adamw',
                               {'learning_rate': 0.01},
                               mesh=make_mesh((4,), ('dp',)),
                               compression_params=comp)
    step_c2(x, y)            # build + accumulate a nonzero residual
    step_c2.set_states_bytes(blob_u)
    for n, r in step_c2._residual.items():
        assert not onp.any(onp.asarray(r)), \
            f"residual {n} not reseeded to zero"


# ---------------------------------------------------------------------------
# telemetry contract
# ---------------------------------------------------------------------------

def test_compression_telemetry_contract():
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        _, step, _ = _run(compression={'type': '2bit'}, hierarchy=2,
                          steps=2)
        rep = step.compression_report()
        enc_step = step._comp_plan['encoded_bytes']   # unrounded
        enc = telemetry.value('mxnet_tpu_comm_compressed_bytes_total',
                              codec='2bit', axis='dph')
        assert enc == pytest.approx(2 * enc_step, rel=1e-6)
        assert telemetry.value('mxnet_tpu_comm_compression_ratio') == \
            pytest.approx(rep['ratio'])
        assert telemetry.value(
            'mxnet_tpu_comm_residual_bytes_per_device') == \
            step.residual_bytes_per_device()
        # per-hop collective bytes: the cross hop carries the ENCODED
        # size under kind=all_reduce/axis=dph
        cross = telemetry.value('mxnet_tpu_comm_collective_bytes_total',
                                kind='all_reduce', axis='dph',
                                stage='zero1')
        assert cross == pytest.approx(2 * enc_step, rel=1e-6)
        intra_rs = telemetry.value(
            'mxnet_tpu_comm_collective_bytes_total',
            kind='reduce_scatter', axis='dpi', stage='zero1')
        assert intra_rs and intra_rs > cross
    finally:
        if not was_on:
            telemetry.disable()


# ---------------------------------------------------------------------------
# gluon.Trainer runs unmodified with compression_params
# ---------------------------------------------------------------------------

def test_trainer_with_compression_on_mesh_weights():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((8,), ('dp',))
    net = _net()
    x, y = _data()
    net(x)
    repl = NamedSharding(mesh, P())
    for p in net.collect_params().values():
        p.data()._data = jax.device_put(p.data()._data, repl)
    x._data = jax.device_put(x._data, repl)
    y._data = jax.device_put(y._data, repl)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.01},
                            compression_params={'type': '2bit'})
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()}
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
    after = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    assert any(not onp.array_equal(before[n], after[n]) for n in before)
    assert all(onp.isfinite(v).all() for v in after.values())
    # a states restore resets the carried residuals (deterministic)
    comp = trainer._kvstore._compression or trainer._local_compression()
    blob = trainer.get_states_bytes()
    trainer.set_states_bytes(blob)
    assert not comp._residual


def test_module_routes_compression_params():
    """The Module API's long-ignored ``compression_params`` now routes
    to the shared codecs (applied to the summed gradient in update() —
    the same contract as the Trainer's no-push paths)."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.module import Module
    from mxnet_tpu.io import NDArrayIter
    rng = onp.random.RandomState(0)
    X = rng.randn(32, 6).astype('float32')
    Y = (X.sum(1) > 0).astype('float32')
    x = sym.Variable('data')
    w = sym.Variable('fc_weight', shape=(2, 6))
    b = sym.Variable('fc_bias', shape=(2,))
    out = sym.SoftmaxOutput(
        sym.FullyConnected(x, w, b, num_hidden=2, name='fc'),
        sym.Variable('softmax_label'), name='softmax')
    mod = Module(out, data_names=('data',),
                 label_names=('softmax_label',), context=mx.cpu(0),
                 compression_params={'type': '2bit', 'threshold': 0.1})
    it = NDArrayIter(X, Y, batch_size=16, label_name='softmax_label')
    mod.fit(it, num_epoch=1, optimizer_params=(('learning_rate', 0.1),))
    assert mod._compression is not None and mod._compression._residual
    with pytest.raises(MXNetError, match='not supported'):
        Module(out, data_names=('data',), label_names=('softmax_label',),
               compression_params={'type': 'bogus'})


def test_compression_determinism_3x():
    """Drives tools/flakiness_checker.py over the compression
    determinism test 3x (distinct MXNET_TEST_SEED per trial): the codec
    epilogue is a pure function of the trajectory, so every trial must
    pass."""
    tools = os.path.join(os.path.dirname(__file__), os.pardir, 'tools',
                         'flakiness_checker.py')
    res = subprocess.run(
        [sys.executable, tools,
         'tests/test_compression.py::'
         'test_2bit_compression_trains_and_is_deterministic',
         '-n', '3'],
        cwd=os.path.join(os.path.dirname(__file__), os.pardir),
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert '3/3 passed' in res.stdout
