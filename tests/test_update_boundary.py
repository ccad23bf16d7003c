"""The fusion boundary between a dense gradient and its update (ISSUE 28):
``ShardedTrainStep`` hands every dense trainable gradient through one
``lax.optimization_barrier`` under ``mxtpu.exchange``, as ``value_and_grad``
produced it, so XLA cannot fuse the optimizer update into the matmul that
makes the gradient. The RowSparse branch has no such gradient and no
barrier. The barrier is an identity: one step equals a hand-written AdamW.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import nd, scopes
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import ShardedTrainStep, make_mesh

DIN, HIDDEN, DOUT, BATCH = 16, 32, 8, 16
LR, WD = 1e-2, 0.01


def _sq_loss(out, label):
    return (out - label) ** 2


def _mlp(dtype='float32'):
    """Two Dense layers: four dense trainable gradients."""
    mx.random.seed(3)
    net = nn.HybridSequential(prefix='mlp_')
    with net.name_scope():
        net.add(nn.Dense(HIDDEN, activation='relu', in_units=DIN))
        net.add(nn.Dense(DOUT, in_units=HIDDEN))
    net.initialize(mx.init.Xavier())
    net.cast(dtype)
    return net


def _batch(dtype='float32'):
    rng = onp.random.RandomState(0)
    return (nd.array(rng.randn(BATCH, DIN).astype(onp.float32)).astype(dtype),
            nd.array(rng.randn(BATCH, DOUT).astype(onp.float32)))


def _embedding_net():
    """A RowSparse table and one Dense layer: two dense gradients."""
    mx.random.seed(11)
    net = nn.HybridSequential(prefix='sp_')
    with net.name_scope():
        net.add(nn.Embedding(2000, 8, sparse_grad=True))
        net.add(nn.Dense(4, flatten=False))
    net.initialize()
    return net


def _embedding_batch():
    rng = onp.random.RandomState(0)
    return (nd.array(rng.randint(0, 40, (16, 5)).astype(onp.float32)),
            nd.array(rng.randn(16, 5, 4).astype(onp.float32)))


def _barriers(jaxpr, found=None):
    """The name stack of every optimization_barrier in a jaxpr, nested
    jaxprs (pjit, custom_vjp, checkpoint) included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'optimization_barrier':
            found.append(str(eqn.source_info.name_stack))
        for value in eqn.params.values():
            for v in (value if isinstance(value, (list, tuple))
                      else [value]):
                inner = getattr(v, 'jaxpr', v)
                inner = getattr(inner, 'jaxpr', inner)
                if hasattr(inner, 'eqns'):
                    _barriers(inner, found)
    return found


def _exchange_barriers(step):
    stacks = _barriers(
        jax.make_jaxpr(step._compiled)(*step._cost_args).jaxpr)
    return [s for s in stacks if scopes.EXCHANGE in s]


@pytest.mark.parametrize('devices, dtype, kwargs', [
    pytest.param(1, 'float32', {}, id='one_device_f32'),
    pytest.param(1, 'bfloat16', {}, id='one_device_bf16_master'),
    pytest.param(8, 'float32', {}, id='zero1_dp8'),
    pytest.param(8, 'float32', {'zero': False}, id='zero0_dp8'),
    pytest.param(8, 'float32', {'zero': 3}, id='zero3_dp8'),
    pytest.param(8, 'float32', {'compression_params': {'type': '2bit'}},
                 id='compressed_dp8'),
    pytest.param(8, 'float32', {'guard': 'skip'}, id='guarded_dp8')])
def test_one_barrier_per_dense_gradient(devices, dtype, kwargs):
    kwargs = dict(kwargs, mesh=make_mesh((devices,), ('dp',)))
    if 'guard' in kwargs:
        from mxnet_tpu.resilience import NonFiniteGuard
        kwargs['guard'] = NonFiniteGuard(policy=kwargs['guard'])
    net = _mlp(dtype)
    step = ShardedTrainStep(net, _sq_loss, 'adamw', {'learning_rate': LR},
                            **kwargs)
    step(*_batch(dtype))
    found = _exchange_barriers(step)
    assert len(found) == len(step._t_names) == 4, found
    # the boundary belongs to the gradient's way, not to the update or
    # to forward/backward
    assert not [s for s in found
                if scopes.UPDATE in s or scopes.FWD_BWD in s], found
    # and it survives lowering: the program XLA is handed has them
    text = step.lower().as_text()
    assert text.count('optimization_barrier') >= 4


@pytest.mark.parametrize('exact', [False, True])
def test_rowsparse_branch_has_no_barrier(monkeypatch, exact):
    monkeypatch.setenv('MXTPU_SPARSE', '1')
    if exact:
        monkeypatch.setenv('MXTPU_SPARSE_EXACT', '1')
    else:
        monkeypatch.delenv('MXTPU_SPARSE_EXACT', raising=False)
    net = _embedding_net()
    step = ShardedTrainStep(net, _sq_loss, 'adam', {'learning_rate': LR},
                            mesh=make_mesh((1,), ('dp',)))
    step(*_embedding_batch())
    assert len(step._sparse_names) == 1
    assert len(step._t_names) == 3
    # the Dense weight and bias, and not the table: its gradient is a row
    # block (lazy) or a scatter of one (exact), never a matmul's output
    assert len(_exchange_barriers(step)) == 2


def _reference_step(params, x, y, dtype):
    """One AdamW step written by hand over the same two layers:
    ops/nn.py's fully_connected, the mean of the squared error, the
    gradient rounded to the parameter's dtype as value_and_grad hands it
    over, the update in float32 on the float32 master."""
    def dense(h, w, b):
        out = lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        return out.astype(h.dtype) + b

    def loss_of(p):
        h = jnp.maximum(dense(x, p['w0'], p['b0']), 0)
        out = dense(h, p['w1'], p['b1'])
        return jnp.mean((out - y) ** 2)

    loss, grads = jax.value_and_grad(loss_of)(params)
    new = {}
    for n, p in params.items():
        assert grads[n].dtype == jnp.dtype(dtype)
        g = grads[n].astype(jnp.float32)
        master = p.astype(jnp.float32)
        m = 0.1 * g                       # beta1 0.9 on a zero moment
        v = 0.001 * jnp.square(g)         # beta2 0.999
        master = master - (LR * m / (jnp.sqrt(v) + 1e-8) + WD * LR * master)
        new[n] = dict(param=master.astype(dtype), master=master, m=m, v=v)
    return loss, new


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_one_step_equals_a_handwritten_adamw(dtype):
    net = _mlp(dtype)
    x, y = _batch(dtype)
    names = dict(zip(('w0', 'b0', 'w1', 'b1'), net.collect_params()))
    # copies: the step donates the parameters' buffers
    before = {k: jnp.array(net.collect_params()[n].data()._data, copy=True)
              for k, n in names.items()}
    step = ShardedTrainStep(net, _sq_loss, 'adamw',
                            {'learning_rate': LR, 'wd': WD},
                            mesh=make_mesh((1,), ('dp',)))
    loss = step(x, y).asscalar()
    want_loss, want = _reference_step(before, x._data, y._data, dtype)
    # held as test_zero1 / test_zero3 hold a trajectory: 1e-6, here
    # relative (XLA contracts a*b+c where the lines above round twice)
    def close(got, ref):
        onp.testing.assert_allclose(
            onp.asarray(got.astype(jnp.float32)),
            onp.asarray(ref.astype(jnp.float32)), rtol=1e-6, atol=1e-9)

    assert abs(float(loss) - float(want_loss)) <= 1e-6
    assert set(step._master) == (set(names.values())
                                 if dtype == 'bfloat16' else set())
    for k, n in names.items():
        got_m, got_v, got_t = step._opt_state[n]
        assert int(got_t) == 1
        close(got_m, want[k]['m'])
        close(got_v, want[k]['v'])
        param = net.collect_params()[n].data()._data
        assert param.dtype == jnp.dtype(dtype)
        if dtype == 'bfloat16':
            assert step._master[n].dtype == jnp.float32
            close(step._master[n], want[k]['master'])
            # the compute copy is exactly the master rounded
            assert onp.array_equal(
                onp.asarray(param.astype(jnp.float32)),
                onp.asarray(step._master[n].astype(jnp.bfloat16)
                            .astype(jnp.float32)))
        else:
            close(param, want[k]['param'])


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_the_barrier_changes_no_bit(monkeypatch, dtype):
    """Three steps with the boundary and three with it taken out: the
    same losses, parameters, masters and moments, bit for bit."""
    def run():
        net = _mlp(dtype)
        step = ShardedTrainStep(net, _sq_loss, 'adamw',
                                {'learning_rate': LR},
                                mesh=make_mesh((1,), ('dp',)))
        batch = _batch(dtype)
        losses = [onp.float32(step(*batch).asscalar()).tobytes()
                  for _ in range(3)]
        leaves = jax.tree_util.tree_leaves(
            ({n: p.data()._data for n, p in net.collect_params().items()},
             step._master, step._opt_state))
        return losses, [onp.asarray(a.astype(jnp.float32)).tobytes()
                        for a in leaves]

    fenced = run()
    calls = []

    def identity(x):
        calls.append(x)
        return x

    monkeypatch.setattr(jax.lax, 'optimization_barrier', identity)
    bare = run()
    assert len(calls) == 4          # the patch did bite, once a gradient
    assert fenced == bare
