"""The step's layout from shapes alone, and ``ShardedTrainStep.lower()``
(ISSUE 32). ``parallel/layout.py`` says where every parameter, master,
moment and residual lives without an array placed; ``lower()`` hands back
the program XLA gets without a call made. ``MATRIX`` is the set of small
steps whose lowered text was held byte-equal across the split of
``step.py`` into layout, exchange and update (CHANGES.md, PR 32).
"""
import contextlib
import gc

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import ShardedTrainStep, make_mesh

LR = 1e-2


def _sq_loss(out, label):
    return (out - label) ** 2


def _mlp(dtype='float32', ragged=False):
    """Two Dense layers. ``ragged`` makes the second 7 wide over 13
    inputs: under ZeRO-3 at dp=8 its weight (91 elements, no dim divides)
    is a flat parameter and its bias (7) a replicated one, beside the
    first layer's dim parameters."""
    mx.random.seed(3)
    hidden, dout = (13, 7) if ragged else (32, 8)
    net = nn.HybridSequential(prefix='mlp_')
    with net.name_scope():
        net.add(nn.Dense(hidden, activation='relu', in_units=16))
        net.add(nn.Dense(dout, in_units=hidden))
    net.initialize(mx.init.Xavier())
    net.cast(dtype)
    return net


def _mlp_batch(dtype='float32', ragged=False):
    rng = onp.random.RandomState(0)
    return (nd.array(rng.randn(16, 16).astype(onp.float32)).astype(dtype),
            nd.array(rng.randn(16, 7 if ragged else 8).astype(onp.float32)))


def _table(vocab=2000, dim=8):
    """A RowSparse table and one Dense layer. 2001 rows of 6 make the
    table a flat parameter under ZeRO-3 at dp=8."""
    mx.random.seed(11)
    net = nn.HybridSequential(prefix='sp_')
    with net.name_scope():
        net.add(nn.Embedding(vocab, dim, sparse_grad=True))
        net.add(nn.Dense(4, flatten=False, in_units=dim))
    net.initialize()
    return net


def _table_batch():
    rng = onp.random.RandomState(0)
    return (nd.array(rng.randint(0, 40, (16, 5)).astype(onp.float32)),
            nd.array(rng.randn(16, 5, 4).astype(onp.float32)))


def _case(mesh=(8,), axes=('dp',), optimizer='adamw', net=_mlp,
          batch=_mlp_batch, env=None, guard=False, **kwargs):
    return dict(mesh=mesh, axes=axes, optimizer=optimizer, net=net,
                batch=batch, env=env or {}, guard=guard, kwargs=kwargs)


def _ragged():
    return _mlp(ragged=True)


def _ragged_batch():
    return _mlp_batch(ragged=True)


def _bf16():
    return _mlp('bfloat16')


def _bf16_batch():
    return _mlp_batch('bfloat16')


def _ragged_table():
    return _table(vocab=2001, dim=6)


def _normed():
    """A BatchNorm between the layers: its running statistics are the
    step's frozen parameters, written back from the forward's aux."""
    mx.random.seed(3)
    net = nn.HybridSequential(prefix='bn_')
    with net.name_scope():
        net.add(nn.Dense(32, in_units=16))
        net.add(nn.BatchNorm(in_channels=32))
        net.add(nn.Dense(8, in_units=32))
    net.initialize(mx.init.Xavier())
    return net


MATRIX = {
    'dp1_zero_off': _case(mesh=(1,)),
    'dp8_zero1_adamw': _case(),
    'dp8_zero1_lamb': _case(optimizer='lamb'),
    'dp8_zero1_sgd': _case(optimizer='sgd'),
    'dp4_tp2_zero1_param_spec': _case(
        mesh=(4, 2), axes=('dp', 'tp'),
        param_specs={'dense0_weight': P('tp', None)}),
    'dp8_zero3_flat_dim_repl': _case(net=_ragged, batch=_ragged_batch,
                                     zero=3),
    'dp8_zero1_int8': _case(compression_params={'type': 'int8'}),
    'dp8_hierarchy2': _case(hierarchy=2),
    'dp8_hierarchy2_2bit': _case(hierarchy=2,
                                 compression_params={'type': '2bit'}),
    'dp8_rowsparse_lazy': _case(optimizer='adam', net=_table,
                                batch=_table_batch,
                                env={'MXTPU_SPARSE': '1'}),
    'dp8_rowsparse_exact': _case(optimizer='adam', net=_table,
                                 batch=_table_batch,
                                 env={'MXTPU_SPARSE': '1',
                                      'MXTPU_SPARSE_EXACT': '1'}),
    'dp8_rowsparse_zero3_flat': _case(optimizer='adam', net=_ragged_table,
                                      batch=_table_batch, zero=3,
                                      env={'MXTPU_SPARSE': '1'}),
    'dp8_rowsparse_lazy_int8': _case(
        optimizer='adam', net=_table, batch=_table_batch,
        compression_params={'type': 'int8'}, env={'MXTPU_SPARSE': '1'}),
    'dp8_hierarchy2_rowsparse_int8': _case(
        optimizer='adam', net=_table, batch=_table_batch, hierarchy=2,
        compression_params={'type': 'int8'}, env={'MXTPU_SPARSE': '1'}),
    'dp4_tp2_rowsparse_table_axis': _case(
        mesh=(4, 2), axes=('dp', 'tp'), optimizer='adam', net=_table,
        batch=_table_batch, env={'MXTPU_SPARSE': '1',
                                 'MXTPU_SPARSE_TABLE_AXIS': 'tp'}),
    'dp8_guard': _case(guard=True),
    'dp8_guard_int8': _case(guard=True,
                            compression_params={'type': 'int8'}),
    'dp8_frozen_batchnorm': _case(net=_normed),
    'dp8_remat_layer': _case(env={'MXTPU_REMAT': 'layer'}),
    'dp8_zero3_remat_layer': _case(zero=3, env={'MXTPU_REMAT': 'layer'}),
    'dp8_bf16_masters': _case(net=_bf16, batch=_bf16_batch),
}


def build_case(name, monkeypatch):
    """A freshly constructed step of ``MATRIX[name]`` and its batch, the
    case's environment set through ``monkeypatch`` first."""
    case = MATRIX[name]
    for key in ('MXTPU_SPARSE', 'MXTPU_SPARSE_EXACT', 'MXTPU_REMAT',
                'MXTPU_SPARSE_TABLE_AXIS'):
        monkeypatch.delenv(key, raising=False)
    for key, value in case['env'].items():
        monkeypatch.setenv(key, value)
    kwargs = dict(case['kwargs'])
    if case['guard']:
        from mxnet_tpu.resilience import NonFiniteGuard
        kwargs['guard'] = NonFiniteGuard(policy='skip')
    step = ShardedTrainStep(
        case['net'](), _sq_loss, case['optimizer'], {'learning_rate': LR},
        mesh=make_mesh(case['mesh'], case['axes']), **kwargs)
    return step, case['batch']()


@contextlib.contextmanager
def no_array_made():
    """Nothing that holds device memory outlives the block."""
    before = {id(a) for a in jax.live_arrays()}
    yield
    gc.collect()
    assert not [a.shape for a in jax.live_arrays() if id(a) not in before]


# the layout, from shapes alone ----------------------------------------------

F32 = jnp.dtype('float32')
# (name, shape, dtype, base spec, trainable) of the ragged MLP plus one
# frozen statistic
PARAMS = [
    ('d0_bias', (13,), F32, P(), True),
    ('d0_weight', (13, 16), F32, P(), True),
    ('d1_bias', (7,), F32, P(), True),
    ('d1_weight', (7, 13), F32, P(), True),
    ('d2_weight', (32, 16), F32, P(), True),
    ('bn_running_mean', (32,), F32, P(), False),
]


def _layout(stage, mesh=(8,), axes=('dp',), optimizer='adam', params=PARAMS,
            hierarchy=None, **kwargs):
    from mxnet_tpu.parallel import layout, update
    with no_array_made():
        return layout.step_layout(
            layout.mesh_axes(make_mesh(mesh, axes), 'dp', hierarchy), stage,
            params, update._OPTS[optimizer][0], **kwargs)


def _specs(shardings):
    return {n: s.spec for n, s in shardings.items()}


def test_layout_zero_off_is_replicated():
    lay = _layout(0)
    assert lay.label == 'off' and lay.t_names == [p[0] for p in PARAMS[:5]]
    assert lay.f_names == ['bn_running_mean']
    assert set(lay.modes.values()) == {'repl'}
    assert set(lay.zero_specs.values()) == {None}
    assert set(_specs(lay.zero_shardings).values()) == {P()}
    assert not lay.master_names and not lay.shard_constraint
    assert not lay.zero3_layouts and not lay.layer_groups
    assert lay.batch_sh.spec == P(('dp',)) and lay.repl.spec == P()


def test_layout_zero1_shards_state_not_parameters():
    lay = _layout(1)
    assert lay.label == 'zero1'
    assert lay.modes == {'d0_bias': 'repl', 'd0_weight': 'shard',
                         'd1_bias': 'repl', 'd1_weight': 'repl',
                         'd2_weight': 'shard'}
    # the first free dim dp divides: 16 of (13, 16), 32 of (32, 16)
    assert lay.zero_specs['d0_weight'] == P(None, 'dp')
    assert lay.zero_specs['d2_weight'] == P('dp', None)
    assert set(_specs(lay.t_shardings).values()) == {P()}
    assert set(lay.shard_constraint) == {'d0_weight', 'd2_weight'}
    # moments like the gradient's slice, the step counter replicated
    assert [s.spec for s in lay.state_shardings['d2_weight']] == \
        [P('dp', None), P('dp', None), P()]
    assert [s.spec for s in lay.state_shardings['d1_weight']] == \
        [P(), P(), P()]
    assert lay.store_shapes == {p[0]: p[1] for p in PARAMS[:5]}


def test_layout_zero3_flat_dim_and_repl():
    lay = _layout(3)
    assert lay.label == 'zero3'
    assert lay.modes == {'d0_bias': 'flat', 'd0_weight': 'dim',
                         'd1_bias': 'repl', 'd1_weight': 'flat',
                         'd2_weight': 'dim'}
    assert lay.dim_names == ['d0_weight', 'd2_weight']
    # dim: the parameter itself lives sharded, and gathers to its base
    assert lay.t_shardings['d2_weight'].spec == P('dp', None)
    assert lay.gather_shardings['d2_weight'].spec == P()
    assert [g for g, _ in lay.layer_groups] == ['d0', 'd2']
    # flat: a padded 1-D fp32 store that is the master, under a
    # replicated logical copy
    assert lay.flat_meta['d1_weight'] == {
        'mode': 'flat', 'size': 91, 'padded': 96, 'pad': 5}
    assert lay.store_shapes['d1_weight'] == (96,)
    assert lay.store_shapes['d0_bias'] == (16,)
    assert lay.master_names == {'d0_bias', 'd1_weight'}
    assert lay.master_shardings['d1_weight'].spec == P('dp')
    assert lay.t_shardings['d1_weight'].spec == P()
    assert [a.shape for a in lay.state_avals['d1_weight']] == \
        [(96,), (96,), ()]
    # repl: too small to shard, nothing to constrain
    assert lay.zero_shardings['d1_bias'].spec == P()
    assert set(lay.shard_constraint) == {'d0_weight', 'd2_weight'}
    # the flat store's round trip, still without a device
    logical = onp.arange(91, dtype=onp.float32).reshape(7, 13)
    stored = lay.to_store('d1_weight', logical)
    assert stored.shape == (96,) and not stored[91:].any()
    assert onp.array_equal(lay.to_logical('d1_weight', stored), logical)
    assert lay.to_store('d2_weight', logical) is logical


def test_layout_composes_zero_with_a_tensor_parallel_spec():
    params = [('w', (32, 16), F32, P('tp', None), True),
              ('frozen', (32, 16), F32, P(None, 'tp'), False)]
    for stage, spec in ((1, P('tp', None)), (3, P('tp', 'dp'))):
        lay = _layout(stage, mesh=(4, 2), axes=('dp', 'tp'), params=params)
        assert lay.zero_specs['w'] == P('tp', 'dp')
        assert lay.t_shardings['w'].spec == spec
        assert lay.f_shardings['frozen'].spec == P(None, 'tp')
    assert lay.gather_shardings['w'].spec == P('tp')


def test_layout_hierarchy_shards_inside_the_host():
    lay = _layout(1, hierarchy=2)
    axes = lay.axes
    assert (axes.dp_axes, axes.dp_size) == (('dph', 'dpi'), 8)
    assert (axes.shard_axis, axes.shard_size) == ('dpi', 4)
    assert (axes.cross_axis, axes.cross_size) == ('dph', 2)
    assert dict(axes.mesh.shape) == {'dph': 2, 'dpi': 4}
    assert lay.batch_sh.spec == P(('dph', 'dpi'))
    assert lay.zero_specs['d2_weight'] == P('dpi', None)
    flat = _layout(1).axes
    assert (flat.dp_axes, flat.shard_axis, flat.shard_size,
            flat.cross_axis, flat.cross_size) == (('dp',), 'dp', 8, None, 1)


@pytest.mark.parametrize('stage', [1, 3])
def test_layout_residuals_lie_where_the_gradient_is_consumed(stage):
    assert not _layout(stage).residual_shapes
    lay = _layout(stage, compressed=True)
    assert lay.residual_shapes == lay.store_shapes
    assert lay.residual_shardings == lay.zero_shardings
    if stage == 3:
        assert lay.residual_shapes['d1_weight'] == (96,)
        assert lay.residual_shardings['d1_weight'].spec == P('dp')


@pytest.mark.parametrize('optimizer, leaves', [
    ('sgd', [(32, 16)]),
    ('adam', [(32, 16), (32, 16), ()]),
    ('lamb', [(32, 16), (32, 16), ()])])
def test_layout_state_shapes_come_from_the_optimizer_init(optimizer,
                                                          leaves):
    lay = _layout(1, optimizer=optimizer)
    assert [a.shape for a in lay.state_avals['d2_weight']] == leaves
    assert [s.spec for s in lay.state_shardings['d2_weight']] == \
        [P('dp', None) if shape else P() for shape in leaves]
    assert str(lay.state_avals['d2_weight'][-1].dtype) == \
        ('int32' if leaves[-1] == () else 'float32')


def test_layout_low_precision_trainables_keep_masters():
    params = [('w', (32, 16), jnp.bfloat16, P(), True),
              ('ids', (32,), jnp.int32, P(), True),
              ('stat', (32,), jnp.bfloat16, P(), False)]
    lay = _layout(1, params=params)
    assert lay.master_names == {'w'}
    assert lay.master_shardings['w'].spec == P('dp', None)
    assert lay.dtypes['w'] == jnp.bfloat16


def test_layout_table_axis_shards_a_divisible_vocabulary():
    params = [('even', (2000, 8), F32, P(), True),
              ('ragged', (2001, 8), F32, P(), True)]
    lay = _layout(1, mesh=(4, 2), axes=('dp', 'tp'), params=params,
                  sparse_names=['even', 'ragged'], table_axis='tp')
    assert lay.table_axis == 'tp' and lay.table_sharded == {'even'}
    assert lay.specs == {'even': P('tp'), 'ragged': P()}
    assert lay.t_shardings['even'].spec == P('tp')
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match='collides'):
        _layout(1, params=params, sparse_names=['even'], table_axis='dp')
    assert _layout(1, params=params, sparse_names=['even'],
                   table_axis='tp').table_axis is None     # no such axis


# lower(): the program, with no array placed and no call made ----------------

def _avals(batch):
    return tuple(jax.ShapeDtypeStruct(a.shape, a._data.dtype) for a in batch)


@pytest.mark.parametrize('name', sorted(MATRIX))
def test_lower_from_avals_is_the_program_that_runs(name, monkeypatch):
    fresh, (x, y) = build_case(name, monkeypatch)
    x_aval, y_aval = _avals((x, y))
    with no_array_made():
        text = fresh.lower(x_aval, y_aval).as_text()
    # and the step is as it was: its first call still builds, creates
    # its state and places it
    assert fresh._compiled is None and fresh._layout is None
    assert fresh._opt_state is None and fresh._master is None
    ran, (x, y) = build_case(name, monkeypatch)
    with pytest.raises(Exception, match='has not run yet'):
        ran.lower()
    ran(x, y)
    assert ran.lower().as_text() == text
    assert ran.lower([x], [y]).as_text() == text


def test_a_step_still_runs_after_lower(monkeypatch):
    fresh, (x, y) = build_case('dp8_zero3_flat_dim_repl', monkeypatch)
    fresh.lower(x, y)
    ran, _ = build_case('dp8_zero3_flat_dim_repl', monkeypatch)
    assert float(fresh(x, y).asscalar()) == float(ran(x, y).asscalar())
    assert fresh.lower().as_text() == ran.lower().as_text()
    assert fresh.cost_analysis()['flops'] > 0
