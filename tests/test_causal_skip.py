"""The causal flash kernels visit only the (q-block, k-block) cells at or
under the diagonal (ops/pallas_attention.py: ``_cell_live``).

What is held here: the causal results against the naive attention of
tests/test_operator.py; that a skipped cell contributed nothing (the same
bits with the skip patched out); the counts of live cells a build records;
that a non-causal build is, equation for equation, the kernel it is known
as; and that Mosaic takes the kernels at the benchmark cells' shapes, the
fused projection addressed in place, grouped and windowed heads, a step of
several lane blocks and the expert layer's grouped matmuls among them,
compiled here for a described
v5e:2x2 with no chip (every such compile of the suite is in this file:
only one test file of a run may load the TPU's library).
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import autotune
from mxnet_tpu.ops import pallas_attention as pa
from test_operator import _naive_mha

SEED = 1234
# T=128: a 2 x 2 forward grid and a 4 x 4 backward grid (3 of 4 and 10 of
# 16 cells live, the GPT-2 cell's pattern)
FWD_BLOCKS = (2, 64, 64)
BWD_BLOCKS = (2, 32, 32)


def _forced(fwd=FWD_BLOCKS, bwd=BWD_BLOCKS):
    stack = contextlib.ExitStack()
    stack.enter_context(autotune.forced(autotune.KERNEL_FA, 'fwd', fwd))
    stack.enter_context(autotune.forced(autotune.KERNEL_FA, 'bwd', bwd))
    return stack


def _qkv(B, H, Tq, Tk, D, seed=0):
    rng = onp.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, H, t, D)), jnp.float32)
                 for t in (Tq, Tk, Tk))


def _right_padding(B, Tk, pad=7):
    """Additive key mask: the first sequence ends ``pad`` keys early. Key 0
    stays, so every causal row sees a key."""
    vlen = jnp.array([Tk - pad] + [Tk] * (B - 1))
    return jnp.where(jnp.arange(Tk)[None, :] < vlen[:, None],
                     0.0, -1e30).astype(jnp.float32)


def _naive_mha_dropped(q, k, v, key_mask, rate):
    """``_naive_mha`` (causal) with the kernels' dropout bits on the
    probabilities, regenerated from coordinates."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / onp.sqrt(D)
    if key_mask is not None:
        s = s + key_mask[:, None, None, :]
    s = jnp.where(jnp.tril(jnp.ones((Tq, Tk), bool)), s, -1e30)
    bh = jnp.arange(B * H, dtype=jnp.uint32).reshape(B, H, 1, 1)
    rows = jnp.arange(Tq, dtype=jnp.uint32)[None, None, :, None]
    cols = jnp.arange(Tk, dtype=jnp.uint32)[None, None, None, :]
    keep = pa._counter_keep(jnp.uint32(SEED), bh, rows, cols, rate)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1) * keep, v)


def _out_and_grads(fn, q, k, v):
    D = q.shape[-1]

    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * jnp.cos(jnp.arange(D, dtype=jnp.float32))), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return [onp.asarray(x) for x in (out,) + grads]


def _flash(key_mask, rate):
    def fn(q, k, v):
        return pa.flash_attention(
            q, k, v, key_mask=key_mask, causal=True, dropout_p=rate,
            dropout_seed=jnp.uint32(SEED) if rate else None, interpret=True)
    return fn


# (a) ------------------------------------------------------------------------

@pytest.mark.parametrize('T', [128, 100])        # 100: padded last blocks
@pytest.mark.parametrize('masked', [False, True])
@pytest.mark.parametrize('rate', [0.0, 0.1])
def test_causal_skip_matches_naive(rate, masked, T):
    """out, dq, dk, dv of the causal kernels, forward and backward tiled
    differently, against the naive attention in float32."""
    B, H, D = 2, 2, 16
    q, k, v = _qkv(B, H, T, T, D)
    km = _right_padding(B, T) if masked else None
    with _forced():
        got = _out_and_grads(_flash(km, rate), q, k, v)
    if rate:
        def naive(q, k, v):
            return _naive_mha_dropped(q, k, v, km, rate)
    else:
        def naive(q, k, v):
            return _naive_mha(q, k, v, km, causal=True)
    want = _out_and_grads(naive, q, k, v)
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, want):
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                    err_msg=name)


# (b) ------------------------------------------------------------------------

@pytest.mark.parametrize('Tq,Tk', [(128, 128), (100, 100), (64, 128),
                                   (128, 64)])
def test_a_skipped_cell_contributed_nothing(monkeypatch, Tq, Tk):
    """With every cell declared live the table lists the full grid, as
    the kernels ran before the skip existed (a dead cell adds
    exp(-1e30 - m) = 0 everywhere). The results are the same to the bit:
    dropout, key mask, square and oblong."""
    B, H, D = 2, 2, 16
    q, k, v = _qkv(B, H, Tq, Tk, D, seed=1)
    km = _right_padding(B, Tk)
    with _forced():
        skipped = _out_and_grads(_flash(km, 0.1), q, k, v)
    monkeypatch.setattr(pa, '_cell_live', lambda qb, kb, bq, bk: qb >= 0)
    with _forced():
        every_cell = _out_and_grads(_flash(km, 0.1), q, k, v)
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), skipped, every_cell):
        onp.testing.assert_array_equal(a, b, err_msg=name)


def test_dropout_bits_do_not_depend_on_the_backward_tiling():
    """Backward blocks 32 or 64 wide: another pattern of dead cells (10 of
    16, 3 of 4), the same dropout bits in the live ones. ``out`` is equal
    to the bit (the forward's tiling is the same); the gradients sum their
    blocks in another order and agree to rounding, where one moved
    dropout bit would move them by a whole probability."""
    q, k, v = _qkv(2, 2, 128, 128, 16, seed=2)
    with _forced(bwd=(2, 32, 32)):
        narrow = _out_and_grads(_flash(None, 0.1), q, k, v)
    with _forced(bwd=(2, 64, 64)):
        wide = _out_and_grads(_flash(None, 0.1), q, k, v)
    onp.testing.assert_array_equal(narrow[0], wide[0])
    for name, a, b in zip(('dq', 'dk', 'dv'), narrow[1:], wide[1:]):
        onp.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                    err_msg=name)


# (c), (d): what a build is, read from its jaxpr ------------------------------

def _walk(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, 'jaxpr', sub)
                if hasattr(inner, 'eqns'):
                    yield from _walk(inner)


def _kernel_builds(causal, B, H, T, D=64, dtype=jnp.bfloat16):
    """{kernel name: its pallas_call equation} of one traced forward +
    backward at the default blocks. Traced, not run."""
    x = jax.ShapeDtypeStruct((B, H, T, D), dtype)
    km = jax.ShapeDtypeStruct((B, T), jnp.float32)

    def loss(q, k, v, km, seed):
        out = pa.flash_attention(q, k, v, key_mask=km, causal=causal,
                                 dropout_p=0.1, dropout_seed=seed,
                                 interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        x, x, x, km, jax.ShapeDtypeStruct((), jnp.uint32))
    return {e.params['name']: e
            for e in _walk(traced.jaxpr) if e.primitive.name == 'pallas_call'}


def test_live_cells_at_the_gpt2_cells_grid():
    """gpt2_small.t1024 (B=24, 12 heads, T=1024): forward (4, 512, 512)
    runs 3 of its 4 cells, backward (4, 256, 256) 10 of 16, and a
    non-causal build records nothing."""
    before = dict(pa.causal_cells)

    def new():
        return {key: n - before.get(key, 0)
                for key, n in pa.causal_cells.items()
                if n != before.get(key, 0)}
    builds = _kernel_builds(False, B=56, H=12, T=512)
    assert len(builds) == 3
    assert new() == {}
    builds = _kernel_builds(True, B=24, H=12, T=1024)
    assert new() == {('fwd', 3, 4): 1, ('bwd_dq', 10, 16): 1,
                     ('bwd_dkv', 10, 16): 1}
    # the causal grid is (row groups, lane blocks, listed cells): 24 rows
    # two a step, 12 heads two a 128-lane block; and nothing guards a
    # cell's body: the two conds are _init and _finalize, as without causal
    assert {name: tuple(e.params['grid_mapping'].grid)
            for name, e in builds.items()} == {
        'mxtpu_flash_fwd': (12, 6, 3), 'mxtpu_flash_bwd_dq': (12, 6, 10),
        'mxtpu_flash_bwd_dkv': (12, 6, 10)}
    for e in builds.values():
        assert sum(x.primitive.name == 'cond'
                   for x in _walk(e.params['jaxpr'])) == 2


def test_the_cell_table_lists_live_cells_in_grid_order():
    """Row by row for the forward and dq, column by column for dk/dv,
    ascending inside a line as the full grid walks it; a column no query
    sees keeps one dead cell, so that its dk/dv are still written."""
    def rows(table):
        return [tuple(int(x) for x in col) for col in table.T]
    assert rows(pa._causal_cell_table('t', 2, 2, 64, 64, by_row=True)) == [
        (0, 0, 1, 1), (1, 0, 1, 0), (1, 1, 0, 1)]
    assert rows(pa._causal_cell_table('t', 2, 2, 64, 64, by_row=False)) == [
        (0, 0, 1, 0), (1, 0, 0, 1), (1, 1, 1, 1)]
    # blocks of different widths: bq=64, bk=32
    assert rows(pa._causal_cell_table('t', 1, 3, 64, 32, by_row=True)) == [
        (0, 0, 1, 0), (0, 1, 0, 1)]
    # Tq=32 < Tk=64 at 32-wide blocks: k-block 1 is seen by no query
    assert rows(pa._causal_cell_table('t', 1, 2, 32, 32, by_row=False)) == [
        (0, 0, 1, 1), (0, 1, 1, 1)]
    for key in [k for k in pa.causal_cells if k[0] == 't']:
        del pa.causal_cells[key]


# BERT-base's cell: (top-level equations, equations with those of nested
# jaxprs, conds, grid). Before the kernels addressed (N, T, H*D) (PR 33)
# these read (276, 338, 2, (168, 1, 1)), (252, 272, 2, (168, 2, 2)) and
# (272, 296, 2, (168, 2, 2)), as at the commit before the skip (a371ff9):
# the same four heads a step, now 2 rows x the 2 heads of a lane block,
# 28 row groups x 6 lane blocks where there were 168 head groups; the
# lane masks that pick a head out of its block came, and what the heads of
# a step share (positions, masks, element ids, a row's loads) is computed
# once a step and no longer once a head. Until the row statistics crossed
# HBM as (1, bq) rows (PR 36) the counts with nested equations read 282,
# 219 and 234: the top-level ones are the same, and the 19, 11 and 5 more
# are all under the two conds -- the forward's _finalize gathers the
# step's four heads into the lanes of one array, transposes it and writes
# four rows; dq's _init lays the eight rows of lse and delta side by side
# and transposes them into columns, dk/dv's the two rows of the key mask
NON_CAUSAL_AT_BERT_T512 = {
    'mxtpu_flash_fwd': (222, 301, 2, (28, 6, 1, 1)),
    'mxtpu_flash_bwd_dq': (198, 230, 2, (28, 6, 2, 2)),
    'mxtpu_flash_bwd_dkv': (208, 239, 2, (28, 6, 2, 2)),
}


@pytest.mark.parametrize('kernel', sorted(NON_CAUSAL_AT_BERT_T512))
def test_the_non_causal_build_is_what_it_was(kernel):
    """causal=False at bert_base.t512's shape (B=56, 12 heads, T=512,
    bf16, key mask, dropout): each kernel's jaxpr has the equations and
    the two conds (_init, _finalize) it is known by, the grid is the full
    one, and no index map computes anything. A skip that leaks into the
    non-causal path changes one of these."""
    e = _kernel_builds(False, B=56, H=12, T=512)[kernel]
    body = e.params['jaxpr']
    nested = list(_walk(body))
    mapping = e.params['grid_mapping']
    assert (len(body.eqns), len(nested),
            sum(x.primitive.name == 'cond' for x in nested),
            tuple(mapping.grid)) == NON_CAUSAL_AT_BERT_T512[kernel]
    for block in mapping.block_mappings:
        assert len(block.index_map_jaxpr.jaxpr.eqns) == 0


# Mosaic, without the chip ----------------------------------------------------

@pytest.fixture(scope='module')
def four_chips():
    """The devices of a described v5e:2x2 to compile for. The compile
    cache is off around them: an executable compiled for a described chip
    cannot be read back without one."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    yield list(topo.devices)
    jax.config.update('jax_enable_compilation_cache', before)


@pytest.fixture(scope='module')
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.mark.parametrize('causal,B,T', [(True, 24, 1024), (False, 56, 512),
                                        (False, 224, 128)],
                         ids=['gpt2_t1024_causal', 'bert_t512', 'bert_t128'])
def test_mosaic_compiles_the_kernels_for_a_described_v5e(one_chip, causal,
                                                         B, T):
    """Forward, dq and dk/dv at the cells' shapes and default blocks, as
    the models call them: the fused (N, T, 3*768) projection addressed
    three times, two 64-wide heads to a lane block, key mask and dropout
    on. A lane mask, a guard or an index map Mosaic refuses fails here, at
    no chip time; and no copy of the activation is left round the calls."""
    H, D = 12, 64
    qkv = jax.ShapeDtypeStruct((B, T, 3 * H * D), jnp.bfloat16,
                               sharding=one_chip)
    km = jax.ShapeDtypeStruct((B, T), jnp.float32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)

    def loss(qkv, km, seed):
        out = pa.flash_mha((qkv,), H, key_mask=km, causal=causal,
                           dropout_p=0.1, dropout_seed=seed,
                           interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    text = jax.jit(jax.value_and_grad(loss)).lower(
        qkv, km, seed).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ('mxtpu_flash_fwd', 'mxtpu_flash_bwd_dq',
                 'mxtpu_flash_bwd_dkv'):
        assert name in text
    assert not re.search(r' transpose\(| copy\(%?(qkv|pallas_call)', text)


@pytest.mark.parametrize('window', [4096, None], ids=['window', 'full'])
def test_mosaic_compiles_grouped_and_windowed_heads_for_a_described_v5e(
        one_chip, window):
    """smallthinker_21b.t8192's two kinds of layer (tests/test_window_gqa.py
    holds their results): one 8192-token sequence, 28 query heads over 4
    key/value heads of 128, bf16, default blocks. Forward, dq and dk/dv
    (seven query heads wide) compile."""
    q = jax.ShapeDtypeStruct((1, 8192, 28 * 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4 * 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = pa.flash_mha((q, k, v), 28, causal=True, num_kv_heads=4,
                           window=window, interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ('mxtpu_flash_fwd', 'mxtpu_flash_bwd_dq',
                 'mxtpu_flash_bwd_dkv'):
        assert name in text


def test_mosaic_compiles_a_step_of_lane_blocks_for_a_described_v5e(one_chip):
    """ouro_2_6b.t4096's layer: one 4096-token sequence, 16 heads of 128,
    bf16, causal, default blocks. With one row a chip a grid step's heads
    are adjacent lane blocks (``_lane_blocks_per_step``): forward, dq and
    dk/dv at the widths that ship compile, so a block Mosaic refuses or
    one that overruns scoped VMEM fails here, at no chip time."""
    x = jax.ShapeDtypeStruct((1, 4096, 16 * 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = pa.flash_mha((q, k, v), 16, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    before = dict(pa.row_stat_blocks)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ('mxtpu_flash_fwd', 'mxtpu_flash_bwd_dq',
                 'mxtpu_flash_bwd_dkv'):
        assert name in text
    built = {key for key, n in pa.row_stat_blocks.items()
             if n != before.get(key, 0)}
    assert len(built) == 3 and all(block[0] == 1 and block[1] > 1
                                   for _, block in built), built


def test_mosaic_compiles_the_grouped_matmuls_for_a_described_v5e(one_chip):
    """ops/moe.py's kernel at smallthinker_21b.t8192's shapes: 208 row
    tiles of 256 against 16 experts' (2560, 1536) and (768, 2560) bf16
    weights, the forward, its transposed-weights twin and the weight
    gradient with its float32 accumulator."""
    from mxnet_tpu.ops import moe
    rows, tile, tiles = moe.plan(8192, 16, 6)

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def products(x, w_gate_up, w_down, tile_expert):
        gate_up = moe.grouped_matmul(x, w_gate_up, tile_expert, tile, False,
                                     False)
        y = moe.grouped_matmul(moe._reglu(gate_up), w_down, tile_expert, tile,
                               False, False)
        dx = moe.grouped_matmul(gate_up, w_gate_up, tile_expert, tile, True,
                                False)
        dw = moe.grouped_matmul_dw(x, gate_up, tile_expert, tile, 16, False)
        return y, dx, dw
    text = jax.jit(products).lower(
        shaped(tile * tiles, 2560), shaped(16, 2560, 1536),
        shaped(16, 768, 2560), shaped(tiles, dtype=jnp.int32)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert 'mxtpu_grouped_matmul' in text


def test_a_train_step_lowers_and_plans_for_a_described_mesh(four_chips):
    """``ShardedTrainStep.lower()`` over shapes, for a dp=4 mesh of
    devices that are described and not attached: how a cell's batch is
    sized before a chip is taken (PERF.md 7). No array can be placed on
    such a device, so this is the layout's purity as well."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    mx.random.seed(3)
    net = nn.HybridSequential(prefix='mlp_')
    with net.name_scope():
        net.add(nn.Dense(256, activation='relu', in_units=128))
        net.add(nn.Dense(128, in_units=256))
    net.initialize()
    net.cast('bfloat16')
    step = ShardedTrainStep(
        net, lambda out, label: (out.astype('float32') - label) ** 2,
        'adamw', {'learning_rate': 1e-3},
        mesh=make_mesh((4,), ('dp',), devices=four_chips))
    lowered = step.lower(jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
                         jax.ShapeDtypeStruct((64, 128), jnp.float32))
    compiled = lowered.compile()
    # ZeRO-1 over the four chips: masters and moments a quarter each
    plan = compiled.memory_analysis()
    masters_and_moments = 3 * 4 * (128 * 256 + 256 + 256 * 128 + 128) // 4
    assert plan.argument_size_in_bytes > masters_and_moments
    assert plan.argument_size_in_bytes < 2 * masters_and_moments
    assert 'reduce-scatter' in compiled.as_text()
