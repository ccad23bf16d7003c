"""Windowed and full grouped-query attention on the flash kernels
(ops/pallas_attention.py: ``num_kv_heads``, ``window``).

What is held here: out, dq, dk and dv against a naive float32 attention,
at a T that is no multiple of the blocks, with the window smaller than,
equal to and larger than T; the cell table of the benchmark cell's
T = 8192; that the builds without a window and without grouped heads are
the kernels they were (the equation counts tests/test_causal_skip.py
holds); the XLA route of ``multi_head_attention`` for the same two
arguments. That Mosaic takes the kernels at the cell's shapes is held in
tests/test_causal_skip.py, beside the other compiles for a described
v5e:2x2: only one test file of a run may load the TPU's library.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import attention as attn_ops
from mxnet_tpu.ops import autotune
from mxnet_tpu.ops import pallas_attention as pa
from test_causal_skip import NON_CAUSAL_AT_BERT_T512, _kernel_builds, _walk

H, HKV, D = 4, 2, 128


def _forced(fwd=(1, 64, 64), bwd=(1, 32, 32)):
    stack = contextlib.ExitStack()
    stack.enter_context(autotune.forced(autotune.KERNEL_FA, 'fwd', fwd))
    stack.enter_context(autotune.forced(autotune.KERNEL_FA, 'bwd', bwd))
    return stack


def _qkv(N, T, seed=0, dtype=jnp.float32):
    rng = onp.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((N, T, h * D)), dtype)
                 for h in (H, HKV, HKV))


def _naive(q, k, v, window):
    """Grouped-query causal attention with a window, the plain way: query
    head h reads key/value head h // (H // HKV); (i, j) is kept iff
    0 <= i - j < window."""
    N, T, _ = q.shape
    rep = H // HKV
    q = q.reshape(N, T, H, D).transpose(0, 2, 1, 3)
    k, v = (jnp.repeat(x.reshape(N, T, HKV, D).transpose(0, 2, 1, 3), rep,
                       axis=1) for x in (k, v))
    s = jnp.einsum('nhqd,nhkd->nhqk', q, k) / onp.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = j <= i
    if window is not None:
        keep &= i - j < window
    out = jnp.einsum('nhqk,nhkd->nhqd',
                     jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)
    return out.transpose(0, 2, 1, 3).reshape(N, T, H * D)


def _out_and_grads(fn, q, k, v):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * jnp.cos(jnp.arange(out.shape[-1],
                                                dtype=jnp.float32))), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return [onp.asarray(x) for x in (out,) + grads]


@pytest.mark.parametrize('T,window', [
    (100, 24), (100, 64), (100, 100), (100, 4096), (100, None), (128, 40)],
    ids=['inside_a_block', 'a_block_wide', 'equal_T', 'over_T', 'full',
         'whole_blocks'])
def test_windowed_grouped_heads_match_naive(T, window):
    """T = 100 pads the last blocks (forward 64 wide, backward 32). The
    dk/dv kernel adds the two query heads of a group inside the kernel."""
    q, k, v = _qkv(2, T)

    def flash(q, k, v):
        return pa.flash_mha((q, k, v), H, causal=True, num_kv_heads=HKV,
                            window=window, interpret=True)
    with _forced():
        got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(lambda q, k, v: _naive(q, k, v, window), q, k, v)
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, want):
        onp.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5,
                                    err_msg=name)


def test_grouped_heads_draw_the_dropout_bits_of_their_query_heads():
    """In-kernel dropout with grouped heads: the dk/dv kernel, whose lane
    blocks are key/value heads, numbers a head as the forward does, so
    gradients agree with finite differences of the forward's own mask --
    read here as: dv is linear in dO through the same kept probabilities
    the forward used."""
    q, k, v = _qkv(1, 64, seed=3)

    def flash(q, k, v):
        return pa.flash_mha((q, k, v), H, causal=True, num_kv_heads=HKV,
                            dropout_p=0.3, dropout_seed=jnp.uint32(7),
                            interpret=True)
    with _forced():
        out, vjp = jax.vjp(flash, q, k, v)
        do = jnp.ones_like(out)
        _, _, dv = vjp(do)
        # out = P_drop v is linear in v: <dO, out(v)> = <dv, v>
        onp.testing.assert_allclose(float(jnp.sum(do * out)),
                                    float(jnp.sum(dv * v)), rtol=1e-4)


def test_the_cell_table_at_t8192():
    """smallthinker_21b.t8192: window 4096 over T = 8192. Forward blocks
    512 wide: 16 x 16 cells, 136 under the diagonal, 108 in the band.
    Backward 256 wide: 32 x 32, 528 causal, 408 in the band; by column
    the same count."""
    before = dict(pa.window_cells)
    fwd = pa._causal_cell_table('t', 16, 16, 512, 512, by_row=True,
                                window=4096)
    dq = pa._causal_cell_table('t', 32, 32, 256, 256, by_row=True,
                               window=4096)
    dkv = pa._causal_cell_table('t', 32, 32, 256, 256, by_row=False,
                                window=4096)
    assert fwd.shape == (4, 108) and dq.shape == dkv.shape == (4, 408)
    # row 15 of the forward: k-blocks 7 to 15, nine cells, first and last
    row = fwd[:, fwd[0] == 15]
    assert row[1].tolist() == list(range(7, 16))
    assert row[2].tolist() == [1] + [0] * 8 and row[3].tolist() == [0] * 8 + [1]
    # column 0 of dk/dv: q-blocks 0 to 16 (16 * 256 - 255 < 4096)
    col = dkv[:, dkv[1] == 0]
    assert col[0].tolist() == list(range(0, 17))
    new = {key: n - before.get(key, 0) for key, n in pa.window_cells.items()
           if n != before.get(key, 0)}
    assert new == {('t', 108, 136): 1, ('t', 408, 528): 2}
    for counter in (pa.window_cells, pa.causal_cells):
        for key in [k for k in counter if k[0] == 't']:
            del counter[key]


def test_builds_without_window_or_groups_are_what_they_were():
    """causal=False at BERT's shape and causal=True at GPT-2's: the
    equation counts, conds and grids tests/test_causal_skip.py holds, and
    no index map computes anything; the window and the head groups left
    no equation in them, and no entry in ``window_cells``."""
    before = dict(pa.window_cells)
    builds = _kernel_builds(False, B=56, H=12, T=512)
    for kernel, known in NON_CAUSAL_AT_BERT_T512.items():
        e = builds[kernel]
        nested = list(_walk(e.params['jaxpr']))
        assert (len(e.params['jaxpr'].eqns), len(nested),
                sum(x.primitive.name == 'cond' for x in nested),
                tuple(e.params['grid_mapping'].grid)) == known
    causal = _kernel_builds(True, B=24, H=12, T=1024)
    assert {name: tuple(e.params['grid_mapping'].grid)
            for name, e in causal.items()} == {
        'mxtpu_flash_fwd': (12, 6, 3), 'mxtpu_flash_bwd_dq': (12, 6, 10),
        'mxtpu_flash_bwd_dkv': (12, 6, 10)}
    for e in list(builds.values()) + list(causal.values()):
        for block in e.params['grid_mapping'].block_mappings:
            # a causal index map reads its cell from the table: one
            # equation or two, none of them arithmetic
            assert all(x.primitive.name in ('get', 'squeeze', 'slice',
                                            'dynamic_slice', 'convert_'
                                            'element_type', 'reshape')
                       for x in block.index_map_jaxpr.jaxpr.eqns)
    assert pa.window_cells == before


def test_a_window_costs_a_windowed_build_one_comparison():
    """The windowed mask is one unsigned comparison of i - j, so a
    windowed kernel has the causal kernel's equations but for the
    subtraction and the bitcast."""
    def build(window):
        x = jax.ShapeDtypeStruct((1, 256, H * D), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 256, HKV * D), jnp.bfloat16)

        def f(q, k, v):
            return pa.flash_mha((q, k, v), H, causal=True, num_kv_heads=HKV,
                                window=window, interpret=False)
        traced = jax.make_jaxpr(f)(x, kv, kv)
        (e,) = [e for e in _walk(traced.jaxpr)
                if e.primitive.name == 'pallas_call']
        return len(list(_walk(e.params['jaxpr'])))
    assert build(128) - build(None) == 2


@pytest.mark.parametrize('window', [None, 24])
def test_the_xla_route_takes_the_same_arguments(window):
    """On the CPU ``multi_head_attention`` takes the XLA route; grouped
    heads and the window mean there what they mean in the kernels."""
    q, k, v = _qkv(2, 64, seed=5)
    before = dict(attn_ops.route_counts)
    got = attn_ops.multi_head_attention(q, k, v, num_heads=H, causal=True,
                                        num_kv_heads=HKV, window=window)
    assert attn_ops.route_counts['xla'] == before['xla'] + 1
    onp.testing.assert_allclose(onp.asarray(got),
                                onp.asarray(_naive(q, k, v, window)),
                                rtol=2e-5, atol=2e-5)


def test_groups_need_whole_lane_heads():
    """A group's key/value heads are whole lane blocks: one head of 128
    columns, or two of 64 (since PR 41; tests/test_gqa64.py holds their
    results); one 64-wide key/value head, or 96-wide heads, are not."""
    assert pa.flash_legal(28, 8192, 8192, 128, jnp.bfloat16, num_heads=28,
                          num_kv_heads=4)
    assert pa.flash_legal(8, 128, 128, 64, jnp.bfloat16, num_heads=8,
                          num_kv_heads=2)
    assert pa.flash_legal(32, 8192, 8192, 64, jnp.bfloat16, num_heads=32,
                          num_kv_heads=8)
    assert not pa.flash_legal(4, 128, 128, 64, jnp.bfloat16, num_heads=4,
                              num_kv_heads=1)
    assert not pa.flash_legal(8, 128, 128, 96, jnp.bfloat16, num_heads=8,
                              num_kv_heads=2)
    assert not pa.flash_legal(6, 128, 128, 128, jnp.bfloat16, num_heads=6,
                              num_kv_heads=4)
    with pytest.raises(ValueError, match='window'):
        pa.flash_mha(_qkv(1, 64), H, num_kv_heads=HKV, window=8,
                     interpret=True)
