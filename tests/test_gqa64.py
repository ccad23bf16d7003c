"""Grouped-query heads of 64 columns on the flash kernels
(ops/pallas_attention.py): a key/value lane block holds two heads, read
by the query lane blocks of their two groups, and the kernels turn the
lanes by 64 where a query head's key/value head lies in the other half.
Forward and the three gradients through the interpreter against XLA
attention with the key/value heads repeated, causal and windowed, one and
two rows, at forced small blocks; the route multi_head_attention takes;
and the block sizes the default ladder gives LFM2's layer."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import attention, autotune
from mxnet_tpu.ops import pallas_attention as pa

D = 64


def _naive(q, k, v, H, HKV, window=None):
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
    o = jnp.einsum('nhqk,nhkd->nhqd',
                   jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)
    return o.transpose(0, 2, 1, 3).reshape(N, T, H * D)


def _out_and_grads(fn, q, k, v):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o * jnp.cos(jnp.arange(o.shape[-1],
                                              dtype=jnp.float32))), o
    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return [onp.asarray(x) for x in (o,) + g]


@pytest.mark.parametrize('H,HKV,N,T,window', [
    (8, 2, 1, 100, None), (8, 2, 2, 128, 40), (8, 2, 1, 64, None),
    (8, 4, 1, 96, None), (4, 2, 2, 64, None)],
    ids=['rep4', 'rep4_window_two_rows', 'rep4_one_block', 'rep2',
         'rep2_one_group'])
def test_the_kernels_match_repeated_heads(H, HKV, N, T, window):
    rng = onp.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((N, T, h * D)), jnp.float32)
               for h in (H, HKV, HKV))
    step = H // HKV * 2         # the query heads that read one lane block
    with contextlib.ExitStack() as st:
        st.enter_context(autotune.forced(autotune.KERNEL_FA, 'fwd',
                                         (step, 64, 64)))
        st.enter_context(autotune.forced(autotune.KERNEL_FA, 'bwd',
                                         (step, 32, 32)))
        got = _out_and_grads(
            lambda q, k, v: pa.flash_mha((q, k, v), H, causal=True,
                                         num_kv_heads=HKV, window=window,
                                         interpret=True), q, k, v)
    want = _out_and_grads(lambda q, k, v: _naive(q, k, v, H, HKV, window),
                          q, k, v)
    for name, a, b in zip(('o', 'dq', 'dk', 'dv'), got, want):
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                    err_msg=name)


def test_the_lanes_turned_are_static_and_zero_for_every_older_shape():
    # 8 query heads of a step over one lane block of two key/value heads:
    # query head h lies in half h % 2 and reads key/value head h // 4,
    # which lies in half h // 4
    assert [pa._kv_shift(h, 2, 4, 64) // 64 for h in range(8)] == \
        [0, 1, 0, 1, 1, 0, 1, 0]
    for hb, rep in ((2, 1), (1, 4), (1, 7)):
        assert all(pa._kv_shift(h, hb, rep, 128) == 0 for h in range(8))


def test_lfm2s_layer_is_legal_and_takes_a_whole_lane_block_a_step():
    """32 query heads over 8 of 64 at T = 8192: both kernels are legal,
    and the default ladder gives a grid step the 8 query heads that read
    one key/value lane block, at the blocks the 64-wide MHA path has."""
    assert pa.flash_legal(32, 8192, 8192, 64, jnp.bfloat16, num_heads=32,
                          num_kv_heads=8)
    assert pa._block_sizes(32, 8192, 8192, 64, jnp.bfloat16, 'fwd', 1,
                           4) == (8, 512, 512)
    assert pa._block_sizes(32, 8192, 8192, 64, jnp.bfloat16, 'bwd', 1,
                           4) == (8, 256, 256)
    # the 64-wide MHA path of BERT and GPT-2 keeps its ladder
    assert pa._block_sizes(24 * 12, 1024, 1024, 64, jnp.bfloat16, 'fwd',
                           24, 1)[0] == 4


def test_multi_head_attention_routes_the_group_to_the_kernel(monkeypatch):
    """With the kernels available, the grouped 64-wide layer takes the
    flash route, not XLA's, and its builds are counted by group."""
    monkeypatch.setattr(pa, 'pallas_available', lambda: True)
    real = pa.flash_mha
    monkeypatch.setattr(pa, 'flash_mha', lambda *a, **kw: real(
        *a, **dict(kw, interpret=True)))
    rng = onp.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 64, h * D)), jnp.float32)
               for h in (8, 2, 2))
    routes, groups = dict(attention.route_counts), dict(pa.group_builds)
    with autotune.forced(autotune.KERNEL_FA, 'fwd', (8, 64, 64)):
        out = jax.eval_shape(
            lambda q, k, v: attention.multi_head_attention(
                q, k, v, None, num_heads=8, causal=True, num_kv_heads=2),
            q, k, v)
    assert out.shape == (1, 64, 8 * D)
    assert attention.route_counts['pallas'] == routes['pallas'] + 1
    assert attention.route_counts['xla'] == routes['xla']
    key = ('fwd', 8, 2, D)
    assert pa.group_builds[key] == groups.get(key, 0) + 1
