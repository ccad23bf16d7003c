"""Ring attention: sequence/context parallelism over the mesh.

Absent in the reference (SURVEY §2.5 — long sequences were handled by
bucketing); first-class here. Q/K/V are sharded over a mesh 'sp' axis along
the sequence dimension; K/V blocks rotate around the ring via ppermute while
each device accumulates its queries' attention with online-softmax
(log-sum-exp) merging, so peak memory is O(T/sp * T/sp) per device and the
transfers ride ICI neighbor links.

Technique: blockwise/ring attention (Liu et al., "Ring Attention with
Blockwise Transformers"); implemented from scratch over lax collectives.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attn(q, k, v, scale, causal, q_offset, kv_offset, kmask=None,
                dropout_p=0.0, dropout_seed=None):
    """One block's contribution: returns (out_unnorm, row_max, row_sumexp).

    q: (B, H, Tq, D), k/v: (B, H, Tk, D). Offsets locate the blocks in the
    global sequence for causal masking. kmask: optional (B, Tk) additive
    f32 key mask for the CURRENT kv block (rotates with k/v).
    Attention dropout uses the same counter-based hash as the Pallas
    flash kernel (ops/pallas_attention.py _counter_keep) keyed on GLOBAL
    (head, q-pos, k-pos): the mask is a pure function of coordinates, so
    it is invariant to how the ring rotates the blocks and identical in
    forward and the transposed backward scan. The softmax normaliser l
    accumulates the UN-dropped p (dropout applies to the probabilities
    after normalisation, as in the dense path), so only the p·V product
    sees the keep mask.
    """
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * scale
    if kmask is not None:
        scores = scores + kmask[:, None, None, :]
    Tq, Tk = q.shape[2], k.shape[2]
    if causal:
        q_pos = q_offset + jnp.arange(Tq)
        k_pos = kv_offset + jnp.arange(Tk)
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)          # (B,H,Tq,1)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = p
    if dropout_p > 0.0:
        from ..ops.pallas_attention import _counter_keep
        B, H = q.shape[0], q.shape[1]
        bh = (jnp.arange(B, dtype=jnp.uint32)[:, None] * jnp.uint32(H)
              + jnp.arange(H, dtype=jnp.uint32)[None, :])
        rows = (q_offset + jnp.arange(Tq)).astype(jnp.uint32)
        cols = (kv_offset + jnp.arange(Tk)).astype(jnp.uint32)
        keep = _counter_keep(dropout_seed.reshape(()),
                             bh[:, :, None, None],
                             rows[None, None, :, None],
                             cols[None, None, None, :], dropout_p)
        pv = p * keep
    out = jnp.einsum('bhqk,bhkd->bhqd', pv.astype(v.dtype), v)
    return out, m, l


def _merge(acc_out, acc_m, acc_l, out, m, l):
    """Online-softmax merge of two partial attention results."""
    new_m = jnp.maximum(acc_m, m)
    alpha = jnp.exp(acc_m - new_m)
    beta = jnp.exp(m - new_m)
    new_l = acc_l * alpha + l * beta
    new_out = acc_out * alpha.astype(acc_out.dtype) \
        + out * beta.astype(out.dtype)
    return new_out, new_m, new_l


def ring_attention(q, k, v, mesh: Mesh, sp_axis: str = 'sp', causal=False,
                   scale=None, key_mask=None, dropout_p=0.0,
                   dropout_seed=None):
    """Sequence-parallel attention.

    q/k/v: (B, H, T, D) jax arrays (global logical shapes); T must divide
    by the sp axis size. key_mask: optional (B, T) mask over keys —
    boolean (True = keep) or additive f32 (0 keep / large-negative drop);
    it is sharded along the sequence axis and rotates around the ring
    with its K/V block. dropout_p > 0 applies in-kernel counter-based
    attention dropout; dropout_seed is a uint32 array (any shape, one
    element used). Returns (B, H, T, D) with the same sharding.
    """
    B, H, T, D = q.shape
    n = mesh.shape[sp_axis]
    assert T % n == 0, f"seq len {T} not divisible by sp={n}"
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    Tl = T // n

    spec = P(None, None, sp_axis, None)
    mspec = P(None, sp_axis)
    if key_mask is not None:
        # framework-wide convention: boolean/INTEGER masks are keep/drop
        # (truthy = keep); only floating masks are additive
        if not jnp.issubdtype(key_mask.dtype, jnp.floating):
            key_mask = jnp.where(key_mask.astype(jnp.bool_), 0.0, -1e30)
        key_mask = key_mask.astype(jnp.float32)
    if dropout_p > 0.0:
        if dropout_seed is None:
            raise ValueError("ring_attention: dropout_p > 0 requires "
                             "dropout_seed")
        dropout_seed = jnp.asarray(dropout_seed, jnp.uint32).reshape(-1)[:1]

    def local_fn(q_blk, k_blk, v_blk, m_blk, seed_blk=None):
        idx = lax.axis_index(sp_axis)
        q_off = idx * Tl

        acc_out = jnp.zeros(q_blk.shape, jnp.float32)
        # -1e30 (not -inf): the first merge computes exp(acc_m - new_m),
        # and inf - inf poisons reverse-mode AD with NaN cotangents
        acc_m = jnp.full(q_blk.shape[:3] + (1,), -1e30, jnp.float32)
        acc_l = jnp.zeros(q_blk.shape[:3] + (1,), jnp.float32)
        # initial accumulators are constants; mark them as varying over the
        # ring axis so the scan carry type matches the per-shard outputs
        acc_out, acc_m, acc_l = lax.pcast((acc_out, acc_m, acc_l), sp_axis,
                                          to='varying')

        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(carry, i):
            # lax.scan (not fori_loop): the ring loop must be
            # reverse-differentiable — jax transposes the ppermute into
            # the counter-rotating ring of the backward pass
            acc_out, acc_m, acc_l, k_cur, v_cur, m_cur = carry
            # block currently held came from device (idx - i) mod n
            kv_off = ((idx - i) % n) * Tl
            out, m, l = _block_attn(q_blk, k_cur, v_cur, scale, causal,
                                    q_off, kv_off, m_cur,
                                    dropout_p=dropout_p,
                                    dropout_seed=seed_blk)
            acc_out, acc_m, acc_l = _merge(acc_out, acc_m, acc_l,
                                           out.astype(jnp.float32), m, l)
            # rotate K/V (+ their key-mask slice) around the ring
            k_next = lax.ppermute(k_cur, sp_axis, perm)
            v_next = lax.ppermute(v_cur, sp_axis, perm)
            m_next = None if m_cur is None else \
                lax.ppermute(m_cur, sp_axis, perm)
            return (acc_out, acc_m, acc_l, k_next, v_next, m_next), None

        (acc_out, acc_m, acc_l, _, _, _), _ = lax.scan(
            body, (acc_out, acc_m, acc_l, k_blk, v_blk, m_blk),
            jnp.arange(n))
        return (acc_out / jnp.maximum(acc_l, 1e-30)).astype(q_blk.dtype)

    # seed is replicated (every device regenerates the same global mask
    # from coordinates); P() marks it unsharded
    if dropout_p > 0.0:
        if key_mask is None:
            def local_nomask_seed(q_blk, k_blk, v_blk, seed_blk):
                return local_fn(q_blk, k_blk, v_blk, None, seed_blk)
            return shard_map(local_nomask_seed, mesh=mesh,
                             in_specs=(spec, spec, spec, P(None)),
                             out_specs=spec)(q, k, v, dropout_seed)
        return shard_map(local_fn, mesh=mesh,
                         in_specs=(spec, spec, spec, mspec, P(None)),
                         out_specs=spec)(q, k, v, key_mask, dropout_seed)
    if key_mask is None:
        def local_nomask(q_blk, k_blk, v_blk):
            return local_fn(q_blk, k_blk, v_blk, None)
        return shard_map(local_nomask, mesh=mesh,
                         in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
    return shard_map(local_fn, mesh=mesh,
                     in_specs=(spec, spec, spec, mspec),
                     out_specs=spec)(q, k, v, key_mask)
