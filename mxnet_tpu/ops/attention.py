"""Attention kernels (ref: src/operator/contrib/transformer.cc:650-828).

The reference exposes interleaved-matmul ops over a packed (T, N, 3*H*D)
projection tensor. We keep that API for parity, plus a fused
`multi_head_attention` that is the TPU-preferred entry: one call that can be
routed to the XLA path or the Pallas flash-attention kernel
(mxnet_tpu.ops.pallas_attention) from the platform, the mask kind and the
kernel's static legality check.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..base import MXNetError, register_op, state as _flags
from .. import random as _random
from .. import scopes as _scopes

__all__ = []


def _reg(fn):
    register_op(fn.__name__)(fn)
    __all__.append(fn.__name__)
    return fn


def _split_heads_interleaved(queries_keys_values, num_heads, parts):
    """(T, N, parts*H*D) interleaved per head → list of (N*H, T, D)."""
    T, N, tot = queries_keys_values.shape
    D = tot // (num_heads * parts)
    x = queries_keys_values.reshape(T, N, num_heads, parts, D)
    outs = []
    for p in range(parts):
        part = x[:, :, :, p, :]                       # (T, N, H, D)
        part = part.transpose(1, 2, 0, 3)             # (N, H, T, D)
        outs.append(part.reshape(N * num_heads, T, D))
    return outs


@_reg
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """scores = scaled Q·K^T from packed qkv (ref: transformer.cc:650)."""
    q, k, _ = _split_heads_interleaved(queries_keys_values, heads, 3)
    scale = 1.0 / math.sqrt(q.shape[-1])
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@_reg
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1):
    """out = att·V, re-packed to (T, N, H*D) (ref: transformer.cc:708)."""
    _, _, v = _split_heads_interleaved(queries_keys_values, heads, 3)
    out = jnp.matmul(attention, v)                    # (N*H, T, D)
    NH, T, D = out.shape
    N = NH // heads
    out = out.reshape(N, heads, T, D).transpose(2, 0, 1, 3)
    return out.reshape(T, N, heads * D)


@_reg
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1):
    """Ref: transformer.cc:766. queries (Tq, N, H*D); keys_values (Tk, N, 2*H*D)."""
    Tq, N, tot = queries.shape
    D = tot // heads
    q = queries.reshape(Tq, N, heads, D).transpose(1, 2, 0, 3).reshape(
        N * heads, Tq, D)
    k, _ = _split_heads_interleaved(keys_values, heads, 2)
    scale = 1.0 / math.sqrt(D)
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@_reg
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1):
    _, v = _split_heads_interleaved(keys_values, heads, 2)
    out = jnp.matmul(attention, v)
    NH, T, D = out.shape
    N = NH // heads
    out = out.reshape(N, heads, T, D).transpose(2, 0, 1, 3)
    return out.reshape(T, N, heads * D)


@_reg
def div_sqrt_dim(data):
    """Ref: transformer.cc _contrib_div_sqrt_dim."""
    return data / math.sqrt(data.shape[-1])


def _as_key_padding_mask(mask, N, Tk):
    """If `mask` is a key-padding mask — broadcastable (N,1,1,Tk) or
    (N,Tk) — return it as (N, Tk) preserving its dtype; else None.
    Mask convention (both attention paths, torch-style): boolean/integer
    masks are keep/drop (truthy = keep); floating masks are ADDITIVE
    (0.0 = keep, large-negative = drop) and are added to the scores."""
    if mask is None:
        return None
    shp = tuple(mask.shape)
    if shp == (N, Tk):
        return mask
    if len(shp) == 4 and shp[0] in (1, N) and shp[1] == 1 and shp[2] == 1 \
            and shp[3] == Tk:
        m = mask.reshape(shp[0], Tk)
        if shp[0] == 1:
            m = jnp.broadcast_to(m, (N, Tk))
        return m
    return None


# trace-time routing telemetry. Incremented when multi_head_attention
# picks a path (once per trace, not per step — jit caches the traced
# program). Lets benches/tests assert the flagship config really routes
# through the flash kernel.
route_counts = {'pallas': 0, 'xla': 0, 'ring': 0}

# active sequence-parallel config: (mesh, axis) or None
_seq_parallel = []


class sequence_parallel:
    """Context manager routing `multi_head_attention` through ring
    attention over `mesh`'s `axis` — transparent long-context support:
    models keep calling the fused op, the sequence dimension shards over
    the mesh and K/V blocks rotate on ICI neighbor links
    (parallel/ring_attention.py; no reference equivalent — it bucketed
    long sequences instead).

        with mx.ops.attention.sequence_parallel(mesh, 'sp'):
            out = model(tokens)          # attention is now ring attention
    """

    def __init__(self, mesh, axis='sp'):
        self._cfg = (mesh, axis)

    def __enter__(self):
        _seq_parallel.append(self._cfg)
        return self

    def __exit__(self, *exc):
        _seq_parallel.pop()


# active mesh placement of the batch: (mesh, batch_axes, head_axes)
_mesh_placement = []


class mesh_placement:
    """Context manager telling ``multi_head_attention`` how the arrays it
    is traced on are laid out over ``mesh``: batch dim sharded over
    ``batch_axes``, heads over ``head_axes`` (either may be empty).
    ``ShardedTrainStep`` enters it while tracing its step.

    The partitioner cannot split the flash kernel — an opaque custom
    call — and jax 0.9 refuses to lower one whose operands are sharded
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"). Inside this context the Pallas route
    does that: it maps the kernel over those axes with ``shard_map``,
    so each chip runs it on its own (B/dp)·(H/tp) slices, with no
    collective."""

    def __init__(self, mesh, batch_axes=(), head_axes=()):
        self._cfg = (mesh, tuple(batch_axes), tuple(head_axes))

    def __enter__(self):
        _mesh_placement.append(self._cfg)
        return self

    def __exit__(self, *exc):
        _mesh_placement.pop()


def _axes_size(mesh, axes):
    return math.prod(mesh.shape[a] for a in axes)


def _flash_route(arrays, H, D, kpm, causal, dropout_p, seed, Hkv=None,
                 window=None):
    """The Pallas route: the flash kernels on the model's own arrays --
    (q, k, v), each (N, T, H*D), or (qkv,), their fused projection --
    with no transpose, mapped over the mesh when a ``mesh_placement`` is
    active: the batch axes shard dim 0, the head axes the columns. A
    fused array's columns are not one range a head shard (a third of
    each of q, k and v), so with head axes it is split first. Grouped
    heads (``Hkv``) stay whole on every chip of the head axes."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    from .pallas_attention import _lane_block, flash_mha
    mesh, batch_axes, head_axes = \
        _mesh_placement[-1] if _mesh_placement else (None, (), ())
    N = arrays[0].shape[0]
    n_b = _axes_size(mesh, batch_axes)
    if N % n_b:
        raise MXNetError(
            f"multi_head_attention: batch {N} does not divide over mesh "
            f"axes {batch_axes} (size {n_b}), so the flash kernel cannot "
            f"be mapped per chip")
    n_h = _axes_size(mesh, head_axes)
    grouped = dict(num_kv_heads=Hkv, window=window)
    if H % n_h or _lane_block(H // n_h * D, D) is None \
            or Hkv not in (None, H):
        head_axes = ()      # heads stay whole on every chip of those axes
        n_h = 1
    if n_b * n_h == 1:
        return flash_mha(arrays, H, key_mask=kpm, causal=causal,
                         dropout_p=dropout_p, dropout_seed=seed, **grouped)
    if n_h > 1 and len(arrays) == 1:
        arrays = tuple(jnp.split(arrays[0], 3, axis=-1))
    n_loc, h_loc = N // n_b, H // n_h

    def local(kpm_, seed_, *arrays_):
        # global id of this shard's first (row, head), so the in-kernel
        # dropout draws the bits the unsharded call would
        base = jnp.zeros((), jnp.uint32)
        if batch_axes:
            base += lax.axis_index(batch_axes).astype(jnp.uint32) \
                * jnp.uint32(n_loc * H)
        if head_axes:
            base += lax.axis_index(head_axes).astype(jnp.uint32) \
                * jnp.uint32(h_loc)
        return flash_mha(arrays_, h_loc, key_mask=kpm_, causal=causal,
                         dropout_p=dropout_p, dropout_seed=seed_,
                         bh_base=base,
                         bh_split=(h_loc, H) if n_h > 1 else None,
                         **grouped)

    b_spec = batch_axes or None
    ntc = P(b_spec, None, head_axes or None)
    return shard_map(local, mesh=mesh,
                     in_specs=(P(b_spec, None), P()) + (ntc,) * len(arrays),
                     out_specs=ntc, check_vma=False)(kpm, seed, *arrays)


@_reg
def multi_head_attention(query, key, value, mask=None, num_heads=1,
                         dropout_p=0.0, causal=False, use_pallas='auto',
                         dropout_key=None, num_kv_heads=None, window=None):
    """Fused MHA on (N, T, H*D)-shaped q/k/v. The TPU-native attention entry.

    Mask convention (torch-style, identical on both paths): boolean/integer
    masks are keep/drop (truthy = keep); floating masks are ADDITIVE
    (0.0 = keep, large-negative = drop), added to the pre-softmax scores.

    use_pallas: 'auto' takes the Pallas flash kernel when the backend is
    a TPU, the mask (if any) is a key-padding mask and the shape passes
    the kernel's static legality check (pallas_attention.flash_legal) —
    this covers the flagship BERT@512-with-padding-mask config — and the
    XLA path otherwise (per-query masks, CPU, illegal shapes). The choice
    is made from those three observations only and counted in
    ``route_counts``; a kernel that then fails to build or compile
    raises. True forces the kernel, False forces XLA.

    The Pallas route hands the (N, T, H*D) arrays to the kernels as they
    are and returns the kernels' (N, T, H*D) result: their blocks address
    a head's columns in place, and nothing is transposed. The XLA and
    ring routes compute on (N, H, T, D) and pay the transposes there and
    back, under ``attn_layout``.

    dropout_p: attention-probability dropout, applied after softmax (the
    standard transformer recipe), active in autograd training mode (same
    gate as the dropout op). The PRNG key comes from the framework key
    provider unless dropout_key overrides it. On the Pallas route the
    dropout keep-mask is generated INSIDE the kernel (counter-based PRNG
    seeded from the key), so the T×T probability matrix is never
    materialised even in training; the flagship BERT config (dropout=0.1)
    runs the flash kernel.

    num_kv_heads: grouped-query attention. key and value are
    (N, T, Hkv*D) and query head h reads key/value head h // (H // Hkv).
    window: with ``causal``, a query sees the ``window`` keys up to its
    own: score (i, j) is kept iff 0 <= i - j < window. Both mean the same
    on the Pallas route (heads of a multiple of 128 columns; the kernels
    visit the band's cells only) and on the XLA route; the ring route
    has neither.
    """
    if window is not None and not causal:
        raise MXNetError("multi_head_attention: a window is a causal "
                         "layer's (causal=True)")
    if num_kv_heads is not None and num_heads % num_kv_heads:
        raise MXNetError(
            f"multi_head_attention: {num_heads} query heads do not divide "
            f"over {num_kv_heads} key/value heads")
    return _attend((query, key, value), mask, num_heads, dropout_p, causal,
                   use_pallas, dropout_key,
                   None if num_kv_heads in (None, num_heads)
                   else int(num_kv_heads), window)


@_reg
def self_attention(qkv, mask=None, num_heads=1, dropout_p=0.0, causal=False,
                   use_pallas='auto', dropout_key=None):
    """Self-attention over the fused (N, T, 3*H*D) projection: by
    definition ``multi_head_attention(*split(qkv, 3, -1), ...)``, and
    exactly that on the XLA and ring routes. The Pallas route hands the
    kernels the one array three times with column offsets, so the three
    slices are never materialised; the cotangent of ``qkv`` is dq, dk and
    dv side by side."""
    return _attend((qkv,), mask, num_heads, dropout_p, causal, use_pallas,
                   dropout_key)


def _attend(arrays, mask, H, dropout_p, causal, use_pallas, dropout_key,
            Hkv=None, window=None):
    """The routes of :func:`multi_head_attention` (``arrays`` = (q, k,
    v)) and :func:`self_attention` ((qkv,))."""
    N, Tq = arrays[0].shape[:2]
    Tk = arrays[-1].shape[1]
    tot = arrays[0].shape[2] // (3 if len(arrays) == 1 else 1)
    D = tot // H

    def split_heads():
        # (N, T, H*D) to (N, H, T, D) and, with merge_heads, back: the
        # layout copies of the routes that compute head-major, a line of
        # their own in a trace
        q, k, v = arrays if len(arrays) == 3 \
            else jnp.split(arrays[0], 3, axis=-1)
        with jax.named_scope(_scopes.ATTN_LAYOUT):
            q, k, v = [x.reshape(N, x.shape[1], -1, D).transpose(0, 2, 1, 3)
                       for x in (q, k, v)]
            if Hkv is not None:     # a group's query heads read one head
                k, v = [jnp.repeat(x, H // Hkv, axis=1) for x in (k, v)]
            return [q, k, v]

    def merge_heads(out):
        with jax.named_scope(_scopes.ATTN_LAYOUT):
            return out.transpose(0, 2, 1, 3).reshape(N, Tq, tot)

    apply_dropout = dropout_p > 0.0 and (dropout_key is not None
                                         or _flags.is_training)

    # key-padding-mask normalization shared by the ring and Pallas
    # routes: (N, Tk), boolean truthy-keep (floating stays additive)
    kpm = _as_key_padding_mask(mask, N, Tk)
    if kpm is not None and not jnp.issubdtype(kpm.dtype, jnp.floating):
        kpm = kpm.astype(jnp.bool_)

    if _seq_parallel and (Hkv is not None or window is not None):
        raise MXNetError("sequence_parallel: ring attention has neither "
                         "grouped heads nor a window")
    if _seq_parallel:
        # dropout no longer blocks the ring route: the ring kernel
        # regenerates the keep mask in-kernel from global coordinates
        # (same counter-based PRNG as the Pallas flash kernel), so the
        # flagship config (dropout=0.1) rides sequence parallelism
        routable = (Tq == Tk and (mask is None or kpm is not None))
        sp_mesh, sp_axis = _seq_parallel[-1]
        if routable and Tq % sp_mesh.shape[sp_axis] != 0:
            routable = False
        if routable:
            from ..parallel.ring_attention import ring_attention
            ring_kwargs = {}
            if apply_dropout:
                key_ = dropout_key if dropout_key is not None \
                    else _random.next_key()
                ring_kwargs = dict(
                    dropout_p=dropout_p,
                    dropout_seed=jax.random.bits(key_, (1,), jnp.uint32))
            q, k, v = split_heads()
            out = ring_attention(q, k, v, sp_mesh, sp_axis=sp_axis,
                                 causal=causal, key_mask=kpm,
                                 **ring_kwargs)
            route_counts['ring'] += 1
            return merge_heads(out)
        # inside the context but unroutable (cross attention, per-query
        # mask, indivisible T): fall through to the dense path — loudly,
        # because the user asked for ring attention
        import warnings
        reason = ('cross-attention / per-query mask / sequence length '
                  'not divisible by the sp axis')
        warnings.warn(
            f"sequence_parallel: falling back to dense attention "
            f"({reason}); the T x T score tensor will be materialized.",
            RuntimeWarning)

    if use_pallas == 'auto':
        from .pallas_attention import flash_legal, pallas_available
        use_pallas = pallas_available() \
            and (mask is None or kpm is not None) \
            and flash_legal(N * H, Tq, Tk, D, arrays[0].dtype, num_heads=H,
                            num_kv_heads=Hkv)
    if use_pallas:
        if mask is not None and kpm is None:
            raise MXNetError(
                "multi_head_attention(use_pallas=True): the flash kernel "
                "takes key-padding masks only, got a per-query mask of "
                f"shape {tuple(mask.shape)}")
        seed = None
        if apply_dropout:
            key_ = dropout_key if dropout_key is not None \
                else _random.next_key()
            seed = jax.random.bits(key_, (1, 1), jnp.uint32)
        out = _flash_route(arrays, H, D, kpm, causal,
                           dropout_p if apply_dropout else 0.0, seed, Hkv,
                           window)
        route_counts['pallas'] += 1
        return out

    route_counts['xla'] += 1
    q, k, v = split_heads()
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum('nhqd,nhkd->nhqk', q * scale, k,
                        preferred_element_type=jnp.float32)
    if causal:
        cmask = jnp.tril(jnp.ones((Tq, Tk), bool))
        if window is not None:
            cmask &= ~jnp.tril(jnp.ones((Tq, Tk), bool), -int(window))
        scores = jnp.where(cmask, scores, -1e30)
    if mask is not None:
        if jnp.issubdtype(mask.dtype, jnp.floating):
            scores = scores + mask.astype(scores.dtype)
        else:
            scores = jnp.where(mask.astype(bool), scores, -1e30)
    att = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if apply_dropout:
        if dropout_key is None:
            dropout_key = _random.next_key()
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, att.shape)
        att = jnp.where(keep, att / (1.0 - dropout_p),
                        jnp.zeros_like(att)).astype(q.dtype)
    out = jnp.einsum('nhqk,nhkd->nhqd', att, v)
    return merge_heads(out)
