"""Gated short convolution: the sequence mixer of a hybrid decoder's
convolution layers (LFM2, arXiv:2511.23404; Hyena's gated form).

The in-projection gives three (N, T, h) thirds side by side, [B | C | u].
The mixer is

    v = B * u
    z_t = sum_{j < L} w_j * v_{t - (L - 1) + j}     (zeros before position 0)
    y = C * z

a depthwise causal convolution of ``L`` taps a channel between two gates.
It touches each element a few times and multiplies nothing large, so it is
bound by the bytes it moves: every operand once is the least it can take.
The backward is written out (``custom_vjp``) so that only the
in-projection's output is kept: v and z are computed again from it, and

    dC = dy * z,  dz = dy * C,
    dv_t = sum_j w_j * dz_{t + (L - 1) - j}         (zeros past the end)
    dB = dv * u,  du = dv * B,
    dw_j = sum over rows and t of dz_t * v_{t - (L - 1) + j}

all float32 inside, the cotangent of the three thirds written side by side
in the operands' dtype.

On the TPU both directions are one Pallas kernel each
(``mxtpu_short_conv``, ``mxtpu_short_conv_bwd``), which read each operand
once: a grid step holds a block of ``bt`` positions and ``bc`` channels of
B, C and u, addressed as column ranges of the one (N, T, 3h) array, and
the 16 positions before it (the backward: also the 16 after it) for the
taps that reach over the block's edge. A shift along the positions is a
turn of the block's rows (``pltpu.roll``), whose first (last) rows are then
computed again from those 16. The backward's grid has a fourth axis, the
third of the cotangent a step writes; the input blocks do not move along
it and are fetched once, and dv, which dB and du share, is kept between
their steps. Elsewhere, or for shapes the blocks do not tile,
XLA computes the same from three shifted multiply-adds. Everything runs
under ``scopes.SHORT_CONV_CORE``, forward and backward, so a device trace
reads the core whatever computes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes as _scopes
from .pallas_attention import pallas_available

# positions read beyond a block's edge: a bf16 row tile
HALO = 16
_TAP_ROWS = 8           # the taps' block, L <= 8 rows of it, zeros after
_VMEM_LIMIT = 48 << 20

# trace-time telemetry: {'pallas' | 'xla': builds}, forward and backward
route_counts = {'pallas': 0, 'xla': 0}


def _blocks(T, h, taps):
    """(bt, bc) the kernels tile (T, h) by, or None where they cannot."""
    if taps - 1 > HALO or taps > _TAP_ROWS or h % 128:
        return None
    bt = next((b for b in (256, 128, 64, 32, 16) if T % b == 0), None)
    bc = next(b for b in (512, 256, 128) if h % b == 0)
    return None if bt is None else (bt, bc)


def _kernel_mode(T, h, taps, interpret):
    """None: XLA; else the pallas_call's ``interpret`` flag."""
    if _blocks(T, h, taps) is None \
            or (interpret is None and not pallas_available()):
        return None
    return bool(interpret)


# ---------------------------------------------------------------------------
# XLA
# ---------------------------------------------------------------------------

def _shift(x, s):
    """x delayed by ``s`` positions along axis 1: x_{t - s}, zeros first
    (``s`` < 0: advanced, zeros last)."""
    if s == 0:
        return x
    T = x.shape[1]
    if s > 0:
        return jnp.pad(x, ((0, 0), (s, 0), (0, 0)))[:, :T]
    return jnp.pad(x, ((0, 0), (0, -s), (0, 0)))[:, -s:]


def _thirds(bcx):
    return (t.astype(jnp.float32) for t in jnp.split(bcx, 3, axis=-1))


def _conv(v, w):
    """z_t = sum_j w_j v_{t - (L - 1) + j}, float32."""
    taps = w.shape[0]
    w = w.astype(jnp.float32)
    return sum(w[j] * _shift(v, taps - 1 - j) for j in range(taps))


def _forward_xla(bcx, w):
    b, c, u = _thirds(bcx)
    return (c * _conv(b * u, w)).astype(bcx.dtype)


def _backward_xla(bcx, w, dy):
    taps = w.shape[0]
    b, c, u = _thirds(bcx)
    v = b * u
    dy = dy.astype(jnp.float32)
    dz = dy * c
    dc = dy * _conv(v, w)
    w32 = w.astype(jnp.float32)
    dv = sum(w32[j] * _shift(dz, j - (taps - 1)) for j in range(taps))
    dw = jnp.stack([jnp.sum(dz * _shift(v, taps - 1 - j), axis=(0, 1))
                    for j in range(taps)])
    dbcx = jnp.concatenate([dv * u, dc, dv * b], axis=-1)
    return dbcx.astype(bcx.dtype), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# Pallas
# ---------------------------------------------------------------------------

def _delayed(x, edge, s):
    """(x_{t - s} over the block's rows, the same over its first HALO rows
    with the ``edge`` rows before the block in front): a turn of the
    rows, right everywhere but in the first s rows, and those rows again,
    right."""
    if s == 0:
        return x, x[:HALO]
    rows = lax.broadcasted_iota(jnp.int32, (HALO, x.shape[1]), 0)
    return pltpu.roll(x, s, 0), jnp.where(
        rows < s, pltpu.roll(edge, s, 0), pltpu.roll(x[:HALO], s, 0))


def _advanced(x, edge, s):
    """(x_{t + s} over the block's rows, the same over its last HALO rows
    with the ``edge`` rows after the block behind)."""
    bt = x.shape[0]
    if s == 0:
        return x, x[bt - HALO:]
    rows = lax.broadcasted_iota(jnp.int32, (HALO, x.shape[1]), 0)
    return pltpu.roll(x, bt - s, 0), jnp.where(
        rows >= HALO - s, pltpu.roll(edge, HALO - s, 0),
        pltpu.roll(x[bt - HALO:], HALO - s, 0))


def _conv_block(v, v_edge, w_ref, taps):
    """(z over the block, z over its first HALO rows, right there)."""
    z = z_head = 0.0
    for j in range(taps):
        tap = w_ref[j:j + 1, :]
        whole, head = _delayed(v, v_edge, taps - 1 - j)
        z, z_head = z + tap * whole, z_head + tap * head
    return z, z_head


def _fwd_kernel(b_ref, c_ref, u_ref, bp_ref, up_ref, w_ref, o_ref, *, taps):
    first = pl.program_id(1) == 0
    f32 = jnp.float32
    v = b_ref[0].astype(f32) * u_ref[0].astype(f32)
    v_edge = jnp.where(first, 0.0,
                       bp_ref[0].astype(f32) * up_ref[0].astype(f32))
    z, z_head = _conv_block(v, v_edge, w_ref, taps)
    c = c_ref[0].astype(f32)
    o_ref[0] = (c * z).astype(o_ref.dtype)
    o_ref[0, :HALO] = (c[:HALO] * z_head).astype(o_ref.dtype)


def _bwd_kernel(b_ref, c_ref, u_ref, dy_ref, bp_ref, up_ref, cn_ref, dyn_ref,
                w_ref, d_ref, dw_ref, dv_ref, *, taps):
    """Third ``program_id(3)`` of the cotangent of one block, in order: dB,
    with dv kept in the scratch ``dv_ref`` and this block's part of dw;
    dC; du from the kept dv."""
    t, third = pl.program_id(1), pl.program_id(3)
    first, last = t == 0, t == pl.num_programs(1) - 1
    f32 = jnp.float32
    bt = dv_ref.shape[0]

    def v_and_edge():
        return b_ref[0].astype(f32) * u_ref[0].astype(f32), jnp.where(
            first, 0.0, bp_ref[0].astype(f32) * up_ref[0].astype(f32))

    @pl.when(third == 0)
    def _db_dw():
        dz = dy_ref[0].astype(f32) * c_ref[0].astype(f32)
        dz_edge = jnp.where(last, 0.0,
                            dyn_ref[0].astype(f32) * cn_ref[0].astype(f32))
        dv = tail = 0.0
        for j in range(taps):
            tap = w_ref[j:j + 1, :]
            whole, end = _advanced(dz, dz_edge, taps - 1 - j)
            dv, tail = dv + tap * whole, tail + tap * end
        dv_ref[...] = dv
        dv_ref[bt - HALO:] = tail
        d_ref[0] = (dv_ref[...] * u_ref[0].astype(f32)).astype(d_ref.dtype)
        v, v_edge = v_and_edge()
        rows = lax.broadcasted_iota(jnp.int32, dw_ref.shape[1:], 0)
        acc = jnp.zeros(dw_ref.shape[1:], f32)
        for j in range(taps):
            whole, head = _delayed(v, v_edge, taps - 1 - j)
            part = jnp.sum(dz[HALO:] * whole[HALO:], axis=0, keepdims=True) \
                + jnp.sum(dz[:HALO] * head, axis=0, keepdims=True)
            acc = jnp.where(rows == j, jnp.broadcast_to(part, acc.shape),
                            acc)
        dw_ref[0] = acc

    @pl.when(third == 1)
    def _dc():
        v, v_edge = v_and_edge()
        dy = dy_ref[0].astype(f32)
        z, z_head = _conv_block(v, v_edge, w_ref, taps)
        d_ref[0] = (dy * z).astype(d_ref.dtype)
        d_ref[0, :HALO] = (dy[:HALO] * z_head).astype(d_ref.dtype)

    @pl.when(third == 2)
    def _du():
        d_ref[0] = (dv_ref[...] * b_ref[0].astype(f32)).astype(d_ref.dtype)


def _specs(T, bt, bc, nc):
    """(block, before, after): BlockSpecs of third ``k`` of an (N, T, k h)
    array (B, C, u of the in-projection's output; k = 0 for dy) over a
    grid (N, T / bt, h / bc[, third]): a block of it, the HALO rows
    before the block and those after it (clamped at the ends, where the
    kernels zero them)."""
    hb, last = bt // HALO, T // HALO - 1

    def block(k):
        return pl.BlockSpec((1, bt, bc),
                            lambda n, t, c, *_: (n, t, k * nc + c))

    def before(k):
        return pl.BlockSpec((1, HALO, bc), lambda n, t, c, *_: (
            n, jnp.maximum(t * hb - 1, 0), k * nc + c))

    def after(k):
        return pl.BlockSpec((1, HALO, bc), lambda n, t, c, *_: (
            n, jnp.minimum((t + 1) * hb, last), k * nc + c))
    return block, before, after


def _taps(w):
    """(8, h) float32: the taps, zeros after."""
    return jnp.zeros((_TAP_ROWS, w.shape[1]), jnp.float32).at[
        :w.shape[0]].set(w.astype(jnp.float32))


def _forward_pallas(bcx, w, interpret):
    N, T, h3 = bcx.shape
    h, taps = h3 // 3, w.shape[0]
    bt, bc = _blocks(T, h, taps)
    block, before, _ = _specs(T, bt, bc, h // bc)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps),
        grid=(N, T // bt, h // bc),
        in_specs=[block(0), block(1), block(2), before(0), before(2),
                  pl.BlockSpec((_TAP_ROWS, bc), lambda n, t, c: (0, c))],
        out_specs=pl.BlockSpec((1, bt, bc), lambda n, t, c: (n, t, c)),
        out_shape=jax.ShapeDtypeStruct((N, T, h), bcx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=_scopes.SHORT_CONV_FWD,
    )(bcx, bcx, bcx, bcx, bcx, _taps(w))


def _backward_pallas(bcx, w, dy, interpret):
    N, T, h3 = bcx.shape
    h, taps = h3 // 3, w.shape[0]
    bt, bc = _blocks(T, h, taps)
    nt, nc = T // bt, h // bc
    block, before, after = _specs(T, bt, bc, nc)
    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps),
        grid=(N, nt, nc, 3),
        in_specs=[block(0), block(1), block(2), block(0), before(0),
                  before(2), after(1), after(0),
                  pl.BlockSpec((_TAP_ROWS, bc), lambda n, t, c, j: (0, c))],
        out_specs=[
            pl.BlockSpec((1, bt, bc), lambda n, t, c, j: (n, t, j * nc + c)),
            pl.BlockSpec((1, _TAP_ROWS, bc),
                         lambda n, t, c, j: (n * nt + t, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((N * nt, _TAP_ROWS, h), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bt, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=_scopes.SHORT_CONV_BWD,
    )(bcx, bcx, bcx, dy, bcx, bcx, bcx, dy, _taps(w))
    return dbcx, jnp.sum(dw, axis=0)[:taps].astype(w.dtype)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _core(bcx, w, interpret):
    return _core_fwd(bcx, w, interpret)[0]


def _core_fwd(bcx, w, interpret):
    N, T, h3 = bcx.shape
    mode = _kernel_mode(T, h3 // 3, w.shape[0], interpret)
    route_counts['xla' if mode is None else 'pallas'] += 1
    y = _forward_xla(bcx, w) if mode is None \
        else _forward_pallas(bcx, w, mode)
    return y, (bcx, w)


def _core_bwd(interpret, res, dy):
    bcx, w = res
    N, T, h3 = bcx.shape
    mode = _kernel_mode(T, h3 // 3, w.shape[0], interpret)
    route_counts['xla' if mode is None else 'pallas'] += 1
    with jax.named_scope(_scopes.SHORT_CONV_CORE):
        if mode is None:
            return _backward_xla(bcx, w, dy)
        return _backward_pallas(bcx, w, dy.astype(bcx.dtype), mode)


_core.defvjp(_core_fwd, _core_bwd)


def gated_short_conv(bcx, w, interpret=None):
    """``C * causal_dwconv(B * u; w)`` of the in-projection's output
    ``bcx`` (N, T, 3h) = [B | C | u] and the taps ``w`` (L, h): (N, T, h)
    in ``bcx``'s dtype. Tap L - 1 weighs the position itself, tap 0 the
    one L - 1 before it. ``interpret``: None runs the kernels on a TPU
    and XLA elsewhere; True runs the kernels through the Pallas
    interpreter."""
    if bcx.shape[-1] % 3 or w.shape[-1] != bcx.shape[-1] // 3:
        raise ValueError(
            f"gated_short_conv: (N, T, 3h) and (L, h), got "
            f"{tuple(bcx.shape)} and {tuple(w.shape)}")
    with jax.named_scope(_scopes.SHORT_CONV_CORE):
        return _core(bcx, w, interpret)
