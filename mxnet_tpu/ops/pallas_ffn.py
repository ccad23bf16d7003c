"""Pallas fused FFN1 epilogue for TPU: gelu(x @ W.T + b) in one kernel.

The span attribution at the flagship BERT-base shape puts the encoder's
XLA-side FFN block next on the headroom list after the flash-attention
and residual+LN kernels landed (PERF_NOTES r4): the FFN1 matmul's bias
add and exact GELU are a separate HBM round trip over the (tokens,
intermediate) activation — 4x the hidden width, the fattest tensor in
the layer. This kernel runs the matmul on the MXU with the bias+GELU
epilogue applied in VMEM before the block ever leaves the core, the
same fused-epilogue ethos as ops/pallas_layernorm.py (ref: the
hand-fused transformer ops in src/operator/contrib/transformer.cc).

Grid (M/bm, N/bn); K (the contraction dim — BERT hidden 768) rides
whole in each block's lane dim, so every block is trailing-tile legal
by the block==array-dim rule and no cross-step accumulator is needed.
fp32 accumulation via preferred_element_type; exact (erf-form) GELU to
match ops/nn.py activation(act_type='gelu'). The Pallas TPU lowering has
no ``erf`` primitive, so the kernel evaluates erf with the
Abramowitz-Stegun 7.1.26 rational form (|error| <= 1.5e-7, built from
exp, multiply, add and divide only).

Backward is the standard dense+GELU gradient in plain jnp (custom_vjp):
it recomputes the pre-activation from the saved (x, W, b) instead of
saving the (M, N) intermediate — deliberately, because that tensor is
exactly the HBM spend the fusion exists to avoid.

Routing: models/bert.py's layers call ops.nn.dense_gelu, which routes
here when ``MXTPU_PALLAS_FFN=1`` and a TPU is present (default OFF
until measured on-chip — flag-gated exactly like MXTPU_PALLAS_LN).
``interpret=True`` runs the identical kernel on CPU for parity tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import scopes as _scopes
from .pallas_attention import pallas_available  # shared TPU probe

_INV_SQRT2 = 1.0 / math.sqrt(2.0)


def _erf_f32(x):
    # Abramowitz & Stegun 7.1.26: erf(|x|) = 1 - poly(t) exp(-x^2),
    # t = 1 / (1 + p |x|); odd extension for x < 0
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * ax)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    y = 1.0 - poly * jnp.exp(-ax * ax)
    return jnp.where(x < 0, -y, y)


def _gelu_f32(s):
    # exact-form GELU, f32: agrees with jax.nn.gelu(approximate=False)
    # to the erf approximation's 1.5e-7
    return 0.5 * s * (1.0 + _erf_f32(s * _INV_SQRT2))


def _ffn_kernel(x_ref, w_ref, b_ref, o_ref):
    """One (bm, bn) output tile: gelu(x_blk @ w_blk.T + b_blk).
    x (bm, K), w (bn, K), b (1, bn) — K whole in the lane dim."""
    s = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s = s + b_ref[...].astype(jnp.float32)
    o_ref[...] = _gelu_f32(s).astype(o_ref.dtype)


def _shrink_to_divisor(block, dim):
    b = min(block, dim)
    while dim % b:
        b -= 1
    return b


def _fwd_impl(x, w, b, block_m, block_n, interpret):
    orig_shape = x.shape
    K = orig_shape[-1]
    N = w.shape[0]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm = _shrink_to_divisor(block_m, M)
    bn = _shrink_to_divisor(block_n, N)
    b2 = b.reshape(1, N)
    out = pl.pallas_call(
        _ffn_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, K), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name=_scopes.FFN_GELU,
    )(x2, w, b2)
    return out.reshape(orig_shape[:-1] + (N,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_dense_gelu(x, w, b, block_m=256, block_n=256, interpret=False):
    """gelu(x @ w.T + b) with the bias+GELU epilogue fused into the
    matmul kernel (see module doc). w: (N, K) Dense weight layout."""
    return _fwd_impl(x, w, b, block_m, block_n, interpret)


def _fwd(x, w, b, block_m, block_n, interpret):
    return _fwd_impl(x, w, b, block_m, block_n, interpret), (x, w, b)


def _bwd(block_m, block_n, interpret, saved, g):
    x, w, b = saved
    K = x.shape[-1]
    x2 = x.reshape(-1, K).astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    g2 = g.reshape(-1, w.shape[0]).astype(jnp.float32)
    # recompute the pre-activation (remat) rather than saving the
    # (M, N) intermediate the fusion exists to keep out of HBM
    s = x2 @ w32.T + b.astype(jnp.float32)
    pdf = jnp.exp(-0.5 * s * s) * (1.0 / math.sqrt(2.0 * math.pi))
    dgelu = 0.5 * (1.0 + jax.lax.erf(s * _INV_SQRT2)) + s * pdf
    ds = g2 * dgelu
    dx = (ds @ w32).reshape(x.shape).astype(x.dtype)
    dw = (ds.T @ x2).astype(w.dtype)
    db = jnp.sum(ds, axis=0).astype(b.dtype)
    return dx, dw, db


fused_dense_gelu.defvjp(_fwd, _bwd)
