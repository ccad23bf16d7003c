"""Pallas fused residual-add + LayerNorm for TPU.

Round 4's profile-directed plan (VERDICT r4 #1) located the remaining
flagship-step headroom in the XLA-side encoder — LN/GELU/FFN — after
the attention kernel landed. The BERT layer computes `LN(x + sub(x))`
twice per layer; under XLA that is an HBM round-trip for the residual
add plus two reduction passes. This kernel does add + mean/var + scale
in ONE pass over VMEM rows, fp32 statistics, bf16-friendly output —
the same fused-epilogue ethos as the reference's hand-fused transformer
ops (ref: src/operator/contrib/transformer.cc:650-828).

Forward only, with a custom_vjp whose backward is the standard LN
gradient expressed in jnp (the backward is matmul-free and XLA fuses it
well; the forward's extra residual read is where the bandwidth win is).

Routing: models/bert.py's layers call ops.nn.add_layer_norm, which
routes here when `MXTPU_PALLAS_LN=1` and a TPU is present (default OFF
until measured on-chip against the XLA path, with tools/tune_bert_step.py).
`interpret=True` runs the identical kernel on CPU for parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import scopes as _scopes
from .pallas_attention import pallas_available  # shared TPU probe


def _ln_kernel(x_ref, r_ref, g_ref, b_ref, o_ref, *, eps):
    """One (rows_block, C) tile: out = LN(x + r) * gamma + beta.

    C rides whole in the lane dim (BERT hidden 768 = 6*128); rows tile
    in the sublane dim. Stats in fp32 regardless of input dtype.
    """
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    out = xc * inv * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def _fwd_impl(x, res, gamma, beta, eps, block_rows, interpret):
    orig_shape = x.shape
    C = orig_shape[-1]
    x2 = x.reshape(-1, C)
    r2 = res.reshape(-1, C)
    N = x2.shape[0]
    br = min(block_rows, N)
    while N % br:
        br -= 1
    g2 = gamma.reshape(1, C)
    b2 = beta.reshape(1, C)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(N // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, C), x.dtype),
        interpret=interpret,
        name=_scopes.ADD_LAYERNORM,
    )(x2, r2, g2, b2)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_add_layer_norm(x, res, gamma, beta, eps=1e-5, block_rows=256,
                         interpret=False):
    """LN(x + res) * gamma + beta in one fused pass (see module doc)."""
    return _fwd_impl(x, res, gamma, beta, eps, block_rows, interpret)


def _fwd(x, res, gamma, beta, eps, block_rows, interpret):
    out = _fwd_impl(x, res, gamma, beta, eps, block_rows, interpret)
    # save the SUM: the backward only ever uses x+res (dx == dres), and
    # saving x and res separately would double the residual footprint
    # on exactly the bandwidth-constrained path this kernel relieves
    return out, (x + res, gamma)


def _bwd(eps, block_rows, interpret, saved, g):
    s_in, gamma = saved
    s = s_in.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    xc = s - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    gf = g.astype(jnp.float32)
    dgamma = jnp.sum(gf * xhat, axis=tuple(range(g.ndim - 1)))
    dbeta = jnp.sum(gf, axis=tuple(range(g.ndim - 1)))
    gg = gf * gamma.astype(jnp.float32)
    dx = inv * (gg - jnp.mean(gg, axis=-1, keepdims=True)
                - xhat * jnp.mean(gg * xhat, axis=-1, keepdims=True))
    dx = dx.astype(s_in.dtype)
    return dx, dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


fused_add_layer_norm.defvjp(_fwd, _bwd)
