"""Plain float32 ``jax.numpy`` pieces the families' reference forwards are
written in, and the comparison that decides whether a model agrees with
its reference. No kernels, no mxnet_tpu ops, nothing cached or batched.

Callers run these under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is computed in bf16 passes otherwise.
"""
import math

import jax
import jax.numpy as jnp


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu(x):
    """The exact (erf) GELU."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def dense(x, weight, bias):
    """``weight`` is (out, in), as gluon's Dense stores it."""
    return x @ weight.T + bias


def attention(q, k, v, heads, key_keep=None, causal=False):
    """Softmax attention on (N, T, heads*D) projections. ``key_keep`` is
    (N, T) bool, True where a key may be attended to."""
    n, t, width = q.shape
    d = width // heads

    def split(x):
        return x.reshape(n, t, heads, d).transpose(0, 2, 1, 3)

    scores = split(q) @ split(k).transpose(0, 1, 3, 2) / math.sqrt(d)
    if key_keep is not None:
        scores = jnp.where(key_keep[:, None, None, :], scores, -1e30)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -1e30)
    out = jax.nn.softmax(scores, axis=-1) @ split(v)
    return out.transpose(0, 2, 1, 3).reshape(n, t, width)


def cross_entropy(logits, labels):
    """Mean of -log softmax(logits)[label] over the labels that are >= 0."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    keep = labels >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * keep) / jnp.sum(keep)


# Tolerances of the check at published widths, and why. The model keeps
# bf16 weights and activations and accumulates its matmuls in float32; the
# reference reads the same bf16-rounded weights and computes everything in
# float32. One bf16 rounding is 2^-9 relative (0.2 %); through 12 blocks
# of about ten rounded operations each, with LayerNorm renormalising after
# every block, the roundings add like a random walk to about
# 0.002 * sqrt(120) = 2 % of the largest logit in the worst element.
# Measured at published widths: 0.75 to 1.27 % on the chip through the
# flash kernel (my chip runs, PR 24: 39 runs of three cells) and 0.9 %
# through XLA attention on the CPU. The limit is 3 %: over twice the
# worst measured, and far under what a fault costs -- a padding mask
# that is not applied reads 15 to 31 % on the chip (the same runs), and
# tests/test_reference.py shows a dropped mask, a layer left out and
# 8-bit weights each outside it at a small size. The loss is a mean over
# many positions, so the roundings average out: the limit is 0.02 nats of
# about 11 (measured: under 0.005).
LOGIT_TOLERANCE = 0.03
LOSS_TOLERANCE = 0.02


def compare(model_logits, reference_logits, model_loss, reference_loss):
    """The verdict and the two errors it rests on: the largest logit
    difference over the largest reference logit, and the loss difference."""
    got = jnp.asarray(model_logits, jnp.float32)
    ref = jnp.asarray(reference_logits, jnp.float32)
    logit_err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    loss_err = abs(float(model_loss) - float(reference_loss))
    finite = bool(jnp.all(jnp.isfinite(got))) and math.isfinite(
        float(model_loss))
    return {
        'ok': finite and logit_err <= LOGIT_TOLERANCE
        and loss_err <= LOSS_TOLERANCE,
        'logit_err': logit_err, 'loss_err': loss_err,
        'model_loss': float(model_loss),
        'reference_loss': float(reference_loss),
    }
