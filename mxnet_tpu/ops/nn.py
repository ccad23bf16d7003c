"""Neural-network ops: FC, convolution, pooling, norms, softmax, dropout.

Ref: src/operator/nn/ (fully_connected.cc, convolution.cc, batch_norm.cc,
layer_norm.cc, softmax.cc, pooling.cc, dropout.cc, activation.cc ...).

Design notes (TPU-first):
- Convolutions use `lax.conv_general_dilated` with NCHW logical layout;
  XLA relayouts for the MXU internally, so we keep the reference's NCHW
  user-facing convention without a perf penalty.
- BatchNorm returns (out, new_running_mean, new_running_var): running stats
  are functional outputs (layers write them back), because everything must
  stay pure under jit.
- Dropout draws keys from mxnet_tpu.random's provider stack so it works in
  both eager and traced (hybridized) modes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import register_op, MXNetError, state
from .. import random as _random
from . import rowsparse as _rowsparse

__all__ = []


def _reg(fn):
    register_op(fn.__name__)(fn)
    __all__.append(fn.__name__)
    return fn


def _tup(v, n):
    if v is None:
        return (0,) * n
    if isinstance(v, int):
        return (v,) * n
    v = tuple(int(x) for x in v)
    if len(v) == 1:
        return v * n
    return v


# conv dimension_numbers by spatial rank, shared with quantized_conv
_CONV_DN = {1: ('NCH', 'OIH', 'NCH'), 2: ('NCHW', 'OIHW', 'NCHW'),
            3: ('NCDHW', 'OIDHW', 'NCDHW')}


@_reg
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """Ref: src/operator/nn/fully_connected.cc. y = x W^T + b; weight is
    (num_hidden, in_dim) as in the reference. Single dot_general → MXU."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = lax.dot_general(data, weight,
                          (((data.ndim - 1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    out = out.astype(data.dtype)
    if bias is not None and not no_bias:
        out = out + bias
    return out


@_reg
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=0, num_group=1, no_bias=False, layout='NCHW'):
    """Ref: src/operator/nn/convolution.cc. Supports 1D/2D/3D via the same
    general conv; grouped conv maps to feature_group_count."""
    nd = data.ndim - 2
    stride = _tup(stride, nd) if stride is not None else (1,) * nd
    dilate = _tup(dilate, nd) if dilate is not None else (1,) * nd
    pad = _tup(pad, nd)
    dn = _CONV_DN[nd]
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=jnp.float32).astype(data.dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@_reg
def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=0, num_group=1, no_bias=False,
                  target_shape=None, layout='NCHW'):
    """Transposed convolution (ref: src/operator/nn/deconvolution.cc)."""
    nd = data.ndim - 2
    stride = _tup(stride, nd) if stride is not None else (1,) * nd
    dilate = _tup(dilate, nd) if dilate is not None else (1,) * nd
    pad = _tup(pad, nd)
    adj = _tup(adj, nd) if adj is not None else (0,) * nd
    kshape = weight.shape[2:]
    # conv_transpose of the forward conv: use input dilation.
    padding = []
    for i in range(nd):
        k = (kshape[i] - 1) * dilate[i] + 1
        lo = k - 1 - pad[i]
        hi = k - 1 - pad[i] + adj[i]
        padding.append((lo, hi))
    dn = {1: ('NCH', 'IOH', 'NCH'), 2: ('NCHW', 'IOHW', 'NCHW'),
          3: ('NCDHW', 'IODHW', 'NCDHW')}[nd]
    if num_group > 1:
        # weight is (in_ch, out_ch/g, *k); split groups along in channel.
        ins = jnp.split(data, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        outs = [lax.conv_general_dilated(
            x, jnp.flip(w, axis=tuple(range(2, 2 + nd))),
            window_strides=(1,) * nd, padding=padding,
            lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)
            for x, w in zip(ins, ws)]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = lax.conv_general_dilated(
            data, jnp.flip(weight, axis=tuple(range(2, 2 + nd))),
            window_strides=(1,) * nd, padding=padding,
            lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)
    out = out.astype(data.dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@_reg
def pooling(data, kernel=None, pool_type='max', global_pool=False, stride=None,
            pad=None, pooling_convention='valid', count_include_pad=True,
            layout='NCHW'):
    """Ref: src/operator/nn/pooling.cc."""
    nd = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, 2 + nd))
        if pool_type == 'max':
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd) if stride is not None else (1,) * nd
    pad = _tup(pad, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    spatial_pad = [(p, p) for p in pad]
    if pooling_convention == 'full':
        # ceil-mode: add extra right padding so ceil division is covered
        extra = []
        for i in range(nd):
            size = data.shape[2 + i]
            out_sz = -(-(size + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - (size + 2 * pad[i])
            extra.append(builtins_max(0, need))
        spatial_pad = [(p, p + e) for p, e in zip(pad, extra)]
    padding = [(0, 0), (0, 0)] + spatial_pad
    if pool_type == 'max':
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ('avg', 'sum'):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == 'sum':
            return summed
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return summed / counts
    if pool_type == 'lp':
        p = 2.0
        summed = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window,
                                   strides, padding)
        return summed ** (1.0 / p)
    raise MXNetError(f"unknown pool_type {pool_type}")


builtins_max = max


@_reg
def activation(data, act_type='relu'):
    """Ref: src/operator/nn/activation.cc."""
    acts = {
        'relu': lambda x: jnp.maximum(x, 0),
        'sigmoid': jax.nn.sigmoid,
        'tanh': jnp.tanh,
        'softrelu': jax.nn.softplus,
        'softsign': lambda x: x / (1 + jnp.abs(x)),
        'gelu': lambda x: jax.nn.gelu(x, approximate=False),
        'gelu_tanh': lambda x: jax.nn.gelu(x, approximate=True),
        'silu': jax.nn.silu,
    }
    if act_type not in acts:
        raise MXNetError(f"unknown act_type {act_type}")
    return acts[act_type](data)


@_reg
def leaky_relu(data, gamma=None, act_type='leaky', slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    """Ref: src/operator/leaky_relu.cc (leaky/prelu/elu/selu/rrelu/gelu)."""
    if act_type == 'leaky':
        return jnp.where(data >= 0, data, slope * data)
    if act_type == 'prelu':
        g = gamma
        if g.ndim < data.ndim and g.ndim == 1:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act_type == 'elu':
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == 'selu':
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == 'gelu':
        return jax.nn.gelu(data, approximate=False)
    if act_type == 'rrelu':
        if state.is_training:
            key = _random.next_key()
            s = jax.random.uniform(key, data.shape, dtype=data.dtype,
                                   minval=lower_bound, maxval=upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise MXNetError(f"unknown act_type {act_type}")


@_reg
def softmax(data, axis=-1, temperature=None, length=None):
    """Ref: src/operator/nn/softmax.cc; optional valid-length masking."""
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    if length is not None:
        pos = jnp.arange(data.shape[axis])
        shape = [1] * data.ndim
        shape[axis] = data.shape[axis]
        mask = pos.reshape(shape) < jnp.expand_dims(length, axis=tuple(
            range(length.ndim, data.ndim)))
        data = jnp.where(mask, data, -jnp.inf)
        out = jax.nn.softmax(data, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(data, axis=axis)


@_reg
def log_softmax(data, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@_reg
def softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@_reg
def softmax_cross_entropy(data, label):
    """Ref: src/operator/softmax_output.cc semantics (sum CE over batch)."""
    logp = jax.nn.log_softmax(data, axis=-1)
    onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(onehot * logp)


@_reg
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1):
    """Ref: src/operator/nn/batch_norm.cc.

    Returns (out, new_moving_mean, new_moving_var); the Gluon layer writes the
    new stats back into its parameters. In training mode batch stats are used;
    in inference (or use_global_stats) the moving stats are used.
    """
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis % data.ndim)
    bshape = [1] * data.ndim
    bshape[axis % data.ndim] = data.shape[axis % data.ndim]
    training = state.is_training and not use_global_stats
    if training:
        mean = jnp.mean(data, axis=reduce_axes)
        var = jnp.var(data, axis=reduce_axes)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape)) * (inv * gamma).reshape(bshape) \
        + beta.reshape(bshape)
    return out, new_mean, new_var


@_reg
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    """Ref: src/operator/nn/layer_norm.cc. Normalises over `axis` only."""
    f32 = data.astype(jnp.float32)
    mean = jnp.mean(f32, axis=axis, keepdims=True)
    var = jnp.var(f32, axis=axis, keepdims=True)
    out = (f32 - mean) * lax.rsqrt(var + eps)
    out = out.astype(data.dtype)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@_reg
def rms_norm(data, gamma, eps=1e-6):
    """Root-mean-square normalisation over the last axis (Zhang &
    Sennrich, 2019): x / sqrt(mean(x^2) + eps) * gamma, no mean taken off
    and no bias. The statistics are float32, the result the input's
    dtype, as :func:`layer_norm`'s."""
    f32 = data.astype(jnp.float32)
    out = f32 * lax.rsqrt(jnp.mean(f32 * f32, axis=-1, keepdims=True) + eps)
    return out.astype(data.dtype) * gamma


@_reg
def rotary_embedding(data, num_heads=1, theta=10000.0, offset=0):
    """Rotary position embedding (Su et al., 2021) on a (N, T, H*D)
    projection, rotate-half form: within each head, column c of the first
    half pairs with column c + D/2, and the pair at position t turns by
    t * theta^(-2c/D). Angles, sines and cosines are float32; the result
    is the input's dtype. ``offset`` is the position of row 0."""
    N, T, C = data.shape
    D = C // num_heads
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    angle = (offset + jnp.arange(T, dtype=jnp.float32))[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x = data.reshape(N, T, num_heads, D).astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(data.dtype).reshape(N, T, C)


def add_layer_norm(x, res, gamma, beta, eps=1e-5):
    """LN(x + res) — the transformer residual epilogue, twice per BERT
    layer. Routes to the fused Pallas kernel (ops/pallas_layernorm.py)
    when MXTPU_PALLAS_LN=1 and a TPU is present; default is the XLA
    path (flag-gated until measured on-chip, like the attention knobs)."""
    from .. import config as _config
    if _config.get('MXTPU_PALLAS_LN'):
        from .pallas_layernorm import fused_add_layer_norm, \
            pallas_available
        if pallas_available() and x.shape[-1] % 128 == 0:
            return fused_add_layer_norm(x, res, gamma, beta, eps)
    return layer_norm(x + res, gamma, beta, eps=eps)


def dense_gelu(x, weight, bias):
    """FFN1 GELU+bias epilogue: gelu(x @ W.T + b) through one seam so
    the fused Pallas matmul kernel (ops/pallas_ffn.py) can take it when
    MXTPU_PALLAS_FFN=1 and a TPU is present; default is the XLA path —
    identical math to Dense + activation('gelu') (flag-gated until
    measured on-chip, like MXTPU_PALLAS_LN and the attention knobs)."""
    from .. import config as _config
    if _config.get('MXTPU_PALLAS_FFN'):
        from .pallas_ffn import fused_dense_gelu, pallas_available
        if pallas_available() and x.shape[-1] % 128 == 0 \
                and weight.shape[0] % 128 == 0:
            return fused_dense_gelu(x, weight, bias)
    return activation(fully_connected(x, weight, bias,
                                      num_hidden=weight.shape[0],
                                      flatten=False), act_type='gelu')


@_reg
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    """Ref: src/operator/nn/group_norm.cc; input NC+spatial."""
    n, c = data.shape[:2]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, c) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@_reg
def instance_norm(data, gamma, beta, eps=1e-3):
    """Ref: src/operator/instance_norm.cc."""
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@_reg
def l2_normalization(data, eps=1e-10, mode='instance'):
    """Ref: src/operator/l2_normalization.cc."""
    if mode == 'instance':
        axes = tuple(range(1, data.ndim))
    elif mode == 'channel':
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


@_reg
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (ref: src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2))
    acc = jnp.zeros_like(data)
    for i in range(nsize):
        acc = acc + lax.dynamic_slice_in_dim(padded, i, data.shape[1], axis=1)
    return data / jnp.power(knorm + alpha / nsize * acc, beta)


@_reg
def dropout(data, p=0.5, mode='training', axes=(), cudnn_off=False):
    """Ref: src/operator/nn/dropout.cc. Active only in autograd train mode."""
    active = state.is_training or mode == 'always'
    if not active or p <= 0.0:
        return data
    keep = 1.0 - p
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    key = _random.next_key()
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


@_reg
def embedding(data, weight, input_dim=0, output_dim=0, dtype='float32',
              sparse_grad=False):
    """Ref: src/operator/tensor/indexing_op.cc Embedding; a gather that XLA
    turns into a dynamic-slice — rows stay in HBM, no host round-trip.

    Backward dedups repeated ids via segment-sum before the table-shaped
    scatter (ref EmbeddingOpBackwardEx / AddTakeGradRspKernel) instead of
    scatter-adding one row slice per occurrence. When parallel/step.py has
    armed a RowSparse capture for this table (matched by trace identity),
    the lookup also records live row ids so the optimizer can update only
    the gathered rows."""
    idx = data.astype(jnp.int32)
    slot = _rowsparse.lookup_capture(weight)
    if slot is not None:
        return slot.lookup(idx)
    if weight.ndim == 2 and idx.size > 0:
        return _rowsparse.dedup_take(weight, idx)
    return jnp.take(weight, idx, axis=0, mode='clip')


@_reg
def one_hot(indices, depth=0, on_value=1.0, off_value=0.0, dtype='float32'):
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=jnp.dtype(dtype))
    return oh * (on_value - off_value) + off_value


@_reg
def upsampling(data, scale=1, sample_type='nearest', num_filter=0):
    """Ref: src/operator/nn/upsampling.cc (nearest)."""
    n, c, h, w = data.shape
    x = data.reshape(n, c, h, 1, w, 1)
    x = jnp.broadcast_to(x, (n, c, h, scale, w, scale))
    return x.reshape(n, c, h * scale, w * scale)


@_reg
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1,
                   use_ignore=False, multi_output=False, preserve_shape=False,
                   normalization='null', out_grad=False, smooth_alpha=0.0):
    """Legacy SoftmaxOutput forward = softmax (ref: src/operator/softmax_output.cc)."""
    return jax.nn.softmax(data, axis=-1)


@_reg
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization='null'):
    return data


@_reg
def blockgrad(data):
    return lax.stop_gradient(data)


@_reg
def identity(data):
    return data


@_reg
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False, blank_label='first'):
    """CTC loss (ref: src/operator/nn/ctc_loss.cc). data: (T, N, C) alphabet
    logits (pre-softmax), label: (N, L) padded with -1 (or 0 for blank_label='last').

    Implemented with the standard log-alpha recursion over lax.scan — a
    compiler-friendly sequential loop on TPU.
    """
    T, N, C = data.shape
    L = label.shape[1]
    blank = 0 if blank_label == 'first' else C - 1
    lab = label.astype(jnp.int32)
    if blank_label == 'first':
        pad_val = 0
        lab_valid = lab >= 0
    else:
        pad_val = C - 1
        lab_valid = lab > 0
    if use_label_lengths and label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        lab_len = jnp.sum(lab_valid.astype(jnp.int32), axis=1)
    lab = jnp.where(lab_valid, lab, pad_val)
    logp = jax.nn.log_softmax(data, axis=-1)  # (T, N, C)
    # extended label sequence: blank, l1, blank, l2, ... blank → length 2L+1
    S = 2 * L + 1
    ext = jnp.full((N, S), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    ext_len = 2 * lab_len + 1
    NEG = -1e30
    # init alpha
    alpha0 = jnp.full((N, S), NEG)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(
        logp[0], ext[:, 1:2], axis=1)[:, 0])

    same_as_prev2 = jnp.concatenate(
        [jnp.ones((N, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, logp_t):
        a_prev = alpha
        a_shift1 = jnp.concatenate([jnp.full((N, 1), NEG), alpha[:, :-1]], axis=1)
        a_shift2 = jnp.concatenate([jnp.full((N, 2), NEG), alpha[:, :-2]], axis=1)
        a_shift2 = jnp.where(same_as_prev2, NEG, a_shift2)
        m = jnp.maximum(jnp.maximum(a_prev, a_shift1), a_shift2)
        m_safe = jnp.maximum(m, NEG)
        summed = (jnp.exp(a_prev - m_safe) + jnp.exp(a_shift1 - m_safe)
                  + jnp.exp(a_shift2 - m_safe))
        new = m_safe + jnp.log(summed)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        new = new + emit
        return new, None

    if use_data_lengths and data_lengths is not None:
        dlen = data_lengths.astype(jnp.int32)

        def step_masked(carry, inp):
            alpha, t = carry
            logp_t = inp
            new, _ = step(alpha, logp_t)
            new = jnp.where((t < dlen)[:, None], new, alpha)
            return (new, t + 1), None

        (alphaT, _), _ = lax.scan(step_masked, (alpha0, jnp.ones((), jnp.int32)),
                                  logp[1:])
    else:
        alphaT, _ = lax.scan(step, alpha0, logp[1:])
    # loss = -log(alpha[ext_len-1] + alpha[ext_len-2])
    idx1 = (ext_len - 1)[:, None]
    idx2 = jnp.maximum(ext_len - 2, 0)[:, None]
    a1 = jnp.take_along_axis(alphaT, idx1, axis=1)[:, 0]
    a2 = jnp.take_along_axis(alphaT, idx2, axis=1)[:, 0]
    m = jnp.maximum(a1, a2)
    total = m + jnp.log(jnp.exp(a1 - m) + jnp.exp(a2 - m))
    return -total


@_reg
def sync_batch_norm_op(data, gamma, beta, moving_mean, moving_var,
                       axis_name=None, eps=1e-3, momentum=0.9,
                       fix_gamma=False, use_global_stats=False, axis=1):
    """Cross-device BatchNorm (ref: src/operator/contrib/sync_batch_norm.cc).

    Inside shard_map over a mesh data axis, batch statistics are psum-reduced
    over `axis_name` so every shard normalises with global-batch moments.
    """
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis % data.ndim)
    bshape = [1] * data.ndim
    bshape[axis % data.ndim] = data.shape[axis % data.ndim]
    training = state.is_training and not use_global_stats
    if training:
        n_local = 1.0
        for i in reduce_axes:
            n_local *= data.shape[i]
        s = jnp.sum(data, axis=reduce_axes)
        sq = jnp.sum(jnp.square(data), axis=reduce_axes)
        if axis_name is not None:
            s = jax.lax.psum(s, axis_name)
            sq = jax.lax.psum(sq, axis_name)
            n = n_local * jax.lax.psum(1.0, axis_name)
        else:
            n = n_local
        mean = s / n
        var = sq / n - jnp.square(mean)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape)) * (inv * gamma).reshape(bshape) \
        + beta.reshape(bshape)
    return out, new_mean, new_var


@_reg
def rnn(data, params, state, state_cell=None, state_size=0, num_layers=1,
        mode='lstm', bidirectional=False, p=0.0, projection_size=None,
        lstm_state_clip_min=None, lstm_state_clip_max=None,
        use_sequence_length=False, sequence_length=None):
    """Fused multi-layer RNN (ref: src/operator/rnn.cc:299 NNVM_REGISTER_OP(RNN)).

    data: (T, N, I). params: flat vector packing per-layer/direction i2h/h2h
    weights then biases, in the reference's canonical order. state: (L*D, N, H)
    hidden; state_cell: (L*D, N, H) cell (lstm only).

    TPU-native: each layer is one `lax.scan` whose step does two MXU matmuls;
    time-major layout keeps the scan carry small and XLA pipelines the layers.
    """
    T, N, I = data.shape
    H = state_size
    L = num_layers
    D = 2 if bidirectional else 1
    ngates = {'rnn_relu': 1, 'rnn_tanh': 1, 'lstm': 4, 'gru': 3}[mode]

    # unpack parameter vector in the reference layout: all weights
    # (layer-major, direction-minor: i2h then h2h), then all biases.
    offset = 0
    weights = []
    for layer in range(L):
        layer_ws = []
        for d in range(D):
            in_size = I if layer == 0 else H * D
            w_i2h = jax.lax.dynamic_slice(params, (offset,), (ngates * H * in_size,)) \
                .reshape(ngates * H, in_size)
            offset += ngates * H * in_size
            w_h2h = jax.lax.dynamic_slice(params, (offset,), (ngates * H * H,)) \
                .reshape(ngates * H, H)
            offset += ngates * H * H
            layer_ws.append((w_i2h, w_h2h))
        weights.append(layer_ws)
    biases = []
    for layer in range(L):
        layer_bs = []
        for d in range(D):
            b_i2h = jax.lax.dynamic_slice(params, (offset,), (ngates * H,))
            offset += ngates * H
            b_h2h = jax.lax.dynamic_slice(params, (offset,), (ngates * H,))
            offset += ngates * H
            layer_bs.append((b_i2h, b_h2h))
        biases.append(layer_bs)

    def cell_step(mode, x_proj, h, c, w_h2h, b_h2h):
        gates = x_proj + jnp.dot(h, w_h2h.T) + b_h2h
        if mode == 'lstm':
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            new_c = f * c + i * g
            if lstm_state_clip_min is not None:
                new_c = jnp.clip(new_c, lstm_state_clip_min, lstm_state_clip_max)
            new_h = o * jnp.tanh(new_c)
            return new_h, new_c
        if mode == 'gru':
            # MXNet gru gate order: r, z, n
            r, z, n = jnp.split(gates, 3, axis=-1)
            # n-gate needs r applied to the h2h part only: recompute
            xr, xz, xn = jnp.split(x_proj + b_h2h * 0, 3, axis=-1)
            hr, hz, hn = jnp.split(jnp.dot(h, w_h2h.T) + b_h2h, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            new_h = (1 - z) * n + z * h
            return new_h, c
        act = jnp.tanh if mode == 'rnn_tanh' else lambda v: jnp.maximum(v, 0)
        new_h = act(gates)
        return new_h, c

    def run_layer(x, h0, c0, w_i2h, w_h2h, b_i2h, b_h2h, reverse=False):
        # x: (T, N, in); project all timesteps at once: one big MXU matmul
        x_proj = jnp.einsum('tni,gi->tng', x, w_i2h) + b_i2h

        def step(carry, xp):
            h, c = carry
            new_h, new_c = cell_step(mode, xp, h, c, w_h2h, b_h2h)
            return (new_h, new_c), new_h

        (hT, cT), ys = lax.scan(step, (h0, c0), x_proj, reverse=reverse)
        if reverse:
            pass  # lax.scan(reverse=True) already emits outputs in orig order
        return ys, hT, cT

    x = data
    h_states = []
    c_states = []
    for layer in range(L):
        outs = []
        for d in range(D):
            idx = layer * D + d
            h0 = state[idx]
            c0 = state_cell[idx] if state_cell is not None else jnp.zeros_like(h0)
            w_i2h, w_h2h = weights[layer][d]
            b_i2h, b_h2h = biases[layer][d]
            ys, hT, cT = run_layer(x, h0, c0, w_i2h, w_h2h, b_i2h, b_h2h,
                                   reverse=(d == 1))
            outs.append(ys)
            h_states.append(hT)
            c_states.append(cT)
        x = outs[0] if D == 1 else jnp.concatenate(outs, axis=-1)
        from ..base import state as _flags
        if p > 0 and layer < L - 1 and _flags.is_training:
            key = _random.next_key()
            keep = 1.0 - p
            mask = jax.random.bernoulli(key, keep, x.shape).astype(x.dtype)
            x = x * mask / keep
    out_h = jnp.stack(h_states, axis=0)
    if mode == 'lstm':
        out_c = jnp.stack(c_states, axis=0)
        return x, out_h, out_c
    return x, out_h
