"""Per-op numerical checks against numpy (ref:
tests/python/unittest/test_operator.py — the backbone suite)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def _r(*shape):
    return onp.random.uniform(-1, 1, shape).astype(onp.float32)


def test_unary_ops():
    x = _r(3, 4)
    assert_almost_equal(nd.exp(nd.array(x)), onp.exp(x), rtol=1e-5)
    assert_almost_equal(nd.log(nd.array(onp.abs(x) + 1)), onp.log(onp.abs(x) + 1), rtol=1e-5)
    assert_almost_equal(nd.sqrt(nd.array(onp.abs(x))), onp.sqrt(onp.abs(x)), rtol=1e-5)
    assert_almost_equal(nd.square(nd.array(x)), x ** 2, rtol=1e-5)
    assert_almost_equal(nd.abs(nd.array(x)), onp.abs(x))
    assert_almost_equal(nd.sign(nd.array(x)), onp.sign(x))
    assert_almost_equal(nd.tanh(nd.array(x)), onp.tanh(x), rtol=1e-5)
    assert_almost_equal(nd.sigmoid(nd.array(x)), 1 / (1 + onp.exp(-x)), rtol=1e-5)
    assert_almost_equal(nd.relu(nd.array(x)), onp.maximum(x, 0))
    assert_almost_equal(nd.reciprocal(nd.array(x + 3)), 1 / (x + 3), rtol=1e-5)
    assert_almost_equal(nd.rsqrt(nd.array(onp.abs(x) + 1)),
                        1 / onp.sqrt(onp.abs(x) + 1), rtol=1e-5)


def test_binary_broadcast():
    a = _r(2, 1, 4)
    b = _r(1, 3, 4)
    assert_almost_equal(nd.broadcast_add(nd.array(a), nd.array(b)), a + b, rtol=1e-6)
    assert_almost_equal(nd.broadcast_mul(nd.array(a), nd.array(b)), a * b, rtol=1e-6)
    assert_almost_equal(nd.broadcast_maximum(nd.array(a), nd.array(b)),
                        onp.maximum(a, b))
    assert_almost_equal(nd.broadcast_power(nd.array(onp.abs(a)), nd.array(b)),
                        onp.abs(a) ** b, rtol=1e-4)
    assert_almost_equal(nd.broadcast_like(nd.array(onp.ones((1, 4))),
                                          nd.array(onp.zeros((3, 4)))),
                        onp.ones((3, 4)))


def test_fully_connected():
    x = _r(4, 5)
    w = _r(3, 5)
    b = _r(3)
    out = nd.fully_connected(nd.array(x), nd.array(w), nd.array(b), num_hidden=3)
    assert_almost_equal(out, x.dot(w.T) + b, rtol=1e-5)
    out_nb = nd.fully_connected(nd.array(x), nd.array(w), no_bias=True, num_hidden=3)
    assert_almost_equal(out_nb, x.dot(w.T), rtol=1e-5)
    # flatten semantics
    x4 = _r(2, 3, 2, 2)
    w2 = _r(7, 12)
    out2 = nd.fully_connected(nd.array(x4), nd.array(w2), no_bias=True, num_hidden=7)
    assert_almost_equal(out2, x4.reshape(2, -1).dot(w2.T), rtol=1e-5)


def test_convolution_vs_reference():
    import torch
    import torch.nn.functional as F
    x = _r(2, 3, 8, 8)
    w = _r(5, 3, 3, 3)
    b = _r(5)
    out = nd.convolution(nd.array(x), nd.array(w), nd.array(b),
                         kernel=(3, 3), stride=(2, 2), pad=(1, 1), num_filter=5)
    ref = F.conv2d(torch.tensor(x), torch.tensor(w), torch.tensor(b),
                   stride=2, padding=1).numpy()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_grouped_and_dilated_conv():
    import torch
    import torch.nn.functional as F
    x = _r(1, 4, 9, 9)
    w = _r(8, 2, 3, 3)
    out = nd.convolution(nd.array(x), nd.array(w), no_bias=True,
                         kernel=(3, 3), num_filter=8, num_group=2,
                         dilate=(2, 2))
    ref = F.conv2d(torch.tensor(x), torch.tensor(w), groups=2,
                   dilation=2).numpy()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_deconvolution():
    import torch
    import torch.nn.functional as F
    x = _r(2, 4, 5, 5)
    w = _r(4, 3, 4, 4)
    out = nd.deconvolution(nd.array(x), nd.array(w), no_bias=True,
                           kernel=(4, 4), stride=(2, 2), pad=(1, 1),
                           num_filter=3)
    ref = F.conv_transpose2d(torch.tensor(x), torch.tensor(w), stride=2,
                             padding=1).numpy()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_pooling():
    import torch
    import torch.nn.functional as F
    x = _r(2, 3, 8, 8)
    out = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type='max')
    ref = F.max_pool2d(torch.tensor(x), 2, 2).numpy()
    assert_almost_equal(out, ref)
    out_avg = nd.pooling(nd.array(x), kernel=(3, 3), stride=(2, 2),
                         pad=(1, 1), pool_type='avg')
    ref_avg = F.avg_pool2d(torch.tensor(x), 3, 2, 1).numpy()
    assert_almost_equal(out_avg, ref_avg, rtol=1e-5)
    out_g = nd.pooling(nd.array(x), global_pool=True, pool_type='avg')
    assert_almost_equal(out_g, x.mean(axis=(2, 3), keepdims=True), rtol=1e-5)


def test_softmax_family():
    x = _r(3, 5)
    ex = onp.exp(x - x.max(axis=-1, keepdims=True))
    sm = ex / ex.sum(axis=-1, keepdims=True)
    assert_almost_equal(nd.softmax(nd.array(x)), sm, rtol=1e-5)
    assert_almost_equal(nd.log_softmax(nd.array(x)), onp.log(sm), rtol=1e-4)
    # masked softmax with valid length
    length = onp.array([2, 5, 3])
    out = nd.softmax(nd.array(x), length=nd.array(length), axis=-1)
    o = out.asnumpy()
    assert abs(o[0, :2].sum() - 1) < 1e-5
    assert o[0, 2:].sum() < 1e-6


def test_layer_norm_op():
    x = _r(4, 6)
    g = _r(6)
    b = _r(6)
    out = nd.layer_norm(nd.array(x), nd.array(g), nd.array(b))
    mu = x.mean(-1, keepdims=True)
    sig = x.std(-1, keepdims=True)
    assert_almost_equal(out, (x - mu) / onp.sqrt(sig ** 2 + 1e-5) * g + b,
                        rtol=1e-4, atol=1e-5)


def test_batch_norm_inference():
    x = _r(2, 3, 4, 4)
    gamma = onp.abs(_r(3)) + 0.5
    beta = _r(3)
    mean = _r(3)
    var = onp.abs(_r(3)) + 0.5
    out, _, _ = nd.batch_norm(nd.array(x), nd.array(gamma), nd.array(beta),
                              nd.array(mean), nd.array(var), fix_gamma=False,
                              eps=1e-3)
    expect = ((x - mean[None, :, None, None])
              / onp.sqrt(var[None, :, None, None] + 1e-3)
              * gamma[None, :, None, None] + beta[None, :, None, None])
    assert_almost_equal(out, expect, rtol=1e-4, atol=1e-5)


def test_gradients_numeric():
    check_numeric_gradient(lambda x: (x * x).sum(), [_r(3)])
    check_numeric_gradient(lambda x: nd.tanh(x).sum(), [_r(3)])
    check_numeric_gradient(lambda a, b: nd.dot(a, b).sum(), [_r(2, 3), _r(3, 2)])
    check_numeric_gradient(lambda x: nd.softmax(x).sum(axis=0).max(), [_r(2, 3)])


def test_take_pick_gather():
    x = onp.arange(12).reshape(3, 4).astype(onp.float32)
    assert_almost_equal(nd.take(nd.array(x), nd.array([0, 2])), x[[0, 2]])
    picked = nd.pick(nd.array(x), nd.array([1, 0, 3]), axis=1)
    assert_almost_equal(picked, [1, 4, 11])
    gnd = nd.gather_nd(nd.array(x), nd.array([[0, 2], [1, 3]]))
    assert_almost_equal(gnd, [x[0, 1], x[2, 3]])
    snd = nd.scatter_nd(nd.array([9., 8.]), nd.array([[0, 2], [1, 3]]),
                        shape=(3, 4))
    expect = onp.zeros((3, 4)); expect[0, 1] = 9; expect[2, 3] = 8
    assert_almost_equal(snd, expect)


def test_sequence_ops():
    x = onp.arange(24).reshape(4, 3, 2).astype(onp.float32)  # (T, N, C)
    length = onp.array([2, 4, 3], onp.float32)
    masked = nd.sequence_mask(nd.array(x), nd.array(length),
                              use_sequence_length=True, value=-1)
    m = masked.asnumpy()
    assert (m[2:, 0] == -1).all() and (m[:2, 0] == x[:2, 0]).all()
    last = nd.sequence_last(nd.array(x), nd.array(length),
                            use_sequence_length=True)
    assert_almost_equal(last, onp.stack([x[1, 0], x[3, 1], x[2, 2]]))
    rev = nd.sequence_reverse(nd.array(x), nd.array(length),
                              use_sequence_length=True)
    r = rev.asnumpy()
    assert_almost_equal(r[:2, 0], x[:2, 0][::-1])
    assert_almost_equal(r[2:, 0], x[2:, 0])


def test_elemwise_misc():
    x = _r(3, 3)
    assert_almost_equal(nd.clip(nd.array(x), -0.5, 0.5), onp.clip(x, -0.5, 0.5))
    assert_almost_equal(nd.where(nd.array((x > 0).astype(onp.float32)),
                                 nd.array(x), nd.array(-x)), onp.abs(x))
    assert_almost_equal(nd.add_n(nd.array(x), nd.array(x), nd.array(x)), 3 * x,
                        rtol=1e-6)
    assert_almost_equal(nd.cast(nd.array(x), dtype='int32'),
                        x.astype(onp.int32))
    out = nd.smooth_l1(nd.array(x), scalar=1.0)
    expect = onp.where(onp.abs(x) < 1, 0.5 * x ** 2, onp.abs(x) - 0.5)
    assert_almost_equal(out, expect, rtol=1e-5)


def test_linalg_ops():
    a = _r(3, 3)
    spd = a.dot(a.T) + 3 * onp.eye(3, dtype=onp.float32)
    from mxnet_tpu.ndarray import linalg
    chol = linalg.potrf(nd.array(spd)).asnumpy()
    assert_almost_equal(chol.dot(chol.T), spd, rtol=1e-4)
    assert_almost_equal(linalg.gemm2(nd.array(a), nd.array(a), transpose_b=True),
                        a.dot(a.T), rtol=1e-5)
    assert_almost_equal(linalg.syrk(nd.array(a)), a.dot(a.T), rtol=1e-5)
    assert_almost_equal(linalg.extractdiag(nd.array(spd)), onp.diag(spd))
    det = linalg.det(nd.array(spd)).asscalar()
    assert abs(det - onp.linalg.det(spd)) / abs(det) < 1e-4


def test_rnn_op_lstm_shapes_and_grad():
    T, N, I, H = 5, 2, 3, 4
    x = nd.array(_r(T, N, I))
    ngates = 4
    nparams = ngates * H * I + ngates * H * H + 2 * ngates * H
    params = nd.array(_r(nparams))
    h0 = nd.zeros((1, N, H))
    c0 = nd.zeros((1, N, H))
    x.attach_grad()
    params.attach_grad()
    with autograd.record():
        out, hT, cT = nd.rnn(x, params, h0, c0, state_size=H, num_layers=1,
                             mode='lstm')
        loss = out.sum()
    loss.backward()
    assert out.shape == (T, N, H)
    assert hT.shape == (1, N, H)
    assert float(onp.abs(params.grad.asnumpy()).sum()) > 0


def test_ctc_loss_simple():
    # trivial case: T=2, single label, compare against hand-computed
    import torch
    import torch.nn.functional as F
    T, N, C = 6, 2, 5
    logits = _r(T, N, C)
    labels = onp.array([[1, 2, -1, -1], [3, -1, -1, -1]], onp.float32)
    loss = nd.ctc_loss(nd.array(logits), nd.array(labels))
    tlabels = torch.tensor([[1, 2], [3, 0]], dtype=torch.long)
    tlens = torch.tensor([2, 1])
    ref = F.ctc_loss(torch.tensor(logits).log_softmax(-1), tlabels,
                     torch.tensor([T, T]), tlens, blank=0, reduction='none')
    assert_almost_equal(loss, ref.numpy(), rtol=1e-4, atol=1e-4)


def test_box_iou_and_nms():
    boxes = onp.array([[0, 0, 2, 2], [1, 1, 3, 3], [10, 10, 12, 12]],
                      onp.float32)
    iou = nd.box_iou(nd.array(boxes), nd.array(boxes)).asnumpy()
    assert abs(iou[0, 1] - 1.0 / 7.0) < 1e-5
    assert iou[0, 2] == 0
    # NMS: data (N, 6) = [cls, score, x1, y1, x2, y2]
    dets = onp.array([
        [0, 0.9, 0, 0, 2, 2],
        [0, 0.8, 1, 1, 3, 3],
        [0, 0.7, 10, 10, 12, 12],
    ], onp.float32)
    out = nd.box_nms(nd.array(dets), overlap_thresh=0.1, coord_start=2,
                     score_index=1, id_index=0).asnumpy()
    kept = out[out[:, 1] > 0]
    assert len(kept) == 2  # middle box suppressed


def test_attention_ops():
    T, N, H, D = 4, 2, 2, 3
    qkv = _r(T, N, 3 * H * D)
    scores = nd.interleaved_matmul_selfatt_qk(nd.array(qkv), heads=H)
    assert scores.shape == (N * H, T, T)
    att = nd.softmax(scores, axis=-1)
    out = nd.interleaved_matmul_selfatt_valatt(nd.array(qkv), att, heads=H)
    assert out.shape == (T, N, H * D)
    # fused MHA equals naive
    q = _r(N, T, H * D)
    k = _r(N, T, H * D)
    v = _r(N, T, H * D)
    fused = nd.multi_head_attention(nd.array(q), nd.array(k), nd.array(v),
                                    num_heads=H, use_pallas=False)
    qh = q.reshape(N, T, H, D).transpose(0, 2, 1, 3)
    kh = k.reshape(N, T, H, D).transpose(0, 2, 1, 3)
    vh = v.reshape(N, T, H, D).transpose(0, 2, 1, 3)
    s = onp.einsum('nhqd,nhkd->nhqk', qh, kh) / onp.sqrt(D)
    p = onp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = onp.einsum('nhqk,nhkd->nhqd', p, vh).transpose(0, 2, 1, 3).reshape(N, T, H * D)
    assert_almost_equal(fused, ref, rtol=1e-4, atol=1e-5)


def _naive_mha(q, k, v, key_mask=None, causal=False):
    import jax
    import jax.numpy as jnp
    D = q.shape[-1]
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k).astype(jnp.float32) \
        / onp.sqrt(D)
    if key_mask is not None:
        s = s + key_mask[:, None, None, :]
    if causal:
        cm = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), bool))
        s = jnp.where(cm, s, -1e30)
    att = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum('bhqk,bhkd->bhqd', att, v)


def test_flash_attention_matches_naive():
    """The real Pallas kernel (interpret mode on CPU) vs naive attention:
    forward and backward, with/without causal and key-padding masks, on a
    non-block-aligned sequence length."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_attention import flash_attention
    B, H, T, D = 2, 2, 20, 8   # T=20 exercises block padding
    q = jnp.asarray(_r(B, H, T, D))
    k = jnp.asarray(_r(B, H, T, D))
    v = jnp.asarray(_r(B, H, T, D))
    vlen = jnp.array([13, 20])
    kmask = jnp.where(jnp.arange(T)[None, :] < vlen[:, None],
                      0.0, -1e30).astype(jnp.float32)
    for causal in (False, True):
        for m in (None, kmask):
            out = flash_attention(q, k, v, key_mask=m, causal=causal)
            ref = _naive_mha(q, k, v, m, causal)
            assert_almost_equal(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-4, atol=1e-5)

    def loss(fn, m):
        return lambda q, k, v: jnp.sum(fn(q, k, v, m) * jnp.cos(
            jnp.arange(D, dtype=jnp.float32)))
    for m in (None, kmask):
        g_fa = jax.grad(loss(lambda q, k, v, m: flash_attention(
            q, k, v, key_mask=m), m), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(_naive_mha, m), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            assert_almost_equal(onp.asarray(a), onp.asarray(b),
                                rtol=1e-4, atol=2e-5)


def test_mha_op_pallas_routing_matches_xla():
    """multi_head_attention with use_pallas=True (kernel path) equals the
    XLA path for key-padding masks — the flagship BERT@512 mask shape."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import multi_head_attention
    N, T, H, D = 2, 24, 2, 8
    q = jnp.asarray(_r(N, T, H * D))
    k = jnp.asarray(_r(N, T, H * D))
    v = jnp.asarray(_r(N, T, H * D))
    vlen = jnp.array([15, 24])
    mask = (jnp.arange(T)[None, None, None, :] <
            vlen[:, None, None, None])          # (N,1,1,T) boolean keep
    out_pl = multi_head_attention(q, k, v, mask=mask, num_heads=H,
                                  use_pallas=True)
    out_xla = multi_head_attention(q, k, v, mask=mask, num_heads=H,
                                   use_pallas=False)
    assert_almost_equal(onp.asarray(out_pl), onp.asarray(out_xla),
                        rtol=1e-4, atol=1e-5)


def test_mha_additive_float_mask():
    """Floating masks are ADDITIVE (0 keep / -1e30 drop) on both attention
    paths; boolean masks are keep/drop. The two conventions must agree
    (advisor r2: additive masks were silently inverted by a bool cast)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import multi_head_attention
    N, T, H, D = 2, 24, 2, 8
    q = jnp.asarray(_r(N, T, H * D))
    k = jnp.asarray(_r(N, T, H * D))
    v = jnp.asarray(_r(N, T, H * D))
    vlen = jnp.array([15, 24])
    keep = jnp.arange(T)[None, :] < vlen[:, None]          # (N, T) bool
    bool_mask = keep[:, None, None, :]
    add_mask = jnp.where(bool_mask, 0.0, -1e30).astype(jnp.float32)
    out_bool = multi_head_attention(q, k, v, mask=bool_mask, num_heads=H,
                                    use_pallas=False)
    out_add = multi_head_attention(q, k, v, mask=add_mask, num_heads=H,
                                   use_pallas=False)
    assert_almost_equal(onp.asarray(out_add), onp.asarray(out_bool),
                        rtol=1e-4, atol=1e-5)
    # pallas (interpreted) path, additive key-padding mask form
    out_add_pl = multi_head_attention(q, k, v, mask=add_mask, num_heads=H,
                                      use_pallas=True)
    assert_almost_equal(onp.asarray(out_add_pl), onp.asarray(out_bool),
                        rtol=1e-4, atol=1e-5)
    # sanity: the mask actually drops keys (row 0 differs from unmasked)
    out_nomask = multi_head_attention(q, k, v, num_heads=H,
                                      use_pallas=False)
    assert onp.abs(onp.asarray(out_bool) - onp.asarray(out_nomask)).max() \
        > 1e-3


def test_mha_attention_dropout():
    """dropout_p is applied in training mode (stochastic, scaled) and a
    no-op in inference mode (advisor r2: it was a silent dead parameter)."""
    import jax.numpy as jnp
    from mxnet_tpu import autograd
    from mxnet_tpu.ops.attention import multi_head_attention
    N, T, H, D = 2, 16, 2, 8
    q = jnp.asarray(_r(N, T, H * D))
    k = jnp.asarray(_r(N, T, H * D))
    v = jnp.asarray(_r(N, T, H * D))
    base = multi_head_attention(q, k, v, num_heads=H, dropout_p=0.5,
                                use_pallas=False)
    with autograd.train_mode():
        d1 = multi_head_attention(q, k, v, num_heads=H, dropout_p=0.5,
                                  use_pallas=False)
        d2 = multi_head_attention(q, k, v, num_heads=H, dropout_p=0.5,
                                  use_pallas=False)
    assert onp.abs(onp.asarray(d1) - onp.asarray(base)).max() > 1e-3
    assert onp.abs(onp.asarray(d1) - onp.asarray(d2)).max() > 1e-3


def test_flash_attention_dropout_kernel():
    """In-kernel attention dropout (counter-based PRNG): deterministic for
    a fixed seed, seed-sensitive, inverse-scaled (mean-preserving), and the
    Pallas backward regenerates the same keep mask (directional FD check)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_attention import flash_attention
    B, H, T, D = 1, 2, 32, 16
    q = jnp.asarray(_r(B, H, T, D))
    k = jnp.asarray(_r(B, H, T, D))
    v = jnp.asarray(_r(B, H, T, D))
    base = flash_attention(q, k, v)
    d1 = flash_attention(q, k, v, dropout_p=0.3, dropout_seed=42)
    d2 = flash_attention(q, k, v, dropout_p=0.3, dropout_seed=42)
    d3 = flash_attention(q, k, v, dropout_p=0.3, dropout_seed=43)
    assert onp.array_equal(onp.asarray(d1), onp.asarray(d2))
    assert onp.abs(onp.asarray(d1) - onp.asarray(d3)).max() > 1e-3
    assert onp.abs(onp.asarray(d1) - onp.asarray(base)).max() > 1e-3
    # scaled dropout keeps the output magnitude in the same ballpark
    ratio = onp.abs(onp.asarray(d1)).mean() / onp.abs(onp.asarray(base)).mean()
    assert 0.7 < ratio < 1.4, ratio
    # backward consistency: AD (Pallas dq/dkv kernels, regenerated mask)
    # vs directional finite difference through the same fixed-seed forward
    def f(q):
        return jnp.mean(jnp.tanh(flash_attention(
            q, k, v, dropout_p=0.3, dropout_seed=42)))
    g = jax.grad(f)(q)
    rng = onp.random.RandomState(3)
    dirn = jnp.asarray(rng.randn(*q.shape).astype(onp.float32))
    dirn = dirn / jnp.linalg.norm(dirn.ravel())
    eps = 1e-2
    fd = (f(q + eps * dirn) - f(q - eps * dirn)) / (2 * eps)
    ad = jnp.vdot(g, dirn)
    assert abs(float(fd) - float(ad)) < 0.05 * max(abs(float(fd)), 1e-4), \
        (float(fd), float(ad))


def test_mha_dropout_routes_through_pallas():
    """The flagship training config (dropout>0 + key-padding mask) must
    route through the flash kernel, not fall back to XLA (VERDICT r3: the
    kernel was bypassed by the very config it was built for)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as attn_ops
    from mxnet_tpu.ops.attention import multi_head_attention
    N, T, H, D = 2, 24, 2, 8
    q = jnp.asarray(_r(N, T, H * D))
    k = jnp.asarray(_r(N, T, H * D))
    v = jnp.asarray(_r(N, T, H * D))
    vlen = jnp.array([15, 24])
    mask = (jnp.arange(T)[None, None, None, :] <
            vlen[:, None, None, None])
    before = dict(attn_ops.route_counts)
    out = multi_head_attention(q, k, v, mask=mask, num_heads=H,
                               dropout_p=0.5, use_pallas=True,
                               dropout_key=__import__('jax').random.PRNGKey(0))
    assert attn_ops.route_counts['pallas'] == before['pallas'] + 1
    assert attn_ops.route_counts['xla'] == before['xla']
    # dropout actually active on the kernel path
    base = multi_head_attention(q, k, v, mask=mask, num_heads=H,
                                use_pallas=True)
    assert onp.abs(onp.asarray(out) - onp.asarray(base)).max() > 1e-3


def test_mha_auto_route_propagates_a_kernel_failure(monkeypatch):
    """'auto' chooses from the platform, the mask kind and the legality
    verdict — and then stands by the choice: a kernel that fails raises,
    it does not warn and hand the step to XLA attention."""
    import warnings
    import jax.numpy as jnp
    import pytest
    from mxnet_tpu.ops import attention as attn_ops, pallas_attention

    def broken(*_a, **_k):
        raise RuntimeError('Mosaic said no')
    monkeypatch.setattr(pallas_attention, 'pallas_available', lambda: True)
    monkeypatch.setattr(pallas_attention, 'flash_mha', broken)
    q = jnp.asarray(_r(2, 16, 16))
    before = dict(attn_ops.route_counts)
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        with pytest.raises(RuntimeError, match='Mosaic said no'):
            attn_ops.multi_head_attention(q, q, q, num_heads=2)
    assert attn_ops.route_counts == before        # nothing ran, nothing counted
    # off the TPU the same call takes XLA, by the platform alone
    monkeypatch.setattr(pallas_attention, 'pallas_available', lambda: False)
    attn_ops.multi_head_attention(q, q, q, num_heads=2)
    assert attn_ops.route_counts['xla'] == before['xla'] + 1
    # and a shape the kernel cannot tile takes XLA on the TPU too
    monkeypatch.setattr(pallas_attention, 'pallas_available', lambda: True)
    odd = jnp.asarray(_r(2, 100, 16)).astype(jnp.bfloat16)
    assert not pallas_attention.flash_legal(4, 100, 100, 8, odd.dtype)
    attn_ops.multi_head_attention(odd, odd, odd, num_heads=2)
    assert attn_ops.route_counts['xla'] == before['xla'] + 2


def test_flash_attention_interpret_mode_is_explicit(monkeypatch):
    """interpret=False means Mosaic, on any backend: nothing turns it
    into True because the backend is a CPU. None asks the one helper,
    which answers from the platform."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_attention as pa
    seen = []

    def spy(arrays, km, meta, heads, causal, dropout_p, interpret, h_all,
            *grouped):
        seen.append(interpret)
        return arrays[0]
    monkeypatch.setattr(pa, '_flash', spy)
    q = jnp.asarray(_r(1, 2, 16, 8))
    pa.flash_attention(q, q, q, interpret=False)
    pa.flash_attention(q, q, q, interpret=True)
    pa.flash_attention(q, q, q)
    assert seen == [False, True, True]            # the CPU helper: interpret
    assert pa.default_interpret() is True and pa.pallas_available() is False
    monkeypatch.setattr(pa.jax, 'default_backend', lambda: 'tpu')
    assert pa.default_interpret() is False and pa.pallas_available() is True


def test_flash_kernel_maps_over_the_mesh_bit_identically():
    """Inside attention.mesh_placement the kernel runs per shard under
    shard_map (a sharded Mosaic call does not lower otherwise): output and
    gradients — in-kernel dropout included — are bit-identical to the
    unsharded call, with the batch split over dp and the heads over tp
    (three arrays, their columns sharded), and with the batch alone split
    under the fused projection (one array, addressed three times). D = 64:
    a shard then holds whole 128-lane blocks of two heads, as the
    unsharded call does, so every contraction is the same one."""
    import jax
    import jax.numpy as jnp
    import pytest
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops import attention as A, pallas_attention
    from mxnet_tpu.parallel import make_mesh
    N, T, H, D = 4, 16, 4, 64
    q, k, v = (jnp.asarray(_r(N, T, H * D)) for _ in range(3))
    mask = (jnp.arange(T)[None, None, None, :] <
            jnp.array([9, 16, 12, 16])[:, None, None, None])
    key = jax.random.PRNGKey(3)

    def loss(q, k, v, mask):
        out = A.multi_head_attention(q, k, v, mask, num_heads=H,
                                     dropout_p=0.3, use_pallas=True,
                                     dropout_key=key)
        return jnp.sum(jnp.tanh(out)), out
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    (_, ref), g_ref = jax.jit(grad)(q, k, v, mask)
    mesh = make_mesh((2, 2), ('dp', 'tp'))
    sh = NamedSharding(mesh, P('dp'))

    def mapped(q, k, v, mask):
        with A.mesh_placement(mesh, ('dp',), ('tp',)):
            return grad(q, k, v, mask)
    (_, out), g = jax.jit(mapped, in_shardings=(sh,) * 4)(q, k, v, mask)
    assert onp.array_equal(onp.asarray(out), onp.asarray(ref))
    for a, b in zip(g, g_ref):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))

    # the fused projection: the batch axes shard the one array, and the
    # kernels address it three times on every shard
    def fused_loss(qkv, mask):
        out = A.self_attention(qkv, mask, num_heads=H, dropout_p=0.3,
                               use_pallas=True, dropout_key=key)
        return jnp.sum(jnp.tanh(out)), out
    fused_grad = jax.value_and_grad(fused_loss, has_aux=True)
    qkv = jnp.concatenate([q, k, v], axis=-1)
    before = dict(pallas_attention.head_blocks)

    def fused_mapped(qkv, mask):
        with A.mesh_placement(mesh, ('dp',), ()):
            return fused_grad(qkv, mask)
    (_, out), g_qkv = jax.jit(fused_mapped, in_shardings=(sh,) * 2)(qkv, mask)
    assert onp.array_equal(onp.asarray(out), onp.asarray(ref))
    assert onp.array_equal(onp.asarray(g_qkv),
                           onp.concatenate([onp.asarray(a) for a in g_ref],
                                           axis=-1))
    assert {key_: n - before.get(key_, 0)
            for key_, n in pallas_attention.head_blocks.items()
            if n != before.get(key_, 0)} == {
        (kind, H, D, 2, True): 1 for kind in ('fwd', 'bwd_dq', 'bwd_dkv')}
    # heads whose shard would hold no whole lane block (6 heads of 64 over
    # tp=2: 192 columns) stay whole on every chip of the head axes
    six = jnp.asarray(_r(N, T, 3 * 6 * D))
    whole = A.self_attention(six, num_heads=6, use_pallas=True)
    with A.mesh_placement(mesh, ('dp',), ('tp',)):
        kept = jax.jit(lambda x: A.self_attention(
            x, num_heads=6, use_pallas=True), in_shardings=sh)(six)
    assert onp.array_equal(onp.asarray(kept), onp.asarray(whole))
    # a batch that does not divide cannot be mapped: say so
    with A.mesh_placement(make_mesh((8,), ('dp',)), ('dp',), ()):
        with pytest.raises(MXNetError, match='does not divide'):
            A.multi_head_attention(q, k, v, mask, num_heads=H,
                                   use_pallas=True)


def test_bert_masked_position_gather():
    """BertForPretraining(masked_positions=...) decodes only the masked
    positions and matches slicing the full-T logits (GluonNLP recipe)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import BertForPretraining
    cfg = dict(vocab_size=128, hidden=32, layers=1, heads=2,
               intermediate=64, max_len=32, type_vocab=2, dropout=0.0)
    mx.random.seed(0)
    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    N, T, M = 2, 16, 4
    rng = onp.random.RandomState(0)
    tokens = nd.array(rng.randint(0, 128, (N, T)).astype(onp.int32))
    mpos = nd.array(onp.stack([rng.choice(T, M, replace=False)
                               for _ in range(N)]).astype(onp.int32))
    mlm_full, nsp_full = model(tokens)
    mlm_m, nsp_m = model(tokens, None, None, mpos)
    assert mlm_m.shape == (N, M, 128)
    full = onp.asarray(mlm_full.asnumpy())
    sel = onp.take_along_axis(
        full, onp.asarray(mpos.asnumpy())[:, :, None].astype(onp.int64),
        axis=1)
    assert_almost_equal(onp.asarray(mlm_m.asnumpy()), sel,
                        rtol=1e-5, atol=1e-5)
