"""The flash kernels' softmax row statistics cross HBM with the queries on
the lanes, and the dk/dv kernel computes a cell keys-down
(ops/pallas_attention.py: ``_block_specs``'s ``row``, ``_cell``'s
orientation, ``row_stat_blocks``).

What is held here, on the CPU with the kernels interpreted: that every
build's statistics blocks are (Gn, heads, 1, bq) rows and no float32
array whose last dimension is 1 goes into or comes out of a
``pallas_call``; out, dq, dk and dv against a float32 reference that
draws the same dropout bits from coordinates -- padding mask on and off,
the causal diagonal on and off, three arrays and the fused projection
addressed in place, sequences the blocks do not divide, q-blocks and
k-blocks of different widths (a transposed cell with its two positions
exchanged would show), grouped-query heads with a window; and that the
statistics the backward kernels are handed, lse and delta, are the
reference's; that padded keys' dk and dv are exact zeros (the sentinel
that stands in for ROADMAP S1, through dk/dv's mask column); and that a
layer traced at each benchmark cell's shape records the statistics
blocks PERF.md lists. The sentinel on real positions' bits is
tests/test_attention_layout.py's, the equation counts
tests/test_causal_skip.py's.
"""
import contextlib
import itertools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import autotune
from mxnet_tpu.ops import pallas_attention as pa
from test_causal_skip import _walk

SEED = 4321
RATE = 0.1


def _forced(fwd, bwd):
    stack = contextlib.ExitStack()
    stack.enter_context(autotune.forced(autotune.KERNEL_FA, 'fwd', fwd))
    stack.enter_context(autotune.forced(autotune.KERNEL_FA, 'bwd', bwd))
    return stack


def _arrays(N, Tq, Tk, H, Hkv, D, seed=0):
    rng = onp.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((N, t, h * D)), jnp.float32)
                 for t, h in ((Tq, H), (Tk, Hkv), (Tk, Hkv)))


def _padding(N, Tk):
    """Additive key mask, mixed valid lengths, the first row full; key 0
    stays, so every causal row sees a key."""
    valid = onp.array([Tk, 17, 29, 8][:N])
    return jnp.asarray(onp.where(onp.arange(Tk)[None, :] < valid[:, None],
                                 0.0, -1e30), jnp.float32)


def _scores(q, k, H, Hkv, key_mask, causal, window):
    """(N, H, Tq, Tk) float32 masked scores, and k's heads repeated."""
    N, Tq, C = q.shape
    Tk, D = k.shape[1], C // H
    qh = q.reshape(N, Tq, H, D).transpose(0, 2, 1, 3)
    kh = jnp.repeat(k.reshape(N, Tk, Hkv, D).transpose(0, 2, 1, 3),
                    H // Hkv, axis=1)
    s = jnp.einsum('nhqd,nhkd->nhqk', qh, kh) / onp.sqrt(D)
    if key_mask is not None:
        s = s + key_mask[:, None, None, :]
    if causal:
        i, j = jnp.arange(Tq)[:, None], jnp.arange(Tk)[None, :]
        keep = j <= i
        if window is not None:
            keep &= i - j < window
        s = jnp.where(keep, s, -1e30)
    return s


def _reference(q, k, v, H, Hkv=None, key_mask=None, causal=False,
               window=None, rate=0.0):
    """Attention the plain way on (N, T, heads*D) arrays in float32, its
    dropout multipliers regenerated from (n*H + h, row, col)."""
    Hkv = Hkv or H
    N, Tq, C = q.shape
    Tk, D = k.shape[1], C // H
    p = jax.nn.softmax(_scores(q, k, H, Hkv, key_mask, causal, window), -1)
    if rate:
        p = p * pa._counter_keep(
            jnp.uint32(SEED),
            jnp.arange(N * H, dtype=jnp.uint32).reshape(N, H, 1, 1),
            jnp.arange(Tq, dtype=jnp.uint32)[None, None, :, None],
            jnp.arange(Tk, dtype=jnp.uint32)[None, None, None, :], rate)
    vh = jnp.repeat(v.reshape(N, Tk, Hkv, D).transpose(0, 2, 1, 3),
                    H // Hkv, axis=1)
    out = jnp.einsum('nhqk,nhkd->nhqd', p, vh)
    return out.transpose(0, 2, 1, 3).reshape(N, Tq, C)


def _out_and_grads(fn, *arrays):
    weight = jnp.asarray(onp.random.default_rng(9).standard_normal(
        jax.eval_shape(fn, *arrays).shape), jnp.float32)

    def loss(*arrays):
        out = fn(*arrays)
        return jnp.sum(out * weight), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=tuple(range(len(arrays))), has_aux=True)(*arrays)
    return [onp.asarray(x) for x in (out,) + grads]


def _new(counter, before):
    return {key: n - before.get(key, 0) for key, n in counter.items()
            if n != before.get(key, 0)}


# the layout ------------------------------------------------------------------

# the attention layers of the benchmark's six cells at the shapes they
# train at (chipbench/traffic/*.json batches, the configurations' published
# widths), and one whose sequence is under a lane tile: N, T, H, Hkv, D,
# causal, window, dropout, then the statistics blocks of fwd, bwd_dq and
# bwd_dkv as PERF.md section 3 lists them. The four batch cells fill a
# step's four heads by rows (two rows x the two heads of a lane block);
# the two cells of one sequence a chip by lane blocks
LAYERS = {
    'bert_base.t512': (56, 512, 12, None, 64, False, None, 0.1,
                       ((2, 2, 1, 512), (2, 2, 1, 256), (2, 2, 1, 256))),
    'bert_base.t128': (224, 128, 12, None, 64, False, None, 0.1,
                       ((2, 2, 1, 128), (2, 2, 1, 128), (2, 2, 1, 128))),
    'gpt2_small.t1024': (24, 1024, 12, None, 64, True, None, 0.1,
                         ((2, 2, 1, 512), (2, 2, 1, 256), (2, 2, 1, 256))),
    # four chips of 56 rows each: a shard's builds are bert_base.t512's
    'bert_base.dp4_t512': (224, 512, 12, None, 64, False, None, 0.1,
                           ((2, 2, 1, 512), (2, 2, 1, 256), (2, 2, 1, 256))),
    # one row: a group of seven query heads a step, in every kernel
    'smallthinker_21b.t8192-window': (
        1, 8192, 28, 4, 128, True, 4096, 0.0,
        ((1, 7, 1, 512), (1, 7, 1, 256), (1, 7, 1, 256))),
    'smallthinker_21b.t8192-full': (
        1, 8192, 28, 4, 128, True, None, 0.0,
        ((1, 7, 1, 512), (1, 7, 1, 256), (1, 7, 1, 256))),
    # one row of 16 heads of 128 (q, k and v three arrays): eight lane
    # blocks a step in the backward, four in the forward, where the VMEM
    # estimate stops eight
    'ouro_2_6b.t4096': (1, 4096, 16, 16, 128, True, None, 0.0,
                        ((1, 4, 1, 512), (1, 8, 1, 256), (1, 8, 1, 256))),
    # bq is the whole sequence where that is under 128 lanes
    'short_sequence': (4, 64, 4, None, 64, True, None, 0.1,
                       ((2, 2, 1, 64), (2, 2, 1, 64), (2, 2, 1, 64))),
}


@pytest.mark.parametrize('layer', sorted(LAYERS))
def test_every_build_moves_its_statistics_as_rows(layer):
    """A layer's forward + backward traced (nothing runs) through the
    entry its model calls, the four-chip cell under ``mesh_placement`` on
    a CPU mesh of four: one build of each kernel, its statistics block
    the listed (Gn, heads, 1, bq) -- dk/dv's a whole group of query heads
    wide -- and no ``pallas_call`` takes or returns a float32 array whose
    last dimension is 1: lse and delta are (N, H, 1, T)."""
    from mxnet_tpu.ops import attention
    from mxnet_tpu.parallel.mesh import make_mesh
    N, T, H, Hkv, D, causal, window, rate, blocks = LAYERS[layer]
    fused, chips = Hkv is None, 4 if 'dp4' in layer else 1
    shapes = [(N, T, 3 * H * D)] if fused else \
        [(N, T, H * D)] + [(N, T, Hkv * D)] * 2
    mask = jax.ShapeDtypeStruct((N, 1, 1, T), jnp.bool_) if fused else None
    placed = attention.mesh_placement(
        make_mesh((chips,), ('dp',)), ('dp',), ()) if chips > 1 \
        else contextlib.nullcontext()

    def loss(key, mask, *arrays):
        kw = dict(num_heads=H, dropout_p=rate, causal=causal,
                  use_pallas=True, dropout_key=key if rate else None)
        out = attention.self_attention(*arrays, mask, **kw) if fused else \
            attention.multi_head_attention(*arrays, mask, num_kv_heads=Hkv,
                                           window=window, **kw)
        return jnp.sum(out.astype(jnp.float32))
    before = dict(pa.row_stat_blocks)
    with placed:
        traced = jax.make_jaxpr(jax.grad(loss, argnums=tuple(
            range(2, 2 + len(shapes)))))(
            jax.random.PRNGKey(0), mask,
            *(jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes))
    assert _new(pa.row_stat_blocks, before) == dict(zip(
        zip(('fwd', 'bwd_dq', 'bwd_dkv'), blocks), (1, 1, 1)))
    assert not any(block[-1] == 1 for _, block in pa.row_stat_blocks)
    calls = [e for e in _walk(traced.jaxpr)
             if e.primitive.name == 'pallas_call']
    assert len(calls) == 3
    for e in calls:
        avals = [v.aval for v in list(e.invars) + list(e.outvars)]
        assert not any(a.dtype == jnp.float32 and a.shape[-1:] == (1,)
                       for a in avals), (e.params['name'], avals)
        stats = [a.shape for a in avals
                 if a.dtype == jnp.float32 and len(a.shape) == 4]
        assert stats == [(N // chips, H, 1, T)] * (
            1 if e.params['name'] == 'mxtpu_flash_fwd' else 2)


def test_the_statistics_the_backward_gets_are_the_references(monkeypatch):
    """The forward's second result is lse = logsumexp of the masked
    scores, a row a head with the queries last, sliced back from the
    blocks' padding (T = 40 in 16-wide q-blocks). The backward pads it
    again and makes delta = rowsum(dO * O) a head in the same layout:
    both kernels are handed the two as (N, H, 1, 48) float32 rows, the
    reference's values and zeros behind them."""
    N, T, H, D = 2, 40, 4, 64
    q, k, v = _arrays(N, T, T, H, H, D)
    km = _padding(N, T)
    meta = jnp.zeros((1, 2), jnp.uint32)
    with _forced((2, 16, 16), (2, 16, 16)):
        out, lse = pa._fa_forward((q, k, v), km, meta, H, True, 0.0, True, H)
    assert lse.shape == (N, H, 1, T) and out.shape == (N, T, H * D)
    want = jax.nn.logsumexp(_scores(q, k, H, H, km, True, None), -1)
    onp.testing.assert_allclose(lse[:, :, 0], want, rtol=1e-5, atol=1e-5)

    handed = []
    build = pa._call

    def spy(*args, **kwargs):
        call = build(*args, **kwargs)

        def run(*operands):
            handed.append(operands[-2:])
            return call(*operands)
        return run
    monkeypatch.setattr(pa, '_call', spy)
    do = _arrays(N, T, T, H, H, D, seed=11)[0]
    with _forced((2, 16, 16), (2, 16, 16)):
        pa._fa_backward((q, k, v), km, meta, H, True, 0.0, True, H, None,
                        None, out, lse, do)
    delta = (do * out).reshape(N, T, H, D).sum(-1).transpose(0, 2, 1)
    assert len(handed) == 2                     # dq, then dk/dv
    for got_lse, got_delta in handed:
        assert got_lse.shape == got_delta.shape == (N, H, 1, 48)
        assert got_lse.dtype == got_delta.dtype == jnp.float32
        onp.testing.assert_array_equal(got_lse[..., :T], lse)
        onp.testing.assert_allclose(got_delta[:, :, 0, :T], delta,
                                    rtol=1e-5, atol=1e-5)
        assert not onp.any(got_lse[..., T:]) \
            and not onp.any(got_delta[..., T:])


# the gradients ---------------------------------------------------------------

@pytest.mark.parametrize('operands', ['three', 'fused'])
@pytest.mark.parametrize('causal', [False, True], ids=['full', 'causal'])
@pytest.mark.parametrize('masked', [False, True], ids=['nomask', 'padding'])
def test_gradients_with_the_same_dropout_bits(masked, causal, operands):
    """Two 64-wide heads a lane block, T = 64 in whole blocks, so the
    fused projection is addressed in place. The forward tiles (16, 32),
    the backward (32, 16): a cell of dk/dv is (16, 32), keys down, and
    one whose positions, mask or bits were those of its transpose would
    be wrong by whole probabilities."""
    N, T, H, D = 2, 64, 4, 64
    q, k, v = _arrays(N, T, T, H, H, D)
    km = _padding(N, T) if masked else None
    kw = dict(key_mask=km, causal=causal)
    before = dict(pa.head_blocks)
    if operands == 'fused':
        qkv = jnp.concatenate([q, k, v], -1)

        def flash(qkv):
            return pa.flash_mha((qkv,), H, dropout_p=RATE,
                                dropout_seed=jnp.uint32(SEED),
                                interpret=True, **kw)

        def naive(qkv):
            return _reference(*jnp.split(qkv, 3, -1), H, rate=RATE, **kw)
        arrays = (qkv,)
    else:
        def flash(q, k, v):
            return pa.flash_mha((q, k, v), H, dropout_p=RATE,
                                dropout_seed=jnp.uint32(SEED),
                                interpret=True, **kw)

        def naive(q, k, v):
            return _reference(q, k, v, H, rate=RATE, **kw)
        arrays = (q, k, v)
    with _forced((2, 16, 32), (2, 32, 16)):
        got = _out_and_grads(flash, *arrays)
    want = _out_and_grads(naive, *arrays)
    onp.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    assert all(key[-1] == (operands == 'fused')
               for key in _new(pa.head_blocks, before))
    if masked:
        # the sentinel (ROADMAP S1): a padded key's p is exp(-1e30 - lse),
        # exactly 0, so its dk and dv are exact zeros -- through dk/dv's
        # mask column, the path this layout changed
        dk, dv = jnp.split(got[1], 3, -1)[1:] if operands == 'fused' \
            else got[2:]
        padded = onp.asarray(km) < 0
        assert padded.any() and not dk[padded].any() \
            and not dv[padded].any()


@pytest.mark.parametrize('Tq,Tk', [(40, 40), (40, 56), (100, 72)],
                         ids=['self', 'longer_keys', 'longer_queries'])
@pytest.mark.parametrize('causal', [False, True], ids=['full', 'causal'])
def test_gradients_on_sequences_the_blocks_do_not_divide(causal, Tq, Tk):
    """Tq and Tk pad to their blocks (forward 16 and 32 wide, backward 32
    and 16), queries and keys of different lengths: the padded statistics
    are zeros that meet a zero dO, padded keys are cut at k_len in either
    orientation, and a padding mask and dropout ride along."""
    N, H, D = 2, 4, 64
    q, k, v = _arrays(N, Tq, Tk, H, H, D, seed=3)
    kw = dict(key_mask=_padding(N, Tk), causal=causal)

    def flash(q, k, v):
        return pa.flash_mha((q, k, v), H, dropout_p=RATE,
                            dropout_seed=jnp.uint32(SEED), interpret=True,
                            **kw)
    with _forced((2, 16, 32), (2, 32, 16)):
        got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(
        lambda q, k, v: _reference(q, k, v, H, rate=RATE, **kw), q, k, v)
    onp.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for name, a, b in zip(('dq', 'dk', 'dv'), got[1:], want[1:]):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                    err_msg=name)


@pytest.mark.parametrize('T,window,blocks', [
    (100, 24, ((1, 64, 64), (1, 32, 32))),
    (128, 40, ((1, 64, 32), (1, 32, 64))),
    (100, None, ((1, 32, 64), (1, 64, 32)))],
    ids=['window_in_a_block', 'oblong_cells', 'full'])
def test_grouped_windowed_gradients_with_dropout(T, window, blocks):
    """Four query heads over two key/value heads of 128 columns, with
    in-kernel dropout: dk/dv's blocks of q, dO, lse and delta are the two
    heads of a group wide, a head's (1, bq) statistics row is picked by
    its number in the group, and the window's comparison is taken in the
    keys-down cell."""
    H, Hkv, D = 4, 2, 128
    q, k, v = _arrays(2, T, T, H, Hkv, D, seed=5)
    kw = dict(causal=True, window=window)

    def flash(q, k, v):
        return pa.flash_mha((q, k, v), H, num_kv_heads=Hkv, dropout_p=RATE,
                            dropout_seed=jnp.uint32(SEED), interpret=True,
                            **kw)
    before = dict(pa.row_stat_blocks)
    with _forced(*blocks):
        got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(
        lambda q, k, v: _reference(q, k, v, H, Hkv, rate=RATE, **kw),
        q, k, v)
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, want):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                    err_msg=name)
    bq = blocks[1][1]
    # (the forward is traced twice: for its value and for its residuals)
    assert _new(pa.row_stat_blocks, before) == {
        ('fwd', (1, 1, 1, blocks[0][1])): 2,
        ('bwd_dq', (1, 1, 1, bq)): 1, ('bwd_dkv', (1, 2, 1, bq)): 1}


@pytest.mark.parametrize('D,dtype', itertools.product(
    (8, 64, 128), (jnp.float32, jnp.bfloat16)), ids=lambda x: str(
        getattr(x, '__name__', x)))
def test_toy_paired_and_whole_lane_heads_in_either_dtype(D, dtype):
    """A toy width that is one block whole (four 8-wide heads, W = C),
    two 64-wide heads to a lane block (the cells' own) and one 128-wide
    head a block (no lane mask), float32 and bfloat16 operands: gradients against the float32 reference on the same
    (rounded) operands, at what the operands' precision allows."""
    N, T, H = 2, 48, 2 if D == 128 else 4
    q, k, v = (x.astype(dtype) for x in _arrays(N, T, T, H, H, D, seed=7))
    km = _padding(N, T)

    def flash(q, k, v):
        return pa.flash_mha((q, k, v), H, key_mask=km, causal=True,
                            dropout_p=RATE, dropout_seed=jnp.uint32(SEED),
                            interpret=True).astype(jnp.float32)

    def naive(q, k, v):
        return _reference(*(x.astype(jnp.float32) for x in (q, k, v)), H,
                          key_mask=km, causal=True, rate=RATE)
    with _forced((2, 16, 16), (2, 16, 16)):
        got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(naive, q, k, v)
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, want):
        onp.testing.assert_allclose(
            onp.asarray(a, onp.float32), onp.asarray(b, onp.float32),
            rtol=tol, atol=tol, err_msg=name)
