"""The flash kernels read and write the model's own (N, T, H*D) arrays
(ops/pallas_attention.py: ``_lane_block``, ``_block_specs``, ``_own``).

What is held here, on the CPU with the kernels interpreted: the (N, T, C)
entry against naive attention, forward and gradients, with two 64-wide
heads to a 128-lane block, one 128-wide head a block and a toy width that
is one block whole, on sequences the blocks do not divide, with and
without a padding mask and the causal diagonal; that the key mask is an
operand indexed by batch row (a sentinel in the padded keys changes
nothing); that the dropout bits are those of the old (n*H + h, row, col)
numbering; that ``self_attention(qkv)`` is ``multi_head_attention`` of its
three thirds on both routes; and that a BERT and a GPT layer on the Pallas
route transpose no activation. The Mosaic compiles of the same addressing
at the benchmark cells' shapes are in tests/test_causal_skip.py, the one
file that describes a topology.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.ops import attention, autotune
from mxnet_tpu.ops import pallas_attention as pa
from test_operator import _naive_mha

SHAPES = {'two_heads_a_block': (2, 40, 4, 64),      # N, T, H, D
          'one_head_a_block': (2, 24, 2, 128),
          'toy_whole_c': (3, 20, 4, 8)}


def _blocks(G=2, b=16):
    """16-wide sequence blocks, forward and backward: T = 40 is three
    blocks with padding, T = 48 three without."""
    stack = contextlib.ExitStack()
    for kind in ('fwd', 'bwd'):
        stack.enter_context(
            autotune.forced(autotune.KERNEL_FA, kind, (G, b, b)))
    return stack


def _qkv(N, T, H, D, seed=0):
    rng = onp.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((N, T, H * D)),
                             jnp.float32) for _ in range(3))


def _padding(N, T):
    """Mixed valid lengths, the first row full: (lengths, additive mask)."""
    valid = onp.array([T, 17, 29, 8][:N])
    keep = onp.arange(T)[None, :] < valid[:, None]
    return valid, jnp.asarray(onp.where(keep, 0.0, -1e30), jnp.float32)


def _heads(x, H):
    N, T, C = x.shape
    return x.reshape(N, T, H, C // H).transpose(0, 2, 1, 3)


def _naive_ntc(q, k, v, H, key_mask, causal):
    out = _naive_mha(_heads(q, H), _heads(k, H), _heads(v, H), key_mask,
                     causal)
    return out.transpose(0, 2, 1, 3).reshape(q.shape)


def _new_builds(before):
    return {key: n - before.get(key, 0) for key, n in pa.head_blocks.items()
            if n != before.get(key, 0)}


# the (N, T, C) entry against the naive reference ---------------------------

@pytest.mark.parametrize('causal', [False, True], ids=['full', 'causal'])
@pytest.mark.parametrize('masked', [False, True], ids=['nomask', 'padding'])
@pytest.mark.parametrize('shape', sorted(SHAPES))
def test_the_ntc_entry_matches_naive_attention(shape, masked, causal):
    N, T, H, D = SHAPES[shape]
    q, k, v = _qkv(N, T, H, D)
    km = _padding(N, T)[1] if masked else None
    weight = jnp.asarray(onp.random.default_rng(1).standard_normal(q.shape),
                         jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    def flash(q, k, v):
        return pa.flash_mha((q, k, v), H, key_mask=km, causal=causal)

    def naive(q, k, v):
        return _naive_ntc(q, k, v, H, km, causal)
    before = dict(pa.head_blocks)
    with _blocks():
        out = flash(q, k, v)
        grads = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    onp.testing.assert_allclose(out, naive(q, k, v), rtol=2e-5, atol=2e-5)
    for got, want in zip(grads,
                         jax.grad(loss(naive), argnums=(0, 1, 2))(q, k, v)):
        onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    hb = {64: 2, 128: 1, 8: 4}[D]
    assert _new_builds(before) == {
        ('fwd', H, D, hb, False): 2, ('bwd_dq', H, D, hb, False): 1,
        ('bwd_dkv', H, D, hb, False): 1}


@pytest.mark.parametrize('C,D,want', [
    (768, 64, (128, 2)), (1024, 128, (128, 1)), (512, 256, (256, 1)),
    (512, 32, (128, 4)), (32, 8, (32, 4)), (192, 64, None),
    (384, 96, None)])
def test_the_lane_block_follows_from_the_head_width(C, D, want):
    """128 lanes of whole heads where D divides 128, D where it is a
    multiple, the whole C under 128; columns that do not come apart so
    have no kernel, and 'auto' hands them to XLA."""
    assert pa._lane_block(C, D) == want
    assert pa.flash_legal(4 * C // D, 64, 64, D, jnp.float32,
                          num_heads=C // D) == (want is not None)


def test_heads_a_step_are_rows_of_a_lane_block():
    """G of ``_block_sizes`` stays heads a grid step: G // hb batch rows,
    at least one, clamped to a divisor of N; and what the rows cannot
    supply, adjacent lane blocks, clamped to a divisor of the array's (of
    one group's, where heads are grouped). Rows first: a batch that fills
    G by rows keeps one lane block a step."""
    assert pa._block_sizes(672, 512, 512, 64, jnp.bfloat16)[0] == 4
    assert pa._rows_per_step(56, 4, 2) == 2
    assert pa._rows_per_step(56, 4, 1) == 4
    assert pa._rows_per_step(3, 4, 2) == 1
    assert pa._rows_per_step(8, 1, 2) == 1
    # (G, Gn, hb, lane blocks to divide) -> lane blocks a step
    assert pa._lane_blocks_per_step(4, 2, 2, 6) == 1      # the BERT cells
    assert pa._lane_blocks_per_step(4, 4, 1, 16) == 1
    assert pa._lane_blocks_per_step(4, 1, 1, 16) == 4     # one row, D = 128
    assert pa._lane_blocks_per_step(8, 1, 1, 16) == 8
    assert pa._lane_blocks_per_step(4, 2, 1, 16) == 2     # two rows
    assert pa._lane_blocks_per_step(4, 1, 2, 4) == 2      # N = 3, D = 64
    assert pa._lane_blocks_per_step(4, 1, 2, 6) == 2
    assert pa._lane_blocks_per_step(8, 1, 2, 6) == 3      # 4 does not divide
    assert pa._lane_blocks_per_step(1, 1, 2, 6) == 1
    assert pa._lane_blocks_per_step(4, 1, 1, 7) == 1      # a group of seven
    assert pa._lane_blocks_per_step(7, 1, 1, 7) == 7
    assert pa._lane_blocks_per_step(64, 1, 1, 16) == 16
    # (N, C, D, G, rep) -> (rows, lane blocks) of a step: the six cells'
    assert pa._step_heads(56, 768, 64, 4) == pa._step_heads(24, 768, 64, 4) \
        == pa._step_heads(224, 768, 64, 4) == (2, 1)
    assert pa._step_heads(1, 2048, 128, 8) == (1, 8)
    assert pa._step_heads(1, 3584, 128, 7, rep=7) == (1, 7)
    assert pa._step_heads(2, 2048, 128, 4) == (2, 2)      # the check's two
    # (kind, lb, rep) -> lane blocks on the query and the key/value side
    assert pa._step_lanes('fwd', 4, 1) == pa._step_lanes('bwd_dkv', 4, 1) \
        == (4, 4)
    assert pa._step_lanes('bwd_dq', 7, 7) == (7, 1)
    assert pa._step_lanes('fwd', 1, 7) == (1, 1)
    assert pa._step_lanes('bwd_dkv', 1, 7) == (7, 1)


# heads a step by lane blocks: the same bits ----------------------------------

# N, T, H, Hkv, D, causal, window, dropout, key mask, fused; T is two
# backward blocks (32) and one forward block (64) long
HEADS_A_STEP = {
    'plain': (1, 64, 8, None, 128, False, None, 0.0, False, False),
    'causal': (1, 64, 8, None, 128, True, None, 0.0, False, False),
    # rep 2: G = 1 is one head a step, 2 and more the group (lb 1 and 2)
    'window_grouped': (1, 64, 8, 4, 128, True, 24, 0.0, False, False),
    'dropout_masked': (1, 64, 8, None, 128, False, None, 0.1, True, False),
    'fused': (1, 64, 8, None, 128, True, None, 0.1, False, True),
    # a lane block of two heads: G = 1 is one block a step, 4 two blocks,
    # 8 three rows of one
    'three_rows_d64': (3, 64, 8, None, 64, True, None, 0.1, True, False),
}
_one_head_a_step = {}


def _forward_and_backward(case, G):
    """(out, lse, dq, dk, dv) of the kernels at ``G`` heads a step, and
    the statistics blocks they were built with."""
    N, T, H, Hkv, D, causal, window, rate, masked, fused = HEADS_A_STEP[case]
    rng = onp.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.standard_normal((N, T, h * D)), jnp.float32)
               for h in (H, Hkv or H, Hkv or H))
    do = jnp.asarray(rng.standard_normal((N, T, H * D)), jnp.float32)
    arrays = (jnp.concatenate([q, k, v], -1),) if fused else (q, k, v)
    km = _padding(N, T)[1] if masked else None
    meta = jnp.asarray([[77, 0]], jnp.uint32)
    before = dict(pa.row_stat_blocks)
    with contextlib.ExitStack() as stack:
        for kind, b in (('fwd', 64), ('bwd', 32)):
            stack.enter_context(
                autotune.forced(autotune.KERNEL_FA, kind, (G, b, b)))
        out, lse = pa._fa_forward(arrays, km, meta, H, causal, rate, True, H,
                                  Hkv, window)
        grads = pa._fa_backward(arrays, km, meta, H, causal, rate, True, H,
                                Hkv, window, out, lse, do)
    blocks = {key: block for key, block in pa.row_stat_blocks
              if pa.row_stat_blocks[key, block] != before.get((key, block), 0)}
    return [onp.asarray(x) for x in (out, lse) + tuple(grads)], blocks


@pytest.mark.parametrize('G', [2, 4, 8])
@pytest.mark.parametrize('case', sorted(HEADS_A_STEP))
def test_heads_a_step_by_lane_blocks_are_the_same_bits(case, G):
    """One row a chip (and three rows of two-head lane blocks): out, lse,
    dq, dk and dv with a grid step forced to one head against 2, 4 and
    all 8 heads a step are equal bit for bit -- plain, causal, window +
    grouped heads, dropout with a key mask (the bits are the old
    numbering's), the fused projection addressed in place -- and the
    statistics blocks say how many heads a step held."""
    N, T, H, Hkv, D = HEADS_A_STEP[case][:5]
    if case not in _one_head_a_step:
        _one_head_a_step[case] = _forward_and_backward(case, 1)
    want, narrow = _one_head_a_step[case]
    got, wide = _forward_and_backward(case, G)
    assert len(got) == (3 if HEADS_A_STEP[case][-1] else 5)
    for name, a, b in zip(('out', 'lse', 'dq', 'dk', 'dv'), got, want):
        assert onp.array_equal(a, b), name
    hb = 128 // D
    rep = H // (Hkv or H)
    assert narrow == {'fwd': (1, hb, 1, 64), 'bwd_dq': (1, hb, 1, 32),
                      'bwd_dkv': (1, hb * rep, 1, 32)}
    if N == 1:
        heads = min(G, rep) if rep > 1 else G
        assert wide == {'fwd': (1, heads, 1, 64), 'bwd_dq': (1, heads, 1, 32),
                        'bwd_dkv': (1, max(heads, rep), 1, 32)}
    else:
        rows, lanes = {2: (1, 1), 4: (1, 2), 8: (3, 1)}[G]
        assert wide == {kind: (rows, lanes * hb, 1, b) for kind, b in (
            ('fwd', 64), ('bwd_dq', 32), ('bwd_dkv', 32))}


# the key mask is an operand, indexed by batch row --------------------------

@pytest.mark.parametrize('fused', [False, True], ids=['three', 'fused'])
def test_a_sentinel_in_the_padded_keys_changes_nothing(fused):
    """k and v overwritten with 1e4 at every padded position: outputs
    and the gradients of real positions keep their bits. A dropped mask
    operand, or one read at another batch row, lets the sentinel in."""
    N, T, H, D = 4, 48, 4, 64
    q, k, v = _qkv(N, T, H, D, seed=2)
    valid, km = _padding(N, T)
    padded = jnp.asarray(onp.arange(T)[None, :] >= valid[:, None])[..., None]
    weight = jnp.asarray(onp.random.default_rng(3).standard_normal(q.shape),
                         jnp.float32)

    def run(k, v):
        def loss(q, k, v):
            arrays = (jnp.concatenate([q, k, v], -1),) if fused \
                else (q, k, v)
            out = pa.flash_mha(arrays, H, key_mask=km)
            return jnp.sum(out * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads
    before = dict(pa.head_blocks)
    with _blocks():
        clean = run(k, v)
        dirty = run(jnp.where(padded, 1e4, k), jnp.where(padded, 1e4, v))
    assert {key[-1] for key in _new_builds(before)} == {fused}
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), clean, dirty):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b)), name
    # and the mask did something: the padded keys got no gradient
    assert not onp.asarray(jnp.where(padded, clean[2], 0.0)).any()
    assert onp.asarray(clean[2]).any()


# the dropout bits are the bits ----------------------------------------------

@pytest.mark.parametrize('fused', [False, True], ids=['three', 'fused'])
def test_dropout_keeps_are_the_old_numberings(fused):
    """Zero scores make every probability 1/T, and a v whose row k is the
    k-th unit vector of each head makes the output's column j the keep
    multiplier of (query, key j): element for element ``_counter_keep``
    at batch*head id n*H + h, as the (N*H, T, D) layout drew it."""
    N, T, H, D, rate, seed = 4, 32, 4, 64, 0.3, 77
    zeros = jnp.zeros((N, T, H * D), jnp.float32)
    v = jnp.tile(jnp.eye(T, D, dtype=jnp.float32), (N, 1, H))
    arrays = (jnp.concatenate([zeros, zeros, v], -1),) if fused \
        else (zeros, zeros, v)
    with _blocks():
        out = pa.flash_mha(arrays, H, dropout_p=rate, dropout_seed=seed)
    got = _heads(out, H)[..., :T] * T                      # (N, H, Tq, Tk)
    u32 = jnp.uint32
    want = pa._counter_keep(
        u32(seed), jnp.arange(N * H, dtype=u32).reshape(N, H, 1, 1),
        jnp.arange(T, dtype=u32).reshape(1, 1, T, 1),
        jnp.arange(T, dtype=u32).reshape(1, 1, 1, T), rate)
    assert onp.array_equal(onp.asarray(got), onp.asarray(want))
    assert 0.2 < float(jnp.mean(want == 0.0)) < 0.4


# self_attention is multi_head_attention of the three thirds -----------------

@pytest.mark.parametrize('use_pallas', [True, False], ids=['pallas', 'xla'])
def test_self_attention_is_mha_of_the_split(use_pallas):
    N, T, H, D = 4, 48, 4, 64
    q, k, v = _qkv(N, T, H, D, seed=4)
    qkv = jnp.concatenate([q, k, v], -1)
    mask = _padding(N, T)[1][:, None, None, :]
    key = jax.random.PRNGKey(5)
    kw = dict(num_heads=H, dropout_p=0.2, dropout_key=key,
              use_pallas=use_pallas)

    def fused(qkv):
        out = attention.self_attention(qkv, mask, **kw)
        return jnp.sum(jnp.tanh(out)), out

    def split(qkv):
        out = attention.multi_head_attention(*jnp.split(qkv, 3, -1), mask,
                                             **kw)
        return jnp.sum(jnp.tanh(out)), out
    before, routes = dict(pa.head_blocks), dict(attention.route_counts)
    with _blocks():
        (_, out), grad = jax.value_and_grad(fused, has_aux=True)(qkv)
        (_, ref), grad_ref = jax.value_and_grad(split, has_aux=True)(qkv)
    assert onp.array_equal(onp.asarray(out), onp.asarray(ref))
    assert onp.array_equal(onp.asarray(grad), onp.asarray(grad_ref))
    route = 'pallas' if use_pallas else 'xla'
    assert attention.route_counts[route] == routes[route] + 2
    # the kernels were built once on the one array, once on three
    assert {key_[-1] for key_ in _new_builds(before)} == \
        ({True, False} if use_pallas else set())


# a layer on the Pallas route transposes nothing ------------------------------

def _walk(jaxpr):
    """Every equation of a jaxpr but a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == 'pallas_call':
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, 'jaxpr', sub)
                if hasattr(inner, 'eqns'):
                    yield from _walk(inner)


@pytest.mark.parametrize('model', ['bert', 'gpt'])
def test_a_layer_on_the_pallas_route_transposes_no_activation(monkeypatch,
                                                              model):
    """Forward and backward of one tiny layer, traced as on a TPU: under
    ``attn_core`` there are the three kernels on the (N, T, 3C)
    projection itself, no transpose of anything activation-sized and no
    split, and ``head_blocks`` counts what was built."""
    from mxnet_tpu.models.bert import BertLayer
    from mxnet_tpu.models.gpt import GPTBlock
    from mxnet_tpu import scopes
    monkeypatch.setattr(pa, 'pallas_available', lambda: True)
    monkeypatch.setattr(pa, 'default_interpret', lambda: False)
    N, T, C, H = 2, 16, 256, 4
    mx.random.seed(0)
    layer = BertLayer(C, H, 2 * C, dropout=0.1) if model == 'bert' \
        else GPTBlock(C, H, dropout=0.1)
    layer.initialize(mx.init.Normal(0.02))
    inputs = (jnp.zeros((N, T, C), jnp.float32),
              jnp.ones((N, T), jnp.float32))[:2 if model == 'bert' else 1]

    def loss(*arrays):
        with autograd.train_mode():
            return jnp.sum(layer(*[nd.NDArray(a) for a in arrays])._data)
    before, routes = dict(pa.head_blocks), dict(attention.route_counts)
    traced = jax.make_jaxpr(jax.grad(loss))(*inputs)
    assert attention.route_counts['pallas'] == routes['pallas'] + 1
    assert _new_builds(before) == {
        (kind, H, C // H, 2, True): 1
        for kind in ('fwd', 'bwd_dq', 'bwd_dkv')}
    core = [e for e in _walk(traced.jaxpr)
            if scopes.ATTN_CORE in str(e.source_info.name_stack)]
    kernels = [e for e in core if e.primitive.name == 'pallas_call']
    assert sorted(e.params['name'] for e in kernels) == [
        'mxtpu_flash_bwd_dkv', 'mxtpu_flash_bwd_dq', 'mxtpu_flash_fwd']
    for e in kernels:
        # q, k and v are the projection's own output, three times
        assert sum(v.aval.shape == (N, T, 3 * C) for v in e.invars) == 3
    assert not [e for e in core if scopes.ATTN_LAYOUT
                in str(e.source_info.name_stack)]
    assert not [e for e in core if e.primitive.name in ('transpose', 'split')
                and e.invars[0].aval.size >= N * T * C]
