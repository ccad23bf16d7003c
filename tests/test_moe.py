"""One share of a sparse expert layer (ops/moe.py).

What is held here: router, dispatch and combine against a dense loop over
the held experts in float32, forward and gradients, the einsum path and
the Pallas kernels through the interpreter; every assignment sent to the
held experts, and every token to one of them (nothing is dropped); none
routed here; the share test of the model-configs guide (the four shares'
parts add up to the uncut layer); the layout's promises; and that the
device work does not depend on the routing (equal jaxprs and grids for two
routings, and no control flow that reads the routing).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import moe

N, T, H, F, E, HELD, K = 2, 64, 32, 16, 8, 2, 2


def _weights(seed=0, experts=HELD):
    rng = onp.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((N, T, H)), jnp.float32),
            jnp.asarray(rng.standard_normal((H, E)), jnp.float32),
            jnp.asarray(rng.standard_normal((experts, H, 2 * F)) * 0.2,
                        jnp.float32),
            jnp.asarray(rng.standard_normal((experts, F, H)) * 0.2,
                        jnp.float32))


def _dense(x, w_router, w_gate_up, w_down, first=0, bias=0.0):
    """The layer as the reference writes it: every held expert on every
    token, weighted by the token's renormalised top-k probability of that
    expert (zero where it is not among the k)."""
    probs = jax.nn.softmax(x @ w_router + bias, axis=-1)
    top, ids = jax.lax.top_k(probs, K)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(w_gate_up.shape[0]):
        gate, up = jnp.split(x @ w_gate_up[e], 2, axis=-1)
        w_e = jnp.sum(jnp.where(ids == first + e, top, 0.0), axis=-1)
        out = out + w_e[..., None] * ((jnp.maximum(gate, 0) * up)
                                      @ w_down[e])
    return out


def _layer(x, w_router, w_gate_up, w_down, first=0, bias=0.0, interpret=None):
    return moe.expert_layer(x, x @ w_router + bias, w_gate_up, w_down,
                            first_expert=first, top_k=K, interpret=interpret)


def _rows_here(x, w_router, first, bias, held=HELD):
    ids, _ = moe.route((x @ w_router + bias).reshape(-1, E), K)
    return int(jnp.sum((ids >= first) & (ids < first + held)))


ALL_TO_EXPERT_0 = jnp.array([30.0] + [0.0] * 7)
ALL_ELSEWHERE = jnp.array([0.0] * 4 + [30.0] * 4)
ALL_HERE = jnp.array([30.0] * 2 + [0.0] * 6)


@pytest.mark.parametrize('interpret', [None, True], ids=['einsum', 'kernel'])
@pytest.mark.parametrize('first,bias', [
    (0, 0.0), (2, 0.0), (0, ALL_TO_EXPERT_0), (0, ALL_HERE),
    (0, ALL_ELSEWHERE)],
    ids=['share0', 'share1', 'one_expert', 'all_here', 'none_here'])
def test_layer_matches_the_dense_loop(first, bias, interpret):
    """Forward and the gradients of x, the router and both expert
    weights. ``all_here`` sends every one of the 256 assignments to the
    two held experts, four times the balanced share; ``one_expert`` every
    token to expert 0. The layout holds them all: nothing is dropped."""
    args = _weights()
    assert moe.plan(N * T, HELD, K) == (256, 32, 10)
    here = _rows_here(args[0], args[1], first, bias)
    if bias is ALL_HERE:
        assert here == N * T * K
    if bias is ALL_TO_EXPERT_0:
        assert here >= N * T
    if bias is ALL_ELSEWHERE:
        assert here == 0

    def loss(fn):
        def f(*a):
            return jnp.sum(jnp.sin(fn(*a, first, bias)))
        return f
    got = _layer(*args, first, bias, interpret)
    want = _dense(*args, first, bias)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    g_got = jax.grad(loss(lambda *a: _layer(*a, interpret=interpret)),
                     argnums=(0, 1, 2, 3))(*args)
    g_want = jax.grad(loss(_dense), argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(('x', 'router', 'gate_up', 'down'), g_got, g_want):
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: experts 0-1, 2-3, 4-5 and 6-7 on four
    chips (the tests' share), each routing over all eight and computing its own part; the
    parts add up to the layer that holds all eight."""
    x, w_router, w_gate_up, w_down = _weights(seed=1, experts=E)
    whole = _dense(x, w_router, w_gate_up, w_down)
    parts = [_layer(x, w_router, w_gate_up[first:first + HELD],
                    w_down[first:first + HELD], first)
             for first in range(0, E, HELD)]
    onp.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)
    # and the uncut layer through the same code
    onp.testing.assert_allclose(
        moe.expert_layer(x, x @ w_router, w_gate_up, w_down, top_k=K),
        whole, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('sizes', [
    [0, 0, 0, 0], [33, 0, 1, 64], [192, 0, 0, 0], [0, 0, 0, 192],
    [48, 48, 48, 48], [191, 1, 0, 0], [1, 1, 1, 189]])
def test_the_layout(sizes):
    """Experts in order on whole tiles, every expert at least one tile
    (its weight gradient is written), each expert's tiles enough for its
    rows, whatever the sizes add up to within rows = tiles - held tiles."""
    tile, tiles = 32, 10
    tile_expert, pad_start = (onp.asarray(a) for a in moe._layout(
        jnp.asarray(sizes, jnp.int32), tile, tiles))
    assert tile_expert.shape == (tiles,)
    assert (onp.diff(tile_expert) >= 0).all()
    assert set(tile_expert) == {0, 1, 2, 3}
    assert pad_start.tolist() == [int(onp.argmax(tile_expert == e)) * tile
                                  for e in range(4)]
    owned = onp.bincount(tile_expert, minlength=4) * tile
    assert (owned >= onp.asarray(sizes)).all()


@pytest.mark.parametrize('bias', [0.0, ALL_HERE, ALL_TO_EXPERT_0],
                         ids=['balanced', 'all_here', 'one_expert'])
def test_every_assignment_to_a_held_expert_has_one_slot(bias):
    """Each at a slot of its expert's tiles that reads its token; the
    assignments to experts elsewhere at none."""
    x, w_router, _, _ = _weights(seed=2)
    ids, _ = moe.route((x @ w_router + bias).reshape(-1, E), K)
    rows, tile, tiles = moe.plan(N * T, HELD, K)
    key, order, rank, sizes = moe._sorted_assignments(ids, 0, HELD)
    tile_expert, src, live, pos = (onp.asarray(a) for a in moe._slots(
        key, order, rank, sizes, K, tile, tiles))
    key = onp.asarray(key)
    hit = pos < tile * tiles
    assert (hit == (key < HELD)).all()
    assert len(set(pos[hit])) == hit.sum()
    assert (live[pos[hit]] == onp.flatnonzero(hit)).all()
    assert (src[pos[hit]] == onp.flatnonzero(hit) // K).all()
    assert (live >= 0).sum() == hit.sum() == int(onp.asarray(sizes).sum())
    # a live slot's expert is its tile's
    owner = tile_expert.repeat(tile)
    assert (key[live[live >= 0]] == owner[live >= 0]).all()


def _kernel_calls(jaxpr, outside=None):
    """[(name, grid)] of the pallas_calls of a jaxpr; ``outside`` collects
    the names of the primitives that are not inside a kernel."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == 'pallas_call':
                found.append((eqn.params['name'],
                              tuple(eqn.params['grid_mapping'].grid)))
                continue
            if outside is not None:
                outside.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, 'jaxpr', sub)
                    if hasattr(inner, 'eqns'):
                        walk(inner)
    walk(jaxpr)
    return found


def test_device_work_does_not_depend_on_the_routing():
    """The program is traced from shapes alone and has no control flow:
    two routings run the same instructions -- the same jaxpr, the same
    kernels on the same grids (rows / tile + held row tiles) -- so a
    step's device time cannot follow how many assignments land here."""
    x, w_router, w_gate_up, w_down = _weights()

    def step(x, logits):
        return jax.grad(lambda x: jnp.sum(moe.expert_layer(
            x, logits, w_gate_up, w_down, top_k=K, interpret=True)))(x)
    balanced = jax.make_jaxpr(step)(x, x @ w_router)
    skewed = jax.make_jaxpr(step)(x, x @ w_router + ALL_TO_EXPERT_0)
    assert str(balanced) == str(skewed)
    outside = set()
    calls = _kernel_calls(balanced.jaxpr, outside)
    rows, tile, tiles = moe.plan(N * T, HELD, K)
    assert tiles == rows // tile + HELD
    assert len(calls) == 6      # gate|up, down; their dx and dw
    assert {name for name, _ in calls} == {'mxtpu_grouped_matmul'}
    assert {grid[-1] for _, grid in calls} == {tiles}
    assert not outside & {'cond', 'while', 'scan'}


@pytest.mark.parametrize('transposed', [False, True])
def test_the_kernels_match_the_einsums(transposed):
    """mxtpu_grouped_matmul through the interpreter against the einsum
    path, and its weight gradient; bf16 operands, float32 accumulation."""
    rng = onp.random.default_rng(3)
    tile, experts = 16, 3
    tile_expert = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    M, K_, N_ = tile * 6, 128, 256
    x = jnp.asarray(rng.standard_normal((M, K_)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal(
        (experts, N_, K_) if transposed else (experts, K_, N_)), jnp.bfloat16)
    got = moe.grouped_matmul(x, w, tile_expert, tile, transposed, True)
    want = moe.grouped_matmul(x, w, tile_expert, tile, transposed, None)
    onp.testing.assert_array_equal(onp.asarray(got, onp.float32),
                                   onp.asarray(want, onp.float32))
    dy = jnp.asarray(rng.standard_normal((M, N_)), jnp.bfloat16)
    onp.testing.assert_allclose(
        onp.asarray(moe.grouped_matmul_dw(x, dy, tile_expert, tile, experts,
                                          True), onp.float32),
        onp.asarray(moe.grouped_matmul_dw(x, dy, tile_expert, tile, experts,
                                          None), onp.float32),
        rtol=1e-2, atol=1e-2)


def test_builds_and_routes_are_counted():
    before, routes = dict(moe.builds), dict(moe.route_counts)
    x, w_router, w_gate_up, w_down = _weights()
    _layer(x, w_router, w_gate_up, w_down)
    _layer(x, w_router, w_gate_up, w_down, interpret=True)
    key = (E, HELD, K, 256, 32)
    assert moe.builds[key] - before.get(key, 0) == 2
    assert moe.route_counts['xla'] == routes['xla'] + 1
    assert moe.route_counts['pallas'] == routes['pallas'] + 1


def test_the_cells_plan():
    """smallthinker_21b.t8192: 8192 tokens, 6 experts a token, 16 held:
    49 152 rows -- every assignment, four times the balanced 12 288 -- in
    192 tiles of 256 and 16 more for the padding."""
    assert moe.plan(8192, 16, 6) == (49152, 256, 208)
