"""One share of a sparse expert layer (ops/moe.py).

What is held here: router, dispatch and combine against a dense loop over
the held experts in float32, forward and gradients, the einsum path and
the Pallas kernels through the interpreter; every assignment sent to the
held experts, and every token to one of them (nothing is dropped); none
routed here; the share test of the model-configs guide (the four shares'
parts add up to the uncut layer); the layout's promises; that the program
does not depend on the routing (equal jaxprs and grids for two routings,
and no control flow that reads the routing); and that the kernels stop at
the live tiles: with every row of the tiles past them NaN before the
kernels run, the layer and its gradients are finite and still right.
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


def _nan_past_live(a, n_live, tile):
    """``a`` with every row of the tiles at or past ``n_live`` NaN."""
    dead = jnp.arange(a.shape[0]) >= n_live[0] * tile
    return jnp.where(dead[:, None], jnp.nan, a)


def poison_dead_rows(monkeypatch):
    """The kernels with the row operands' dead tiles NaN before they run.
    The interpreter leaves an output block no grid step computes NaN too,
    so a dead row that reached a result would show there."""
    gmm, tgmm = moe._gmm_pallas, moe._tgmm_pallas

    def poisoned_gmm(x, w, tile_expert, n_live, tile, *rest):
        return gmm(_nan_past_live(x, n_live, tile), w, tile_expert, n_live,
                   tile, *rest)

    def poisoned_tgmm(x, dy, tile_expert, n_live, tile, *rest):
        return tgmm(_nan_past_live(x, n_live, tile),
                    _nan_past_live(dy, n_live, tile), tile_expert, n_live,
                    tile, *rest)
    monkeypatch.setattr(moe, '_gmm_pallas', poisoned_gmm)
    monkeypatch.setattr(moe, '_tgmm_pallas', poisoned_tgmm)


def interpret_for(mode, monkeypatch):
    """The layer's ``interpret`` for a test's mode: ``einsum``, ``kernel``
    (the interpreter) or ``poisoned`` (the interpreter, dead rows NaN)."""
    if mode == 'poisoned':
        poison_dead_rows(monkeypatch)
    return None if mode == 'einsum' else True


def live_tiles(ids, first, held, tile):
    """The tiles the grouped matmuls compute for a routing."""
    return moe.tiles_computed(moe._sorted_assignments(ids, first, held)[3],
                              tile)


MODES = ['einsum', 'kernel', 'poisoned']


def _rows_here(x, w_router, first, bias, held=HELD):
    ids, _ = moe.route((x @ w_router + bias).reshape(-1, E), K)
    return int(jnp.sum((ids >= first) & (ids < first + held)))


ALL_TO_EXPERT_0 = jnp.array([30.0] + [0.0] * 7)
ALL_ELSEWHERE = jnp.array([0.0] * 4 + [30.0] * 4)
ALL_HERE = jnp.array([30.0] * 2 + [0.0] * 6)


@pytest.mark.parametrize('mode', MODES)
@pytest.mark.parametrize('first,bias', [
    (0, 0.0), (2, 0.0), (0, ALL_TO_EXPERT_0), (0, ALL_HERE),
    (0, ALL_ELSEWHERE)],
    ids=['share0', 'share1', 'one_expert', 'all_here', 'none_here'])
def test_layer_matches_the_dense_loop(first, bias, mode, monkeypatch):
    """Forward and the gradients of x, the router and both expert
    weights. ``all_here`` sends every one of the 256 assignments to the
    two held experts, four times the balanced share, and the live tiles
    are as many as they can be; ``one_expert`` every token to expert 0;
    ``none_here`` leaves one live tile an expert. The layout holds them
    all: nothing is dropped. ``poisoned``: no row of a dead tile reaches
    a result, forward or backward."""
    interpret = interpret_for(mode, monkeypatch)
    args = _weights()
    rows, tile, tiles = moe.plan(N * T, HELD, K)
    assert (rows, tile, tiles) == (256, 32, 10)
    here = _rows_here(args[0], args[1], first, bias)
    ids, _ = moe.route((args[0] @ args[1] + bias).reshape(-1, E), K)
    n_live = live_tiles(ids, first, HELD, tile)
    assert HELD <= n_live < tiles
    if bias is ALL_HERE:
        assert here == N * T * K
        assert n_live >= rows // tile
    if bias is ALL_TO_EXPERT_0:
        assert here >= N * T
    if bias is ALL_ELSEWHERE:
        assert here == 0
        assert n_live == HELD

    def loss(fn):
        def f(*a):
            return jnp.sum(jnp.sin(fn(*a, first, bias)))
        return f
    got = _layer(*args, first, bias, interpret)
    want = _dense(*args, first, bias)
    assert onp.isfinite(got).all()
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    g_got = jax.grad(loss(lambda *a: _layer(*a, interpret=interpret)),
                     argnums=(0, 1, 2, 3))(*args)
    g_want = jax.grad(loss(_dense), argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(('x', 'router', 'gate_up', 'down'), g_got, g_want):
        assert onp.isfinite(a).all(), name
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
    rows, whatever the sizes add up to within rows = tiles - held tiles;
    and all of them among the first n_live tiles, the ones computed."""
    tile, tiles = 32, 10
    tile_expert, pad_start, n_live = (onp.asarray(a) for a in moe._layout(
        jnp.asarray(sizes, jnp.int32), tile, tiles))
    assert tile_expert.shape == (tiles,)
    assert (onp.diff(tile_expert) >= 0).all()
    assert pad_start.tolist() == [int(onp.argmax(tile_expert == e)) * tile
                                  for e in range(4)]
    n_live = int(n_live[0])
    assert n_live == moe.tiles_computed(sizes, tile)
    assert n_live == sum(max(1, -(-s // tile)) for s in sizes)
    assert 4 <= n_live <= tiles
    assert (tile_expert[n_live - 1:] == 3).all()
    live = tile_expert[:n_live]
    assert set(live) == {0, 1, 2, 3}
    owned = onp.bincount(live, minlength=4) * tile
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
    tile_expert, n_live, src, live, pos = (onp.asarray(a) for a in moe._slots(
        key, order, rank, sizes, K, tile, tiles))
    key = onp.asarray(key)
    hit = pos < tile * tiles
    # no assignment lies in a tile the kernels skip
    assert (pos[hit] < int(n_live[0]) * tile).all()
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


def test_the_program_does_not_depend_on_the_routing():
    """The program is traced from shapes alone and has no control flow:
    two routings run the same instructions -- the same jaxpr, the same
    kernels on the same grids (rows / tile + held row tiles). What
    follows the routing is data: the live tile count, which bounds the
    grid steps that do any work."""
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


@pytest.mark.parametrize('n_live', [6, 4], ids=['full', 'two_dead'])
@pytest.mark.parametrize('transposed', [False, True])
def test_the_kernels_match_the_einsums(transposed, n_live):
    """mxtpu_grouped_matmul through the interpreter against the einsum
    path, and its weight gradient; bf16 operands, float32 accumulation.
    With tiles 4 and 5 dead, their rows NaN, the live rows are the
    einsums', the dead ones are never written (the interpreter's NaN),
    and the last expert's gradient is its one live tile's, written at
    the live end."""
    rng = onp.random.default_rng(3)
    tile, experts = 16, 3
    tile_expert = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    live = jnp.asarray([n_live], jnp.int32)
    M, K_, N_ = tile * 6, 128, 256
    x = jnp.asarray(rng.standard_normal((M, K_)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal(
        (experts, N_, K_) if transposed else (experts, K_, N_)), jnp.bfloat16)
    got = moe.grouped_matmul(_nan_past_live(x, live, tile), w, tile_expert,
                             live, tile, transposed, True)
    want = moe.grouped_matmul(x, w, tile_expert, live, tile, transposed, None)
    rows = n_live * tile
    onp.testing.assert_array_equal(onp.asarray(got[:rows], onp.float32),
                                   onp.asarray(want[:rows], onp.float32))
    assert onp.isnan(onp.asarray(got[rows:], onp.float32)).all()
    dy = jnp.asarray(rng.standard_normal((M, N_)), jnp.bfloat16)
    got = moe.grouped_matmul_dw(_nan_past_live(x, live, tile),
                                _nan_past_live(dy, live, tile), tile_expert,
                                live, tile, experts, True)
    # the einsums over the live rows alone
    keep = (jnp.arange(M) < rows)[:, None]
    want = moe.grouped_matmul_dw(jnp.where(keep, x, 0), dy, tile_expert,
                                 live, tile, experts, None)
    onp.testing.assert_allclose(onp.asarray(got, onp.float32),
                                onp.asarray(want, onp.float32),
                                rtol=1e-2, atol=1e-2)
    last = onp.asarray(x[3 * tile:4 * tile], onp.float32).T \
        @ onp.asarray(dy[3 * tile:4 * tile], onp.float32)
    if n_live == 4:
        onp.testing.assert_allclose(onp.asarray(got[2], onp.float32), last,
                                    rtol=2e-2, atol=2e-1)


def test_builds_and_routes_are_counted():
    """A layer and its gradient through the kernels build the grouped
    matmul four times and its weight gradient twice, each bounded by the
    live tiles; the einsums build none."""
    before, routes = dict(moe.builds), dict(moe.route_counts)
    live_before = dict(moe.live_tile_builds)
    x, w_router, w_gate_up, w_down = _weights()
    _layer(x, w_router, w_gate_up, w_down)
    jax.grad(lambda x: jnp.sum(_layer(x, w_router, w_gate_up, w_down,
                                      interpret=True)))(x)
    key = (E, HELD, K, 256, 32)
    assert moe.builds[key] - before.get(key, 0) == 2
    assert moe.route_counts['xla'] == routes['xla'] + 1
    assert moe.route_counts['pallas'] == routes['pallas'] + 1
    added = {k: v - live_before.get(k, 0)
             for k, v in moe.live_tile_builds.items()
             if v != live_before.get(k, 0)}
    assert added == {('gmm', 10, 32): 4, ('tgmm', 10, 32): 2}


def test_the_cells_plan():
    """smallthinker_21b.t8192: 8192 tokens, 6 experts a token, 16 held:
    49 152 rows -- every assignment, four times the balanced 12 288 -- in
    192 tiles of 256 and 16 more for the padding."""
    assert moe.plan(8192, 16, 6) == (49152, 256, 208)


# ---------------------------------------------------------------------------
# the squared-ReLU expert that is not gated, the routed scaling factor and
# the shared expert (Nemotron-H's expert layer)
# ---------------------------------------------------------------------------

SCALE = 2.5


def _relu2_weights(experts, held, seed=0):
    rng = onp.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((N, T, H)), jnp.float32),
            jnp.asarray(rng.standard_normal((H, experts)), jnp.float32),
            jnp.asarray(rng.standard_normal((held, H, F)) * 0.2, jnp.float32),
            jnp.asarray(rng.standard_normal((held, F, H)) * 0.2, jnp.float32))


def _relu2_dense(x, w_router, w_up, w_down, first, bias):
    """Every held expert on every token, relu(x W_up)^2 W_down, weighted
    by the token's renormalised sigmoid score of it times ``SCALE`` (zero
    where it is not among the k of sigmoid + bias)."""
    scores = jax.nn.sigmoid(x @ w_router)
    _, ids = jax.lax.top_k(scores + bias, K)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    top = SCALE * top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(w_up.shape[0]):
        w_e = jnp.sum(jnp.where(ids == first + e, top, 0.0), axis=-1)
        out = out + w_e[..., None] * (jnp.square(jnp.maximum(x @ w_up[e], 0))
                                      @ w_down[e])
    return out


def _relu2_layer(x, w_router, w_up, w_down, first, bias, interpret=None):
    return moe.expert_layer(x, x @ w_router, w_up, w_down, first_expert=first,
                            top_k=K, interpret=interpret, scoring='sigmoid',
                            gate='relu2', bias=bias, scale=SCALE)


@pytest.mark.parametrize('mode', MODES)
@pytest.mark.parametrize('first', [0, 2])
def test_the_squared_relu_layer_matches_the_dense_loop(first, mode,
                                                       monkeypatch):
    """Up (held, h, f) and down, no gate, the weights scaled: forward and
    the gradients of x, the router and both expert weights, through the
    einsums and through the kernels in the interpreter, finite with the
    kernels' dead rows NaN (``poisoned``)."""
    interpret = interpret_for(mode, monkeypatch)
    args = _relu2_weights(E, HELD)
    bias = jnp.asarray(onp.random.default_rng(7).standard_normal(E) * 0.1,
                       jnp.float32)
    got = _relu2_layer(*args, first, bias, interpret)
    assert onp.isfinite(got).all()
    onp.testing.assert_allclose(got, _relu2_dense(*args, first, bias),
                                rtol=1e-5, atol=1e-5)

    def loss(fn, **kw):
        return lambda *a: jnp.sum(jnp.sin(fn(*a, first, bias, **kw)))
    got = jax.grad(loss(_relu2_layer, interpret=interpret),
                   argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss(_relu2_dense), argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(('x', 'router', 'up', 'down'), got, want):
        assert onp.isfinite(a).all(), name
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


def test_the_scale_multiplies_the_renormalised_weights():
    logits = jnp.asarray(onp.random.default_rng(8).standard_normal((5, E)),
                         jnp.float32)
    ids, plain = moe.route(logits, K, 'sigmoid')
    ids2, scaled = moe.route(logits, K, 'sigmoid', scale=SCALE)
    assert onp.array_equal(ids, ids2)
    onp.testing.assert_allclose(jnp.sum(plain, -1), 1.0, rtol=1e-6)
    onp.testing.assert_allclose(scaled, SCALE * plain, rtol=1e-6)


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """model-configs section 4 with a shared expert: four shares of two
    experts each compute their routed part; the shared expert, which every
    chip computes alike, is counted once; together they are the layer that
    holds all eight experts and the shared one."""
    x, w_router, w_up, w_down = _relu2_weights(E, E, seed=9)
    rng = onp.random.default_rng(10)
    w_sup = jnp.asarray(rng.standard_normal((H, 24)) * 0.2, jnp.float32)
    w_sdown = jnp.asarray(rng.standard_normal((24, H)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(E) * 0.1, jnp.float32)
    shared = jnp.square(jnp.maximum(x @ w_sup, 0)) @ w_sdown
    whole = _relu2_dense(x, w_router, w_up, w_down, 0, bias) + shared
    parts = [_relu2_layer(x, w_router, w_up[first:first + HELD],
                          w_down[first:first + HELD], first, bias)
             for first in range(0, E, HELD)]
    onp.testing.assert_allclose(
        sum(parts) + moe.shared_expert(x, w_sup, w_sdown), whole, rtol=1e-5,
        atol=1e-5)


def test_the_squared_relu_gate_and_the_scale_are_counted_apart():
    before = dict(moe.builds)
    args = _relu2_weights(E, HELD)
    _relu2_layer(*args, 0, jnp.zeros(E))
    key = (E, HELD, K, 256, 32, 'sigmoid', 'relu2', SCALE)
    assert moe.builds[key] - before.get(key, 0) == 1


# ---------------------------------------------------------------------------
# an expert weight whose minor dimension is no multiple of 128 lanes is read
# through its swap (ops/moe.py:_swapped): the same products, the same
# results
# ---------------------------------------------------------------------------

WIDE_H = 256


def _wide_weights(gate, width):
    """x, logits and the held experts' weights at hidden 256: the up (or
    gate|up) weight (HELD, 256, 192), whose minor dimension is no multiple
    of 128 and whose other one is, for ``width`` 192 (96 gated); the down
    weight (HELD, width, 256). x is half as wide as the other tests' so
    that, over sums of 256 and 192 terms, the gradients stay of order one
    as theirs do, where the tolerances below were set."""
    rng = onp.random.default_rng(11)
    up = width * (1 if gate == 'relu2' else 2)
    x = jnp.asarray(rng.standard_normal((N, T, WIDE_H)) * 0.5, jnp.float32)
    w_router = jnp.asarray(rng.standard_normal((WIDE_H, E)) * 0.05,
                           jnp.float32)
    return (x, x @ w_router,
            jnp.asarray(rng.standard_normal((HELD, WIDE_H, up)) * 0.05,
                        jnp.float32),
            jnp.asarray(rng.standard_normal((HELD, width, WIDE_H)) * 0.05,
                        jnp.float32))


def _reads_added(before):
    return {k: v - before.get(k, 0) for k, v in moe.weight_reads.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize('mode', ['einsum', 'kernel'])
@pytest.mark.parametrize('gate,width', [('relu2', 192), ('silu', 96)])
def test_the_swapped_read_matches_the_stored_one(gate, width, mode,
                                                 monkeypatch):
    """An up weight (2, 256, 192) is read through its swap, the down weight
    (2, width, 256) as stored: the output and the gradients of x, the
    router's logits and both weights equal those of the layer that reads
    both as stored (``_swapped`` patched false), through the einsums and
    through the kernels in the interpreter. The counter says which read
    each weight took; an aligned up weight (2, 256, 128) is read stored."""
    interpret = interpret_for(mode, monkeypatch)
    args = _wide_weights(gate, width)
    kw = dict(top_k=K, interpret=interpret, gate=gate)
    if gate == 'relu2':
        kw.update(scoring='sigmoid', scale=SCALE)

    def loss(*a):
        return jnp.sum(jnp.sin(moe.expert_layer(*a, **kw)))
    grads = jax.grad(loss, argnums=(0, 1, 2, 3))
    before = dict(moe.weight_reads)
    got = moe.expert_layer(*args, **kw)
    got_g = grads(*args)
    assert _reads_added(before) == {('up', 'swapped'): 2, ('down', 'stored'): 2}
    with monkeypatch.context() as m:
        m.setattr(moe, '_swapped', lambda w: False)
        want = moe.expert_layer(*args, **kw)
        want_g = grads(*args)
    assert onp.isfinite(got).all()
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip(('x', 'logits', 'up', 'down'), got_g, want_g):
        assert a.shape == b.shape, name
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
    aligned = _wide_weights('relu2', 128)
    before = dict(moe.weight_reads)
    jax.eval_shape(lambda *a: moe.expert_layer(*a, top_k=K, gate='relu2'),
                   *aligned)
    assert _reads_added(before) == {('up', 'stored'): 1, ('down', 'stored'): 1}
