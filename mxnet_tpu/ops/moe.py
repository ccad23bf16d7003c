"""Sparse expert layer for one share of an expert-parallel group.

The layer is told which experts it holds (``first_expert`` and the leading
dimension of its weights), routes every token over all the experts of the
model by one of two scoring rules (:func:`route`: ``softmax`` over the
router's logits, or ``sigmoid`` of each logit with a balancing bias that
enters the choice alone; top-k, the k scores renormalised and, where the
model says so, scaled), and computes the part of the result that its own
experts give: ``sum over the held experts among a token's k of w *
expert(x)``, an expert being a gated feed-forward with a ReGLU or a SiLU
gate, or an ungated one whose activation is the squared ReLU (``GATES``).
What the absent experts would add is their chips' to add; nothing here
stands in for them or for their traffic. A shared expert, which every
token takes beside its k, is every chip's alike (:func:`shared_expert`).

No assignment to a held expert is ever dropped, and the program has one
shape whatever the router decides: a token's k assignments may all land
here, so the layout holds ``tokens * k`` rows, always (:func:`plan`). A
smaller layout with a ``lax.cond`` for what overflows it was built first
and measured on the chip (PERF.md section 6, SmallThinker's entry): at
1.5 times the balanced share the overflow ran in the traced run and two
other seeds' steps read 6 % apart, because a randomly initialised router
sends most tokens of a deep layer to the same few experts (395 to 31 931
rows here where balance is 12 288), and training without a balancing
loss moves it further.

* the ``tokens * k`` assignments are sorted by a key that puts the held
  experts first, in expert order (one stable sort, and its inverse);
* the sorted rows are laid out in row tiles of ``tile`` rows, every
  expert's rows starting on a tile boundary and every expert owning at
  least one tile (:func:`_layout`): ``tokens * k / tile + held`` tiles
  hold any routing. Slots past an expert's rows compute on some token's
  row and are read by nobody;
* the tiles that hold some expert's rows come first, ``n_live`` of them
  (``held <= n_live <= tiles``, :func:`tiles_computed`), and the grouped
  matmuls stop there: a grid step past them issues no copy and computes
  nothing, so the device's time follows the rows that landed and not the
  layout's size. The rows of those dead tiles are undefined in every
  product, and nothing reads them;
* the rows are gathered into that layout, and the result is combined by
  gathering each token's k slots back: no scatter, forward or backward.
  A miss is a select of zero, not a zero weight on a clamped index, since
  the clamped index is the last slot, which lies in a dead tile unless
  the layout is full, and 0 * NaN is NaN.

The grouped matmul is a Pallas kernel, ``mxtpu_grouped_matmul``: one row
tile a grid step against the weights of the tile's expert, read from a
scalar-prefetched table; the two backward products are the same kernel on
the transposed weights and a second one that adds a group's tiles into
its weight gradient. Off the TPU the same products are plain einsums over
the tiles. An expert weight whose minor dimension is no multiple of 128
lanes, and whose other matrix dimension is, is read through its swap of
those two (:func:`_swapped`): the device stores such an array with the
other dimension in the lanes, and the swap is a bitcast of that layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes as _scopes
from .pallas_attention import pallas_available

# Rows of one grid step. 256 rows against a (2560, 768) bf16 weight block
# is 1 GFLOP a step, far over the per-step overhead, and the padding of
# sixteen experts to whole tiles is 16 * 256 rows on 49 152.
ROW_TILE = 256
# A weight block (and the float32 accumulator of a weight gradient) is
# kept under this, so that blocks double-buffer inside the limit below.
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20

# the scoring rules of :func:`route` and the gates between the grouped
# matmuls: 'relu2' is no gate, relu(x W_up)^2, its up matrix (h, f) where
# a gated expert's gate and up are (h, 2f)
SCORINGS = ('softmax', 'sigmoid')
GATES = ('reglu', 'silu', 'relu2')

# trace-time telemetry, beside ops.attention.route_counts: how the
# grouped matmuls were computed, the scoring rules and gates the layers
# were built with, and {(experts, held, top-k, rows, tile): layers built}
# -- with ``(scoring, gate)`` appended to the key where they are not
# softmax and ReGLU, and the routed scaling factor where it is not 1;
# and {(kernel kind, tiles a layer, tile): builds} of the grouped-matmul
# kernels bounded by the live tile count, the kind
# ``gmm`` (forward, dx, d_hidden) or ``tgmm`` (a weight gradient); and
# {(weight, read): layers built}, the weight ``up`` (gate|up, or up alone)
# or ``down``, read ``swapped`` or ``stored`` (:func:`_swapped`)
route_counts = {'pallas': 0, 'xla': 0}
builds = {}
live_tile_builds = {}
weight_reads = {}


def plan(tokens, held, top_k):
    """(rows, tile, tiles): the rows the layout is sized for -- every
    assignment, since all of them may land here --, the rows of a tile,
    and the tiles of the layout: the rows' and one more an expert, for
    the padding to tile boundaries. One size, nothing dropped; the
    grouped matmuls compute only the leading tiles that hold rows
    (:func:`tiles_computed`), and the rows of the others are undefined."""
    rows = tokens * top_k
    tile = min(ROW_TILE, max(16, 16 * math.ceil(rows / held / 64)))
    return rows, tile, math.ceil(rows / tile) + held


def router_logits(x, weight):
    """x @ weight^T in float32, whatever the operands' dtype: ``weight``
    is (experts, h) as Dense stores it. The top-k below is discrete, and a
    logit rounded to bf16 (8 bits: 0.004 near 1) would move as many
    choices as all the roundings before it do together."""
    return jnp.einsum('...h,eh->...e', x, weight,
                      preferred_element_type=jnp.float32)


def route(logits, top_k, scoring='softmax', bias=None, scale=1.0):
    """(expert ids (T, k) int32, weights (T, k) float32) of each token.
    ``softmax``: softmax over all the experts, the k largest, renormalised
    to sum 1. ``sigmoid``: s = sigmoid(logit) of each expert, the k
    largest of s + ``bias`` (E,) chosen, and their s -- not s + bias --
    renormalised to sum 1: the bias moves the choice alone (DeepSeek-V3's
    auxiliary-loss-free balancing, arXiv:2412.19437 section 2.1.2). The
    renormalised weights are then multiplied by ``scale``, the routed
    scaling factor (DeepSeek-V3's, Nemotron-H's 2.5)."""
    logits = logits.astype(jnp.float32)
    if scoring == 'softmax':
        weights, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    elif scoring == 'sigmoid':
        scores = jax.nn.sigmoid(logits)
        chosen = scores if bias is None \
            else scores + lax.stop_gradient(bias.astype(jnp.float32))
        _, ids = lax.top_k(chosen, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        raise ValueError(f"scoring {scoring!r}: one of {SCORINGS}")
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1:
        weights = weights * scale
    return ids.astype(jnp.int32), weights


def assignment_counts(ids, experts):
    """(E,) int32: the assignments of ``ids`` (any shape, expert ids) to
    each of the model's ``experts``: what a step's balancing bias moves
    by, and the load a step puts on each expert."""
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(experts, dtype=ids.dtype),
                   axis=0, dtype=jnp.int32)


def next_bias(bias, counts, rate):
    """The balancing bias after a step: b + rate * sign(mean(n) - n) over
    the ``counts`` n of every expert of the model (float32): an expert
    that took more than its share is chosen a little less often."""
    counts = counts.astype(jnp.float32)
    return bias.astype(jnp.float32) \
        + rate * jnp.sign(jnp.mean(counts) - counts)


# ---------------------------------------------------------------------------
# where each assignment goes: small integer vectors, no gradient
# ---------------------------------------------------------------------------

def _sorted_assignments(ids, first, held):
    """The assignments (flat index t * k + c) sorted by held expert, the
    ones to experts elsewhere last: (key (A,), order (A,), rank (A,),
    sizes (held,)) -- ``key`` the local expert or ``held``, ``order`` the
    assignment at each sorted place, ``rank`` an assignment's place among
    those of its expert, ``sizes`` the rows of each held expert."""
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    place = jnp.arange(key.size, dtype=jnp.int32)
    _, order = lax.sort((key, place), num_keys=1, is_stable=True)
    _, inverse = lax.sort((order, place), num_keys=1)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    start = jnp.cumsum(sizes) - sizes
    rank = inverse - jnp.take(jnp.append(start, 0), key)
    return key, order, rank, sizes


def _expert_tiles(sizes, tile):
    """The tiles each expert owns: its rows' and at least one."""
    return jnp.maximum(1, -(-sizes // tile))


def tiles_computed(sizes, tile):
    """The tiles of a layer that the grouped matmuls compute when the
    held experts take ``sizes`` rows (one number an expert): each
    expert's rows on whole tiles, and at least one tile an expert."""
    return int(jnp.sum(_expert_tiles(jnp.asarray(sizes, jnp.int32), tile)))


def _layout(sizes, tile, tiles):
    """(tile_expert (tiles,), pad_start (held,), n_live (1,)): the experts
    in order, each on whole tiles and each on at least one (so that a
    weight gradient is written for every expert); the tiles after the
    last expert's are its too, and the first ``n_live`` tiles are the
    ones that hold an expert's rows. sum(sizes) <= (tiles - held) * tile."""
    held = sizes.shape[0]
    end = jnp.cumsum(_expert_tiles(sizes, tile))
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(tiles, dtype=jnp.int32)[:, None] >= end,
                axis=-1, dtype=jnp.int32), held - 1)
    return tile_expert, (end - jnp.diff(end, prepend=0)) * tile, end[-1:]


def _slots(key, order, rank, sizes, top_k, tile, tiles):
    """The gather indices: (tile_expert, n_live, src (slots,) the token
    each slot reads, live (slots,) the assignment whose row it is or -1,
    pos (A,) each assignment's slot or ``slots`` for one to an expert
    elsewhere)."""
    slots = tile * tiles
    tile_expert, pad_start, n_live = _layout(sizes, tile, tiles)
    start = jnp.cumsum(sizes) - sizes
    g = jnp.repeat(tile_expert, tile)
    j = jnp.arange(slots, dtype=jnp.int32) - pad_start[g]
    live = jnp.where(j < sizes[g],
                     order[jnp.minimum(start[g] + j, order.size - 1)], -1)
    src = jnp.maximum(live, 0) // top_k
    # an assignment elsewhere has key == held: it gets no slot
    pos = jnp.where(key < sizes.shape[0],
                    jnp.append(pad_start, 0)[key] + rank, slots)
    return tile_expert, n_live, src, live, pos.astype(jnp.int32)


# ---------------------------------------------------------------------------
# dispatch and combine: gathers both ways
# ---------------------------------------------------------------------------

def _no_grad(x):
    return onp.zeros(x.shape, jax.dtypes.float0)


def _gather_sum(rows, pos, weights=None):
    """sum_c weights[t, c] * rows[pos[t, c]] (weights None: 1), a miss
    (pos == len(rows)) reading zero; added up in float32 a column of
    ``pos`` at a time, so that no (T, k, h) array exists. The miss is a
    select on a clamped index: ``take(mode='fill')`` becomes a gather that
    XLA:TPU strips of its op_name, and a trace then cannot say whose it
    is; and the clamped row is the last slot, undefined where its tile is
    past the live ones, so a zero weight on it could read 0 * NaN."""
    hit = pos < rows.shape[0]
    total = 0.0
    for c in range(pos.shape[1]):
        picked = jnp.where(
            hit[:, c, None],
            jnp.take(rows, pos[:, c], axis=0, mode='clip').astype(jnp.float32),
            0.0)
        if weights is not None:
            picked = weights[:, c, None].astype(jnp.float32) * picked
        total = total + picked
    return total.astype(rows.dtype)


def _act(gate, kind):
    """The gate's activation, float32 or the operands' dtype."""
    return jnp.maximum(gate, 0) if kind == 'reglu' else jax.nn.silu(gate)


def _silu_grad(gate):
    """d silu / d gate = s (1 + g (1 - s)), s = sigmoid(g)."""
    s = jax.nn.sigmoid(gate)
    return s * (1 + gate * (1 - s))


def _glu(gate_up, kind):
    if kind == 'relu2':
        return jnp.square(jnp.maximum(gate_up, 0))
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return _act(gate, kind) * up


# ---------------------------------------------------------------------------
# the grouped matmul
# ---------------------------------------------------------------------------

def _col_tile(n, rows, itemsize):
    """Columns of one block of an (rows, n) operand: the largest divisor
    of ``n`` that is a multiple of 128 lanes and keeps the block under
    ``_BLOCK_BYTES``; the whole ``n`` where it has no such divisor."""
    fits = [d for d in range(128, n + 1, 128)
            if n % d == 0 and rows * d * itemsize <= _BLOCK_BYTES]
    return max(fits) if fits else n


def _live_tile(i, nl):
    """The row tile grid step ``i`` maps its blocks to: its own up to the
    last live tile, that one after it, so that a dead step's blocks are
    the ones already in VMEM and the pipeline issues no copy."""
    return jnp.minimum(i, nl[0] - 1)


def _count_build(kind, tiles, tile):
    key = (kind, tiles, tile)
    live_tile_builds[key] = live_tile_builds.get(key, 0) + 1


def _gmm_kernel(te_ref, nl_ref, x_ref, w_ref, o_ref, *, transposed):
    """One row tile against its expert's weight block: o = x @ w, or
    x @ w^T where the weights are stored (out, in) for this product; a
    tile past the live ones is skipped, and its rows stay undefined."""
    contract = (((1,), (1,)), ((), ())) if transposed \
        else (((1,), (0,)), ((), ()))

    @pl.when(pl.program_id(1) < nl_ref[0])
    def _compute():
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], contract,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _gmm_pallas(x, w, tile_expert, n_live, tile, transposed, interpret):
    """(M, K) rows in tiles of ``tile``, tile i < n_live against
    w[tile_expert[i]]: (E, K, N), or (E, N, K) ``transposed``. Grid
    (column tiles, row tiles), the row tiles inner: consecutive tiles of
    one expert find its weight block already in VMEM, and the steps past
    the live tiles find every block there."""
    M, K = x.shape
    N = w.shape[1] if transposed else w.shape[2]
    tn = _col_tile(N, K, w.dtype.itemsize)
    _count_build('gmm', M // tile, tile)
    w_spec = pl.BlockSpec((1, tn, K), lambda n, i, te, nl: (te[i], n, 0)) \
        if transposed else \
        pl.BlockSpec((1, K, tn), lambda n, i, te, nl: (te[i], 0, n))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // tn, M // tile),
            in_specs=[pl.BlockSpec(
                (tile, K), lambda n, i, te, nl: (_live_tile(i, nl), 0)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda n, i, te, nl: (_live_tile(i, nl), n))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=_scopes.GROUPED_MATMUL,
    )(tile_expert, n_live, x, w)


def _tgmm_kernel(te_ref, nl_ref, x_ref, dy_ref, o_ref, acc_ref):
    """dw[e] = sum over the live tiles of expert e of x^T @ dy: the
    float32 accumulator is cleared at a group's first tile and written at
    its last, the last expert's at the last live tile. The groups are
    runs of the table, and every expert has one."""
    i, last = pl.program_id(1), nl_ref[0] - 1
    e = te_ref[i]

    @pl.when(i <= last)
    def _compute():
        @pl.when((i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != e))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((i == last) | (te_ref[jnp.minimum(i + 1, last)] != e))
        def _write():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _tgmm_pallas(x, dy, tile_expert, n_live, tile, experts, interpret,
                 tn=None):
    """The weight gradient of :func:`_gmm_pallas`: (E, K, N) from x (M, K)
    and dy (M, N), over the live tiles, in column blocks of ``tn``
    (default :func:`_col_tile`'s). Every column block sweeps the row
    tiles again, the dead ones too."""
    M, K = x.shape
    N = dy.shape[1]
    tn = tn or _col_tile(N, K, 4)
    _count_build('tgmm', M // tile, tile)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // tn, M // tile),
            in_specs=[pl.BlockSpec(
                (tile, K), lambda n, i, te, nl: (_live_tile(i, nl), 0)),
                pl.BlockSpec(
                (tile, tn), lambda n, i, te, nl: (_live_tile(i, nl), n))],
            out_specs=pl.BlockSpec((1, K, tn),
                                   lambda n, i, te, nl: (te[i], 0, n)),
            scratch_shapes=[pltpu.VMEM((K, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((experts, K, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=_scopes.GROUPED_MATMUL,
    )(tile_expert, n_live, x, dy)


def _gmm_xla(x, w, tile_expert, tile, transposed):
    tiles = x.reshape(-1, tile, x.shape[1])
    out = jnp.einsum('imn,ikn->imk' if transposed else 'imk,ikn->imn', tiles,
                     w[tile_expert], preferred_element_type=jnp.float32)
    return out.astype(x.dtype).reshape(x.shape[0], -1)


def _tgmm_xla(x, dy, tile_expert, tile, experts):
    each = jnp.einsum('imk,imn->ikn', x.reshape(-1, tile, x.shape[1]),
                      dy.reshape(-1, tile, dy.shape[1]),
                      preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(each, tile_expert, num_segments=experts
                               ).astype(x.dtype)


def _kernel_mode(interpret):
    """None: no kernel (einsums), off the TPU and unasked; else the
    ``interpret`` flag of the pallas_call."""
    if interpret is None and not pallas_available():
        return None
    return bool(interpret)


def grouped_matmul(x, w, tile_expert, n_live, tile, transposed=False,
                   interpret=None):
    """out[i-th row tile] = x[i-th row tile] @ w[tile_expert[i]], the
    weights (E, K, N), or @ w[...]^T with them (E, N, K) ``transposed``.
    ``tile_expert`` is non-decreasing; the kernel computes the first
    ``n_live`` (1,) row tiles and leaves the others' rows undefined, the
    einsums compute them all. ``interpret``: None compiles the kernel on a
    TPU and takes einsums elsewhere; True runs the kernel through the
    Pallas interpreter. No gradient of its own: the layer's backward
    (:func:`_experts_bwd`) is written out."""
    mode = _kernel_mode(interpret)
    if mode is None:
        return _gmm_xla(x, w, tile_expert, tile, transposed)
    return _gmm_pallas(x, w, tile_expert, n_live, tile, transposed, mode)


def grouped_matmul_dw(x, dy, tile_expert, n_live, tile, experts,
                      interpret=None, col_tile=None):
    """The weight gradient of ``grouped_matmul(x, w)`` for the cotangent
    ``dy``: (experts, K, N), expert e's the sum of x^T @ dy over its
    tiles, of the first ``n_live`` in the kernel. ``tile_expert`` names
    every expert at least once, within the first ``n_live`` tiles.
    ``col_tile``: the kernel's column block, if not its default."""
    mode = _kernel_mode(interpret)
    if mode is None:
        return _tgmm_xla(x, dy, tile_expert, tile, experts)
    return _tgmm_pallas(x, dy, tile_expert, n_live, tile, experts, mode,
                        col_tile)


def _swapped(w):
    """Whether the products read an expert weight (held, K, N) as its
    swap (held, N, K): where N, the minor dimension, is no multiple of 128
    lanes and K is. The device then lays the array out with K in the lanes
    (Nemotron-H's (8, 2688, 1856) up weights: ``{1,2,0}``), and the swap is
    a bitcast of that layout. Read as stored, the kernels take it
    row-major, and XLA lays the weight, its float32 master and both AdamW
    moments out again round every update and back after it."""
    return w.shape[2] % 128 != 0 and w.shape[1] % 128 == 0


def _product(x, w, tile_expert, n_live, tile, transposed, interpret):
    """:func:`grouped_matmul` of an expert weight (held, K, N) as the
    layer holds it, read through its swap where :func:`_swapped` says."""
    if _swapped(w):
        return grouped_matmul(x, jnp.swapaxes(w, 1, 2), tile_expert, n_live,
                              tile, not transposed, interpret)
    return grouped_matmul(x, w, tile_expert, n_live, tile, transposed,
                          interpret)


def _product_dw(x, dy, w, tile_expert, n_live, tile, interpret):
    """The gradient of w in ``_product(x, w)`` (not transposed) for the
    cotangent ``dy``, in w's shape: computed as its swap's where w is read
    swapped, so that it leaves in the layout w has. The swap keeps the
    stored read's one column block (N, unaligned, has no lane-aligned
    divisor), and so its grid of one sweep over the row tiles."""
    if _swapped(w):
        return jnp.swapaxes(grouped_matmul_dw(
            dy, x, tile_expert, n_live, tile, w.shape[0], interpret,
            col_tile=w.shape[1]), 1, 2)
    return grouped_matmul_dw(x, dy, tile_expert, n_live, tile, w.shape[0],
                             interpret)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _forward(x, weights, w_gate_up, w_down, slots, tile, interpret, gate):
    """(y, gate|up): gather the rows into the layout, gate and up in one
    grouped matmul, the gate (``GATES``), down, and each token's k slots
    weighted and added back. x (T, h), weights (T, k) float32, ``slots``
    what :func:`_slots` gives."""
    tile_expert, n_live, src, _live, pos = slots
    with jax.named_scope(_scopes.MOE_ROUTE):
        xs = jnp.take(x, src, axis=0, mode='clip')
    with jax.named_scope(_scopes.MOE_EXPERTS):
        gate_up = _product(xs, w_gate_up, tile_expert, n_live, tile, False,
                           interpret)
        ys = _product(_glu(gate_up, gate), w_down, tile_expert, n_live, tile,
                      False, interpret)
    with jax.named_scope(_scopes.MOE_ROUTE):
        return _gather_sum(ys, pos.reshape(weights.shape), weights), gate_up


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _experts(x, weights, w_gate_up, w_down, slots, tile, interpret, gate):
    """The held experts' part of the result, (T, h). Its backward is
    written out, because every transpose of a gather is here a gather
    through the inverse indices (autodiff would scatter-add), and so
    that only gate|up has to be kept: the gathered rows and the gate are
    computed again, the down projection's result is not needed."""
    return _forward(x, weights, w_gate_up, w_down, slots, tile, interpret,
                    gate)[0]


def _experts_fwd(x, weights, w_gate_up, w_down, slots, tile, interpret,
                 gate):
    y, gate_up = _forward(x, weights, w_gate_up, w_down, slots, tile,
                          interpret, gate)
    return y, (x, weights, w_gate_up, w_down, slots, gate_up)


def _experts_bwd(tile, interpret, gate_kind, res, dy):
    x, weights, w_gate_up, w_down, slots, gate_up = res
    tile_expert, n_live, src, live, pos = slots
    pos = pos.reshape(weights.shape)
    f32 = jnp.float32
    with jax.named_scope(_scopes.MOE_ROUTE):
        # a slot's weight is its assignment's; a slot nobody owns has none
        slot_w = jnp.where(live >= 0,
                           weights.reshape(-1)[jnp.maximum(live, 0)],
                           0.0)[:, None]
        xs = jnp.take(x, src, axis=0, mode='clip')
        dy_slots = jnp.take(dy, src, axis=0, mode='clip')
    with jax.named_scope(_scopes.MOE_EXPERTS):
        if gate_kind == 'relu2':
            relu = jnp.maximum(gate_up.astype(f32), 0)
            hidden = relu * relu
        else:
            gate, up = (t.astype(f32) for t in jnp.split(gate_up, 2, axis=-1))
            hidden = _act(gate, gate_kind) * up
        # y = sum_c w_c * (hidden @ down): the weight scales a row, so it
        # is put on the narrow side of both products
        d_hidden = _product(dy_slots, w_down, tile_expert, n_live, tile, True,
                            interpret).astype(f32)
        d_slot_w = jnp.sum(d_hidden * hidden, axis=-1)
        d_down = _product_dw((hidden * slot_w).astype(x.dtype), dy_slots,
                             w_down, tile_expert, n_live, tile, interpret)
        d_hidden = d_hidden * slot_w
        if gate_kind == 'relu2':
            d_gate_up = (2 * relu * d_hidden).astype(x.dtype)
        else:
            d_gate = jnp.where(gate > 0, d_hidden * up, 0.0) \
                if gate_kind == 'reglu' else d_hidden * up * _silu_grad(gate)
            d_gate_up = jnp.concatenate(
                [d_gate, d_hidden * _act(gate, gate_kind)],
                axis=-1).astype(x.dtype)
        d_xs = _product(d_gate_up, w_gate_up, tile_expert, n_live, tile, True,
                        interpret)
        d_gate_up_w = _product_dw(xs, d_gate_up, w_gate_up, tile_expert,
                                  n_live, tile, interpret)
    with jax.named_scope(_scopes.MOE_ROUTE):
        d_weights = jnp.where(pos < d_slot_w.shape[0],
                              jnp.take(d_slot_w, pos, mode='clip'), 0.0)
        d_x = _gather_sum(d_xs, pos)
    return (d_x, d_weights.astype(weights.dtype), d_gate_up_w, d_down,
            jax.tree.map(_no_grad, slots))


_experts.defvjp(_experts_fwd, _experts_bwd)


def expert_layer(x, router_logits, w_gate_up, w_down, first_expert=0,
                 top_k=1, interpret=None, scoring='softmax', gate='reglu',
                 bias=None, with_ids=False, scale=1.0):
    """This share's part of a sparse expert layer.

    x (N, T, h): the tokens; router_logits (N, T, E) over all E experts
    of the model; w_gate_up (held, h, 2f): the held experts' gate and up
    projections side by side, or (held, h, f) the up projection alone
    where ``gate`` is 'relu2'; w_down (held, f, h). The held experts are
    ``first_expert`` to ``first_expert + held - 1``. Returns (N, T, h):

        sum over c of k, expert e_c held here:
            w_c * (act(x @ gate_e) * (x @ up_e)) @ down_e
            (relu2: w_c * relu(x @ up_e)^2 @ down_e)

    with act relu (``gate`` 'reglu') or silu ('silu'), e_1..e_k and w
    the choice and the renormalised weights of :func:`route` by
    ``scoring`` ('softmax', or 'sigmoid' with the balancing ``bias``
    (E,)), times ``scale``. ``with_ids``: the tokens' (N, T, k) expert ids
    too, as the second of a pair."""
    if gate not in GATES:
        raise ValueError(f"gate {gate!r}: one of {GATES}")
    N, T, h = x.shape
    experts, held = router_logits.shape[-1], w_gate_up.shape[0]
    tokens = N * T
    rows, tile, tiles = plan(tokens, held, top_k)
    key_ = (experts, held, top_k, rows, tile)
    if (scoring, gate) != ('softmax', 'reglu'):
        key_ += (scoring, gate)
    if scale != 1:
        key_ += (scale,)
    builds[key_] = builds.get(key_, 0) + 1
    for which, w in (('up', w_gate_up), ('down', w_down)):
        read = (which, 'swapped' if _swapped(w) else 'stored')
        weight_reads[read] = weight_reads.get(read, 0) + 1
    route_counts['xla' if _kernel_mode(interpret) is None else 'pallas'] += 1
    with jax.named_scope(_scopes.MOE_ROUTE):
        ids, weights = route(router_logits.reshape(tokens, experts), top_k,
                             scoring, bias, scale)
        slots = _slots(*_sorted_assignments(ids, first_expert, held), top_k,
                       tile, tiles)
    out = _experts(x.reshape(tokens, h), weights.astype(jnp.float32),
                   w_gate_up, w_down, slots, tile, interpret, gate)
    out = out.reshape(N, T, h)
    return (out, ids.reshape(N, T, top_k)) if with_ids else out


def shared_expert(x, w_up, w_down):
    """The expert every token takes beside its routed ones, computed on
    every token by every chip alike: relu(x @ w_up)^2 @ w_down, with w_up
    (h, f) and w_down (f, h); float32 accumulation, the result in x's
    dtype. Its ops lie under ``scopes.MOE_SHARED``."""
    with jax.named_scope(_scopes.MOE_SHARED):
        up = jnp.einsum('...h,hf->...f', x, w_up,
                        preferred_element_type=jnp.float32)
        hidden = jnp.square(jnp.maximum(up, 0)).astype(x.dtype)
        return jnp.einsum('...f,fh->...h', hidden, w_down,
                          preferred_element_type=jnp.float32).astype(x.dtype)
