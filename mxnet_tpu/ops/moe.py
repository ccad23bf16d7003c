"""Sparse expert layer for one share of an expert-parallel group.

The layer is told which experts it holds (``first_expert`` and the leading
dimension of its weights), routes every token over all the experts of the
model (softmax over the router's logits, top-k, the k weights
renormalised), and computes the part of the result that its own experts
give: ``sum over the held experts among a token's k of w * expert(x)``.
What the absent experts would add is their chips' to add; nothing here
stands in for them or for their traffic.

No assignment to a held expert is ever dropped, and the device does the
same work a step whatever the router decides. The two together leave one
size: a token's k assignments may all land here, so the layer computes
``tokens * k`` rows, always (:func:`plan`). A smaller layout with a
``lax.cond`` for what overflows it was built first and measured on the
chip (PERF.md section 6, PR 34): at 1.5 times the balanced share the
overflow ran in the traced run and two other seeds' steps read 6 % apart,
because a randomly initialised router sends most tokens of a deep layer
to the same few experts (395 to 31 931 rows here where balance is
12 288), and training without a balancing loss moves it further.

* the ``tokens * k`` assignments are sorted by a key that puts the held
  experts first, in expert order (one stable sort, and its inverse);
* the sorted rows are laid out in row tiles of ``tile`` rows, every
  expert's rows starting on a tile boundary and every expert owning at
  least one tile (:func:`_layout`): ``tokens * k / tile + held`` tiles
  hold any routing. Slots past an expert's rows compute on some token's
  row and are read by nobody;
* the rows are gathered into that layout, and the result is combined by
  gathering each token's k slots back (a miss reads zero): no scatter,
  forward or backward.

The grouped matmul is a Pallas kernel, ``mxtpu_grouped_matmul``: one row
tile a grid step against the weights of the tile's expert, read from a
scalar-prefetched table; the two backward products are the same kernel on
the transposed weights and a second one that adds a group's tiles into
its weight gradient. Off the TPU the same products are plain einsums over
the tiles.
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

# trace-time telemetry, beside ops.attention.route_counts: how the
# grouped matmuls were computed, and {(experts, held, top-k, rows, tile):
# layers built}
route_counts = {'pallas': 0, 'xla': 0}
builds = {}


def plan(tokens, held, top_k):
    """(rows, tile, tiles): the rows the grouped matmuls are sized for --
    every assignment, since all of them may land here --, the rows of a
    tile, and the tiles of the layout: the rows' and one more an expert,
    for the padding to tile boundaries."""
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


def route(logits, top_k):
    """(expert ids (T, k) int32, weights (T, k) float32) of each token:
    softmax over all the experts, the k largest, renormalised to sum 1."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = lax.top_k(probs, top_k)
    return ids.astype(jnp.int32), \
        weights / jnp.sum(weights, axis=-1, keepdims=True)


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


def _layout(sizes, tile, tiles):
    """(tile_expert (tiles,), pad_start (held,)): the experts in order,
    each on whole tiles and each on at least one (so that a weight
    gradient is written for every expert); the tiles after the last
    expert's are its too. sum(sizes) <= (tiles - held) * tile."""
    held = sizes.shape[0]
    end = jnp.cumsum(jnp.maximum(1, -(-sizes // tile)))
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(tiles, dtype=jnp.int32)[:, None] >= end,
                axis=-1, dtype=jnp.int32), held - 1)
    return tile_expert, (end - jnp.diff(end, prepend=0)) * tile


def _slots(key, order, rank, sizes, top_k, tile, tiles):
    """The gather indices: (tile_expert, src (slots,) the token each slot
    reads, live (slots,) the assignment whose row it is or -1, pos (A,)
    each assignment's slot or ``slots`` for one to an expert elsewhere)."""
    slots = tile * tiles
    tile_expert, pad_start = _layout(sizes, tile, tiles)
    start = jnp.cumsum(sizes) - sizes
    g = jnp.repeat(tile_expert, tile)
    j = jnp.arange(slots, dtype=jnp.int32) - pad_start[g]
    live = jnp.where(j < sizes[g],
                     order[jnp.minimum(start[g] + j, order.size - 1)], -1)
    src = jnp.maximum(live, 0) // top_k
    # an assignment elsewhere has key == held: it gets no slot
    pos = jnp.where(key < sizes.shape[0],
                    jnp.append(pad_start, 0)[key] + rank, slots)
    return tile_expert, src, live, pos.astype(jnp.int32)


# ---------------------------------------------------------------------------
# dispatch and combine: gathers both ways
# ---------------------------------------------------------------------------

def _no_grad(x):
    return onp.zeros(x.shape, jax.dtypes.float0)


def _gather_sum(rows, pos, weights=None):
    """sum_c weights[t, c] * rows[pos[t, c]] (weights None: 1), a miss
    (pos == len(rows)) reading zero; added up in float32 a column of
    ``pos`` at a time, so that no (T, k, h) array exists. The miss is a
    zero weight on a clamped index: ``take(mode='fill')`` becomes a gather
    that XLA:TPU strips of its op_name, and a trace then cannot say whose
    it is."""
    hit = pos < rows.shape[0]
    weights = hit if weights is None else jnp.where(hit, weights, 0.0)
    total = 0.0
    for c in range(pos.shape[1]):
        picked = jnp.take(rows, pos[:, c], axis=0, mode='clip')
        total = total + weights[:, c, None].astype(jnp.float32) \
            * picked.astype(jnp.float32)
    return total.astype(rows.dtype)


def _reglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jnp.maximum(gate, 0) * up


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


def _gmm_kernel(te_ref, x_ref, w_ref, o_ref, *, transposed):
    """One row tile against its expert's weight block: o = x @ w, or
    x @ w^T where the weights are stored (out, in) for this product."""
    contract = (((1,), (1,)), ((), ())) if transposed \
        else (((1,), (0,)), ((), ()))
    o_ref[...] = lax.dot_general(
        x_ref[...], w_ref[0], contract,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _gmm_pallas(x, w, tile_expert, tile, transposed, interpret):
    """(M, K) rows in tiles of ``tile``, tile i against w[tile_expert[i]]:
    (E, K, N), or (E, N, K) ``transposed``. Grid (column tiles, row
    tiles), the row tiles inner: consecutive tiles of one expert find its
    weight block already in VMEM."""
    M, K = x.shape
    N = w.shape[1] if transposed else w.shape[2]
    tn = _col_tile(N, K, w.dtype.itemsize)
    w_spec = pl.BlockSpec((1, tn, K), lambda n, i, te: (te[i], n, 0)) \
        if transposed else \
        pl.BlockSpec((1, K, tn), lambda n, i, te: (te[i], 0, n))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // tn, M // tile),
            in_specs=[pl.BlockSpec((tile, K), lambda n, i, te: (i, 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tile, tn), lambda n, i, te: (i, n))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=_scopes.GROUPED_MATMUL,
    )(tile_expert, x, w)


def _tgmm_kernel(te_ref, x_ref, dy_ref, o_ref, acc_ref):
    """dw[e] = sum over the tiles of expert e of x^T @ dy: the float32
    accumulator is cleared at a group's first tile and written at its
    last. The groups are runs of the table, and every expert has one."""
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    e = te_ref[i]

    @pl.when((i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != e))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((i == last) | (te_ref[jnp.minimum(i + 1, last)] != e))
    def _write():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _tgmm_pallas(x, dy, tile_expert, tile, experts, interpret):
    """The weight gradient of :func:`_gmm_pallas`: (E, K, N) from x (M, K)
    and dy (M, N)."""
    M, K = x.shape
    N = dy.shape[1]
    tn = _col_tile(N, K, 4)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // tn, M // tile),
            in_specs=[pl.BlockSpec((tile, K), lambda n, i, te: (i, 0)),
                      pl.BlockSpec((tile, tn), lambda n, i, te: (i, n))],
            out_specs=pl.BlockSpec((1, K, tn),
                                   lambda n, i, te: (te[i], 0, n)),
            scratch_shapes=[pltpu.VMEM((K, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((experts, K, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=_scopes.GROUPED_MATMUL,
    )(tile_expert, x, dy)


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


def grouped_matmul(x, w, tile_expert, tile, transposed=False, interpret=None):
    """out[i-th row tile] = x[i-th row tile] @ w[tile_expert[i]], the
    weights (E, K, N), or @ w[...]^T with them (E, N, K) ``transposed``.
    ``tile_expert`` is non-decreasing. ``interpret``: None compiles the
    kernel on a TPU and takes einsums elsewhere; True runs the kernel
    through the Pallas interpreter. No gradient of its own: the layer's
    backward (:func:`_experts_bwd`) is written out."""
    mode = _kernel_mode(interpret)
    if mode is None:
        return _gmm_xla(x, w, tile_expert, tile, transposed)
    return _gmm_pallas(x, w, tile_expert, tile, transposed, mode)


def grouped_matmul_dw(x, dy, tile_expert, tile, experts, interpret=None):
    """The weight gradient of ``grouped_matmul(x, w)`` for the cotangent
    ``dy``: (experts, K, N), expert e's the sum of x^T @ dy over its
    tiles. ``tile_expert`` names every expert at least once."""
    mode = _kernel_mode(interpret)
    if mode is None:
        return _tgmm_xla(x, dy, tile_expert, tile, experts)
    return _tgmm_pallas(x, dy, tile_expert, tile, experts, mode)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _forward(x, weights, w_gate_up, w_down, slots, tile, interpret):
    """(y, gate|up): gather the rows into the layout, gate and up in one
    grouped matmul, ReGLU, down, and each token's k slots weighted and
    added back. x (T, h), weights (T, k) float32, ``slots`` what
    :func:`_slots` gives."""
    tile_expert, src, _live, pos = slots
    with jax.named_scope(_scopes.MOE_ROUTE):
        xs = jnp.take(x, src, axis=0, mode='clip')
    with jax.named_scope(_scopes.MOE_EXPERTS):
        gate_up = grouped_matmul(xs, w_gate_up, tile_expert, tile, False,
                                 interpret)
        ys = grouped_matmul(_reglu(gate_up), w_down, tile_expert, tile,
                            False, interpret)
    with jax.named_scope(_scopes.MOE_ROUTE):
        return _gather_sum(ys, pos.reshape(weights.shape), weights), gate_up


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _experts(x, weights, w_gate_up, w_down, slots, tile, interpret):
    """The held experts' part of the result, (T, h). Its backward is
    written out, because every transpose of a gather is here a gather
    through the inverse indices (autodiff would scatter-add), and so
    that only gate|up has to be kept: the gathered rows and ReGLU are
    computed again, the down projection's result is not needed."""
    return _forward(x, weights, w_gate_up, w_down, slots, tile, interpret)[0]


def _experts_fwd(x, weights, w_gate_up, w_down, slots, tile, interpret):
    y, gate_up = _forward(x, weights, w_gate_up, w_down, slots, tile,
                          interpret)
    return y, (x, weights, w_gate_up, w_down, slots, gate_up)


def _experts_bwd(tile, interpret, res, dy):
    x, weights, w_gate_up, w_down, slots, gate_up = res
    tile_expert, src, live, pos = slots
    pos = pos.reshape(weights.shape)
    held = w_down.shape[0]
    f32 = jnp.float32
    with jax.named_scope(_scopes.MOE_ROUTE):
        # a slot's weight is its assignment's; a slot nobody owns has none
        slot_w = jnp.where(live >= 0,
                           weights.reshape(-1)[jnp.maximum(live, 0)],
                           0.0)[:, None]
        xs = jnp.take(x, src, axis=0, mode='clip')
        dy_slots = jnp.take(dy, src, axis=0, mode='clip')
    with jax.named_scope(_scopes.MOE_EXPERTS):
        gate, up = (t.astype(f32) for t in jnp.split(gate_up, 2, axis=-1))
        hidden = jnp.maximum(gate, 0) * up
        # y = sum_c w_c * (hidden @ down): the weight scales a row, so it
        # is put on the narrow side of both products
        d_hidden = grouped_matmul(dy_slots, w_down, tile_expert, tile, True,
                                  interpret).astype(f32)
        d_slot_w = jnp.sum(d_hidden * hidden, axis=-1)
        d_down = grouped_matmul_dw(
            (hidden * slot_w).astype(x.dtype), dy_slots, tile_expert, tile,
            held, interpret)
        d_hidden = d_hidden * slot_w
        d_gate_up = jnp.concatenate(
            [jnp.where(gate > 0, d_hidden * up, 0.0),
             d_hidden * jnp.maximum(gate, 0)], axis=-1).astype(x.dtype)
        d_xs = grouped_matmul(d_gate_up, w_gate_up, tile_expert, tile, True,
                              interpret)
        d_gate_up_w = grouped_matmul_dw(xs, d_gate_up, tile_expert, tile,
                                        held, interpret)
    with jax.named_scope(_scopes.MOE_ROUTE):
        d_weights = jnp.where(pos < d_slot_w.shape[0],
                              jnp.take(d_slot_w, pos, mode='clip'), 0.0)
        d_x = _gather_sum(d_xs, pos)
    return (d_x, d_weights.astype(weights.dtype), d_gate_up_w, d_down,
            jax.tree.map(_no_grad, slots))


_experts.defvjp(_experts_fwd, _experts_bwd)


def expert_layer(x, router_logits, w_gate_up, w_down, first_expert=0,
                 top_k=1, interpret=None):
    """This share's part of a sparse ReGLU expert layer.

    x (N, T, h): the tokens; router_logits (N, T, E) over all E experts
    of the model; w_gate_up (held, h, 2f): the held experts' gate and up
    projections side by side; w_down (held, f, h). The held experts are
    ``first_expert`` to ``first_expert + held - 1``. Returns (N, T, h):

        sum over c of k, expert e_c held here:
            w_c * (relu(x @ gate_e) * (x @ up_e)) @ down_e

    with e_1..e_k the ``top_k`` largest of softmax(router_logits) and w
    their probabilities renormalised to sum 1."""
    N, T, h = x.shape
    experts, held = router_logits.shape[-1], w_gate_up.shape[0]
    tokens = N * T
    rows, tile, tiles = plan(tokens, held, top_k)
    key_ = (experts, held, top_k, rows, tile)
    builds[key_] = builds.get(key_, 0) + 1
    route_counts['xla' if _kernel_mode(interpret) is None else 'pallas'] += 1
    with jax.named_scope(_scopes.MOE_ROUTE):
        ids, weights = route(router_logits.reshape(tokens, experts), top_k)
        slots = _slots(*_sorted_assignments(ids, first_expert, held), top_k,
                       tile, tiles)
    out = _experts(x.reshape(tokens, h), weights.astype(jnp.float32),
                   w_gate_up, w_down, slots, tile, interpret)
    return out.reshape(N, T, h)
