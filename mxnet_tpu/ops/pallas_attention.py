"""Pallas flash-attention kernels for TPU (forward AND backward).

The fused MHA ops (ops/attention.py multi_head_attention, self_attention)
route here. This is the TPU-native realisation of the reference's
interleaved_matmul attention kernels (ref:
src/operator/contrib/transformer.cc:650-828): one hand-written kernel
instead of two batched-GEMM ops, with the T×T score matrix living only in
VMEM.

Operands and results are the model's own arrays: q, k, v and o, dO, dq,
dk, dv are (N, T, C = H*D), in the model's dtype, and q, k and v may be
the three thirds of one fused (N, T, 3C) projection, each addressed at a
static column offset. A block is (Gn, bq | bk, lb·W): Gn batch rows, a
sequence block, ``lb`` adjacent *lane blocks* of W columns that hold
W // D whole heads each (:func:`_lane_block`: two 64-wide heads to 128
lanes). Inside a grid step the kernels walk the lane blocks by static
column slices of their refs (:func:`_step_blocks`), and a head is picked
out of its lane block by a lane mask on the (·, W) operands
(:func:`_own`): a contraction over 128 lanes of which 64 are zero costs
the 128-deep MXU what one over 64 does. Nothing is transposed, split or
cast round the calls.

A grid step holds G = Gn·lb·(W // D) batch·head slices
(:func:`_block_sizes`): filled first by batch rows
(:func:`_rows_per_step`) and, what the rows cannot supply, by lane blocks
(:func:`_lane_blocks_per_step`), so a batch of one row still gets its G
heads a step. What G amortises is a grid step's fixed cost, measured on a
v5e at 0.4 µs in the forward and dq and 0.6 µs in dk/dv (chip runs, PR 40:
one row of 16 heads of 128 at 1, 2, 4 and 8 heads a step fits step = a +
heads·b with b, a head and cell, 2.0, 0.29 and 0.39 µs): beside one head's
256 × 256 backward cell it was three fifths of the step. However its heads
come, a head's scores, softmax, dropout bits, accumulation order over
cells and rounding are the same: out, lse, dq, dk and dv do not change by
a bit with G.

Forward: grid (N/Gn, C/(lb·W), Tq/BQ, Tk/BK). Scratch (VMEM) carries the
online-softmax state (running max m, running sum l, f32 accumulator)
across k-blocks; the final k-block normalises and writes the output block
plus the logsumexp (saved for the backward pass) as a row, the queries on
the lanes.

Backward: two Pallas kernels — dq (grid (N/Gn, C/(lb·W), Tq/BQ, Tk/BK),
accumulating over k-blocks) and dk/dv (grid (N/Gn, C/(lb·W), Tk/BK,
Tq/BQ), accumulating over q-blocks) — both recompute the probability block
from the saved LSE (flash-attention backward recurrence), so live memory
stays O(T); each rounds its float32 accumulator to the operands' dtype
once, as it writes. dq holds a cell as the forward does, (bq, bk), queries
down the sublanes. dk/dv holds it keys down, (bk, bq): s^T = k·q^T, p^T,
dp^T = v·dO^T, ds^T, so that dv += p^T·dO and dk += ds^T·q are plain
products and the queries' statistics broadcast down the sublanes as the
rows they are (:func:`_cell` hands out every mask and the dropout bits in
either orientation: the same function of (q position, k position)).

The softmax row statistics — lse = m + log l from the forward, delta =
rowsum(dO·O) from XLA — are float32 (N, H, 1, Tq) arrays in (Gn, heads of
a row of the step, 1, bq) blocks: the queries lie on the lanes, so HBM
holds and moves them at their size (a last dimension of 1 would be held,
and moved, in 128-lane tiles, 128 times it). dk/dv reads the (1, bq) rows
as they are. The forward and dq want a statistic beside each row of a (bq,
bk) cell and turn rows into columns, or back, once an *outer* block and
not once a cell, all the heads of the step in one transpose: dq lays its
rows side by side and transposes them into a VMEM scratch at the first
k-block of a q-block (:func:`_as_columns`), the forward gathers its heads'
m + log l into the lanes of one (bq, 128) array and transposes that at the
last. dk/dv does the same for the additive key mask, a (1, bk) row it
wants as a column, once a k-block. ``row_stat_blocks`` counts the
statistics' blocks of every build.

Without ``causal`` every (q-block, k-block) cell of those grids is
computed. With it the grid is (N/Gn, C/(lb·W), listed cells): the cells
that hold a score at or under the diagonal (:func:`_cell_live`), row by
row for the forward and dq, column by column for dk/dv, from a small table
the kernels and their index maps read as a scalar-prefetch operand
(:func:`_causal_cell_table`). A cell wholly above the diagonal is no grid
step at all: no matmul, no hash, no exp, no copy. Such a cell used to add
exp(-1e30 - m) = 0 to every accumulator, and the listed cells keep their
order, so no result changes by a bit. The cells the diagonal crosses still
mask element by element (:func:`_cell`'s ``scores``). All of it hangs on
the static ``causal`` flag. Each causal build counts its cells in
``causal_cells``, and every build its addressing in ``head_blocks``.

A ``window`` (a causal layer that sees the ``window`` keys up to its own)
is a second inequality beside ``_cell_live`` (:func:`_cell_in_window`):
the table lists the band's cells only, and inside a cell the two masks are
one unsigned comparison of i - j. Grouped-query heads (``num_kv_heads``
fewer than the query heads): the forward and dq grids run over the query
heads, ``lb`` lane blocks a step, and read the key/value lane block their
heads share through the index map; the dk/dv grid runs over the key/value
lane blocks, its q, dO, lse and delta blocks the query heads of their
groups wide, and adds a group's heads into its dk and dv inside the
kernel: the same walk over a step's lane blocks, ``rep`` of them on the
query side to one on the key/value side (:func:`_step_lanes`). With a
head a whole lane block, ``lb`` divides ``rep``. With 64-wide heads a
key/value lane block holds two heads and is read by the ``rep`` query lane
blocks of their two groups, and a step takes all of them, so that which
key/value head a query head reads is static: where that head lies in the
other half of the lanes, the kernels turn the lanes by 64
(:func:`_kv_shift`, ``pltpu.roll``) -- k and v in the forward and dq, a
query head's q and dO in dk/dv -- and the contraction and the mask are
then those of any head. Each windowed build counts its cells
in ``window_cells``. Without either, the builds are the ones
tests/test_causal_skip.py pins, equation for equation.

Attention dropout runs INSIDE the kernels: the keep mask is a
counter-based hash (murmur3 finalizer) of the global (batch·head, q, k)
element coordinates mixed with a per-call seed, so the forward and both
backward kernels regenerate bit-identical masks with no T×T tensor ever
materialised, and the same bits fall out in Mosaic and interpreter modes.
The batch·head id is n·H + h (:func:`_cell`), whatever the blocks.
Softmax statistics (m, l) are computed on the UNdropped probabilities —
dropout scales only the value accumulation — matching the standard
softmax→dropout→matmul recipe.

Mosaic layout constraints honoured throughout: every block's trailing two
dims are (multiple-of-8, multiple-of-128) or equal to the array dims — the
key-mask rides as (N, 1, Tk) with (Gn, 1, bk) blocks, one row a batch row,
and the statistics as (N, H, 1, Tq) with (Gn, lb·(W // D), 1, bq) blocks,
one row a head (a (1, bk) 2-D mask block is refused): bq and bk are
multiples of 128 or the whole padded sequence. The two scalars the kernels
read (dropout seed, global batch·head base) ride in SMEM.

What the backward reads of the forward, ``o`` and ``lse``, the
``custom_vjp``'s forward rule (:func:`_flash_fwd`) passes through
``jax.ad_checkpoint.checkpoint_name`` under ``scopes.FLASH_OUT`` and
``scopes.FLASH_LSE``, and hands the named ``o`` on as the result too. A
name is an identity and lowers to nothing. Its one reader is a
``jax.checkpoint`` region whose policy lists the two
(``save_only_these_names``: a looped decoder's block applications,
models/decoder.py:_recomputed): such a region keeps them beside its inputs
and its backward does not run the forward kernel again; q, k and v it
computes again from the kept input. No other policy in the program reads
a name of these (parallel/step.py's ``MXTPU_REMAT`` policies read none,
parallel/exchange.py's ZeRO-3 policy drops ``zero3_gather`` alone), and
the primal :func:`_flash`, which predict mode takes, never sees the rule.

Kernel mode is explicit: ``interpret=True`` runs the identical kernels
through the Pallas interpreter (CPU tests exercise the real kernel code),
``interpret=False`` compiles them with Mosaic, and ``None`` asks
:func:`default_interpret`, which decides from the platform alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes as _scopes

_NEG_INF = -1e30
_LANES = 128

# every grid here is (batch-row groups, steps of lane blocks, outer seq
# blocks, inner seq blocks) with the scratch accumulators carried over
# the innermost axis
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'parallel', 'arbitrary'))

# the causal grid is (batch-row groups, steps of lane blocks, listed
# cells), the accumulators carried over the cells of one row or column
_CAUSAL_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'arbitrary'))

# trace-time telemetry of the causal skip, beside ops.attention.route_counts
# and autotune.decisions(): {(kind, live cells, cells of the full plane):
# kernel builds}, the plane being one head-group's (q-block, k-block) grid.
# Static numbers; a non-causal build records nothing.
causal_cells = {}

# of a windowed build (a causal one besides): {(kind, live cells, cells a
# causal build without the window lists): kernel builds}
window_cells = {}

# and of the addressing: {(kind, H, D, heads per lane block, fused):
# kernel builds}, ``fused`` being whether q, k and v are column ranges of
# one (N, T, 3*H*D) array. Static numbers, counted at build.
head_blocks = {}

# of a grouped-query build: {(kind, H, Hkv, D): kernel builds}
group_builds = {}

# and of the layout the softmax row statistics (lse, delta) cross HBM in:
# {(kind, statistics block shape): kernel builds}, counted where the
# specs are built. Static numbers.
row_stat_blocks = {}


def _lane_block(C, D):
    """(W, hb): the columns one block of the kernels spans in an
    (N, T, C) array of D-wide heads, and the whole heads they hold. 128
    where D divides 128 (two heads at D = 64), D where D is a multiple
    of 128, the whole C where C < 128. None where the columns do not come
    apart into such blocks: the XLA route's shapes."""
    if C < _LANES:
        W = C
    elif _LANES % D == 0:
        W = _LANES
    elif D % _LANES == 0:
        W = D
    else:
        return None
    return (W, W // D) if C % W == 0 else None


def _rows_per_step(N, G, hb):
    """Batch rows of one grid step: ``G`` heads a step (:func:`_block_sizes`)
    are G // hb rows of one lane block, at least one, clamped to a divisor
    of N as ``autotune.resolve`` clamps G to one of N*H."""
    Gn = max(1, min(G // hb, N))
    while N % Gn:
        Gn -= 1
    return Gn


def _lane_blocks_per_step(G, Gn, hb, blocks):
    """Adjacent lane blocks of one grid step: what of ``G`` heads the Gn
    rows of one lane block (Gn * hb heads) do not supply, at least one,
    clamped to a divisor of ``blocks``: the lane blocks of the array, or
    with grouped-query heads those of one group, so that the step's
    heads share a key/value block. Rows come first: a batch that fills G
    by rows gets one lane block a step, the build it always had."""
    lb = max(1, min(G // (Gn * hb), blocks))
    while blocks % lb:
        lb -= 1
    return lb


def _step_heads(N, C, D, G, rep=1):
    """(Gn, lb): how the ``G`` heads of a grid step are made up over an
    (N, T, C) array of D-wide heads -- rows first, then lane blocks of
    the array, or of one group where ``rep`` query heads share a
    key/value head."""
    W, hb = _lane_block(C, D)
    if rep > 1 and hb > 1:
        # a key/value lane block holds hb heads, read by the rep lane
        # blocks of their groups' query heads: a step takes them all
        return _rows_per_step(N, G // rep, hb), rep
    Gn = _rows_per_step(N, G, hb)
    return Gn, _lane_blocks_per_step(G, Gn, hb, rep if rep > 1 else C // W)


def _kv_shift(head, hb, rep, D):
    """Lanes to turn a key/value lane block by so that the head that query
    head ``head`` of a step reads lies in that query head's own columns:
    0 without groups, where query and key/value head share a position,
    and with one head a lane block. A step's query heads read key/value
    lane block 0 of the step (:func:`_step_heads`)."""
    if rep == 1 or hb == 1:
        return 0
    return (head % hb - head // rep % hb) % hb * D


def _step_lanes(kind, lb, rep):
    """(lq, lk): the lane blocks a grid step of ``kind`` spans on the
    query side (q, o, dO, dq; lq * hb rows of lse and delta) and on the
    key/value side (k, v, dk, dv). ``lb`` on both without groups. With
    ``rep`` query heads to a key/value head the step's query heads share
    one key/value lane block: ``lb`` of the group's in the forward and
    dq, whose grid runs over the query heads, the whole group in dk/dv,
    whose grid runs over the key/value heads. Query lane block j of a
    step reads key/value lane block j * lk // lq."""
    if rep == 1:
        return lb, lb
    return (rep, 1) if kind == 'bwd_dkv' else (lb, 1)


def _cols(j, n, W):
    """The static column slice of lane block ``j`` of the ``n`` a block
    spans; the whole block where it spans one."""
    return slice(None) if n == 1 else slice(j * W, (j + 1) * W)


def _step_blocks(Gn, lq, lk, W):
    """The (row, query lane block) pairs of one grid step, in the order
    the kernels unroll them, as (g, j, query columns, key/value columns,
    first, last): the column slices of lane block j and of the key/value
    lane block it reads, j * lk // lq, and whether j is the first or the
    last query lane block of that key/value block -- where a kernel
    loads, and dk/dv stores, what the block's query heads share."""
    for g in range(Gn):
        for j in range(lq):
            yield (g, j, _cols(j, lq, W), _cols(j * lk // lq, lk, W),
                   j * lk % lq == 0, (j + 1) * lk % lq == 0)


def _cell_live(qb, kb, bq, bk):
    """Does cell (q-block qb, k-block kb) hold a score at or under the
    causal diagonal (top-left aligned, as :func:`_cell`'s scores mask)?
    True iff its last query row sees its first key. A window (ROADMAP R5)
    is a second inequality here."""
    return qb * bq + (bq - 1) >= kb * bk


def _cell_in_window(qb, kb, bq, bk, window):
    """The second inequality of a windowed causal layer (score (i, j) is
    kept iff 0 <= i - j < window): does the cell hold a pair nearer than
    ``window``? True iff its first query row is that near its last key."""
    return qb * bq - (kb * bk + bk - 1) < window


def _causal_cell_table(kind, nq, nk, bq, bk, by_row, window=None):
    """int32 (4, n): the cells a causal kernel visits, in grid order, as
    rows [q-block, k-block, first of its line, last of its line]. A line
    is a q-block row (``by_row``: forward, dq) or a k-block column
    (dk/dv), walked in ascending order as the full grid walks it, so the
    accumulators add up in the same order. A line without a live cell (a
    k-block no query sees, when Tk > Tq) keeps one dead cell: it adds
    exact zeros, as every dead cell used to, and the line's output is
    still initialised and written. Counts the build in ``causal_cells``.
    With a ``window`` only the band's cells are listed
    (:func:`_cell_in_window`), and the build is counted in
    ``window_cells`` beside the causal cells it would have had."""
    table, live_cells, causal_live = [], 0, 0
    for outer in range(nq if by_row else nk):
        line = [(outer, inner) if by_row else (inner, outer)
                for inner in range(nk if by_row else nq)]
        live = [cell for cell in line if _cell_live(*cell, bq, bk)]
        causal_live += len(live)
        if window is not None:
            live = [cell for cell in live
                    if _cell_in_window(*cell, bq, bk, window)]
        live_cells += len(live)
        live = live or line[-1:]
        table += [(qb, kb, cell == 0, cell == len(live) - 1)
                  for cell, (qb, kb) in enumerate(live)]
    key = (kind, live_cells, nq * nk)
    causal_cells[key] = causal_cells.get(key, 0) + 1
    if window is not None:
        key = (kind, live_cells, causal_live)
        window_cells[key] = window_cells.get(key, 0) + 1
    return onp.asarray(table, onp.int32).T


def _step_cell(cells_ref, q_axis):
    """(q-block, k-block, is-first, is-last) of this grid step, the last
    two as thunks evaluated where the kernel asks. Without a table: the
    full grid, q-blocks on grid axis ``q_axis`` (2: forward and dq, 3:
    dk/dv) and the accumulators carried over axis 3. With one: the
    causal grid's listed cell (:func:`_causal_cell_table`)."""
    if cells_ref is None:
        outer, inner = pl.program_id(2), pl.program_id(3)
        n_inner = pl.num_programs(3)
        qb, kb = (outer, inner) if q_axis == 2 else (inner, outer)
        return qb, kb, lambda: inner == 0, lambda: inner == n_inner - 1
    cell = pl.program_id(2)
    return (cells_ref[0, cell], cells_ref[1, cell],
            lambda: cells_ref[2, cell] == 1, lambda: cells_ref[3, cell] == 1)


def _block_specs(kind, Gn, hb, bq, bk, W, causal, lb=1, rep=1):
    """The BlockSpecs of one call of ``kind`` ('fwd', 'bwd_dq',
    'bwd_dkv'), by role, as (seq, row, mask): ``seq(side, off)`` a
    (Gn, bq | bk, lq * W | lk * W) block of an (N, T, columns) array on
    the 'q' or the 'k' side (:func:`_step_lanes`), ``off`` such blocks
    into the columns (where q, k and v are ranges of one array); ``row``
    the q-side (Gn, lq * hb, 1, bq) block of an (N, H, 1, Tq) array of
    row statistics (lse, delta), Tq on the lanes; ``mask`` the
    (Gn, 1, bk) block of the (N, 1, Tk) key mask. The grid is (row group
    b, step l of ``lb`` lane blocks, then the full plane, k-blocks
    outermost in dk/dv and q-blocks elsewhere, or the listed cell with
    the cell table as scalar-prefetch operand, ``causal``). No index map
    computes anything, but for an ``off``. Counts the build in
    ``row_stat_blocks``.

    Grouped-query heads (``rep`` query heads to a key/value head, a head
    a lane block): where the steps of the grid are over the query heads
    (forward, dq), ``rep // lb`` of them read one key/value lane block,
    ``l // (rep // lb)``; where they are over the key/value heads
    (dk/dv), the 'q' side and ``row`` are the whole group of head ``l``."""
    if causal:
        def qi(c, cells): return cells[0, c]
        def ki(c, cells): return cells[1, c]
    elif kind != 'bwd_dkv':
        def qi(i, j): return i
        def ki(i, j): return j
    else:
        def qi(j, i): return i
        def ki(j, i): return j
    lq, lk = _step_lanes(kind, lb, rep)
    # steps of the grid to one key/value lane block
    shared = rep // lq if rep > 1 and kind != 'bwd_dkv' else 1

    def seq(side, off=0):
        rows, at, lanes = (bq, qi, lq) if side == 'q' else (bk, ki, lk)
        block = (Gn, rows, lanes * W)
        if side == 'k' and shared > 1:
            return pl.BlockSpec(block,
                                lambda b, l, *s: (b, at(*s), l // shared))
        if off:
            return pl.BlockSpec(block, lambda b, l, *s: (b, at(*s), l + off))
        return pl.BlockSpec(block, lambda b, l, *s: (b, at(*s), l))
    row = pl.BlockSpec((Gn, lq * hb, 1, bq),
                       lambda b, l, *s: (b, l, 0, qi(*s)))
    mask = pl.BlockSpec((Gn, 1, bk), lambda b, l, *s: (b, 0, ki(*s)))
    key = (kind, row.block_shape)
    row_stat_blocks[key] = row_stat_blocks.get(key, 0) + 1
    return seq, row, mask


@functools.lru_cache(maxsize=None)
def _body(kernel, tabled, **static):
    """``kernel`` with its static arguments bound, as the function
    ``pallas_call`` traces: of the refs, the cell table's first where the
    build has one (``tabled``). A kernel's body follows from these
    arguments and the shapes of its refs alone, so it is traced once for
    them and not once a call: ``jit(inline=True)`` keeps the jaxpr and
    writes its equations into the ``pallas_call``'s own, the same
    equations. A step of twelve layers, or of 32 applications of eight
    blocks, traces three bodies; the wider a grid step (G heads unrolled),
    the more each costs."""
    def body(*refs):
        if tabled:
            return kernel(*refs[1:], cells_ref=refs[0], **static)
        return kernel(*refs, **static)
    return jax.jit(body, inline=True)


def _call(kernel, static, cells, grid, in_specs, out_specs, scratch_shapes,
          **call):
    """``pallas_call`` of ``kernel`` with its ``static`` arguments: over
    ``grid`` as it is (``cells`` None: it ends in the full plane's two
    axes), or over ``grid`` + the listed cells of a causal build, already
    applied to the cell table."""
    body = _body(kernel, cells is not None, **static)
    if cells is None:
        return pl.pallas_call(
            body, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes,
            compiler_params=_COMPILER_PARAMS, **call)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid + (cells.shape[1],),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)
    return functools.partial(
        pl.pallas_call(body, grid_spec=grid_spec,
                       compiler_params=_CAUSAL_COMPILER_PARAMS, **call),
        jnp.asarray(cells))


def pallas_available() -> bool:
    """Mosaic compiles these kernels for one platform: the TPU."""
    return jax.default_backend() == 'tpu'


def default_interpret() -> bool:
    """Kernel mode when the caller names none. The CPU backend has no
    Mosaic, so Pallas kernels run through the interpreter there; on any
    other platform they compile. Decided from the platform and nothing
    else — a failed query raises, it does not pick a mode."""
    return jax.default_backend() == 'cpu'


def _block_sizes(BH, Tq, Tk, D, dtype, kind='fwd', N=None, rep=1):
    """(G, bq, bk): heads a grid step and MXU/VPU-aligned seq blocks, for
    BH = N*H batch·head slices. Sublane minimum is 8 (f32) / 16 (bf16);
    lanes are 128. G amortises a grid step's fixed cost over several
    batch·head slices; the kernels take them as G // hb batch rows of
    one lane block of hb heads (:func:`_rows_per_step`) and, what the
    rows cannot supply, as adjacent lane blocks
    (:func:`_lane_blocks_per_step`).

    kind='bwd' sizes the backward kernels, whose per-cell stack holds
    ~6 live (bq, bk) f32 temporaries (s, p, dp, ds, keep, pv) vs the
    forward's ~3, so backward defaults to 256-wide blocks where the
    forward takes 512. Established on a v5e with libtpu 0.0.34 (chip
    run, PR 23; again with two heads to a lane block, PR 33): these
    defaults — forward (4, 512, 512), backward (4, 256, 256) — compile
    under Mosaic's default scoped-VMEM limit, with no
    ``vmem_limit_bytes``, at BERT-base (BH=672, T=512, D=64, bf16,
    padding mask, dropout) and at GPT-2's causal T=1024 (BH=288).
    Wider backward blocks on this installation: not measured.

    G follows from the shapes. Where the ``N`` rows of the batch fill
    four heads a step (N * hb >= 4; ``N`` unknown: taken to), 4: measured
    best on v5e at BERT-base shape, and the build those shapes have
    always had. Where they cannot (one sequence a chip), the heads come
    as lane blocks and 8 is asked for, which the VMEM estimate takes
    down to 4 in the forward at D = 128 (chip runs, PR 40, one row of
    16 heads of 128, T = 4096, ms a step of 32 calls at 1 / 2 / 4 / 8
    heads a step: dq 47.0 / 33.8 / 26.6 / 23.1, dk/dv 67.3 / 47.4 /
    36.5 / 31.8, the forward 44.4 / 40.7 / 38.9 at 1 / 2 / 4); with
    ``rep`` grouped query heads to a key/value head the group, ``rep``,
    so that its one key/value block is fetched once (28 query heads over
    4, T = 8192, 4 calls, 1 / 7 heads a step: dq 39.1 / 16.7, the
    forward 32.0 / 26.2; dk/dv took the group before). With 64-wide
    grouped heads the ``rep * 2`` query heads that read one key/value lane
    block (:func:`_step_heads`).

    The defaults computed here are only the LAST rung of the ISSUE 18
    precedence ladder, applied by ops/autotune.resolve: explicit env
    override (registered MXTPU_FA_{G,BQ,BK} / MXTPU_FA_BWD_* knobs) >
    tuning-DB winner (MXTPU_AUTOTUNE_DIR, keyed by device kind +
    shape signature) > these defaults — with the divisor/VMEM clamps
    applied to whatever won, and the decision recorded for the
    compile-ledger signature."""
    min_sub = 16 if dtype == jnp.bfloat16 else 8
    cap = 512 if kind == 'fwd' else 256
    bq = max(min_sub, min(cap, Tq))
    bk = max(min_sub, min(cap, Tk))
    hb = max(1, _LANES // D)
    if rep > 1 and hb > 1:
        wanted = (rep * hb,)        # the query heads of one key/value block
    elif N is None or N * hb >= 4:
        wanted = (4, 8, 2)
    else:
        wanted = (rep,) if rep > 1 else (8, 4, 2)
    G = next((g for g in wanted if BH % g == 0), 1)
    from . import autotune
    return autotune.resolve(autotune.KERNEL_FA, BH, Tq, Tk, D,
                            jnp.dtype(dtype), kind, default=(G, bq, bk),
                            rep=rep)


# ---------------------------------------------------------------------------
# portable counter-based dropout bits
# ---------------------------------------------------------------------------

def _element_ids(rows, cols):
    """uint32 id of score element (row, col), the part of the hash every
    head shares. The row mixing uses a CONSTANT odd multiplier (not the
    padded key length) so the backward kernels may tile the sequence
    differently from the forward and still reproduce bit-identical
    masks. The odd multiplier is a bijection on uint32, so no two rows
    ever share a whole mask row (a power-of-two stride would duplicate
    rows every 2^32/stride queries)."""
    return rows * jnp.uint32(0x9E3779B1) + cols


def _keep_of(ids, seed, bh, rate):
    """float32 keep/(1-rate) multipliers of the elements ``ids`` of
    batch·head slice ``bh``: the hash of (seed, global element id)
    through the murmur3 finalizer. uint32 arithmetic wraps identically
    in Mosaic, XLA and the Pallas interpreter, so forward and backward
    kernels regenerate the same mask from coordinates alone — grid
    iteration order is irrelevant."""
    h = ids + bh * jnp.uint32(0x9e3779b9)
    h = h ^ seed
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85ebca6b)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xc2b2ae35)
    h = h ^ (h >> jnp.uint32(16))
    thresh = jnp.uint32(min(int(rate * 2.0**32), 2**32 - 1))
    keep = (h >= thresh).astype(jnp.float32)
    return keep * jnp.float32(1.0 / (1.0 - rate))


def _counter_keep(seed, bh, rows, cols, rate):
    """The shared hash core: keep/(1-rate) multipliers from broadcastable
    uint32 (bh, rows, cols) index arrays. Used by the Pallas kernels
    (:func:`_cell`) and by ring attention (parallel/ring_attention.py)
    with GLOBAL sequence positions, so both regenerate identical masks
    from coordinates alone."""
    return _keep_of(_element_ids(rows, cols), seed, bh, rate)


def _cell(cells_ref, q_axis, meta_ref, Gn, W, D, bq, bk, scale, causal,
          k_len, dropout_p, h_all, window=None, lq=1):
    """What the heads of one grid step share, computed once a step:
    (own, is-first, is-last, scores, keep).

    Orientation: the forward and dq (``q_axis`` 2) hold a cell's scores
    as (bq, bk), queries down the sublanes; dk/dv (``q_axis`` 3) as
    (bk, bq), keys down the sublanes, so that the row statistics of the
    queries broadcast down the sublanes as the (1, bq) rows they arrive
    as and dv, dk are plain (bk, bq)·(bq, W) products. Every mask and
    the dropout bits are functions of (q position, k position) and are
    handed out with the two iotas exchanged: the same values, transposed.

    ``own``: per head of the step's W-column lane block, the (bq, W) mask
    of its own D columns (:func:`_lane_masks`).

    ``scores(q, k, kmask)``: f32 scores of one head for this (q-block,
    k-block) cell: QK^T * scale (K Q^T in dk/dv), key-padding cut at
    k_len, additive user mask (a (1, bk) row, a (bk, 1) column in dk/dv),
    causal. The causal ``where`` is what masks inside the cells the
    diagonal crosses (and, needlessly, in those wholly under it); the
    cells wholly above it are not in the causal grid (:func:`_cell_live`).

    ``keep(g, hh)``: the dropout multiplier of row g, head hh of the
    step's ``lq`` lane blocks (hh = lane block * hb + head of the block),
    shaped as the scores, or None without dropout. Its batch·head
    id is n * h_all + h from ``meta_ref[0, 1]``, the numbering the
    (N*H, T, D) layout had. A call that holds the whole (N, H) problem
    has ``h_all`` = H and ``meta_ref[0, 1]`` 0. A call mapped over a mesh
    (ops/attention.py) holds one shard: ``meta_ref[0, 1]`` is the global
    id of its first (row, head) and ``h_all`` the heads of the whole
    problem, its own being fewer when the heads are sharded too — so a
    sharded run draws the same dropout bits as the unsharded one.

    ``window``: keep a score iff 0 <= i - j < window. The difference read
    as an unsigned number makes that one comparison, as the causal mask
    alone is, in the cells the window's edge crosses and (needlessly) in
    the others."""
    own = _lane_masks(bq, W, D)
    qb, kb, first, last = _step_cell(cells_ref, q_axis)
    kq = q_axis == 3
    q_dim, k_dim = (1, 0) if kq else (0, 1)
    cell = (bk, bq) if kq else (bq, bk)
    q_line, k_line = ((1, bq), (bk, 1)) if kq else ((bq, 1), (1, bk))
    k_pos = kb * bk + lax.broadcasted_iota(jnp.int32, k_line, k_dim)
    in_range = k_pos < k_len
    under = None
    if causal:
        q_pos = qb * bq + lax.broadcasted_iota(jnp.int32, q_line, q_dim)
        if window is None:
            under = q_pos >= k_pos
        else:
            under = lax.bitcast_convert_type(
                q_pos - k_pos, jnp.uint32) < jnp.uint32(window)

    def scores(q, k, kmask):
        s = lax.dot_general(*((k, q) if kq else (q, k)),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(in_range, s, _NEG_INF) + kmask
        return s if under is None else jnp.where(under, s, _NEG_INF)

    if dropout_p <= 0.0:
        return own, first, last, scores, lambda g, hh: None
    ids = _element_ids(
        jnp.uint32(qb * bq) + lax.broadcasted_iota(jnp.uint32, cell, q_dim),
        jnp.uint32(kb * bk) + lax.broadcasted_iota(jnp.uint32, cell, k_dim))
    seed = meta_ref[0, 0]
    bh0 = meta_ref[0, 1] + (pl.program_id(0) * (Gn * h_all)
                            + pl.program_id(1) * (len(own) * lq)
                            ).astype(jnp.uint32)

    def keep(g, hh):
        return _keep_of(ids, seed, bh0 + jnp.uint32(g * h_all + hh),
                        dropout_p)
    return own, first, last, scores, keep


def _lane_masks(rows, W, D):
    """Per head of a W-column lane block, the (rows, W) mask of its own
    D columns; [None] where the block is one head."""
    if W == D:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    masks = [lane < D]
    masks += [(lane >= h * D) & (lane < (h + 1) * D)
              for h in range(1, W // D - 1)]
    return masks + [lane >= W - D]


def _own(x, mask):
    """``x`` with the columns of the block's other heads zeroed: a
    contraction over the block's W lanes is then one over this head's D,
    and a product into W columns adds exact zeros to the others'."""
    return x if mask is None else lax.select(mask, x, lax.full_like(x, 0))


def _turn(x, lanes):
    """``x`` (rows, W) with its lanes turned by ``lanes``: column c moves to
    c + lanes, mod W. The identity at 0. Mosaic turns 32-bit data only, so
    a bf16 block goes through float32, which loses nothing."""
    if lanes == 0:
        return x
    return pltpu.roll(x.astype(jnp.float32), lanes, 1).astype(x.dtype)


def _as_columns(rows):
    """(1, n) rows, each along the lanes, as the columns of one (n, rows)
    array, row i down the sublanes at lane i: one small transpose for all
    of them, where turning each row into a (n, 1) column of its own is a
    relayout a row."""
    return jnp.concatenate(rows, axis=0).T


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, kmask_ref, meta_ref,
                   o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                   scale, causal, W, D, bq, bk, k_len, dropout_p, h_all,
                   window=None, rep=1, cells_ref=None):
    """One (row-group, step of lane blocks, q-block, k-block) cell. Refs
    are VMEM blocks: q (Gn, bq, lq*W), k/v (Gn, bk, lk*W), kmask
    (Gn, 1, bk) additive f32, o (Gn, bq, lq*W), lse (Gn, lq*hb, 1, bq),
    lq lane blocks of hb = W // D heads side by side in the columns
    (:func:`_step_lanes`); meta (1, 2) uint32 in SMEM [dropout seed,
    global batch*head base]; scratch acc (Gn, bq, lq*W) f32, m/l
    (Gn*lq*hb, bq, 128) f32, every lane a row's value. A lane block is a
    static column slice of the refs; a head's scores, softmax, dropout
    and accumulation are what they were when it had a block of its own.
    The last k-block writes m + log l as (1, bq) rows: the step's heads
    side by side in the lanes of one (bq, 128) array, transposed once."""
    Gn, lq, lk = q_ref.shape[0], q_ref.shape[2] // W, k_ref.shape[2] // W
    own, first, last, scores, keep = _cell(
        cells_ref, 2, meta_ref, Gn, W, D, bq, bk, scale, causal, k_len,
        dropout_p, h_all, window, lq)
    hb = len(own)
    heads = lq * hb                               # of one row of the step

    @pl.when(first())
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    for g, j, qc, kc, shared, _ in _step_blocks(Gn, lq, lk, W):
        q = q_ref[g, :, qc]
        if shared:
            k, v, kmask_row = k_ref[g, :, kc], v_ref[g, :, kc], kmask_ref[g]
            turned = {0: (k, v)}    # k and v by the lanes they are turned
        acc = acc_ref[g, :, qc]
        for hh in range(hb):
            head = j * hb + hh
            slot = g * heads + head
            lanes = _kv_shift(head, hb, rep, D)
            if lanes not in turned:
                turned[lanes] = (_turn(k, lanes), _turn(v, lanes))
            k_h, v_h = turned[lanes]
            s = scores(_own(q, own[hh]), k_h, kmask_row)
            m_prev = m_ref[slot, :, :1]                      # (bq, 1)
            l_prev = l_ref[slot, :, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                           # (bq, bk) f32
            alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            kept = keep(g, head)
            pv = p if kept is None else p * kept
            # p·v fills all W columns; this head's are kept
            new = acc * alpha + lax.dot_general(
                pv.astype(v.dtype), v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = new if own[hh] is None else lax.select(own[hh], new, acc)
            m_ref[slot] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[slot] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        acc_ref[g, :, qc] = acc

    @pl.when(last())
    def _finalize():
        lane = lax.broadcasted_iota(jnp.int32, m_ref.shape[1:], 1)
        for g, j, qc, *_ in _step_blocks(Gn, lq, lk, W):
            acc = out = acc_ref[g, :, qc]
            for hh in range(hb):
                slot = g * heads + j * hb + hh
                safe_l = jnp.maximum(l_ref[slot], 1e-30)      # (bq, 128)
                out = acc / safe_l[:, :1] if own[hh] is None \
                    else lax.select(own[hh], acc / safe_l[:, :1], out)
                # every lane of m and l is the row's value: lane ``slot``
                # of ``lse`` takes this head's
                head = m_ref[slot] + jnp.log(safe_l)
                lse = head if slot == 0 else jnp.where(lane == slot, head,
                                                       lse)
            o_ref[g, :, qc] = out.astype(o_ref.dtype)
        rows = lse.T                                          # (128, bq)
        for slot in range(Gn * heads):
            lse_ref[slot // heads, slot % heads] = rows[slot:slot + 1]


def _addressing(arrays, H, kind, Hkv=None):
    """The static numbers of one build from its operands' shapes:
    (N, Tq, Tk, C, D, W, hb, Gn, lb, bq, bk). ``arrays`` is (q, k, v),
    each (N, T, C = H*D), or (qkv,), their (N, T, 3C) fusion. With
    ``Hkv`` key/value heads to the H query heads, k and v are
    (N, T, Hkv*D) and a head is a lane block. The G heads of a grid step
    are Gn rows of ``lb`` lane blocks (:func:`_step_heads`)."""
    N, Tq = arrays[0].shape[:2]
    Tk = arrays[-1].shape[1]
    C = arrays[0].shape[2] // (3 if len(arrays) == 1 else 1)
    D = C // H
    if _lane_block(C, D) is None:
        raise ValueError(
            f"flash attention: {H} heads of {D} columns do not come apart "
            f"into 128-lane blocks of whole heads (flash_legal says so)")
    W, hb = _lane_block(C, D)
    if Hkv not in (None, H) and (
            not _groups_legal(H, Hkv, D) or len(arrays) != 3
            or arrays[-1].shape[2] != Hkv * D):
        raise ValueError(
            f"flash attention: {H} query heads over {Hkv} key/value heads "
            f"of {D} columns: a group needs H a multiple of Hkv, k and v "
            f"of Hkv*D columns that are whole lane blocks of the query "
            f"side's width (128: two heads of 64, or one of 128 or more) "
            f"(flash_legal says so)")
    rep = H // (Hkv or H)
    G, bq, bk = _block_sizes(N * H, Tq, Tk, D, arrays[0].dtype, kind,
                             N=N, rep=rep)
    if rep > 1 and hb > 1 and G % (rep * hb):
        raise ValueError(
            f"flash attention: {G} heads a grid step, where a step of "
            f"{D}-wide grouped heads holds the {rep * hb} that read one "
            f"key/value lane block (flash_legal says so)")
    Gn, lb = _step_heads(N, C, D, G, rep)
    return N, Tq, Tk, C, D, W, hb, Gn, lb, bq, bk


def _operands(arrays, C, W, pq, pk):
    """((q, k, v), their offsets in blocks of W columns, a grid step's
    own) as the kernels address them: a fused (N, T, 3C) array three
    times, at 0, C/W and 2C/W, where its lane blocks are whole 128s and
    no sequence needs padding to its blocks (pq, pk rows); else three
    arrays, padded."""
    if len(arrays) == 1:
        if W % _LANES == 0 and not pq and not pk:
            return arrays * 3, (0, C // W, 2 * C // W)
        arrays = jnp.split(arrays[0], 3, axis=-1)
    q, k, v = arrays
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    return (q, k, v), (0, 0, 0)


def _mask_operand(kmask, N, Tk, pk):
    """(N, Tk) additive mask or None -> the kernels' (N, 1, Tk + pk) f32
    operand, one row a batch row."""
    if kmask is None:
        return jnp.zeros((N, 1, Tk + pk), jnp.float32)
    return jnp.pad(kmask.astype(jnp.float32), ((0, 0), (0, pk)))[:, None, :]


def _count_build(kind, H, D, hb, offs, rep=1):
    key = (kind, H, D, hb, offs != (0, 0, 0))
    head_blocks[key] = head_blocks.get(key, 0) + 1
    if rep > 1:
        key = (kind, H, H // rep, D)
        group_builds[key] = group_builds.get(key, 0) + 1


def _fa_forward(arrays, kmask, meta, H, causal, dropout_p, interpret, h_all,
                Hkv=None, window=None):
    """arrays: (q, k, v), each (N, T, H*D) as the model holds them, or
    (qkv,), the fused (N, T, 3*H*D) projection; the kernels' blocks
    address them in place (:func:`_block_specs`). kmask: (N, Tk) additive
    f32 or None. meta: (1, 2) uint32 [dropout seed, global batch*head
    base]. ``Hkv``: key/value heads where they are fewer than the H
    query heads (k, v: (N, T, Hkv*D)); ``window``: with ``causal``, keep
    a score iff i - j < window. Returns (out (N, Tq, H*D), lse
    (N, H, 1, Tq), Tq on the lanes as the kernel wrote it), sliced back
    from the blocks' padding -- the backward re-pads them for its own
    (possibly different) tiling."""
    N, Tq, Tk, C, D, W, hb, Gn, lb, bq, bk = _addressing(arrays, H, 'fwd',
                                                          Hkv)
    rep = H // (Hkv or H)
    lq, _ = _step_lanes('fwd', lb, rep)
    dtype = arrays[0].dtype
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    pq, pk = nq * bq - Tq, nk * bk - Tk
    (q, k, v), (qo, ko, vo) = _operands(arrays, C, lb * W, pq, pk)
    _count_build('fwd', H, D, hb, (qo, ko, vo), rep)

    kw = dict(scale=1.0 / math.sqrt(D), causal=causal, W=W, D=D, bq=bq,
              bk=bk, k_len=Tk, dropout_p=float(dropout_p), h_all=h_all,
              window=window, rep=rep)
    seq, row, mask = _block_specs('fwd', Gn, hb, bq, bk, W, causal, lb, rep)
    cells = _causal_cell_table('fwd', nq, nk, bq, bk, by_row=True,
                               window=window) if causal else None
    out, lse = _call(
        _fa_fwd_kernel, kw, cells,
        (N // Gn, C // (lq * W)) + (() if causal else (nq, nk)),
        in_specs=[seq('q', qo), seq('k', ko), seq('k', vo), mask,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[seq('q'), row],
        out_shape=[jax.ShapeDtypeStruct((N, nq * bq, C), dtype),
                   jax.ShapeDtypeStruct((N, H, 1, nq * bq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((Gn, bq, lq * W), jnp.float32),
                        pltpu.VMEM((Gn * lq * hb, bq, 128), jnp.float32),
                        pltpu.VMEM((Gn * lq * hb, bq, 128), jnp.float32)],
        interpret=interpret, name=_scopes.FLASH_FWD,
    )(q, k, v, _mask_operand(kmask, N, Tk, pk), meta)
    if pq:
        out = out[:, :Tq]
        lse = lse[..., :Tq]
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _fa_dq_kernel(q_ref, k_ref, v_ref, kmask_ref, meta_ref, do_ref,
                  lse_ref, delta_ref, dq_ref, dq_acc, stat_col, *,
                  scale, causal, W, D, bq, bk, k_len, dropout_p, h_all,
                  window=None, rep=1, cells_ref=None):
    """dq for one q-block of one step's lane blocks, accumulated over
    k-blocks (grid (N/Gn, steps, nq, nk)), written in the operands'
    dtype. lse and delta arrive as (Gn, lq*hb, 1, bq) rows and are wanted
    down the sublanes, beside the (bq, bk) scores: the first k-block of a
    q-block lays them into ``stat_col`` (bq, 2*Gn*lq*hb), a column a head
    and statistic, with one transpose an outer block."""
    Gn, lq, lk = q_ref.shape[0], q_ref.shape[2] // W, k_ref.shape[2] // W
    own, first, last, scores, keep = _cell(
        cells_ref, 2, meta_ref, Gn, W, D, bq, bk, scale, causal, k_len,
        dropout_p, h_all, window, lq)
    hb = len(own)
    heads = lq * hb                               # of one row of the step
    all_heads = Gn * heads  # lse in columns [0, all_heads), delta the next

    @pl.when(first())
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        stat_col[:] = _as_columns([ref[g, hh] for ref in (lse_ref, delta_ref)
                                   for g in range(Gn) for hh in range(heads)])

    for g, j, qc, kc, shared, _ in _step_blocks(Gn, lq, lk, W):
        q = q_ref[g, :, qc]
        if shared:
            k, kmask_row = k_ref[g, :, kc], kmask_ref[g]
            k32 = k.astype(jnp.float32)                   # (bk, W)
            v32 = v_ref[g, :, kc].astype(jnp.float32)
            turned = {0: (k, k32, v32)}
        do32 = do_ref[g, :, qc].astype(jnp.float32)       # (bq, W)
        dq = dq_acc[g, :, qc]
        for hh in range(hb):
            head = j * hb + hh
            slot = g * heads + head
            lanes = _kv_shift(head, hb, rep, D)
            if lanes not in turned:
                k32_h, v32_h = _turn(k32, lanes), _turn(v32, lanes)
                turned[lanes] = (k32_h.astype(k.dtype), k32_h, v32_h)
            k_h, k32_h, v32_h = turned[lanes]
            s = scores(_own(q, own[hh]), k_h, kmask_row)
            p = jnp.exp(s - stat_col[:, slot:slot + 1])   # (bq, bk)
            dp = lax.dot_general(
                _own(do32, own[hh]), v32_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (bq, bk)
            kept = keep(g, head)
            if kept is not None:
                dp = dp * kept
            delta = stat_col[:, all_heads + slot:all_heads + slot + 1]
            ds = p * (dp - delta) * scale                 # (bq, bk)
            # ds·k fills all W columns; this head's are kept
            dq = dq + _own(lax.dot_general(
                ds, k32_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32), own[hh])
        dq_acc[g, :, qc] = dq

    @pl.when(last())
    def _finalize():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, kmask_ref, meta_ref, do_ref,
                   lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                   kmask_col, *,
                   scale, causal, W, D, bq, bk, k_len, dropout_p, h_all,
                   window=None, rep=1, cells_ref=None):
    """dk/dv for one k-block of one step's lane blocks, accumulated over
    q-blocks (grid (N/Gn, steps, nk, nq): k-block is program 2, q-block
    program 3), written in the operands' dtype. A cell is computed keys
    down the sublanes, (bk, bq) (:func:`_cell`): lse and delta are the
    (1, bq) rows they arrive as, and dv += p^T·dO, dk += ds^T·q are plain
    products. The additive key mask is wanted as a column here; the first
    q-block of a k-block lays it into ``kmask_col`` (bk, Gn), once an
    outer block. With grouped-query heads the steps are over the
    key/value heads, q, dO, lse and delta come the whole group wide, and
    the group's heads add into the one dk and dv here, a head at a
    time: a query head whose key/value head lies in the other lanes of the
    block has its q and dO turned there (:func:`_kv_shift`)."""
    Gn, lq, lk = k_ref.shape[0], q_ref.shape[2] // W, k_ref.shape[2] // W
    own, first, last, scores, keep = _cell(
        cells_ref, 3, meta_ref, Gn, W, D, bq, bk, scale, causal, k_len,
        dropout_p, h_all, window, lq)
    hb = len(own)

    @pl.when(first())
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        kmask_col[:] = _as_columns([kmask_ref[g] for g in range(Gn)])

    for g, j, qc, kc, shared, done in _step_blocks(Gn, lq, lk, W):
        if shared:
            k, kmask = k_ref[g, :, kc], kmask_col[:, g:g + 1]
            v32 = v_ref[g, :, kc].astype(jnp.float32)     # (bk, W)
            dk, dv = dk_acc[g, :, kc], dv_acc[g, :, kc]
        q = q_ref[g, :, qc]
        do32 = do_ref[g, :, qc].astype(jnp.float32)       # (bq, W)
        for hh in range(hb):
            head = j * hb + hh
            # q and dO with the other heads' columns zeroed, in the
            # columns of the key/value head they read: what they are
            # contracted into lands in that head's columns alone
            lanes = -_kv_shift(head, hb, rep, D) % W
            q_own, do_own = (_turn(_own(x, own[hh]), lanes)
                             for x in (q, do32))
            s = scores(q_own, k, kmask)
            p = jnp.exp(s - lse_ref[g, head])             # (bk, bq)
            kept = keep(g, head)
            pv = p if kept is None else p * kept
            # dv_j += sum_i P_drop_ij dO_i
            dv = dv + lax.dot_general(
                pv, do_own, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # (bk, W)
            dp = lax.dot_general(
                v32, do_own, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (bk, bq)
            if kept is not None:
                dp = dp * kept
            ds = p * (dp - delta_ref[g, head]) * scale    # (bk, bq)
            dk = dk + lax.dot_general(
                ds, q_own.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # (bk, W)
        if done:
            dk_acc[g, :, kc], dv_acc[g, :, kc] = dk, dv

    @pl.when(last())
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _fa_backward(arrays, kmask, meta, H, causal, dropout_p, interpret,
                 h_all, Hkv, window, out, lse, do):
    """Pallas backward: recompute probability blocks from the saved LSE.
    Returns the cotangents of ``arrays``, in their dtype: (dq, dk, dv),
    or (dqkv,), the three side by side."""
    N, Tq, Tk, C, D, W, hb, Gn, lb, bq, bk = _addressing(arrays, H, 'bwd',
                                                          Hkv)
    rep = H // (Hkv or H)
    dtype = arrays[0].dtype
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    pq, pk = nq * bq - Tq, nk * bk - Tk
    (q, k, v), (qo, ko, vo) = _operands(arrays, C, lb * W, pq, pk)
    if pq:
        # padded q rows contribute nothing: their dO is zero, so dv += p·0
        # and ds = p·(0 - 0) vanish; lse pads as 0 harmlessly
        do = jnp.pad(do, ((0, 0), (0, pq), (0, 0)))
        out = jnp.pad(out, ((0, 0), (0, pq), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, pq)))

    # delta_i = dO_i · O_i (rowwise, a head at a time) — cheap XLA
    # preprocessing, laid out as the lse is: (N, H, 1, Tq_pad). The sum
    # over a head's D columns is a product with the 0/1 matrix that says
    # which head a column is of: a reduction over part of the lanes would
    # have XLA copy the (N, T, C) product into another layout first
    head_of = (jnp.arange(C)[:, None] // D == jnp.arange(H)).astype(
        jnp.float32)
    delta = jnp.einsum(
        'ntc,ch->nht', do.astype(jnp.float32) * out.astype(jnp.float32),
        head_of, precision=lax.Precision.HIGHEST).reshape(lse.shape)

    kw = dict(scale=1.0 / math.sqrt(D), causal=causal, W=W, D=D, bq=bq,
              bk=bk, k_len=Tk, dropout_p=float(dropout_p), h_all=h_all,
              window=window, rep=rep)
    operands = (q, k, v, _mask_operand(kmask, N, Tk, pk), meta, do, lse,
                delta)
    calls = []
    for kind, kernel, plane, side, n_out in (
            ('bwd_dq', _fa_dq_kernel, (nq, nk), 'q', 1),
            ('bwd_dkv', _fa_dkv_kernel, (nk, nq), 'k', 2)):
        _count_build(kind, H, D, hb, (qo, ko, vo), rep)
        # the grid's steps are over the columns dq, or dk and dv, have:
        # with grouped heads dk/dv's run over the key/value heads, each
        # a whole group of query heads wide
        lq, lk = _step_lanes(kind, lb, rep)
        cols, lanes = (C, lq) if side == 'q' else (C // rep, lk)
        seq, row, mask = _block_specs(kind, Gn, hb, bq, bk, W, causal, lb,
                                      rep)
        rows, blk = (nq * bq, bq) if side == 'q' else (nk * bk, bk)
        cells = _causal_cell_table(kind, nq, nk, bq, bk, by_row=side == 'q',
                                   window=window) if causal else None
        # what an outer block turns into columns once: the statistics in
        # dq, the key mask in dk/dv
        columns = pltpu.VMEM(
            (bq, 2 * Gn * lq * hb) if side == 'q' else (bk, Gn), jnp.float32)
        calls.append(_call(
            kernel, kw, cells,
            (N // Gn, cols // (lanes * W)) + (() if causal else plane),
            in_specs=[seq('q', qo), seq('k', ko), seq('k', vo), mask,
                      pl.BlockSpec(memory_space=pltpu.SMEM), seq('q'),
                      row, row],
            out_specs=[seq(side)] * n_out,
            out_shape=[jax.ShapeDtypeStruct((N, rows, cols), dtype)] * n_out,
            scratch_shapes=[pltpu.VMEM((Gn, blk, lanes * W),
                                       jnp.float32)] * n_out
            + [columns],
            interpret=interpret,
            name=_scopes.FLASH_BWD_DQ if side == 'q'
            else _scopes.FLASH_BWD_DKV)(*operands))
    (dq,), (dk, dv) = calls
    grads = (dq[:, :Tq], dk[:, :Tk], dv[:, :Tk])
    if len(arrays) == 1:
        return (jnp.concatenate(grads, axis=-1),)
    return grads


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(arrays, kmask, meta, H, causal, dropout_p, interpret, h_all,
           Hkv=None, window=None):
    out, _ = _fa_forward(arrays, kmask, meta, H, causal, dropout_p,
                         interpret, h_all, Hkv, window)
    return out


def _flash_fwd(arrays, kmask, meta, H, causal, dropout_p, interpret, h_all,
               Hkv, window):
    out, lse = _fa_forward(arrays, kmask, meta, H, causal, dropout_p,
                           interpret, h_all, Hkv, window)
    # the named out is the primal result too: what follows reads the
    # array a checkpoint region keeps, and its second run needs no kernel
    out = checkpoint_name(out, _scopes.FLASH_OUT)
    lse = checkpoint_name(lse, _scopes.FLASH_LSE)
    return out, (arrays, kmask, meta, out, lse)


def _flash_bwd(H, causal, dropout_p, interpret, h_all, Hkv, window, res, do):
    arrays, kmask, meta, out, lse = res
    grads = _fa_backward(arrays, kmask, meta, H, causal, dropout_p,
                         interpret, h_all, Hkv, window, out, lse, do)
    dmask = None if kmask is None else jnp.zeros_like(kmask)
    dmeta = onp.zeros(meta.shape, jax.dtypes.float0)
    return grads, dmask, dmeta


_flash.defvjp(_flash_fwd, _flash_bwd)


def _groups_legal(H, Hkv, D):
    """Can ``H`` query heads of ``D`` columns read ``Hkv`` key/value heads
    in the kernels' lane blocks: H a multiple of Hkv, and the key/value
    heads' columns whole lane blocks of the query side's width -- a head
    of a multiple of 128 columns, or 64-wide heads two to a block, of
    which there are then an even number."""
    blocks = _lane_block(H * D, D)
    return not H % Hkv and blocks is not None and Hkv * D % blocks[0] == 0


def flash_legal(BH, Tq, Tk, D, dtype, num_heads=1, num_kv_heads=None) -> bool:
    """Can the forward AND backward kernels be built for this shape at
    the block sizes they would use? The heads' columns have to come
    apart into lane blocks (:func:`_lane_block`); grouped-query heads
    (``num_kv_heads`` fewer than ``num_heads``) need H a multiple of Hkv
    and the key/value heads in whole lane blocks (:func:`_groups_legal`:
    one head of 128 or more columns a block, or two of 64), and with
    64-wide heads a grid step of all the query heads that read one
    key/value block; and the static Mosaic rules of
    ops/autotune.check_candidate hold for what :func:`_block_sizes`
    resolves — the verdict ``multi_head_attention`` routes on."""
    from . import autotune
    lanes = _lane_block(num_heads * D, D)
    if lanes is None or num_kv_heads not in (None, num_heads) and \
            not _groups_legal(num_heads, num_kv_heads, D):
        return False
    N, rep = BH // num_heads, num_heads // (num_kv_heads or num_heads)
    # 64-wide grouped heads: a step holds every query head of its block
    step = rep * lanes[1] if rep > 1 and lanes[1] > 1 else 1
    for kind in ('fwd', 'bwd'):
        blocks = _block_sizes(BH, Tq, Tk, D, dtype, kind, N, rep)
        if blocks[0] % step or not autotune.check_candidate(
                BH, Tq, Tk, D, jnp.dtype(dtype), kind, *blocks, N, rep)[0]:
            return False
    return True


def flash_mha(arrays, num_heads, key_mask=None, causal=False, dropout_p=0.0,
              dropout_seed=None, interpret=None, bh_base=None,
              bh_split=None, num_kv_heads=None, window=None):
    """Flash attention on the model's own arrays. ``arrays``: (q, k, v),
    each (N, T, H*D), or (qkv,), the fused (N, T, 3*H*D) projection whose
    thirds they are; the kernels read them in place, two 64-wide heads to
    a 128-lane block (:func:`_lane_block`). key_mask: optional (N, Tk)
    additive f32 mask (0 = keep, large-negative = drop) or boolean
    (True = keep). dropout_p: in-kernel attention-probability dropout;
    dropout_seed: uint32 scalar/array seeding the kernel PRNG (required
    when dropout_p > 0). Returns (N, Tq, H*D).

    num_kv_heads: grouped-query attention, k and v being (N, T, Hkv*D)
    and query head h reading key/value head h // (H // Hkv); a head of a
    multiple of 128 columns, or of 64 with Hkv even
    (:func:`_groups_legal`). window: with ``causal``, a query
    sees the ``window`` keys up to its own (score (i, j) kept iff
    0 <= i - j < window); the kernels then visit the band's cells only.

    interpret: True runs the kernels through the Pallas interpreter,
    False compiles them with Mosaic (and fails where there is no TPU),
    None takes :func:`default_interpret`.

    bh_base / bh_split: for a call that holds one shard of a larger
    (batch, heads) problem — the global batch·head id of its first
    (row, head) (uint32 scalar, may be traced) and the static
    (H_local, H_global) pair — see :func:`_cell`."""
    if interpret is None:
        interpret = default_interpret()
    N, Tk = arrays[-1].shape[:2]
    if key_mask is not None:
        if key_mask.shape != (N, Tk):
            raise ValueError(
                f"key_mask shape {tuple(key_mask.shape)} is not (batch, "
                f"keys) = {(N, Tk)}")
        if key_mask.dtype == jnp.bool_:
            key_mask = jnp.where(key_mask, 0.0, _NEG_INF)
        key_mask = key_mask.astype(jnp.float32)
    dropout_p = float(dropout_p)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    seed = jnp.zeros((), jnp.uint32) if dropout_seed is None \
        else jnp.asarray(dropout_seed, jnp.uint32).reshape(())
    base = jnp.zeros((), jnp.uint32) if bh_base is None \
        else jnp.asarray(bh_base, jnp.uint32).reshape(())
    meta = jnp.stack([seed, base]).reshape(1, 2)
    h_all = int(num_heads) if bh_split is None else int(bh_split[1])
    if window is not None and not causal:
        raise ValueError("flash_mha: a window is a causal layer's")
    if num_kv_heads is not None and int(num_kv_heads) == int(num_heads):
        num_kv_heads = None
    return _flash(tuple(arrays), key_mask, meta, int(num_heads), causal,
                  dropout_p, bool(interpret), h_all,
                  None if num_kv_heads is None else int(num_kv_heads),
                  None if window is None else int(window))


def flash_attention(q, k, v, key_mask=None, **kwargs):
    """:func:`flash_mha` for callers that hold q/k/v as (B, H, T, D):
    they pay the transposes to (B, T, H*D) and back that the model's own
    arrays do not need. Returns (B, H, Tq, D)."""
    B, H, Tq, D = q.shape

    def merge(x):
        return x.transpose(0, 2, 1, 3).reshape(B, x.shape[2], H * D)
    out = flash_mha((merge(q), merge(k), merge(v)), H, key_mask, **kwargs)
    return out.reshape(B, Tq, H, D).transpose(0, 2, 1, 3)
