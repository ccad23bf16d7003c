"""Pallas flash-attention kernels for TPU (forward AND backward).

The fused MHA op (ops/attention.py multi_head_attention) routes here. This
is the TPU-native realisation of the reference's interleaved_matmul
attention kernels (ref: src/operator/contrib/transformer.cc:650-828): one
hand-written kernel instead of two batched-GEMM ops, with the T×T score
matrix living only in VMEM.

Forward: grid (B*H/G, Tq/BQ, Tk/BK) — each invocation processes G
batch·head slices (per-invocation overhead on the TPU is tens of
microseconds, so tiny per-head grids are dispatch-bound; G amortises it).
Scratch (VMEM) carries the online-softmax state (running max m, running
sum l, f32 accumulator) across k-blocks; the final k-block normalises and
writes the output block plus the logsumexp (saved for the backward pass).

Backward: two Pallas kernels — dq (grid (BH/G, Tq/BQ, Tk/BK), accumulating
over k-blocks) and dk/dv (grid (BH/G, Tk/BK, Tq/BQ), accumulating over
q-blocks) — both recompute the probability block from the saved LSE
(flash-attention backward recurrence), so live memory stays O(T).

Without ``causal`` every (q-block, k-block) cell of those grids is
computed. With it the grid is (B*H/G, listed cells): the cells that hold
a score at or under the diagonal (:func:`_cell_live`), row by row for the
forward and dq, column by column for dk/dv, from a small table the
kernels and their index maps read as a scalar-prefetch operand
(:func:`_causal_cell_table`). A cell wholly above the diagonal is no grid
step at all: no matmul, no hash, no exp, no copy. Such a cell used to add
exp(-1e30 - m) = 0 to every accumulator, and the listed cells keep their
order, so no result changes by a bit. The cells the diagonal crosses
still mask element by element (:func:`_masked_scores`). All of it hangs on
the static ``causal`` flag: the non-causal build is the kernels, grids
and index maps it was before. Each causal build counts its cells in
``causal_cells``.

Attention dropout runs INSIDE the kernels: the keep mask is a
counter-based hash (murmur3 finalizer) of the global (batch·head, q, k)
element coordinates mixed with a per-call seed, so the forward and both
backward kernels regenerate bit-identical masks with no T×T tensor ever
materialised, and the same bits fall out in Mosaic and interpreter modes.
Softmax statistics (m, l) are computed on the UNdropped probabilities —
dropout scales only the value accumulation — matching the standard
softmax→dropout→matmul recipe.

Mosaic layout constraints honoured throughout: every block's trailing two
dims are (multiple-of-8, multiple-of-128) or equal to the array dims —
the key-mask rides as (BH, 1, Tk) with (G, 1, bk) blocks and the LSE as
(BH, Tq, 1) with (G, bq, 1) blocks (a (1, bk) 2-D mask block is refused).
The two scalars the kernels read (dropout seed, global batch·head base)
ride in SMEM.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes as _scopes

_NEG_INF = -1e30

# every grid here is (batch·head groups, outer seq blocks, inner seq
# blocks) with the scratch accumulators carried over the innermost axis
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'arbitrary'))

# the causal grid is (batch·head groups, listed cells), the accumulators
# carried over the cells of one row or column
_CAUSAL_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'arbitrary'))

# trace-time telemetry of the causal skip, beside ops.attention.route_counts
# and autotune.decisions(): {(kind, live cells, cells of the full plane):
# kernel builds}, the plane being one head-group's (q-block, k-block) grid.
# Static numbers; a non-causal build records nothing.
causal_cells = {}


def _cell_live(qb, kb, bq, bk):
    """Does cell (q-block qb, k-block kb) hold a score at or under the
    causal diagonal (top-left aligned, as :func:`_masked_scores` masks)?
    True iff its last query row sees its first key. A window (ROADMAP R5)
    is a second inequality here."""
    return qb * bq + (bq - 1) >= kb * bk


def _causal_cell_table(kind, nq, nk, bq, bk, by_row):
    """int32 (4, n): the cells a causal kernel visits, in grid order, as
    rows [q-block, k-block, first of its line, last of its line]. A line
    is a q-block row (``by_row``: forward, dq) or a k-block column
    (dk/dv), walked in ascending order as the full grid walks it, so the
    accumulators add up in the same order. A line without a live cell (a
    k-block no query sees, when Tk > Tq) keeps one dead cell: it adds
    exact zeros, as every dead cell used to, and the line's output is
    still initialised and written. Counts the build in ``causal_cells``."""
    table, live_cells = [], 0
    for outer in range(nq if by_row else nk):
        line = [(outer, inner) if by_row else (inner, outer)
                for inner in range(nk if by_row else nq)]
        live = [cell for cell in line if _cell_live(*cell, bq, bk)]
        live_cells += len(live)
        live = live or line[-1:]
        table += [(qb, kb, cell == 0, cell == len(live) - 1)
                  for cell, (qb, kb) in enumerate(live)]
    key = (kind, live_cells, nq * nk)
    causal_cells[key] = causal_cells.get(key, 0) + 1
    return onp.asarray(table, onp.int32).T


def _step_cell(cells_ref, q_axis):
    """(q-block, k-block, is-first, is-last) of this grid step, the last
    two as thunks evaluated where the kernel asks. Without a table: the
    full grid, q-blocks on grid axis ``q_axis`` (1: forward and dq, 2:
    dk/dv) and the accumulators carried over axis 2. With one: the
    causal grid's listed cell (:func:`_causal_cell_table`)."""
    if cells_ref is None:
        outer, inner = pl.program_id(1), pl.program_id(2)
        n_inner = pl.num_programs(2)
        qb, kb = (outer, inner) if q_axis == 1 else (inner, outer)
        return qb, kb, lambda: inner == 0, lambda: inner == n_inner - 1
    cell = pl.program_id(1)
    return (cells_ref[0, cell], cells_ref[1, cell],
            lambda: cells_ref[2, cell] == 1, lambda: cells_ref[3, cell] == 1)


def _causal_specs(G, bq, bk, D):
    """BlockSpecs over the causal grid (head group b, listed cell c) with
    the cell table as scalar-prefetch operand, by role: q-side block,
    q-side column (lse, delta), k-side block, key mask."""
    return (pl.BlockSpec((G, bq, D), lambda b, c, cells: (b, cells[0, c], 0)),
            pl.BlockSpec((G, bq, 1), lambda b, c, cells: (b, cells[0, c], 0)),
            pl.BlockSpec((G, bk, D), lambda b, c, cells: (b, cells[1, c], 0)),
            pl.BlockSpec((G, 1, bk), lambda b, c, cells: (b, 0, cells[1, c])))


def _causal_call(kernel, cells, groups, in_specs, out_specs, scratch_shapes,
                 **call):
    """``pallas_call`` of ``kernel`` over the grid (groups, listed cells),
    already applied to the cell table."""
    def with_table(cells_ref, *refs):
        return kernel(*refs, cells_ref=cells_ref)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(groups, cells.shape[1]),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)
    return functools.partial(
        pl.pallas_call(with_table, grid_spec=grid_spec,
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


def _block_sizes(BH, Tq, Tk, D, dtype, kind='fwd'):
    """(G, bq, bk): head-group size and MXU/VPU-aligned seq blocks.
    Sublane minimum is 8 (f32) / 16 (bf16); lanes are 128. G amortises
    the per-invocation kernel overhead over several batch·head slices.

    kind='bwd' sizes the backward kernels, whose per-cell stack holds
    ~6 live (bq, bk) f32 temporaries (s, p, dp, ds, keep, pv) vs the
    forward's ~3, so backward defaults to 256-wide blocks where the
    forward takes 512. Established on a v5e with libtpu 0.0.34 (chip
    run, PR 23): these defaults — forward (4, 512, 512), backward
    (4, 256, 256) — compile under Mosaic's default scoped-VMEM limit,
    with no ``vmem_limit_bytes``, at BERT-base (BH=384, T=512, D=64,
    bf16, padding mask, dropout) and at GPT-2's causal T=1024 (BH=96).
    Wider backward blocks on this installation: not measured.

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
    G = 1
    for cand in (4, 8, 2):    # 4 measured best on v5e at BERT-base shape
        if BH % cand == 0:
            G = cand
            break
    from . import autotune
    return autotune.resolve(autotune.KERNEL_FA, BH, Tq, Tk, D,
                            jnp.dtype(dtype), kind, default=(G, bq, bk))


# ---------------------------------------------------------------------------
# portable counter-based dropout bits
# ---------------------------------------------------------------------------

def _global_bh(meta_ref, local_bh, bh_split):
    """Global batch·head id (uint32) of this call's slice ``local_bh``.

    A call that holds the whole (B, H) problem numbers its slices
    0..BH-1 and ``meta_ref[0, 1]`` is 0. A call mapped over a mesh
    (ops/attention.py) holds one shard: ``meta_ref[0, 1]`` is the global
    id of its first slice, and ``bh_split=(H_local, H)`` re-strides the
    local (batch, head) numbering into the global one when the heads are
    sharded too — so a sharded run draws the same dropout bits as the
    unsharded one."""
    if bh_split is not None:
        h_loc, h_all = bh_split
        local_bh = lax.div(local_bh, h_loc) * h_all + lax.rem(local_bh, h_loc)
    return meta_ref[0, 1] + local_bh.astype(jnp.uint32)


def _dropout_keep(seed, bh, q_base, k_base, bq, bk, rate):
    """(bq, bk) float32 keep/(1-rate) multiplier for one attention block.

    Hash of (seed, global element id) through the murmur3 finalizer.
    uint32 arithmetic wraps identically in Mosaic, XLA and the Pallas
    interpreter, so forward and backward kernels regenerate the same
    mask from coordinates alone — grid iteration order is irrelevant,
    and the row mixing uses a CONSTANT odd multiplier (not the padded
    key length) so the backward kernels may tile the sequence
    differently from the forward and still reproduce bit-identical
    masks. The odd multiplier is a bijection on uint32, so no two rows
    ever share a whole mask row (a power-of-two stride would duplicate
    rows every 2^32/stride queries)."""
    rows = q_base + lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
    cols = k_base + lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
    return _counter_keep(seed, bh, rows, cols, rate)


def _counter_keep(seed, bh, rows, cols, rate):
    """The shared hash core: keep/(1-rate) multipliers from broadcastable
    uint32 (bh, rows, cols) index arrays. Used by the Pallas kernels via
    _dropout_keep and by ring attention (parallel/ring_attention.py) with
    GLOBAL sequence positions, so both regenerate identical masks from
    coordinates alone."""
    h = rows * jnp.uint32(0x9E3779B1) + cols
    h = h + bh * jnp.uint32(0x9e3779b9)
    h = h ^ seed
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85ebca6b)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xc2b2ae35)
    h = h ^ (h >> jnp.uint32(16))
    thresh = jnp.uint32(min(int(rate * 2.0**32), 2**32 - 1))
    keep = (h >= thresh).astype(jnp.float32)
    return keep * jnp.float32(1.0 / (1.0 - rate))


def _masked_scores(q, k, kmask_row, qb, kb, bq, bk, scale, causal, k_len):
    """(bq, bk) f32 scores for one (q-block, k-block) cell of one head:
    QK^T * scale, key-padding cut at k_len, additive user mask, causal.
    The causal ``where`` is what masks inside the cells the diagonal
    crosses (and, needlessly, in those wholly under it); the cells wholly
    above it are not in the causal grid (:func:`_cell_live`)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(k_pos < k_len, s, _NEG_INF)
    s = s + kmask_row
    if causal:
        q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fa_fwd_kernel(q_ref, k_ref, v_ref, kmask_ref, meta_ref,
                   o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                   scale, causal, G, bq, bk, k_len, dropout_p, bh_split,
                   cells_ref=None):
    """One (head-group, q-block, k-block) cell. Refs are VMEM blocks:
    q (G, bq, D), k/v (G, bk, D), kmask (G, 1, bk) additive f32,
    o (G, bq, D), lse (G, bq, 1); meta (1, 2) uint32 in SMEM
    [dropout seed, global batch·head base];
    scratch acc (G, bq, D) f32, m/l (G, bq, 128) f32."""
    qb, kb, first, last = _step_cell(cells_ref, q_axis=1)

    @pl.when(first())
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    for g in range(G):
        s = _masked_scores(q_ref[g], k_ref[g], kmask_ref[g], qb, kb,
                           bq, bk, scale, causal, k_len)
        m_prev = m_ref[g, :, :1]                         # (bq, 1)
        l_prev = l_ref[g, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (bq, bk) f32
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            bh = _global_bh(meta_ref, pl.program_id(0) * G + g, bh_split)
            keep = _dropout_keep(meta_ref[0, 0], bh,
                                 jnp.uint32(qb * bq), jnp.uint32(kb * bk),
                                 bq, bk, dropout_p)
            pv = p * keep
        else:
            pv = p
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            pv.astype(v_ref.dtype), v_ref[g], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(last())
    def _finalize():
        for g in range(G):
            l = l_ref[g, :, :1]
            safe_l = jnp.maximum(l, 1e-30)
            o_ref[g] = (acc_ref[g] / safe_l).astype(o_ref.dtype)
            lse_ref[g] = m_ref[g, :, :1] + jnp.log(safe_l)


def _fa_forward(q, k, v, kmask, meta, causal, dropout_p, interpret,
                bh_split):
    """q/k/v: (BH, T, D) flattened over batch*heads.
    kmask: (BH, Tk) additive f32 or None. meta: (1, 2) uint32
    [dropout seed, global batch·head base].
    Returns (out, lse), both sliced back to (BH, Tq[, D]) — the backward
    re-pads them for its own (possibly different) tiling."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    G, bq, bk = _block_sizes(BH, Tq, Tk, D, q.dtype)
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    pq, pk = nq * bq - Tq, nk * bk - Tk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        if kmask is not None:
            kmask = jnp.pad(kmask, ((0, 0), (0, pk)))
    tk_pad = nk * bk
    if kmask is None:
        km3 = jnp.zeros((BH, 1, tk_pad), jnp.float32)
    else:
        km3 = kmask.astype(jnp.float32).reshape(BH, 1, tk_pad)

    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal, G=G, bq=bq, bk=bk,
        k_len=Tk, dropout_p=float(dropout_p), bh_split=bh_split)
    out_shape = [jax.ShapeDtypeStruct((BH, nq * bq, D), q.dtype),
                 jax.ShapeDtypeStruct((BH, nq * bq, 1), jnp.float32)]
    scratch = [pltpu.VMEM((G, bq, D), jnp.float32),
               pltpu.VMEM((G, bq, 128), jnp.float32),
               pltpu.VMEM((G, bq, 128), jnp.float32)]
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    if causal:
        qspec, col1, kspec, mspec = _causal_specs(G, bq, bk, D)
        call = _causal_call(
            kernel, _causal_cell_table('fwd', nq, nk, bq, bk, by_row=True),
            BH // G, in_specs=[qspec, kspec, kspec, mspec, sspec],
            out_specs=[qspec, col1], out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
            name=_scopes.FLASH_FWD)
    else:
        call = pl.pallas_call(
            kernel,
            grid=(BH // G, nq, nk),
            in_specs=[
                pl.BlockSpec((G, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((G, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((G, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((G, 1, bk), lambda b, i, j: (b, 0, j)),
                sspec,
            ],
            out_specs=[
                pl.BlockSpec((G, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((G, bq, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=_COMPILER_PARAMS,
            name=_scopes.FLASH_FWD,
        )
    out, lse = call(q, k, v, km3, meta)
    lse = lse[..., 0]
    if pq:
        out = out[:, :Tq]
        lse = lse[:, :Tq]
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _fa_dq_kernel(q_ref, k_ref, v_ref, kmask_ref, meta_ref, do_ref,
                  lse_ref, delta_ref, dq_ref, dq_acc, *,
                  scale, causal, G, bq, bk, k_len, dropout_p, bh_split,
                  cells_ref=None):
    """dq for one q-block, accumulated over k-blocks (grid (BH/G, nq, nk))."""
    qb, kb, first, last = _step_cell(cells_ref, q_axis=1)

    @pl.when(first())
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    for g in range(G):
        s = _masked_scores(q_ref[g], k_ref[g], kmask_ref[g], qb, kb,
                           bq, bk, scale, causal, k_len)
        p = jnp.exp(s - lse_ref[g])                   # (bq, bk), lse (bq,1)
        dp = jax.lax.dot_general(
            do_ref[g].astype(jnp.float32), v_ref[g].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bq, bk)
        if dropout_p > 0.0:
            bh = _global_bh(meta_ref, pl.program_id(0) * G + g, bh_split)
            keep = _dropout_keep(meta_ref[0, 0], bh,
                                 jnp.uint32(qb * bq), jnp.uint32(kb * bk),
                                 bq, bk, dropout_p)
            dp = dp * keep
        ds = p * (dp - delta_ref[g]) * scale          # (bq, bk)
        dq_acc[g] = dq_acc[g] + jax.lax.dot_general(
            ds, k_ref[g].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last())
    def _finalize():
        dq_ref[:] = dq_acc[:]


def _fa_dkv_kernel(q_ref, k_ref, v_ref, kmask_ref, meta_ref, do_ref,
                   lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                   scale, causal, G, bq, bk, k_len, dropout_p, bh_split,
                   cells_ref=None):
    """dk/dv for one k-block, accumulated over q-blocks
    (grid (BH/G, nk, nq): k-block is program 1, q-block is program 2)."""
    qb, kb, first, last = _step_cell(cells_ref, q_axis=2)

    @pl.when(first())
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    for g in range(G):
        s = _masked_scores(q_ref[g], k_ref[g], kmask_ref[g], qb, kb,
                           bq, bk, scale, causal, k_len)
        p = jnp.exp(s - lse_ref[g])                   # (bq, bk)
        do32 = do_ref[g].astype(jnp.float32)          # (bq, D)
        if dropout_p > 0.0:
            bh = _global_bh(meta_ref, pl.program_id(0) * G + g, bh_split)
            keep = _dropout_keep(meta_ref[0, 0], bh,
                                 jnp.uint32(qb * bq), jnp.uint32(kb * bk),
                                 bq, bk, dropout_p)
            pv = p * keep
        else:
            keep = None
            pv = p
        # dv_j += sum_i P_drop_ij dO_i
        dv_acc[g] = dv_acc[g] + jax.lax.dot_general(
            pv, do32, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)
        dp = jax.lax.dot_general(
            do32, v_ref[g].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bq, bk)
        if keep is not None:
            dp = dp * keep
        ds = p * (dp - delta_ref[g]) * scale          # (bq, bk)
        dk_acc[g] = dk_acc[g] + jax.lax.dot_general(
            ds, q_ref[g].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)

    @pl.when(last())
    def _finalize():
        dk_ref[:] = dk_acc[:]
        dv_ref[:] = dv_acc[:]


def _fa_backward(q, k, v, kmask, meta, causal, dropout_p, interpret,
                 bh_split, out, lse, do):
    """Pallas backward: recompute probability blocks from the saved LSE.
    Returns (dq, dk, dv) in the input dtypes."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    G, bq, bk = _block_sizes(BH, Tq, Tk, D, q.dtype, kind='bwd')
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    pq, pk = nq * bq - Tq, nk * bk - Tk
    if pq:
        # padded q rows contribute nothing: their dO is zero, so dv += p·0
        # and ds = p·(0 - 0) vanish; lse pads as 0 harmlessly
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, pq), (0, 0)))
        out = jnp.pad(out, ((0, 0), (0, pq), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pq)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        if kmask is not None:
            kmask = jnp.pad(kmask, ((0, 0), (0, pk)))
    tk_pad = nk * bk
    if kmask is None:
        km3 = jnp.zeros((BH, 1, tk_pad), jnp.float32)
    else:
        km3 = kmask.astype(jnp.float32).reshape(BH, 1, tk_pad)

    # delta_i = dO_i · O_i (rowwise) — cheap XLA preprocessing
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)            # (BH, Tq_pad, 1)
    lse3 = lse.reshape(BH, nq * bq, 1)

    kw = dict(scale=scale, causal=causal, G=G, bq=bq, bk=bk, k_len=Tk,
              dropout_p=float(dropout_p), bh_split=bh_split)
    qspec_i = pl.BlockSpec((G, bq, D), lambda b, i, j: (b, i, 0))
    kspec_j = pl.BlockSpec((G, bk, D), lambda b, i, j: (b, j, 0))
    col1_i = pl.BlockSpec((G, bq, 1), lambda b, i, j: (b, i, 0))
    mspec_j = pl.BlockSpec((G, 1, bk), lambda b, i, j: (b, 0, j))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)

    dq_shape = jax.ShapeDtypeStruct((BH, nq * bq, D), jnp.float32)
    dq_scratch = [pltpu.VMEM((G, bq, D), jnp.float32)]
    dkv_shape = [jax.ShapeDtypeStruct((BH, nk * bk, D), jnp.float32),
                 jax.ShapeDtypeStruct((BH, nk * bk, D), jnp.float32)]
    dkv_scratch = [pltpu.VMEM((G, bk, D), jnp.float32),
                   pltpu.VMEM((G, bk, D), jnp.float32)]
    operands = (q, k, v, km3, meta, do, lse3, delta)
    if causal:
        qspec, col1, kspec, mspec = _causal_specs(G, bq, bk, D)
        in_specs = [qspec, kspec, kspec, mspec, sspec, qspec, col1, col1]
        dq = _causal_call(
            functools.partial(_fa_dq_kernel, **kw),
            _causal_cell_table('bwd_dq', nq, nk, bq, bk, by_row=True),
            BH // G, in_specs=in_specs, out_specs=qspec, out_shape=dq_shape,
            scratch_shapes=dq_scratch, interpret=interpret,
            name=_scopes.FLASH_BWD_DQ)(*operands)
        dk, dv = _causal_call(
            functools.partial(_fa_dkv_kernel, **kw),
            _causal_cell_table('bwd_dkv', nq, nk, bq, bk, by_row=False),
            BH // G, in_specs=in_specs, out_specs=[kspec, kspec],
            out_shape=dkv_shape, scratch_shapes=dkv_scratch,
            interpret=interpret, name=_scopes.FLASH_BWD_DKV)(*operands)
    else:
        dq = pl.pallas_call(
            functools.partial(_fa_dq_kernel, **kw),
            grid=(BH // G, nq, nk),
            in_specs=[qspec_i, kspec_j, kspec_j, mspec_j, sspec,
                      qspec_i, col1_i, col1_i],
            out_specs=pl.BlockSpec((G, bq, D), lambda b, i, j: (b, i, 0)),
            out_shape=dq_shape,
            scratch_shapes=dq_scratch,
            interpret=interpret,
            compiler_params=_COMPILER_PARAMS,
            name=_scopes.FLASH_BWD_DQ,
        )(*operands)

        # dk/dv grid permutes (q-block, k-block): q innermost
        qspec_2 = pl.BlockSpec((G, bq, D), lambda b, j, i: (b, i, 0))
        kspec_1 = pl.BlockSpec((G, bk, D), lambda b, j, i: (b, j, 0))
        col1_2 = pl.BlockSpec((G, bq, 1), lambda b, j, i: (b, i, 0))
        mspec_1 = pl.BlockSpec((G, 1, bk), lambda b, j, i: (b, 0, j))
        dk, dv = pl.pallas_call(
            functools.partial(_fa_dkv_kernel, **kw),
            grid=(BH // G, nk, nq),
            in_specs=[qspec_2, kspec_1, kspec_1, mspec_1, sspec,
                      qspec_2, col1_2, col1_2],
            out_specs=[pl.BlockSpec((G, bk, D), lambda b, j, i: (b, j, 0)),
                       pl.BlockSpec((G, bk, D), lambda b, j, i: (b, j, 0))],
            out_shape=dkv_shape,
            scratch_shapes=dkv_scratch,
            interpret=interpret,
            compiler_params=_COMPILER_PARAMS,
            name=_scopes.FLASH_BWD_DKV,
        )(*operands)

    dq = dq[:, :Tq].astype(q.dtype)
    dk = dk[:, :Tk].astype(k.dtype)
    dv = dv[:, :Tk].astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, kmask, meta, causal, dropout_p, interpret, bh_split):
    out, _ = _fa_forward(q, k, v, kmask, meta, causal, dropout_p, interpret,
                         bh_split)
    return out


def _flash_fwd(q, k, v, kmask, meta, causal, dropout_p, interpret, bh_split):
    out, lse = _fa_forward(q, k, v, kmask, meta, causal, dropout_p,
                           interpret, bh_split)
    return out, (q, k, v, kmask, meta, out, lse)


def _flash_bwd(causal, dropout_p, interpret, bh_split, res, do):
    q, k, v, kmask, meta, out, lse = res
    dq, dk, dv = _fa_backward(q, k, v, kmask, meta, causal, dropout_p,
                              interpret, bh_split, out, lse, do)
    dmask = None if kmask is None else jnp.zeros_like(kmask)
    dmeta = onp.zeros(meta.shape, jax.dtypes.float0)
    return dq, dk, dv, dmask, dmeta


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_legal(BH, Tq, Tk, D, dtype) -> bool:
    """Can the forward AND backward kernels be built for this shape at
    the block sizes they would use? The static Mosaic rules of
    ops/autotune.check_candidate, applied to what :func:`_block_sizes`
    resolves — the verdict ``multi_head_attention`` routes on."""
    from . import autotune
    return all(
        autotune.check_candidate(
            BH, Tq, Tk, D, jnp.dtype(dtype), kind,
            *_block_sizes(BH, Tq, Tk, D, dtype, kind))[0]
        for kind in ('fwd', 'bwd'))


def flash_attention(q, k, v, key_mask=None, causal=False, dropout_p=0.0,
                    dropout_seed=None, interpret=None, bh_base=None,
                    bh_split=None):
    """Flash attention. q/k/v: (B, H, T, D). key_mask: optional (B, Tk)
    additive f32 mask (0 = keep, large-negative = drop) or boolean
    (True = keep). dropout_p: in-kernel attention-probability dropout;
    dropout_seed: uint32 scalar/array seeding the kernel PRNG (required
    when dropout_p > 0). Returns (B, H, Tq, D).

    interpret: True runs the kernels through the Pallas interpreter,
    False compiles them with Mosaic (and fails where there is no TPU),
    None takes :func:`default_interpret`.

    bh_base / bh_split: for a call that holds one shard of a larger
    (batch, heads) problem — the global batch·head id of its first
    slice (uint32 scalar, may be traced) and the static
    (H_local, H_global) pair — see :func:`_global_bh`."""
    if interpret is None:
        interpret = default_interpret()
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * H, Tk, D)
    vf = v.reshape(B * H, Tk, D)
    km = None
    if key_mask is not None:
        if key_mask.dtype == jnp.bool_:
            key_mask = jnp.where(key_mask, 0.0, _NEG_INF)
        key_mask = key_mask.astype(jnp.float32)
        if key_mask.shape[0] == B * H:
            km = key_mask
        elif key_mask.shape[0] == B:
            km = jnp.broadcast_to(key_mask[:, None, :],
                                  (B, H, Tk)).reshape(B * H, Tk)
        else:
            raise ValueError(
                f"key_mask leading dim {key_mask.shape[0]} matches neither "
                f"batch {B} nor batch*heads {B * H}")
    dropout_p = float(dropout_p)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    seed = jnp.zeros((), jnp.uint32) if dropout_seed is None \
        else jnp.asarray(dropout_seed, jnp.uint32).reshape(())
    base = jnp.zeros((), jnp.uint32) if bh_base is None \
        else jnp.asarray(bh_base, jnp.uint32).reshape(())
    meta = jnp.stack([seed, base]).reshape(1, 2)
    if bh_split is not None:
        bh_split = (int(bh_split[0]), int(bh_split[1]))
    out = _flash(qf, kf, vf, km, meta, causal, dropout_p, bool(interpret),
                 bh_split)
    return out.reshape(B, H, Tq, D)
