"""How a gradient gets to where it is consumed inside the compiled step,
and what that costs on the wire.

The dense way: a fusion boundary, the cast to fp32, the layout the
optimizer shard reads it in (GSPMD makes the reduce-scatter), the
error-feedback codec. The RowSparse way: (unique row ids, row-block
values). The way back under ZeRO-3: the per-layer all-gather on use.
``wire_plan`` accounts for all of it from the ``StepLayout``.

Gradient compression + hierarchical collectives (ISSUE 12): with
``compression_params={'type': 'fp16'|'int8'|'2bit'}`` (or
``MXTPU_COMPRESSION``) the gradient exchange gains an error-feedback
quantization epilogue INSIDE the compiled step:
``dec = Q^-1(Q(grad + residual))`` feeds the optimizer and
``residual = grad + residual - dec`` persists per-param as SHARDED
optimizer-side state (donated, checkpointed in the layout-independent
states payload). Under a hierarchical dp axis (``layout.mesh_axes``) only
this (compressed) exchange crosses the slow DCN hop. The non-finite guard
reduces over the DECODED grads (and the residual epilogue), so a
poisoned step still skips on device with the residual writeback gated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp
from jax.ad_checkpoint import checkpoint_name

from .. import scopes as _scopes
from ..base import telem_flags as _telem
from ..telemetry import trace as _trace
from ..ops import rowsparse as _rowsparse
from . import compression as _compression
from .collectives import ordered_barrier


def discover_row_budgets(forward_loss, candidates, avals, cap):
    """RowSparse fast path (ISSUE 19): parameters declared
    grad_stype='row_sparse' (Embedding(sparse_grad=True)) carry
    (unique row ids, row-block values) gradients and live-rows-only
    optimizer updates. Budgets — the static worst-case unique-row
    counts per lookup — are discovered with one abstract
    jax.eval_shape trace (no compile, no FLOPs) before the real
    program is built. ``avals`` is (trainable, frozen, inputs, labels);
    returns ({table: [per-lookup row budget]}, {table: flat ids per
    step, pre-dedup}) for the ``candidates`` that take the path."""
    budgets, id_counts = {}, {}
    if not candidates:
        return budgets, id_counts
    discovered = {}

    def _discover(t_params, f_params, inputs, labels, key, fault_scale):
        cap_ = _rowsparse.trace_capture(
            {n: t_params[n] for n in candidates})
        with cap_:
            forward_loss(t_params, f_params, inputs, labels, key,
                         fault_scale)
        for cn, slot in cap_.slots.items():
            discovered[cn] = list(slot.call_sizes)
        return jnp.zeros(())

    jax.eval_shape(_discover, *avals, jax.random.PRNGKey(0),
                   jnp.float32(1.0))
    for n in candidates:
        sizes = discovered.get(n) or []
        if not sizes:
            continue     # never looked up through embedding
        vocab = int(avals[0][n].shape[0])
        buds = [min(s, vocab) for s in sizes]
        if cap and sum(buds) > cap:
            continue     # budget over ceiling: dense fallback
        budgets[n] = buds
        id_counts[n] = int(sum(sizes))
    return budgets, id_counts


def row_block(rec, g_rows, n_lookups, shape, densify):
    """(unique ids, fp32 gradient, live-row count) of one RowSparse table
    from the forward's capture record and the tangent's cotangent. The
    gradient is the (budget, dim) row block, or with ``densify`` (exact
    mode) that block scattered into a table-shaped grad for the regular
    dense path — bit-identical trajectories to dense training (the parity
    oracle). The WIRE exchange still happened on row blocks, only the
    local update is dense."""
    uids, n_live = rec['uids'], rec['n_live']
    rows = g_rows.astype(jnp.float32)
    if n_lookups > 1:
        # several lookups of the same table in one step: segment-sum
        # overlapping ids into one block
        uids, rows, n_live = _rowsparse.merge_row_blocks(
            uids, rows, shape[0])
    if densify:
        rows = jnp.zeros(shape, jnp.float32).at[uids].add(rows, mode='drop')
    return uids, rows, n_live


def dense(grad):
    """A dense gradient enters its exchange stretch through an
    optimization_barrier, as value_and_grad hands it over (bf16 for a
    bf16 parameter: an identity, no rounding is added). It is a fusion
    boundary. Without it XLA:TPU puts the whole AdamW update (new bf16
    weight, master, both moments) into the epilogue of the
    weight-gradient matmul, and the seven f32 tiles of that epilogue
    leave the matmul a smaller output window: BERT's FFN2 gradient,
    135 GFLOP, took 2.99 ms with the update inside on one v5e and
    1.30 ms on each of four, where ZeRO-1's reduce-scatter already stood
    between the two; 66.2 of bert_base.t512's 297.6 ms step were such
    fusions (ledger, PR 26). With the boundary the same matmul takes
    1.49 ms, the separate updates 4.7 ms a step, and the step 278.9 ms
    (PERF.md 6, PR 28). phase_mixed_ms_per_step guards this line: a few
    ms there mean an update is back inside a matmul. Per leaf, not over
    the gradient tree: a gradient lives from its matmul to its update,
    and XLA's plan for the step grew by 0.002 GiB. The RowSparse row
    blocks have no matmul-shaped gradient and take no barrier."""
    return jax.lax.optimization_barrier(grad).astype(jnp.float32)


def to_store(g32, flat, store_sharding, constraint):
    """Lay a table- or parameter-shaped fp32 gradient out as the
    optimizer's shard reads it."""
    if flat is not None:
        # ragged param (ZeRO-3 flatten+pad): the grad flattens and
        # zero-pads into the flat 1/dp layout
        g32 = jnp.pad(g32.reshape(-1), (0, flat['pad']))
        return jax.lax.with_sharding_constraint(g32, store_sharding)
    if constraint is not None:
        # reduce-scatter: the grad is only ever consumed in this
        # dp-sharded layout, so the partitioner combines the backward
        # psum + slice into one reduce-scatter
        return jax.lax.with_sharding_constraint(g32, constraint)
    return g32


def error_feedback(grad, residual, codec, access, block):
    """Error-feedback quantized exchange epilogue: the cross-host hop
    carries Q(g + r); the decoded value feeds the update and the
    quantization error r' is re-offered next step instead of lost
    (Lin et al.; Karimireddy et al.). Elementwise on the sharded grad —
    adds no collective of its own. On a RowSparse row block ``access``
    reads and writes the live rows of the table-shaped, persistent
    residual and ``block`` is the row width (per-row scales): only live
    rows accumulate/flush error. Returns (decoded grad, new residual)."""
    get, put = access
    acc = grad + get(residual)
    dec = _compression.encode_decode(acc, codec['type'],
                                     codec['threshold'], block)
    return dec, put(residual, acc - dec)


def gather_all(t_params, layer_groups, gather_shardings):
    """All-gather the dim-sharded params layer by layer: each group's
    gather is barrier-chained to the PREVIOUS group's gather (not its
    compute), so XLA can prefetch layer k+1's params while layer k
    computes; the gathered values are checkpoint-named so the step's
    remat policy drops them from the autodiff residuals (the backward
    pass regathers)."""
    gathered = dict(t_params)
    token = None
    for _gname, names in layer_groups:
        vals = [t_params[n] for n in names]
        with jax.named_scope(_scopes.GATHER):
            if token is not None:
                out = ordered_barrier(*(vals + [token]))
                vals = list(out[:-1])
            vals = [checkpoint_name(
                jax.lax.with_sharding_constraint(
                    v, gather_shardings[n]), 'zero3_gather')
                for n, v in zip(names, vals)]
        for n, v in zip(names, vals):
            gathered[n] = v
        token = vals[0]
    return gathered



def gathered(forward_loss, layout):
    """(``forward_loss`` behind the gathers of ``layout``'s dim-sharded
    parameters, the remat policy that drops the gathered copies) — the
    function itself and None where nothing is dim-sharded."""
    if not layout.dim_names:
        return forward_loss, None

    def forward_sharded(t_params, f_params, inputs, labels, key,
                        fault_scale, row_tangents=None):
        return forward_loss(
            gather_all(t_params, layout.layer_groups,
                       layout.gather_shardings),
            f_params, inputs, labels, key, fault_scale, row_tangents)
    return forward_sharded, \
        jax.checkpoint_policies.save_any_names_but_these('zero3_gather')


def _ring(k):
    return (k - 1) / k if k > 1 else 0.0


def wire_plan(layout, codec, sparse_budgets, sparse_exact):
    """Per-step collective accounting (mxnet_tpu_comm_* contract):
    ring-algorithm wire bytes per device — all_reduce(N) costs
    2*(dp-1)/dp*N while reduce_scatter(N)+all_gather(N) cost
    (dp-1)/dp*N each, so ZeRO-1 provably moves the SAME total as
    the replicated path. ZeRO-3 is honestly MORE: each dim-sharded
    param all-gathers twice per step (forward use + backward
    regather under the remat policy) in the compute dtype, and its
    fp32 grad reduce-scatters once; flat params reduce-scatter the
    padded fp32 grad and gather the updated flat master back to
    the replicated logical copy. Analytic (XLA does not expose
    per-collective byte counters), recorded once per step by the
    step's telemetry.

    Hierarchy decomposition (H hosts x h devices, dp = H*h): the
    GRADIENT exchange splits into an intra-host reduce-scatter
    ((h-1)/h * N on the ICI hop) plus a cross-host all-reduce of
    the 1/h partial (2*(H-1)/H * N/h on the DCN hop — the ONLY
    cross-host traffic, and the hop the codec shrinks: its
    operand is the encoded payload). Param writebacks/gathers
    stay entirely on the intra hop because the ZeRO shard degree
    is h (states replicate across hosts — ZeRO++-style hpZ).

    Returns a dict: ``comm`` {kind: (bytes, count)}, the kind-aggregated
    view (back-compat); ``hop`` {(kind, axis): (bytes, count)} for
    per-hop telemetry; ``sparse_hop``/``sparse_dense_hop`` {axis: bytes},
    the RowSparse side ledger of sparse wire bytes and the
    dense-equivalent bytes the same exchange would have moved — the
    measurable shrink sparse_report()/dryrun assert on; ``comp``, the
    compressed hop's raw and encoded bytes, None with no codec;
    ``gather`` [(layer, bytes/step, gathers)], per layer under ZeRO-3."""
    axes = layout.axes
    dp = axes.dp_size
    H, h = axes.cross_size, axes.shard_size
    hier = H > 1
    ring = _ring(h) if hier else _ring(dp)   # the shard/param hop
    ring_h = _ring(H)
    intra_axis = axes.shard_axis
    cross_axis = axes.cross_axis or axes.dp_axis
    comp_on = codec is not None
    ctype = codec['type'] if comp_on else 'none'
    cblock = codec['block'] if comp_on else 0
    plan = {}
    hop_plan = {}
    comp_raw = 0.0          # fp32 bytes the compressed hop replaces
    comp_enc = 0.0          # encoded bytes it actually carries
    sparse_hop = {}
    sparse_dense_hop = {}

    def _add(kind, axis, nbytes, cnt):
        b, c = plan.get(kind, (0.0, 0))
        plan[kind] = (b + nbytes, c + cnt)
        b, c = hop_plan.get((kind, axis), (0.0, 0))
        hop_plan[(kind, axis)] = (b + nbytes, c + cnt)

    def _sadd(axis, nbytes, dense_nbytes):
        sparse_hop[axis] = sparse_hop.get(axis, 0.0) + nbytes
        sparse_dense_hop[axis] = \
            sparse_dense_hop.get(axis, 0.0) + dense_nbytes

    param_nbytes = {}
    for n in layout.t_names:
        shape, mode = layout.shapes[n], layout.modes[n]
        size = int(onp.prod(shape)) if shape else 1
        nbytes = size * layout.dtypes[n].itemsize
        param_nbytes[n] = nbytes
        enc = _compression.wire_bytes(
            layout.store_shapes[n], ctype, cblock) if comp_on else None
        if mode == 'dim':
            _add('all_gather', intra_axis, 2 * ring * nbytes, 2)
            grad_raw = size * 4
        elif mode == 'flat':
            padded = layout.flat_meta[n]['padded']
            _add('all_gather', intra_axis, ring * padded * 4, 1)
            grad_raw = padded * 4
        elif mode == 'shard':
            _add('all_gather', intra_axis, ring * nbytes, 1)
            grad_raw = nbytes
        elif dp > 1:
            grad_raw = nbytes
        else:
            continue
        # the gradient exchange itself: what the hop would carry in fp32
        # (raw) and what it carries (wire)
        raw, wire = grad_raw, enc if comp_on else grad_raw
        sparse = n in sparse_budgets
        if sparse:
            # RowSparse exchange: the wire carries (int32 ids +
            # row-block values) instead of the table-shaped grad —
            # exchange bytes scale with the live-row budget, not the
            # vocab. Exact mode densifies LOCALLY after the row
            # exchange, so the wire shrink holds for both modes;
            # only the lazy codec re-encodes the rows (per-row
            # scales, block = dim) for the cross-host hop.
            B = sum(sparse_budgets[n])
            dim = shape[1]
            dense_wire = wire
            raw = wire = B * (dim * 4 + 4)
            if comp_on and not sparse_exact:
                wire = _compression.wire_bytes((B, dim), ctype, dim) + B * 4
        if hier:
            if h > 1:
                _add('reduce_scatter', intra_axis, ring * raw, 1)
                if sparse:
                    _sadd(intra_axis, ring * raw, ring * grad_raw)
            cross = 2 * ring_h * wire / h
            _add('all_reduce', cross_axis, cross, 1)
            if sparse:
                _sadd(cross_axis, cross, 2 * ring_h * dense_wire / h)
            comp_raw += 2 * ring_h * raw / h
            comp_enc += cross
        else:
            # a gradient consumed sharded reduce-scatters; a replicated
            # one, and every row block, all-reduces: twice the bytes
            scatter = mode != 'repl' and not sparse
            trips = 1 if scatter else 2
            _add('reduce_scatter' if scatter else 'all_reduce',
                 intra_axis, trips * ring * wire, 1)
            if sparse:
                _sadd(intra_axis, trips * ring * wire,
                      2 * ring * dense_wire)
            comp_raw += trips * ring * raw
            comp_enc += trips * ring * wire
    # table-axis feature exchange (model-parallel tables): the
    # forward gathers remote rows and the backward scatters their
    # updates — one all-to-all pair per step, bytes proportional to
    # the live-row budget in the compute dtype (+ the id vector)
    for n in sorted(layout.table_sharded):
        tsize = int(dict(zip(axes.mesh.axis_names,
                             axes.mesh.devices.shape))[layout.table_axis])
        B = sum(sparse_budgets[n])
        dim = layout.shapes[n][1]
        a2a = 2 * _ring(tsize) * B * (dim * layout.dtypes[n].itemsize + 4)
        _add('all_to_all', layout.table_axis, a2a, 2)
        _sadd(layout.table_axis, a2a, a2a)
    return {
        'comm': plan, 'hop': hop_plan, 'sparse_hop': sparse_hop,
        'sparse_dense_hop': sparse_dense_hop,
        'comp': {
            'codec': ctype, 'raw_bytes': comp_raw, 'encoded_bytes':
            comp_enc, 'axis': cross_axis if hier else intra_axis,
        } if comp_on else None,
        'gather': [
            (gname, 2 * ring * sum(param_nbytes[n] for n in names), 2)
            for gname, names in layout.layer_groups]}


def record_wire(hop, gather, comp, label, shard_axis):
    """One step's trace instants and telemetry counters for the ``hop``,
    ``gather`` and ``comp`` of a ``wire_plan``: the collectives run
    INSIDE the compiled program, so the analytic plan stands in for
    them."""
    if _trace.enabled():
        # the stage label separates the zero1 writeback gather from
        # the zero3 per-layer on-use gathers, the axis label
        # separates the intra-host (ici) hop from the cross-host
        # (dcn) hop under the hierarchical decomposition
        for (kind, axis), (nbytes, count) in hop.items():
            _trace.instant(f'comm.{kind}', bytes=int(nbytes),
                           count=count, axis=axis, stage=label)
        for layer, nbytes, count in gather:
            _trace.instant('comm.all_gather', bytes=int(nbytes),
                           count=count, axis=shard_axis,
                           stage=label, layer=layer)
        if comp is not None:
            _trace.instant('comm.compress',
                           bytes=int(comp['encoded_bytes']),
                           codec=comp['codec'],
                           axis=comp['axis'])
            _trace.instant('comm.decompress',
                           bytes=int(comp['raw_bytes']),
                           codec=comp['codec'],
                           axis=comp['axis'])
    if _telem['on']:
        from .. import telemetry as _telemetry
        for (kind, axis), (nbytes, count) in hop.items():
            _telemetry.counter(
                'mxnet_tpu_comm_collective_bytes_total').inc(
                    nbytes, kind=kind, axis=axis, stage=label)
            _telemetry.counter('mxnet_tpu_comm_collectives_total').inc(
                count, kind=kind, axis=axis, stage=label)
        if comp is not None:
            _telemetry.counter(
                'mxnet_tpu_comm_compressed_bytes_total').inc(
                    comp['encoded_bytes'],
                    codec=comp['codec'],
                    axis=comp['axis'])


def sparse_report(layout, sparse_budgets, sparse_exact, sparse_hop,
                  sparse_dense_hop):
    """Analytic per-step cost of the RowSparse fast path vs the dense
    path it replaced.

    - ``update_bytes_per_step``: optimizer-touched bytes (param +
      fp32 master + vector moments rows) across sparse tables;
      lazy mode scales with the live-row budget, exact mode is
      honestly dense (it densifies before the kernel).
    - ``exchange_bytes_per_hop``: analytic ring-wire bytes of the
      row-block gradient exchange by mesh hop (``wire_plan``'s side
      ledger), with the dense-equivalent bytes the same hop would have
      moved.
    """
    tables = {}
    upd = dense_upd = 0
    for n in sorted(sparse_budgets):
        vocab, dim = layout.shapes[n]
        budget = min(int(sum(sparse_budgets[n])), int(vocab))
        leaves = 1 + sum(1 for s in layout.state_avals[n] if s.ndim)
        if n in layout.master_names:
            leaves += 1
        per_row = dim * 4 * leaves
        touched = vocab if sparse_exact else budget
        tables[n] = {'vocab': int(vocab), 'dim': int(dim),
                     'budget': budget,
                     'update_bytes': touched * per_row,
                     'dense_update_bytes': int(vocab) * per_row}
        upd += touched * per_row
        dense_upd += int(vocab) * per_row
    hops = {axis: {'bytes': int(b),
                   'dense_bytes':
                       int(sparse_dense_hop.get(axis, 0))}
            for axis, b in sparse_hop.items()}
    return {
        'mode': 'exact' if sparse_exact else 'lazy',
        'table_axis': layout.table_axis,
        'tables': tables,
        'update_bytes_per_step': int(upd),
        'dense_update_bytes_per_step': int(dense_upd),
        'update_shrink': dense_upd / max(1, upd),
        'exchange_bytes_per_hop': hops,
    }
