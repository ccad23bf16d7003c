"""Compiled sharded training step — the performance path.

This is the TPU-native realisation of the north star (BASELINE.json): the
whole train step (forward + backward + optimizer update + gradient
all-reduce) is ONE pjit-compiled XLA program per step. Parameters are
replicated (DP) or sharded (TP via param_specs) over the mesh; the batch is
sharded over the 'dp' axis; XLA inserts the gradient all-reduce over ICI.
Buffer donation on params/optimizer state gives the reference's
static-alloc in-place update behavior (ref: CachedOp static_alloc,
src/imperative/cached_op.cc:525).

This module composes what three others decide: ``layout`` (where every
parameter, master, moment and residual lives: ZeRO-1, ZeRO-3 / FSDP, the
hierarchical dp axis), ``exchange`` (a gradient's way to where it is
consumed, the ZeRO-3 gathers, and the wire bytes of each) and ``update``
(the optimizer kernels, the write-back, the non-finite guard's gate).
"""
from __future__ import annotations

import copy
import functools
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, state as _flags, telem_flags as _telem
from ..ndarray.ndarray import NDArray
from ..resilience import faults as _faults
from ..telemetry import trace as _trace, flight as _flight, \
    memory as _memory, compile as _compile
from .. import random as _random
from .. import scopes as _scopes
from ..ops import attention as _attention, rowsparse as _rowsparse
from . import compression as _compression
from . import exchange as _exchange, layout as _layout, update as _update
from .collectives import group_params_by_layer
from .mesh import default_mesh
from .update import _OPTS


def device_nbytes(arr):
    """Bytes of ``arr`` ONE device physically holds: the local shard for
    a sharded global array, the full buffer for replicated/host arrays —
    the unit of the per-device residency accounting (ZeRO gauges)."""
    shards = getattr(arr, 'addressable_shards', None)
    if shards:
        return shards[0].data.nbytes
    return int(arr.size) * jnp.dtype(arr.dtype).itemsize



def _aval(x):
    return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)


def _datas(batch):
    """The jax (or numpy) arrays of one NDArray/array or a list of them."""
    if not isinstance(batch, (list, tuple)):
        batch = [batch]
    return tuple(x._data if isinstance(x, NDArray) else x for x in batch)


class ShardedTrainStep:
    """One-pjit-call training step for a Gluon block over a device mesh.

    Usage:
        step = ShardedTrainStep(net, loss_fn, 'adam',
                                optimizer_params={'lr': 1e-3}, mesh=mesh)
        loss = step(data, label)      # NDArrays; params updated in place
    """

    def __init__(self, block, loss_fn, optimizer='sgd', optimizer_params=None,
                 mesh=None, dp_axis='dp', param_specs=None, zero=None,
                 compression_params=None, guard=None, hierarchy=None):
        self.block = block
        self.loss_fn = loss_fn
        self.dp_axis = dp_axis
        self.optimizer_params = dict(optimizer_params or {})
        self.lr = self.optimizer_params.pop('learning_rate',
                                            self.optimizer_params.pop('lr', 0.01))
        # reference Optimizer(lazy_update=...): lazy (default) updates
        # only the live rows of row_sparse-grad params inside the step;
        # False forces the exact densified path (bit-identical to dense
        # training — the parity oracle, like MXTPU_SPARSE_EXACT)
        self._lazy_sparse = bool(self.optimizer_params.pop(
            'lazy_update', True))
        self._sparse_names = []
        self._sparse_prev_stats = None
        if optimizer not in _OPTS:
            raise ValueError(f"ShardedTrainStep supports {sorted(_OPTS)}")
        self._opt_init, self._opt_update = _OPTS[optimizer]
        self.param_specs = param_specs or {}
        # error-feedback gradient compression (ISSUE 12): routed for
        # real — validated into a codec spec here, applied as the
        # quantize/decode epilogue inside the compiled step; only a
        # genuinely unknown ctype string still raises
        self.compression = _compression.resolve(compression_params)
        self._requested_hierarchy = hierarchy
        self._adopt_mesh(mesh if mesh is not None else default_mesh())
        if zero is None:
            from .. import config as _cfg
            zero = _cfg.get('MXTPU_ZERO')
        stage = int(zero) if not isinstance(zero, bool) else int(bool(zero))
        if stage not in (0, 1, 3):
            raise MXNetError(
                f"zero={zero!r}: supported ZeRO stages are 0 (off), 1 "
                f"(sharded optimizer state) and 3 (sharded params + "
                f"grads + state / FSDP); stage 2 has no separate "
                f"meaning on the GSPMD path (gradients already "
                f"reduce-scatter under stage 1).")
        # ZeRO-1: default-on when a >1-device dp axis exists (the fp32
        # masters + Adam moments then live 1/dp per device). ZeRO-3
        # additionally shards the persistent params (gathered per layer
        # on use inside the step). The REQUESTED stage is kept so an
        # elastic reset_mesh() re-derives the effective stage at the
        # survivor world's dp degree.
        self._requested_stage = stage
        self.zero_stage = stage if self._dp_size > 1 else 0
        # MXTPU_REMAT (ISSUE 18): activation-remat policy for the
        # forward, read once at construction so the build signature and
        # the checkpoint seam agree for this step's lifetime
        from .. import config as _remat_cfg
        self._remat_policy = _remat_cfg.get('MXTPU_REMAT')
        self._spans_processes = self._mesh_spans_processes()
        self.zero = self.zero_stage > 0
        self._layout = None       # layout.StepLayout of the built step
        self._master = None       # fp32 master copies of bf16/fp16 params
        self._opt_state = None
        self._residual = None     # error-feedback residuals (compression)
        self._compiled = None
        self._executable = None   # compiled_program()'s, run from then on
        self._alias = None        # name-stable jit-boundary key aliases
        self._alias_rev = None
        self._step_count = 0
        self._pending_states = None   # restored blob awaiting first build
        self._cost_args = None        # avals of the first call, for lower()
        # resilience.NonFiniteGuard: the pjit step then also reduces
        # isfinite over loss + every grad and gates the whole writeback
        # on device; the guard reads the flag one step deferred
        self._guard = guard
        if guard is not None:
            guard.add_post_restore_hook(self._replace_params_on_mesh)

    def _adopt_mesh(self, mesh):
        """Adopt ``mesh`` as ``layout.mesh_axes`` reads it (the dp axis
        split in two under a hierarchy). ``_dp_size`` is what checkpoint
        manifests read off a trainer of either kind."""
        self._axes = _layout.mesh_axes(
            mesh, self.dp_axis, self._requested_hierarchy, self.param_specs)
        self.mesh = self._axes.mesh
        self._dp_size = self._axes.dp_size

    def _mesh_spans_processes(self):
        """Does this step's mesh include other processes' devices? Then
        every step is a cross-process collective — one that a lost peer
        wedges forever, which is why dispatch refuses to enter it once
        the membership layer has declared a loss."""
        try:
            devices = list(self.mesh.devices.flat)
        except Exception:
            return jax.process_count() > 1
        return _layout.devices_span_processes(devices)

    # ------------------------------------------------------------------
    def _collect(self):
        params = sorted(self.block.collect_params().items())
        trainable = [(n, p) for n, p in params if p.grad_req != 'null']
        frozen = [(n, p) for n, p in params if p.grad_req == 'null']
        return trainable, frozen

    def _forward_loss(self, trainable, frozen, model_axes, sparse_budgets):
        """The model's forward and loss as a function of the step's
        arrays. ``sparse_budgets`` is read when a trace arms the RowSparse
        capture, so the caller may fill it after budget discovery."""
        block, loss_fn = self.block, self.loss_fn
        name_to_param = dict(trainable + frozen)
        f_names = [n for n, _ in frozen]

        def forward_loss(t_params, f_params, inputs, labels, key,
                         fault_scale, row_tangents=None):
            all_params = dict(t_params)
            all_params.update(f_params)
            proxies = {}
            for n, p in name_to_param.items():
                proxies[n] = NDArray(all_params[n])
                p._set_trace_proxy(proxies[n])
            # RowSparse capture (ISSUE 19): armed INSIDE this function —
            # which jax.checkpoint re-traces during backward — so the
            # table identities the embedding op matches on are always
            # the CURRENT trace's tracers. Each captured lookup routes
            # through the dedup-first gather, adds its slice of the
            # zero row tangent (whose cotangent IS the RowSparse row
            # block), and records the live ids for the optimizer.
            cap = None
            if row_tangents is not None:
                cap = _rowsparse.trace_capture(
                    {n: all_params[n] for n in row_tangents},
                    tangents=row_tangents, budgets=sparse_budgets)
            prev = _flags.is_training
            _flags.is_training = True
            try:
                with _random.key_provider(_random.TraceKeyProvider(key)), \
                        _attention.mesh_placement(
                            self.mesh, self._axes.dp_axes, model_axes), \
                        (cap if cap is not None else nullcontext()):
                    # the names a device trace reads (scopes.py): the
                    # model's block path, then the loss
                    with block._trace_scope():
                        out = block.forward(*[NDArray(x) for x in inputs])
                    outs = out if isinstance(out, (list, tuple)) else (out,)
                    with jax.named_scope(_scopes.LOSS):
                        loss = loss_fn(*outs, *[NDArray(l) for l in labels])
            finally:
                _flags.is_training = prev
                for p in name_to_param.values():
                    p._clear_trace_proxy()
            # fault_scale is 1.0 on every normal step (an exact-identity
            # multiply); an injected step.dispatch:nan passes NaN here,
            # poisoning the loss AND (via the chain rule) every gradient
            # regardless of the model's input dtypes — int-token models
            # like BERT included
            with jax.named_scope(_scopes.LOSS):
                loss_val = jnp.mean(loss._data) * fault_scale
            aux = {n: proxies[n]._data for n in f_names}
            if cap is not None:
                return loss_val, (aux, cap.results())
            return loss_val, aux
        return forward_loss

    def _with_remat(self, forward_loss, layout):
        """``forward_loss`` behind the ZeRO-3 gathers and under the remat
        policy: the function the step differentiates. Whatever MXTPU_REMAT
        says, the gathered params are NEVER kept as autodiff residuals
        (the backward pass regathers: full copies exist only transiently)."""
        loss_base, base_policy = _exchange.gathered(forward_loss, layout)
        # MXTPU_REMAT (ISSUE 18): parameterized activation remat of the
        # forward. 'none' keeps the historical behavior bit-for-bit
        # (checkpoint only as the ZeRO-3 gather-drop floor above);
        # 'layer' saves only matmul outputs without batch dims — the
        # classic per-layer checkpoint trade (~1 extra forward of FLOPs
        # for O(layers) activation HBM; the gathers stay dropped since
        # an all-gather is not a dot); 'aggressive' saves nothing.
        # Remat never changes values, only what backward recomputes —
        # tests assert loss parity across all three policies, and
        # memory_analysis() cross-validates the HBM deltas.
        remat = self._remat_policy
        if remat == 'layer':
            return jax.checkpoint(
                loss_base,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        if remat == 'aggressive':
            return jax.checkpoint(
                loss_base,
                policy=jax.checkpoint_policies.nothing_saveable)
        if base_policy is not None:
            return jax.checkpoint(loss_base, policy=base_policy)
        return loss_base

    def _train_step(self, loss_forward, layout, sparse_budgets):
        """The step as one function of its arrays: value-and-grad, then
        for each parameter exchange, guard and update, then the outputs."""
        t_names, f_names, shapes = \
            layout.t_names, layout.f_names, layout.shapes
        s_names = sorted(sparse_budgets)
        opt_update, opt_kwargs = self._opt_update, self.optimizer_params
        codec, sparse_exact = self.compression, self._sparse_exact
        guard_on = self._guard is not None
        # each parameter's stretch of the program is traced under
        # three names (scopes.py): the gradient's way to where it is
        # consumed, the non-finite check, the update
        exchange = functools.partial(jax.named_scope, _scopes.EXCHANGE)
        guard = functools.partial(jax.named_scope, _scopes.GUARD)
        update = functools.partial(jax.named_scope, _scopes.UPDATE)

        def train_step(t_params, f_params, master, opt_state, residual,
                       inputs, labels, key, lr, fault_scale):
            if s_names:
                # RowSparse tables ride as zero tangents: the embedding
                # lookup adds tangent[live-row slice] to the gathered
                # rows (the table itself is stop_gradient-ed in the
                # capture), so d loss/d tangent IS the deduped row-block
                # gradient — no table-shaped cotangent ever exists
                tangents = {n: jnp.zeros(
                    (sum(sparse_budgets[n]), shapes[n][1]), jnp.float32)
                    for n in s_names}
                with jax.named_scope(_scopes.FWD_BWD):
                    (loss_val, (aux, srec)), (grads, g_rows) = \
                        jax.value_and_grad(
                            loss_forward, argnums=(0, 6), has_aux=True)(
                                t_params, f_params, inputs, labels, key,
                                fault_scale, tangents)
            else:
                with jax.named_scope(_scopes.FWD_BWD):
                    (loss_val, aux), grads = jax.value_and_grad(
                        loss_forward, has_aux=True)(t_params, f_params,
                                                    inputs, labels, key,
                                                    fault_scale)
                srec, g_rows = {}, {}
            new_params, new_master, new_state, new_residual = {}, {}, {}, {}
            sparse_stats = {}
            with guard():
                ok = jnp.isfinite(loss_val) if guard_on else None
            for n in t_names:
                flat = layout.flat_meta.get(n)
                rec = srec.get(n)
                lazy = rec is not None and not sparse_exact
                with exchange():
                    if rec is None:
                        g = _exchange.dense(grads[n])
                    else:
                        uids, g, sparse_stats[n] = _exchange.row_block(
                            rec, g_rows[n], len(sparse_budgets[n]),
                            shapes[n], densify=not lazy)
                if lazy:
                    # codec, guard and update see the live rows only,
                    # addressed in the store as it lies
                    access = _update.row_access(uids, shapes[n][1], flat)
                    constraint = None
                else:
                    access = _update.WHOLE
                    constraint = layout.shard_constraint.get(n)
                with exchange():
                    if not lazy:
                        g = _exchange.to_store(
                            g, flat, layout.zero_shardings[n], constraint)
                    if codec is not None:
                        g, new_residual[n] = _exchange.error_feedback(
                            g, residual[n], codec, access,
                            shapes[n][1] if lazy else codec['block'])
                if guard_on:
                    # isfinite over the SHARDED (and, under compression,
                    # DECODED) grad: each device reduces its slice and
                    # GSPMD psums the scalar — never a full-grad rebuild.
                    # encode_decode propagates non-finite inputs, so a
                    # poisoned gradient cannot hide behind the quantizer.
                    with guard():
                        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
                with update():
                    new_params[n], new32, new_state[n] = _update.apply(
                        opt_update, opt_kwargs, lr, t_params[n],
                        master[n] if n in layout.master_names else None,
                        opt_state[n], g, shapes[n], access, flat,
                        constraint)
                    if new32 is not None:
                        new_master[n] = new32
            new_f = {n: aux.get(n, f_params[n]) for n in f_names}
            outs = (new_params, new_f, new_master, new_state, new_residual)
            if guard_on:
                with guard():
                    outs = _update.gate_writeback(
                        ok, outs,
                        (t_params, f_params, master, opt_state, residual))
                outs = outs + (loss_val, ok)
            else:
                outs = outs + (loss_val,)
            if s_names:
                # per-table live-row counts as a last (replicated)
                # output — the telemetry side reads them one step
                # deferred, never stalling the dispatch
                outs = outs + (sparse_stats,)
            return outs
        return train_step

    def _build(self, example_inputs, example_labels):
        """Everything the step decides, from shapes alone: the RowSparse
        budgets, the layout, the program (``self._compiled``, not yet
        lowered) and the wire plan. Places no array."""
        from .. import config as _cfg
        trainable, frozen = self._collect()
        t_avals = {n: _aval(p.data()._data) for n, p in trainable}
        f_avals = {n: _aval(p.data()._data) for n, p in frozen}
        avals = (t_avals, f_avals, tuple(map(_aval, example_inputs)),
                 tuple(map(_aval, example_labels)))
        specs, self.param_spec_report = _layout.resolve_param_specs(
            list(t_avals) + list(f_avals), self.param_specs)
        # mesh axes the param specs shard weights over (tp): where the
        # attention heads divide over them, the flash kernel maps over
        # them as well as over the batch axes (ops/attention.py)
        spec_axes = {a for spec in specs.values() for e in spec
                     for a in (e if isinstance(e, tuple) else (e,))}
        model_axes = tuple(
            a for a in self.mesh.axis_names
            if a in spec_axes and a not in self._axes.dp_axes
            and self.mesh.shape[a] > 1)
        sparse_budgets = {}          # name -> [per-lookup row budget]
        forward_loss = self._forward_loss(trainable, frozen, model_axes,
                                          sparse_budgets)
        candidates = [
            n for n, p in trainable
            if getattr(p, '_grad_stype', 'default') == 'row_sparse'
            and len(t_avals[n].shape) == 2] \
            if _cfg.get('MXTPU_SPARSE') else []
        found, self._sparse_id_counts = _exchange.discover_row_budgets(
            forward_loss, candidates, avals,
            int(_cfg.get('MXTPU_SPARSE_ROWS')))
        sparse_budgets.update(found)
        self._sparse_names = s_names = sorted(sparse_budgets)
        self._sparse_budgets = sparse_budgets
        self._sparse_exact = bool(_cfg.get('MXTPU_SPARSE_EXACT')) \
            or not self._lazy_sparse
        layout = self._layout = _layout.step_layout(
            self._axes, self.zero_stage,
            [(n, a.shape, a.dtype, specs[n], True)
             for n, a in t_avals.items()]
            + [(n, a.shape, a.dtype, specs[n], False)
               for n, a in f_avals.items()],
            self._opt_init, compressed=self.compression is not None,
            sparse_names=s_names, table_axis=str(
                _cfg.get('MXTPU_SPARSE_TABLE_AXIS') or '') or None)
        self._spec_map = layout.specs
        self._sparse_table_axis = layout.table_axis
        self._sparse_sig = {
            'mode': 'exact' if self._sparse_exact else 'lazy',
            'table_axis': layout.table_axis,
            'tables': {n: int(sum(sparse_budgets[n])) for n in s_names},
        } if s_names else None
        self._trainable, self._frozen = trainable, frozen
        self._t_names, self._shapes = layout.t_names, layout.shapes
        self.zero_specs = layout.zero_specs
        self.zero3_layouts = layout.zero3_layouts
        self._flat_meta = layout.flat_meta
        self._layer_groups = layout.layer_groups
        self._residual_shapes = layout.residual_shapes
        train_step = self._train_step(
            self._with_remat(forward_loss, layout), layout, sparse_budgets)
        # Name-stable jit boundary: the pytree dict keys of every param
        # container land in the lowered module's arg metadata and hence
        # the persistent XLA cache key. gluon's auto-naming counter
        # (bertforpretraining0_, ...3_, ...) would churn that key across
        # processes for structurally identical models, so each name is
        # aliased to a positional token derived from sorted order —
        # identical relative order for any two models differing only in
        # prefix — and the real names never cross into the traced
        # program. ``_alias_enc``/``_alias_dec`` translate at the call
        # site; the jitted function holds the reverse map in closure.
        self._alias = {n: f'p{i:04d}' for i, n in enumerate(
            sorted(set(layout.t_names) | set(layout.f_names)))}
        self._alias_rev = {t: n for n, t in self._alias.items()}
        _enc, _dec = self._alias_enc, self._alias_dec

        def stable_step(t_params, f_params, master, opt_state, residual,
                        inputs, labels, key, lr, fault_scale):
            out = train_step(_dec(t_params), _dec(f_params), _dec(master),
                             _dec(opt_state), _dec(residual),
                             inputs, labels, key, lr, fault_scale)
            return tuple(_enc(o) if isinstance(o, dict) else o
                         for o in out)

        repl, batch_sh = layout.repl, layout.batch_sh
        arrays = (_enc(layout.t_shardings), _enc(layout.f_shardings),
                  _enc(layout.master_shardings),
                  _enc(layout.state_shardings),
                  _enc(layout.residual_shardings))
        in_shardings = arrays + (
            tuple(batch_sh for _ in example_inputs),
            tuple(batch_sh for _ in example_labels), repl, repl, repl)
        out_shardings = arrays + (repl,)
        if self._guard is not None:
            out_shardings = out_shardings + (repl,)
        if s_names:
            out_shardings = out_shardings + (
                {self._alias[n]: repl for n in s_names},)
        self._compiled = jax.jit(stable_step, in_shardings=in_shardings,
                                 out_shardings=out_shardings,
                                 donate_argnums=(0, 2, 3, 4))
        plan = _exchange.wire_plan(layout, self.compression,
                                   sparse_budgets, self._sparse_exact)
        self._comm_plan, self._hop_plan = plan['comm'], plan['hop']
        self._sparse_hop = plan['sparse_hop']
        self._sparse_dense_hop = plan['sparse_dense_hop']
        self._comp_plan = plan['comp']
        # per-layer gather bytes (zero3): [(layer, bytes/step, gathers)]
        self._gather_plan = plan['gather']

    # ------------------------------------------------------------------
    def init(self, *example_inputs):
        """Force parameter init (deferred shapes) by one eager forward."""
        rec = _flags.is_recording
        _flags.is_recording = False
        try:
            self.block(*example_inputs)
        finally:
            _flags.is_recording = rec

    def _alias_enc(self, d):
        """Real-name dict -> positional-token dict (the compiled step's
        name-stable pytree keys; see the aliasing note in _build)."""
        a = self._alias
        return {a[n]: v for n, v in d.items()}

    def _alias_dec(self, d):
        """Positional-token dict -> real-name dict."""
        r = self._alias_rev
        return {r[t]: v for t, v in d.items()}

    def _build_signature(self, in_datas, lab_datas):
        """Structured compile-ledger signature of the step program:
        per-batch-arg shape/dtype (+ the dp batch sharding) and the flag
        knobs that change the compiled HLO — ZeRO stage, compression
        codec, guard, donation, mesh layout, parameter count."""
        batch_spec = str(self._layout.batch_sh.spec)
        args = [_compile.arg_sig(f'data{i}', x.shape, x.dtype,
                                 sharding=batch_spec,
                                 donated=False)
                for i, x in enumerate(in_datas)]
        args += [_compile.arg_sig(f'label{i}', x.shape, x.dtype,
                                  sharding=batch_spec, donated=False)
                 for i, x in enumerate(lab_datas)]
        try:
            mesh_shape = {str(k): int(v)
                          for k, v in dict(self.mesh.shape).items()}
        except Exception:
            mesh_shape = None
        from ..ops import autotune as _autotune
        return _compile.signature(args=args, flags={
            'zero': self._layout.label,
            'codec': self.compression['type']
            if self.compression is not None else None,
            'guard': self._guard is not None,
            'donate': True,
            'params': len(self._layout.t_names)
            + len(self._layout.f_names),
            'mesh': mesh_shape,
            'remat': self._remat_policy,
            # RowSparse fast path (ISSUE 19): mode + per-table row
            # budgets — a batch-shape change that moves a budget is a
            # legitimate recompile, and the ledger should say why
            'sparse': getattr(self, '_sparse_sig', None),
            # kernel block shapes the Pallas calls in this program
            # resolved to (env/db/default) — ISSUE 18: a DB-sourced
            # shape change is then a visible churn axis in the ledger,
            # not a silent recompile
            'autotune': _autotune.decision_flags() or None,
        })

    def __call__(self, inputs, labels, lr=None):
        cctx = None
        try:
            with _trace.span('step.dispatch', step=self._step_count):
                if self._compiled is None:
                    # compile ledger: everything from here to the first
                    # dispatch (where jit lazily lowers and
                    # backend-compiles) is compile time, and a stall
                    # anywhere inside the window classifies as COMPILING
                    # in the watchdog's stall verdict. Opened INSIDE the
                    # step.dispatch span: both sides end in-span, and a
                    # window straddling the span boundary corrupts the
                    # chrome B/E nesting.
                    cctx = _compile.begin('step:train_step')
                return self._call_traced(inputs, labels, lr, cctx)
        except BaseException:
            _compile.abort(cctx)
            raise

    def _call_traced(self, inputs, labels, lr=None, cctx=None):
        if self._guard is not None:
            # deferred read of the previous step's finiteness flag; a
            # rollback restores params/states/RNG and the post-restore
            # hook re-places them on the mesh — the CURRENT batch then
            # trains against the restored weights (fwd+bwd happen below,
            # after the restore, so nothing here is stale)
            self._guard.pre_step()
        fault = _faults.fire('step.dispatch')
        if self._spans_processes:
            # a process-spanning step IS a collective: once the
            # membership side channel has declared a peer lost, entering
            # it would wedge this process forever — fail fast instead
            # (ElasticController.pre_step turns the same signal into
            # commit + re-form before dispatch ever gets here)
            from ..resilience.elastic import raise_if_peer_lost
            raise_if_peer_lost()
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        in_datas, lab_datas = _datas(inputs), _datas(labels)
        # 1.0 on normal steps (exact-identity multiply on the loss); an
        # injected step.dispatch:nan flips it to NaN inside the compiled
        # step, so loss AND every gradient go non-finite even for
        # int-input models (BERT token ids)
        fault_scale = jnp.asarray(
            float('nan') if fault == 'nan' else 1.0, jnp.float32)
        if self._compiled is None:
            self._first_call(inputs, in_datas, lab_datas, cctx)
        t_params = self._alias_enc(
            {n: p.data()._data for n, p in self._trainable})
        f_params = self._alias_enc(
            {n: p.data()._data for n, p in self._frozen})
        master = self._alias_enc(self._master)
        opt_state = self._alias_enc(self._opt_state)
        residual = self._alias_enc(self._residual)
        key = _random.next_key()
        lr_val = jnp.asarray(lr if lr is not None else self.lr, jnp.float32)
        with _trace.span('h2d.batch_put'), \
                _memory.oom_guard('h2d.batch_put'):
            in_datas = tuple(_layout.put_batch(x, self._layout.batch_sh)
                             for x in in_datas)
            lab_datas = tuple(_layout.put_batch(x, self._layout.batch_sh)
                              for x in lab_datas)
        if self._cost_args is None:
            # abstract avals of one step call, kept for lower()
            self._cost_args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)),
                (t_params, f_params, master, opt_state, residual,
                 in_datas, lab_datas, key, lr_val, fault_scale))
        run = self._compiled
        if self._executable is not None and self._is_stored_batch(
                in_datas, lab_datas):
            # the program compiled_program() handed out: what a reader
            # of its text or its byte plan was told about is what runs
            run = self._executable
        with _trace.span('step.compiled'), \
                _memory.oom_guard('step.dispatch'):
            out = run(
                t_params, f_params, master, opt_state, residual,
                in_datas, lab_datas, key, lr_val, fault_scale)
        if cctx is not None:
            # the first dispatch returned: XLA's lower + backend compile
            # are done. Re-stamp the signature first: the lazy trace ran
            # inside the dispatch above, so any Pallas block-size
            # decisions (autotune.resolve) only exist NOW — the pre-trace
            # stamp in the build branch had 'autotune': None.
            _compile.set_signature(
                cctx, self._build_signature(in_datas, lab_datas))
            _compile.end(cctx)
        sparse_stats = None
        if self._sparse_names:
            sparse_stats = self._alias_dec(out[-1])
            out = out[:-1]
        if self._guard is not None:
            new_t, new_f, new_master, new_state, new_residual, loss, ok \
                = out
            self._guard.push_flag(ok)
        else:
            new_t, new_f, new_master, new_state, new_residual, loss = out
        new_t, new_f = self._alias_dec(new_t), self._alias_dec(new_f)
        new_master = self._alias_dec(new_master)
        new_state = self._alias_dec(new_state)
        new_residual = self._alias_dec(new_residual)
        with _trace.span('step.gather'):
            # donate/gather bookkeeping: swap the donated buffers'
            # NDArray views to the program's outputs (host pointer
            # swaps; the all-gather itself ran inside the program)
            for n, p in self._trainable:
                p.data()._data = new_t[n]
            for n, p in self._frozen:
                p.data()._data = new_f[n]
            self._master = new_master
            self._opt_state = new_state
            self._residual = new_residual
        self._step_count += 1
        self._record_step(sparse_stats)
        loss_nd = NDArray(_layout.local_value(loss))
        _memory.on_step(self._step_count)
        _flight.record_step(self._step_count, loss=loss_nd)
        return loss_nd

    def _is_stored_batch(self, in_datas, lab_datas):
        """Whether this call's batch has the shapes and dtypes of the
        stored avals, the only part of a call's signature that can move
        (a compiled executable, unlike the jitted step, takes no other)."""
        stored = self._cost_args[5] + self._cost_args[6]
        batch = in_datas + lab_datas
        return len(batch) == len(stored) and all(
            x.shape == a.shape and x.dtype == a.dtype
            for x, a in zip(batch, stored))

    def _first_call(self, inputs, in_datas, lab_datas, cctx):
        """Build the step for this batch's shapes, create the optimizer
        state and place everything to the layout; a restored states
        payload that waited for the build goes in last."""
        trainable, frozen = self._collect()
        if not trainable and not frozen:
            self.init(*inputs)
            trainable, frozen = self._collect()
        if any(p._data is None for _, p in trainable + frozen):
            self.init(*inputs)
        self._build(in_datas, lab_datas)
        lay = self._layout
        with _trace.span('optimizer.state_init'):
            # ZeRO-3 flat params carry flat (padded) moments
            self._opt_state = {
                n: self._opt_init(
                    jnp.zeros(lay.store_shapes[n], jnp.float32)
                    if n in lay.flat_meta
                    else p.data()._data.astype(jnp.float32))
                for n, p in self._trainable}
        if cctx is not None:
            _compile.set_signature(
                cctx, self._build_signature(in_datas, lab_datas))
        # place params on the mesh with their shardings
        with _trace.span('h2d.param_place'), \
                _memory.oom_guard('h2d.param_place'):
            for n, p in self._trainable:
                p._data[0]._data = _layout.put_replicated(
                    p.data()._data, lay.t_shardings[n])
            for n, p in self._frozen:
                p._data[0]._data = _layout.put_replicated(
                    p.data()._data, lay.f_shardings[n])
            self._master = {
                n: _layout.put_replicated(
                    self._master_host(n, p.data()._data),
                    lay.master_shardings[n])
                for n, p in self._trainable
                if n in lay.master_names}
            self._opt_state = {
                n: tuple(_layout.put_replicated(s, sh) for s, sh in
                         zip(self._opt_state[n], lay.state_shardings[n]))
                for n in lay.t_names}
            # error-feedback residuals seed to zero (a restore may
            # overwrite them from the states payload just below)
            self._residual = {
                n: _layout.put_replicated(
                    onp.zeros(lay.residual_shapes[n], onp.float32),
                    lay.residual_shardings[n])
                for n in lay.residual_shapes}
        if self._pending_states is not None:
            doc, self._pending_states = self._pending_states, None
            self._apply_states(doc)
        # memory observability: this step's live arrays (params /
        # masters+moments / residuals) become tracked pools for the
        # fallback watermark, and its memory_analysis() feeds the
        # OOM post-mortem's bucket table. Weakly referenced — a
        # rebuilt/dropped step never double-counts or pins arrays.
        _memory.register_provider(self)
        _memory.set_analysis_provider(self.memory_analysis,
                                      owner=self)
        if _telem['on']:
            from .. import telemetry as _telemetry
            _telemetry.set_gauge(
                'mxnet_tpu_comm_opt_state_bytes_per_device',
                self.opt_state_bytes_per_device())
            _telemetry.set_gauge(
                'mxnet_tpu_comm_param_bytes_per_device',
                self.param_bytes_per_device())
            if self.compression is not None:
                _telemetry.set_gauge(
                    'mxnet_tpu_comm_residual_bytes_per_device',
                    self.residual_bytes_per_device())
                cp = self._comp_plan
                if cp and cp['encoded_bytes']:
                    _telemetry.set_gauge(
                        'mxnet_tpu_comm_compression_ratio',
                        cp['raw_bytes'] / cp['encoded_bytes'])

    def _record_step(self, sparse_stats):
        """One step's trace instants and counters: the wire plan's, then
        the RowSparse live-row statistics."""
        if self._comm_plan:
            _exchange.record_wire(
                self._hop_plan, self._gather_plan, self._comp_plan,
                self._layout.label, self._axes.shard_axis)
        if sparse_stats is None:
            return
        prev_stats = self._sparse_prev_stats
        self._sparse_prev_stats = sparse_stats
        if _trace.enabled():
            for axis, nbytes in (self._sparse_hop or {}).items():
                _trace.instant('sparse.exchange', bytes=int(nbytes),
                               axis=axis,
                               tables=len(self._sparse_names))
            _trace.instant(
                'optimizer.sparse_update',
                mode='exact' if self._sparse_exact else 'lazy',
                tables=len(self._sparse_names))
        if _telem['on']:
            from .. import telemetry as _telemetry
            for axis, nbytes in (self._sparse_hop or {}).items():
                _telemetry.counter(
                    'mxnet_tpu_sparse_exchange_bytes_total').inc(
                        nbytes, axis=axis)
            if prev_stats is not None:
                for n, v in prev_stats.items():
                    # one-step-deferred host read: the PREVIOUS
                    # step's scalar has already materialized, so
                    # this never stalls the step just dispatched
                    live = int(v)
                    dim = self._shapes[n][1]
                    _telemetry.set_gauge(
                        'mxnet_tpu_sparse_live_rows', live, table=n)
                    _telemetry.counter(
                        'mxnet_tpu_sparse_row_bytes_total').inc(
                            live * dim * 4, table=n)
                    ids = self._sparse_id_counts.get(n, 0)
                    if live:
                        _telemetry.set_gauge(
                            'mxnet_tpu_sparse_dedup_ratio',
                            ids / live, table=n)

    def lower(self, inputs=None, labels=None):
        """The step program as XLA is handed it (``jax.stages.Lowered``)
        for ``inputs`` and ``labels`` given as arrays or
        ``jax.ShapeDtypeStruct``s — without them, for the avals of the
        first call. Places no array, dispatches nothing and needs no prior
        call, so the step's mesh may be one over a described topology:
        ``.compile().memory_analysis()`` of the result is how a batch is
        sized before a chip is taken."""
        step = self
        if inputs is None:
            if self._cost_args is None:
                raise MXNetError("lower(): the step has not run yet, "
                                 "pass inputs and labels")
            args = self._cost_args
        else:
            batch = (tuple(map(_aval, _datas(inputs))),
                     tuple(map(_aval, _datas(labels))))
            if self._compiled is None:
                # built on a copy: this step stays as it was, and its
                # first call still creates its state and places it
                step = copy.copy(self)
                step._build(*batch)
            args = step._step_avals(*batch)
        return step._compiled.lower(*args)

    def _step_avals(self, in_avals, lab_avals):
        """The avals of one call of the built step, from its layout."""
        lay, enc = self._layout, self._alias_enc
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        return (
            enc({n: jax.ShapeDtypeStruct(lay.shapes[n], lay.dtypes[n])
                 for n in lay.t_names}),
            enc({n: jax.ShapeDtypeStruct(lay.shapes[n], lay.dtypes[n])
                 for n in lay.f_names}),
            enc({n: jax.ShapeDtypeStruct(lay.store_shapes[n], jnp.float32)
                 for n in lay.master_names}),
            enc(lay.state_avals),
            enc({n: jax.ShapeDtypeStruct(shape, jnp.float32)
                 for n, shape in lay.residual_shapes.items()}),
            in_avals, lab_avals, jax.eval_shape(jax.random.PRNGKey, 0),
            scalar, scalar)

    def reset_mesh(self, mesh=None):
        """Adopt a NEW mesh (the elastic re-form path: the survivor
        world's device set after a peer loss, or any deliberate
        resize). Drops the compiled program, shardings and ZeRO layout
        — all rebuilt at the new dp degree on the next ``__call__`` —
        while carrying the training state across:

        - parameters gather to host (when addressable) and re-place
          with the new shardings at the next step;
        - optimizer state + fp32 masters ride the layout-independent
          ``get_states_bytes`` payload (the same contract checkpoints
          use), so dp=N ZeRO shards re-scatter as dp=M — or fully
          replicated — without precision loss;
        - when the old world's arrays are no longer addressable (their
          processes are gone), state is simply dropped: the caller
          restores the committed checkpoint right after, which is the
          elastic contract's source of truth anyway.
        """
        states = None
        if self._compiled is not None:
            try:
                states = self.get_states_bytes()
            except Exception:
                states = None   # unaddressable shards: restore supplies
            for _n, p in self._trainable + self._frozen:
                d = p.data()._data
                if getattr(d, 'is_fully_addressable', True):
                    p.data()._data = jnp.asarray(onp.asarray(d))
        # re-derive the hierarchy at the new world (survivor topologies
        # may have lost a whole host group)
        self._adopt_mesh(mesh if mesh is not None else default_mesh())
        self.zero_stage = self._requested_stage \
            if self._dp_size > 1 else 0
        self.zero = self.zero_stage > 0
        self._spans_processes = self._mesh_spans_processes()
        self._compiled = None
        self._executable = None
        self._cost_args = None
        self._master = None
        self._opt_state = None
        self._residual = None
        self._pending_states = None
        if states is not None:
            self.set_states_bytes(states)
        return self

    def _replace_params_on_mesh(self):
        """After an external restore wrote host arrays into the
        parameters (NonFiniteGuard rollback via CheckpointManager), put
        them back on the mesh with the step's shardings — the compiled
        step cannot consume cpu-committed arrays."""
        if self._compiled is None:
            return
        lay = self._layout
        with _memory.oom_guard('checkpoint.restore'):
            for n, p in self._trainable:
                p._data[0]._data = _layout.put_replicated(
                    onp.asarray(p.data()._data), lay.t_shardings[n])
            for n, p in self._frozen:
                p._data[0]._data = _layout.put_replicated(
                    onp.asarray(p.data()._data), lay.f_shardings[n])

    # ------------------------------------------------------------------
    # optimizer-state introspection + layout-independent checkpointing
    # ------------------------------------------------------------------
    def cost_analysis(self):
        """{'flops', 'bytes'} of ONE compiled step from XLA's own
        cost_analysis — the deterministic device-side half of the
        per-step attribution report (telemetry.attribution joins it
        with the measured wall-time spans). Read from
        ``compiled_program()``, which the step runs from then on; None
        before the first step or when the backend exposes no cost
        model."""
        from ..telemetry import attribution as _attribution
        try:
            compiled = self.compiled_program()
        except Exception:
            return None
        return _attribution.xla_cost(compiled)

    def compiled_program(self):
        """The step program as the backend compiled it (``as_text()`` is
        the optimized HLO chip_smoke.py reads for the Mosaic custom
        calls and the collectives around them; ``memory_analysis()`` is
        XLA's own byte plan). Raises before the first step.

        The first call compiles the stored avals ahead of time and keeps
        the executable; every later call returns the same object, and
        from then on the step *runs* it (``_call_traced``; a batch of
        another shape still takes the jitted function). So the text is
        the text of what a profile taken afterwards profiles: its
        instruction names are the executed ones, on any mesh. A step
        that is never asked for its program, its cost or its byte plan
        dispatches through ``jax.jit`` alone. ``reset_mesh()`` drops it.

        Its ``op_name``s are this process's own. The persistent cache's
        key leaves metadata out, so a hit may hand back an executable
        built from an older source, or from a model under another
        prefix, with *that* program's names in its text: the same
        instructions, scopes that no longer exist. A reader that splits a
        device trace by scope (chipbench/scopes.py) would then split by
        nothing. So this one compile makes the metadata part of the key:
        a persistent-cache hit only on a program traced from the same
        source, a compile of its own otherwise."""
        if self._executable is None:
            flag = 'jax_compilation_cache_include_metadata_in_key'
            before = getattr(jax.config, flag)
            jax.config.update(flag, True)
            try:
                self._executable = self.lower().compile()
            finally:
                jax.config.update(flag, before)
        return self._executable

    def memory_pools(self):
        """This step's live persistent arrays as named residency pools
        for ``telemetry.memory``'s fallback watermark:
        ``{'params', 'optimizer_state', 'residuals'} ->
        {array_name: jax array}``. Per-device byte accounting happens in
        the memory module (``entry_nbytes`` — the local shard for
        sharded arrays, so ZeRO residency is *measured*, not derived)."""
        pools = {'params': {}, 'optimizer_state': {}, 'residuals': {}}
        for n, p in (self._trainable or []) + (self._frozen or []):
            if p._data is not None:
                pools['params'][n] = p.data()._data
        for n, m in (self._master or {}).items():
            pools['optimizer_state'][f'master/{n}'] = m
        for n, st in (self._opt_state or {}).items():
            for i, s in enumerate(st):
                pools['optimizer_state'][f'moment{i}/{n}'] = s
        for n, r in (self._residual or {}).items():
            pools['residuals'][n] = r
        return pools

    def memory_analysis(self, peak_bytes=None):
        """Per-device memory attribution — the ``cost_analysis()``
        sibling (ISSUE 14). Joins the measured residency pools (local
        shard bytes of every live param/master/moment/residual), the
        ZeRO-3 per-layer layout + gather-plan accounting, and XLA's own
        compiled-program memory analysis into a bucket table

            params / optimizer_state / residuals / io_leases /
            activations_temp

        whose sum reconstructs the measured peak by construction:
        ``activations_temp`` is the explicit residual (peak minus the
        tracked persistent buckets), exactly how the wall-time report
        defines ``compute`` — with ``measured_fraction`` stating how
        much of the peak the tracked pools explain. ``peak_bytes``
        defaults to the backend allocator's peak where exposed, else
        the fallback watermark high-water mark (so on CPU the table is
        still honest: the residual is then ~0 and the buckets ARE the
        measurement). None before the first step."""
        if self._compiled is None:
            return None
        pools = self.memory_pools()
        buckets = {
            'params': _memory.pool_nbytes(pools.get('params')),
            'optimizer_state':
                _memory.pool_nbytes(pools.get('optimizer_state')),
            'residuals': _memory.pool_nbytes(pools.get('residuals')),
            'io_leases': _memory.pool_bytes_by_name('io_leases'),
        }
        persistent = sum(buckets.values())
        source = 'fallback'
        if peak_bytes is None:
            stats = _memory.device_memory_stats()
            if stats is not None and stats.get('peak_bytes_in_use'):
                peak_bytes = int(stats['peak_bytes_in_use'])
                source = 'memory_stats'
            else:
                peak_bytes = max(_memory.peak_bytes(), persistent)
        peak_bytes = max(int(peak_bytes), persistent)
        buckets['activations_temp'] = peak_bytes - persistent
        # per-layer persistent residency: the same layer grouping the
        # ZeRO-3 gather pipeline schedules by, summed over the layer's
        # params + masters + moments + residuals (per-device bytes) —
        # with the analytic gather wire plan alongside so the
        # remat-policy sweep can weigh persistent vs transient per layer
        per_layer = {}
        by_param = {}
        for pool in pools.values():
            for aname, arr in pool.items():
                pname = aname.split('/', 1)[-1]
                by_param[pname] = by_param.get(pname, 0) \
                    + _memory.entry_nbytes(arr)
        for gname, names in group_params_by_layer(self._t_names or []):
            per_layer[gname] = sum(by_param.get(n, 0) for n in names)
        self.opt_state_bytes_per_device()       # refreshes pad bytes
        out = {
            'peak_bytes_per_device': peak_bytes,
            'source': source,
            'buckets_bytes': buckets,
            'bucket_fractions': {
                k: round(v / peak_bytes, 4) if peak_bytes else 0.0
                for k, v in buckets.items()},
            'bucket_sum_over_peak':
                round(sum(buckets.values()) / peak_bytes, 4)
                if peak_bytes else 0.0,
            'measured_fraction':
                round(min(persistent, peak_bytes) / peak_bytes, 4)
                if peak_bytes else 0.0,
            'zero_stage': self.zero_stage,
            'dp': self._dp_size,
            'compression': self.compression['type']
            if self.compression else None,
            'pad_bytes': getattr(self, 'opt_state_pad_bytes', 0),
            'per_layer_bytes': per_layer,
            'host_rss_bytes': _memory.host_rss_bytes(),
        }
        if getattr(self, '_gather_plan', None):
            out['gather_bytes_per_layer'] = {
                str(layer): int(nbytes)
                for layer, nbytes, _c in self._gather_plan}
        xla = self._xla_memory_analysis()
        if xla:
            out['xla'] = xla
        return out

    def _xla_memory_analysis(self):
        """XLA's CompiledMemoryStats for one step program (argument /
        output / temp / generated-code / alias bytes), or None where
        the backend exposes none — reported alongside the measured
        buckets, never substituted for them."""
        try:
            ma = self.compiled_program().memory_analysis()
        except Exception:
            return None
        out = {}
        for k in ('argument_size_in_bytes', 'output_size_in_bytes',
                  'temp_size_in_bytes', 'alias_size_in_bytes',
                  'generated_code_size_in_bytes'):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        return out or None

    def _master_host(self, n, arr):
        """Host-side fp32 master for param ``n`` in its PERSISTENT
        layout: logical shape, or flattened + zero-padded to the dp
        multiple for ZeRO-3 flat params."""
        # lint: host-sync-ok master seeding runs once at build/restore, not in the step loop
        return self._layout.to_store(n, onp.asarray(arr, onp.float32))

    def opt_state_bytes_per_device(self):
        """Bytes of optimizer state (masters + moments) ONE device holds
        — physical ``addressable_shards`` bytes, so ZeRO-3 flat pad
        bytes are included (the per-param breakdown is on
        ``self.opt_state_pad_bytes`` after the first step). Under ZeRO
        this is ~1/dp of the replicated footprint (± the tensors too
        small to shard)."""
        total = 0
        for st in (self._opt_state or {}).values():
            for s in st:
                total += device_nbytes(s)
        for m in (self._master or {}).values():
            total += device_nbytes(m)
        # pad-to-divisible slack of the zero3 flat stores, per device:
        # pad elements * fp32 * (1 master + moment leaves) / dp
        pad = 0
        for n, fz in getattr(self, '_flat_meta', {}).items():
            leaves = 1 + sum(1 for s in self._opt_state[n] if s.ndim)
            pad += fz['pad'] * 4 * leaves // self._dp_size
        self.opt_state_pad_bytes = pad
        return total

    def param_bytes_per_device(self):
        """Bytes of the persistent parameters (trainable + frozen, in
        compute dtype) ONE device holds — under ZeRO-3 the dim-sharded
        params count their 1/dp shard. Masters are accounted by
        ``opt_state_bytes_per_device``; the two sum to the persistent
        model footprint per device."""
        total = 0
        for _n, p in (self._trainable or []) + (self._frozen or []):
            total += device_nbytes(p.data()._data)
        return total

    def gather_bytes_per_step(self):
        """Total analytic ring-wire bytes of the ZeRO-3 per-layer
        param gathers ONE step moves (sum of ``self._gather_plan``;
        0 outside stage 3)."""
        return int(sum(b for _l, b, _c in
                       getattr(self, '_gather_plan', None) or []))

    def residual_bytes_per_device(self):
        """Bytes of error-feedback compression residual ONE device
        holds (0 with compression off). Sharded with the grad layout,
        so ~1/shard-degree of the fp32 gradient footprint."""
        total = 0
        for r in (self._residual or {}).values():
            total += device_nbytes(r)
        return total

    def comm_bytes_per_hop(self):
        """Analytic ring-wire bytes ONE step moves, by mesh hop:
        ``{axis: bytes}``. Flat topologies report one ``dp`` hop;
        hierarchical ones separate the intra-host (``<dp>i``, ICI) hop
        from the cross-host (``<dp>h``, DCN) hop — the latter carries
        the encoded payload under compression, which is the measurable
        wire win."""
        hops = {}
        for (_kind, axis), (nbytes, _c) in \
                (getattr(self, '_hop_plan', None) or {}).items():
            hops[axis] = hops.get(axis, 0) + int(nbytes)
        return hops

    def compression_report(self):
        """{'codec', 'raw_bytes_per_step', 'encoded_bytes_per_step',
        'ratio', 'hierarchy', 'residual_bytes_per_device'} of the
        compressed gradient exchange — None with compression off."""
        cp = getattr(self, '_comp_plan', None)
        if cp is None:
            return None
        return {
            'codec': cp['codec'],
            'raw_bytes_per_step': int(cp['raw_bytes']),
            'encoded_bytes_per_step': int(cp['encoded_bytes']),
            'ratio': cp['raw_bytes'] / max(1.0, cp['encoded_bytes']),
            'axis': cp['axis'],
            'hierarchy': (self._axes.cross_size, self._axes.shard_size),
            'residual_bytes_per_device': self.residual_bytes_per_device(),
        }

    def sparse_layout(self):
        """RowSparse layout description for the checkpoint manifest
        (``optimizer_state_layout.sparse``): update mode, table-shard
        axis and per-table (vocab, dim, live-row budget). None before
        the first build or when no table took the sparse path. The
        state tensors themselves stay table-shaped (lazy updates touch
        rows in place), so dense<->sparse and dp=N<->dp=M restores need
        no layout conversion — this record is provenance, not a
        decoder requirement."""
        if not getattr(self, '_sparse_names', None):
            return None
        return {
            'mode': 'exact' if self._sparse_exact else 'lazy',
            'table_axis': self._sparse_table_axis,
            'tables': {n: {'vocab': int(self._shapes[n][0]),
                           'dim': int(self._shapes[n][1]),
                           'budget': int(sum(self._sparse_budgets[n])),
                           'ids_per_step':
                               int(self._sparse_id_counts.get(n, 0))}
                       for n in self._sparse_names},
        }

    def sparse_report(self):
        """Analytic per-step cost of the RowSparse fast path vs the
        dense path it replaced (``exchange.sparse_report``) — None when
        no table took it."""
        if not getattr(self, '_sparse_names', None):
            return None
        return _exchange.sparse_report(
            self._layout, self._sparse_budgets, self._sparse_exact,
            self._sparse_hop, self._sparse_dense_hop)

    def get_states_bytes(self):
        """Optimizer state as a layout-independent bytes payload: every
        shard is gathered to host fp32 numpy, so a checkpoint written at
        one dp degree (or under ZeRO) restores at any other — the same
        contract as gluon.Trainer.get_states_bytes, and what
        checkpoint.CheckpointManager snapshots when bound as `trainer=`."""
        import pickle
        if self._compiled is None:
            if self._pending_states is not None:
                # resumed but not yet stepped (e.g. a preemption save in
                # the restore->first-step window): the restored payload
                # IS the current state — hand it back unchanged
                return pickle.dumps(self._pending_states)
            raise MXNetError("get_states_bytes: no optimizer state yet — "
                             "run at least one step first")
        # every leaf gathers to host in LOGICAL shape (zero3 flat
        # stores un-flatten), so the payload restores at any dp/stage
        states = {n: tuple(self._layout.to_logical(n, s) for s in st)
                  for n, st in self._opt_state.items()}
        master = {n: self._layout.to_logical(n, m)
                  for n, m in self._master.items()}
        doc = {
            'format': 'sharded_train_step_v1',
            'opt_state': states, 'master': master,
            'step_count': self._step_count,
            'zero': self.zero, 'stage': self.zero_stage,
            'dp': self._dp_size}
        if self._residual:
            # error-feedback residuals ride the layout-independent
            # payload in LOGICAL shape (flat stores un-flatten), so a
            # compressed run restores its exact error state at any dp
            # degree; an uncompressed restore target simply drops them
            doc['residual'] = {n: self._layout.to_logical(n, r)
                               for n, r in self._residual.items()}
            doc['compression'] = dict(self.compression)
        sp = self.sparse_layout()
        if sp is not None:
            # provenance only: sparse state tensors are table-shaped,
            # so restore needs no conversion in either direction
            doc['sparse'] = sp
        return pickle.dumps(doc)

    def set_states_bytes(self, blob):
        """Restore a get_states_bytes() payload, scattering each tensor
        into THIS step's current layout (replicated, tp, or ZeRO 1/dp —
        the saved layout does not have to match)."""
        import pickle
        doc = pickle.loads(blob)
        if doc.get('format') != 'sharded_train_step_v1':
            raise MXNetError(
                f"set_states_bytes: not a ShardedTrainStep payload "
                f"(format={doc.get('format')!r})")
        if self._compiled is None:
            self._pending_states = doc   # applied right after first build
            return
        self._apply_states(doc)

    def _apply_states(self, doc):
        # restore re-place is a burst of device allocations over a
        # device already holding the pre-restore state — an OOM here
        # must leave the same forensics as one mid-step
        with _memory.oom_guard('checkpoint.restore'):
            self._apply_states_guarded(doc)

    def _apply_states_guarded(self, doc):
        for n, st in doc['opt_state'].items():
            if n not in self._opt_state:
                raise MXNetError(f"set_states_bytes: unknown parameter "
                                 f"{n!r} in restored optimizer state")
            self._opt_state[n] = tuple(
                _layout.put_replicated(self._layout.to_store(n, s), sh)
                for s, sh in zip(st, self._layout.state_shardings[n]))
        restored_master = doc.get('master', {})
        for n, m in restored_master.items():
            if n in self._layout.master_names:
                self._master[n] = _layout.put_replicated(
                    self._layout.to_store(n, m),
                    self._layout.master_shardings[n])
        # zero3 flat masters with no saved counterpart (payload written
        # under zero off/1, where the param carried the value itself):
        # reseed from the CURRENT param so the flat store matches the
        # restored weights instead of keeping a pre-restore value
        for n, p in self._trainable or []:
            if n in self._flat_meta and n not in restored_master \
                    and n in self._layout.master_names:
                self._master[n] = _layout.put_replicated(
                    # lint: host-sync-ok restore-time reseed, runs once per restore
                    self._master_host(n, onp.asarray(p.data()._data)),
                    self._layout.master_shardings[n])
        # error-feedback residuals: restored when the payload carries
        # them (scattered into THIS step's layout), deterministically
        # reseeded to zero otherwise (a payload saved without
        # compression has no error state to carry — documented
        # trajectory note in README "Gradient compression")
        if self._residual is not None and self._residual_shapes:
            restored_res = doc.get('residual', {})
            for n in self._residual_shapes:
                if n in restored_res:
                    self._residual[n] = _layout.put_replicated(
                        self._layout.to_store(n, restored_res[n]),
                        self._layout.residual_shardings[n])
                else:
                    self._residual[n] = _layout.put_replicated(
                        onp.zeros(self._residual_shapes[n], onp.float32),
                        self._layout.residual_shardings[n])
        self._step_count = int(doc.get('step_count', self._step_count))
