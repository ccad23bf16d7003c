"""Gluon Trainer (ref: python/mxnet/gluon/trainer.py).

API-compatible DP training driver. On a mesh-sharded compiled path the
gradient all-reduce is emitted by XLA inside the step function; in the
eager/multi-context path the kvstore reduces across device copies
(ref: trainer.py:174-261 _init_kvstore, :320 step, :349 allreduce_grads,
:430 _update).
"""
from __future__ import annotations

from ..base import MXNetError, telem_flags as _telem
from ..ndarray.ndarray import NDArray
from .. import optimizer as opt
from .. import kvstore as kvs
from ..resilience import faults as _faults
from ..telemetry import trace as _trace, flight as _flight, \
    memory as _memory, compile as _compile
from .parameter import ParameterDict, Parameter


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore='device',
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError("First argument must be a list or dict of Parameters")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(f"First argument must contain Parameters, got {type(param)}")
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get('rescale_grad', 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        self._params_to_init = []
        self._contains_sparse_weight = any(
            p._stype != 'default' for p in self._params)
        self._contains_sparse_grad = any(
            p._grad_stype != 'default' for p in self._params)
        # telemetry: perf_counter of the previous step() call — the
        # inter-step interval is the true iteration time (fwd+bwd+update).
        # The EMA guards the histogram against counting pauses between
        # steps (eval pass, checkpoint save) as step time.
        self._telem_last_step = None
        self._telem_step_ema = None
        # ZeRO state of the fused update; populated by _fused_apply
        # when the weights live on a >1-device dp mesh (see _zero_layout).
        # Stage 1 shards the optimizer states 1/dp; stage 3 (MXTPU_ZERO=3)
        # additionally re-places the weight NDArrays themselves sharded.
        self._zero_active = False
        self._zero_dp = 1
        self._zero_stage = 0
        self._zero3_mesh = None   # mesh to re-place onto after a restore
        # resilience.NonFiniteGuard bound via attach_guard(): the fused
        # update then also reduces isfinite over every gradient and
        # skips the writeback ON DEVICE when the step is non-finite
        self._guard = None
        # resilience.ElasticController bound via attach_elastic():
        # step() then consults it first, so preemption/peer loss turns
        # into commit -> re-form -> resume with the user loop unmodified
        self._elastic = None

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = None

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def sparse_layout(self):
        """RowSparse layout of the eager update path for the checkpoint
        manifest (``optimizer_state_layout.sparse``), mirroring
        ShardedTrainStep.sparse_layout: None when no parameter carries
        a ``row_sparse`` gradient; otherwise the update mode (lazy when
        the optimizer dispatches lazy row updates) and the (vocab, dim)
        of every sparse-grad table. Provenance only — state tensors
        stay table-shaped either way."""
        tables = {}
        for p in self._params:
            if p._grad_stype != 'row_sparse':
                continue
            shape = tuple(p.shape or ())
            if len(shape) == 2:
                tables[p.name] = {'vocab': int(shape[0]),
                                  'dim': int(shape[1])}
        if not tables:
            return None
        lazy = bool(getattr(self._optimizer, 'lazy_update', False))
        return {'mode': 'lazy' if lazy else 'exact',
                'table_axis': None, 'tables': tables}

    def _compression_requested(self):
        return self._compression_params is not None and \
            self._compression_params.get('type', '2bit') != 'none'

    def _local_compression(self):
        """The trainer-owned error-feedback compressor for the paths
        that never pass a kvstore push (kvstore=None, and the
        GSPMD-mesh / single-copy path where the push is skipped) —
        routed for real instead of rejected (ISSUE 12). Residuals key
        by parameter index; a ``set_states_bytes`` restore resets them
        (deterministic reseed — the old error state no longer describes
        the rewound trajectory)."""
        comp = getattr(self, '_local_gc', None)
        if comp is None:
            from ..kvstore.gradient_compression import GradientCompression
            p = self._compression_params or {}
            comp = self._local_gc = GradientCompression(
                p.get('type', '2bit'), p.get('threshold', 0.5),
                p.get('block_size', 0))
        return comp

    def _init_kvstore(self):
        """Ref: trainer.py:174."""
        if self._kvstore_type is None or self._kvstore_type is False:
            self._kvstore = None
            if self._update_on_kvstore is None:
                self._update_on_kvstore = False
        else:
            kv = self._kvstore_type if isinstance(self._kvstore_type, kvs.KVStoreBase) \
                else kvs.create(self._kvstore_type)
            self._kvstore = kv
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            if self._update_on_kvstore is None:
                # local training prefers updating on workers (ref :195);
                # dist + sparse forces update_on_kvstore
                self._update_on_kvstore = bool(self._contains_sparse_weight)
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
        # one updater shared across ctxs: reference keeps per-device updaters
        # but states are per-parameter, so a single updater suffices here.
        self._updater = opt.get_updater(self._optimizer)
        # register params into kvstore
        if self._kvstore is not None:
            for i, param in enumerate(self._params):
                if param._data is not None:
                    self._kvstore.init(i, param.data(param.list_ctx()[0]))
        self._kv_initialized = True
        # memory observability: this trainer's params + optimizer state
        # become tracked pools for the fallback watermark (weakly
        # referenced — a dropped trainer never pins its arrays)
        _memory.register_provider(self)

    def _row_sparse_pull(self, parameter, out, row_id, full_idx=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None:
            return
        idx = self._param2idx[parameter.name]
        self._kvstore.row_sparse_pull(idx, out=out, row_ids=row_id)

    def step(self, batch_size, ignore_stale_grad=False):
        """Gradient sync + optimizer update (ref: trainer.py:320)."""
        if _telem['on']:
            import time as _time
            from .. import telemetry as _telemetry
            now = _time.perf_counter()
            last, ema = self._telem_last_step, self._telem_step_ema
            self._telem_last_step = now
            if last is not None:
                dt = now - last
                if ema is None:
                    # the first interval seeds the filter but is NOT
                    # recorded: it typically contains the step compile
                    # (and may contain a pause), either of which would
                    # poison both the histogram and the EMA baseline
                    self._telem_step_ema = dt
                elif dt <= 20.0 * ema:
                    _telemetry.record_step(dt, batch_size)
                    self._telem_step_ema = 0.9 * ema + 0.1 * dt
                # else: >20x the running step time is a pause (eval,
                # checkpoint) or a recompile spike, not a step — keep it
                # out of the histogram and the samples/sec + MFU gauges
        if self._elastic is not None:
            # preemption -> Preempted (final checkpoint committed);
            # peer loss -> commit + mesh re-form + restore happened just
            # now: the gradients in the param buffers were computed
            # against pre-re-form weights, so this step's update is
            # dropped and training resumes on the next batch
            if self._elastic.pre_step() is not None:
                return
        if not self._kv_initialized:
            self._init_kvstore()
        with _trace.span('step.dispatch'):
            kind = _faults.fire('step.dispatch')
            if kind == 'nan':
                self._poison_grads()
            if self._guard is not None and \
                    self._guard.pre_step(on_bad=self._rewind_update_counts):
                # a rollback just restored params/optimizer/RNG: the
                # gradients sitting in the param buffers were computed
                # against the pre-rollback weights — applying them would
                # corrupt the freshly restored state, so this step's
                # update is dropped and training resumes on the next
                # batch
                return
            self._optimizer.rescale_grad = self._scale / batch_size
            with _trace.span('comm.allreduce'):
                self._allreduce_grads()
            with _trace.span('optimizer.update'), \
                    _memory.oom_guard('step.dispatch'):
                self._update(ignore_stale_grad)
        _memory.on_step(self._optimizer.num_update)
        _flight.record_step(self._optimizer.num_update)
        if self._elastic is not None:
            # feed the controller's commit point (and the heartbeat's
            # piggybacked step) — an elastic commit must capture THIS
            # step, not the last cadence save
            self._elastic.beat(self._optimizer.num_update)

    def attach_guard(self, guard):
        """Bind a ``resilience.NonFiniteGuard``. The fused update gains
        an on-device all-gradients-finite reduction whose flag the guard
        reads (deferred, no extra host sync) at the next step; a
        non-finite step's weight/state writeback is skipped inside the
        same XLA program. Forces a retrace (the guard changes the fused
        program's signature)."""
        self._guard = guard
        self._fused_cache = None
        self._fused_traced = False

    def attach_elastic(self, controller):
        """Bind a ``resilience.ElasticController``: every ``step()``
        then consults it first (preemption -> ``Preempted`` after the
        final commit; peer loss -> commit + re-form + restore, this
        step's stale gradients dropped) and the controller re-forms this
        trainer via ``_on_reform`` — user training loops run
        unmodified."""
        self._elastic = controller
        controller.attach_trainer(self)
        return controller

    def _on_reform(self, mesh=None):
        """Elastic re-form: the world size (and with it the dp degree
        and ZeRO layout) just changed. Drop the fused-update cache and
        the remembered ZeRO placement so the next step re-derives the
        layout from wherever the restored weights now live; the
        optimizer-state scatter re-runs there too (the restored states
        payload is host-gathered, same as after set_states_bytes)."""
        self._fused_cache = None
        self._fused_traced = False
        self._zero_active = False
        self._zero_dp = 1
        self._zero_stage = 0
        self._zero3_mesh = mesh if mesh is not None and \
            dict(getattr(mesh, 'shape', {})).get('dp', 0) > 1 else None
        self.reset_step_timer()

    def _poison_grads(self):
        """Injected ``step.dispatch:nan`` fault: overwrite every gradient
        with NaN on device, so the guard's detection/skip/rollback path
        is exercised by a REAL non-finite step."""
        for param in self._params:
            if param.grad_req == 'null' or param._data is None:
                continue
            for g in param.list_grad():
                g._data = g._data * float('nan')

    def _guard_grads_ok(self, grads=None):
        """Eager all-finite check (host sync — only for the paths that
        cannot fuse the check into a compiled program: kvstore-side
        updates and non-traceable optimizers). ``grads`` is an optional
        iterable of gradient NDArrays; by default every parameter's
        gradient copies are scanned."""
        import jax.numpy as jnp
        if grads is None:
            grads = (g for param in self._params
                     if param.grad_req != 'null' and param._data is not None
                     for g in param.list_grad())
        # reduce on device first: ONE host sync per step, not one per
        # gradient
        checks = [jnp.all(jnp.isfinite(g._data)) for g in grads]
        if not checks:
            return True
        return bool(jnp.all(jnp.stack(checks)))

    def _rewind_update_counts(self):
        """A guard-skipped step was a device no-op, but the fused
        dispatch advanced the host-side optimizer update counts before
        the flag was known — rewind them so bias correction and
        num_update-keyed LR schedules see the skip as a true no-op.
        (The pjit ShardedTrainStep keeps t inside the where-gated
        optimizer state, so only this path needs the rewind.)"""
        snap = getattr(self, '_fused_count_snapshot', None)
        if snap is not None:
            counts, num = snap
            self._optimizer._index_update_count = dict(counts)
            self._optimizer.num_update = num
            self._fused_count_snapshot = None

    def reset_step_timer(self):
        """Forget the previous step() timestamp so an intervening pause
        (validation pass, checkpoint save) is not measured as step time
        by the telemetry step histogram. Call after any long gap."""
        self._telem_last_step = None

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        """Ref: trainer.py:349."""
        if self._kvstore is None:
            return
        for i, param in enumerate(self._params):
            if param.grad_req == 'null' or param._data is None:
                continue
            grads = param.list_grad()
            if len(grads) == 1 and self._kvstore.num_workers == 1:
                if self._compression_requested() and \
                        not self._update_on_kvstore:
                    # update_on_kvstore pushes in _update (compression
                    # applies there); THIS path skips the push entirely,
                    # so apply the SAME error-feedback codec in place —
                    # the semantics of a push through a compressing
                    # kvstore, minus the no-op self-reduce (ISSUE 12:
                    # routed for real instead of raising)
                    comp = self._kvstore._compression \
                        if getattr(self._kvstore, '_compression', None) \
                        is not None else self._local_compression()
                    grads[0]._data = comp.compress_decompress(
                        grads[0], i)._data
                continue
            if self._update_on_kvstore:
                continue  # push+pull happens in _update via kvstore updater
            self._kvstore.push(i, grads)
            self._kvstore.pull(i, grads, ignore_sparse=False)

    def _update(self, ignore_stale_grad=False):
        """Ref: trainer.py:430."""
        # AMP dynamic loss scaling: skip the update on non-finite grads and
        # shrink the scale (ref: contrib/amp/loss_scaler.py via trainer
        # hook). Lives here so both step() and update()/allreduce_grads()
        # (gradient accumulation) are covered.
        scaler = getattr(self, '_amp_loss_scaler', None)
        if scaler is not None and scaler.dynamic:
            overflow = scaler.has_overflow(self._params)
            scaler.update_scale(overflow)
            if overflow:
                return
        if self._update_on_kvstore and self._kvstore is not None:
            if self._guard is not None:
                # the update applies on the kvstore side, out of reach of
                # the fused on-device gate — check eagerly BEFORE the
                # push, or a NaN step poisons every replica
                self._fused_count_snapshot = None   # nothing to rewind
                ok = self._guard_grads_ok()
                self._guard.push_flag(ok)
                if not ok:
                    return
            for i, param in enumerate(self._params):
                if param.grad_req == 'null' or param._data is None:
                    continue
                self._kvstore.push(i, param.list_grad())
                self._kvstore.pull(i, param.list_data())
            return
        import jax
        from ..kvstore.kvstore import _reduce
        compress_here = self._kvstore is None and \
            self._compression_requested()
        items = []
        for i, param in enumerate(self._params):
            if param.grad_req == 'null' or param._data is None:
                continue
            datas = param.list_data()
            grads = param.list_grad()
            # after allreduce every ctx grad is identical; with no kvstore
            # the reduction happens here so no context's contribution drops
            g = grads[0] if (self._kvstore is not None or len(grads) == 1) \
                else _reduce(grads)
            if compress_here:
                # kvstore=None: no push exists, so the error-feedback
                # codec applies to the merged gradient right here
                # (ISSUE 12: routed for real instead of raising)
                g = self._local_compression().compress_decompress(g, i)
            items.append((i, param, g, datas))
        # one jitted multi-tensor apply for ALL parameters (the analog of
        # the reference's fused preloaded_multi_sgd/multi_lamb update ops,
        # ref: src/operator/contrib/preloaded_multi_sgd.cc) — falls back to
        # the per-param python loop for optimizers that sync to host
        # mid-update (e.g. LARS norms)
        if self._fused_apply(items):
            pass
        else:
            if self._guard is not None and items:
                # eager fallback can't skip on device: check the grads
                # up front (this path already syncs per parameter); the
                # skip happens before any count advances — no rewind
                self._fused_count_snapshot = None
                ok = self._guard_grads_ok([g for _, _, g, _ in items])
                self._guard.push_flag(ok)
                if not ok:
                    return
            for i, param, g, datas in items:
                self._updater(i, g, datas[0])
        # broadcast the updated first copy to the other context copies
        # (ref: trainer.py:430 per-device update; collapsed so state
        # copies don't ping-pong between devices). ONE batched
        # device_put for every (param, copy) pair — per-array transfers
        # paid a dispatch round-trip per parameter per step.
        dsts, srcs, shards = [], [], []
        for i, param, g, datas in items:
            src = datas[0]._data
            for d in datas[1:]:
                dsts.append(d)
                srcs.append(src)
                shards.append(d._data.sharding)
        if dsts:
            with _trace.span('comm.broadcast'):
                for d, out in zip(dsts, jax.device_put(srcs, shards)):
                    d._data = out
            if _telem['on']:
                from .. import telemetry as _telemetry
                _telemetry.counter(
                    'mxnet_tpu_comm_collective_bytes_total').inc(
                        sum(int(s.size) * s.dtype.itemsize for s in srcs),
                        kind='broadcast', axis='ctx')
                _telemetry.counter('mxnet_tpu_comm_collectives_total').inc(
                    1, kind='broadcast', axis='ctx')

    def _zero_layout(self, items):
        """Mesh layout for the fused update, or None when the weights'
        primary copies do not all live on one NamedSharding mesh. When
        they do, the optimizer states must be placed on that mesh too
        (a jit cannot mix device sets). 'zero' is set when MXTPU_ZERO
        allows (default on) and the mesh has a 'dp' axis of >1 devices:
        each optimizer-state tensor (fp32 master + moments) then shards
        1/dp over that axis — the traced multi-tensor update computes
        only the local slice and all-gathers the updated weights back to
        their own layout. With zero off the states replicate.

        Stage 3 (MXTPU_ZERO=3): the weight NDArrays THEMSELVES are
        re-placed dp-sharded (one batched device_put) and the fused
        update's out_shardings keep them sharded — eager forward/backward
        consume the logically-global sharded arrays directly, so user
        training loops run unmodified. A checkpoint restore rewrites the
        params as host arrays; the mesh is remembered and the placement
        re-runs on the next fused-cache rebuild (set_states_bytes clears
        the cache)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.layout import compose_zero_spec
        from .. import config as _config
        stage = int(_config.get('MXTPU_ZERO') or 0)
        mesh = None
        on_mesh = True
        for _, _, _, datas in items:
            sh = datas[0]._data.sharding
            if not isinstance(sh, NamedSharding):
                on_mesh = False
                break
            if mesh is None:
                mesh = sh.mesh
            elif sh.mesh != mesh:
                return None
        replaced = False
        if not on_mesh:
            # a restore (CheckpointManager / load_params) rewrote the
            # weights as host arrays: under a previously-active stage 3
            # re-adopt the remembered mesh and re-place below
            if stage == 3 and self._zero3_mesh is not None:
                mesh, replaced = self._zero3_mesh, True
            else:
                return None
        if mesh is None:
            return None
        dp = dict(mesh.shape).get('dp', 0)
        zero_on = stage >= 1 and dp > 1
        stage3 = stage == 3 and dp > 1
        repl = NamedSharding(mesh, PartitionSpec())
        w_sh, state_sh, place = [], [], []
        for _, _, _, datas in items:
            cur = datas[0]._data.sharding
            if not isinstance(cur, NamedSharding):
                cur = repl
            zspec = compose_zero_spec(tuple(datas[0].shape), cur.spec,
                                      'dp', dp) if zero_on else None
            zsh = NamedSharding(mesh, zspec) if zspec is not None else None
            target = zsh if (stage3 and zsh is not None) else cur
            w_sh.append(target)
            state_sh.append(zsh)
            if (stage3 or replaced) and \
                    datas[0]._data.sharding != target:
                place.append((datas[0], target))
        if place:
            import jax
            with _memory.oom_guard('h2d.param_place'):
                placed = jax.device_put([d._data for d, _ in place],
                                        [sh for _, sh in place])
            nbytes = 0
            for (d, _), out in zip(place, placed):
                d._data = out
                nbytes += int(out.size) * out.dtype.itemsize
            if _telem['on']:
                from .. import telemetry as _telemetry
                _telemetry.counter(
                    'mxnet_tpu_comm_collective_bytes_total').inc(
                        nbytes, kind='param_scatter', axis='dp')
                _telemetry.counter('mxnet_tpu_comm_collectives_total').inc(
                    1, kind='param_scatter', axis='dp')
        self._zero3_mesh = mesh if stage3 else None
        return {'mesh': mesh, 'dp': dp if zero_on else 1, 'zero': zero_on,
                'stage': (3 if stage3 else 1) if zero_on else 0,
                'w_sh': w_sh, 'state_sh': state_sh, 'repl': repl}

    def _zero_place_states(self, items, zero):
        """Scatter optimizer-state NDArrays into the ZeRO layout (one
        batched transfer). Weight-shaped leaves take the param's 1/dp
        spec; everything else replicates onto the mesh so the fused jit
        sees one device set. Re-runs after set_states_bytes — a restored
        payload is host-gathered numpy, so checkpoints stay
        layout-independent and resume at any dp degree."""
        import jax
        from ..ndarray.ndarray import NDArray
        pending = []

        def _walk(s, target, wshape):
            if isinstance(s, NDArray):
                sh = target if tuple(s._data.shape) == wshape \
                    else zero['repl']
                if s._data.sharding != sh:
                    pending.append((s, sh))
            elif isinstance(s, (list, tuple)):
                for x in s:
                    _walk(x, target, wshape)

        for n, (i, p, g, datas) in enumerate(items):
            # no 1/dp spec -> weight-shaped leaves follow the weight's own
            # layout (fsdp-style dp-sharded weights keep sharded states)
            _walk(self._updater.states[i],
                  zero['state_sh'][n] or zero['w_sh'][n],
                  tuple(datas[0].shape))
        if pending:
            with _memory.oom_guard('h2d.param_place'):
                placed = jax.device_put([s._data for s, _ in pending],
                                        [sh for _, sh in pending])
            nbytes = 0
            for (s, _), d in zip(pending, placed):
                s._data = d
                nbytes += int(d.size) * d.dtype.itemsize
            if _telem['on']:
                from .. import telemetry as _telemetry
                _telemetry.counter(
                    'mxnet_tpu_comm_collective_bytes_total').inc(
                        nbytes, kind='state_scatter', axis='dp')
                _telemetry.counter('mxnet_tpu_comm_collectives_total').inc(
                    1, kind='state_scatter', axis='dp')
        if _telem['on']:
            from .. import telemetry as _telemetry
            _telemetry.set_gauge(
                'mxnet_tpu_comm_opt_state_bytes_per_device',
                self.opt_state_bytes_per_device())
            _telemetry.set_gauge(
                'mxnet_tpu_comm_param_bytes_per_device',
                self.param_bytes_per_device())

    def opt_state_bytes_per_device(self):
        """Bytes of optimizer state ONE device holds (ZeRO-1: ~1/dp of
        the replicated footprint, ± tensors too small to shard)."""
        from ..ndarray.ndarray import NDArray
        from ..parallel.step import device_nbytes
        total = 0

        def _walk(s):
            nonlocal total
            if isinstance(s, NDArray):
                total += device_nbytes(s._data)
            elif isinstance(s, (list, tuple)):
                for x in s:
                    _walk(x)

        if self._updater is not None:
            for st in self._updater.states.values():
                _walk(st)
        return total

    def param_bytes_per_device(self):
        """Bytes of the parameters' primary copies ONE device holds —
        under ZeRO-3 (stage-3 fused layout) the dp-sharded weights count
        their 1/dp shard; replicated/single-device weights count in
        full."""
        from ..parallel.step import device_nbytes
        total = 0
        for p in self._params:
            if p._data is None:
                continue
            total += device_nbytes(p.data()._data)
        return total

    def memory_pools(self):
        """The trainer path's live arrays as named residency pools for
        ``telemetry.memory``'s fallback watermark — the gluon sibling
        of ``ShardedTrainStep.memory_pools`` (params' primary copies +
        the updater's per-param optimizer state)."""
        from ..ndarray.ndarray import NDArray
        pools = {'params': {}, 'optimizer_state': {}}
        for p in self._params:
            if p._data is not None:
                pools['params'][p.name] = p.data()._data

        def _walk(prefix, s):
            if isinstance(s, NDArray):
                pools['optimizer_state'][prefix] = s._data
            elif isinstance(s, (list, tuple)):
                for j, x in enumerate(s):
                    _walk(f'{prefix}/{j}', x)

        if self._updater is not None:
            names = {i: p.name for i, p in enumerate(self._params)}
            for i, st in self._updater.states.items():
                _walk(f'state/{names.get(i, i)}', st)
        return pools

    def _fused_apply(self, items):
        """Run every parameter update as ONE compiled XLA program.

        The optimizer's python `update()` is traced once (per param-set /
        dtype signature) with the per-step host scalars — lr, wd, update
        count t, rescale_grad — fed in as traced inputs, so subsequent
        steps re-run the cached program with zero python dispatch per
        parameter. Optimizer state NDArrays are updated in place (their
        `_data` is swapped), preserving save_states()/set_states().
        Returns False when the optimizer cannot be traced (host syncs) —
        caller falls back to the eager per-param loop."""
        if not items:
            return True
        if getattr(self, '_fused_disabled', False):
            return False
        if not getattr(self._optimizer, 'fused_update', False):
            # opt-in only: an impure update() (host syncs, python-state
            # mutation) can trace "successfully" but compute the wrong
            # schedule — never guess
            self._fused_disabled = True
            return False
        if any(p._stype != 'default' or p._grad_stype != 'default'
               for _, p, _, _ in items):
            return False
        import jax
        import jax.numpy as jnp
        from ..ndarray.ndarray import NDArray

        opt = self._optimizer
        updater = self._updater
        indices = [i for i, _, _, _ in items]
        # materialize states eagerly (outside the trace)
        for i, p, g, datas in items:
            if i not in updater.states:
                updater.states[i] = opt.create_state_multi_precision(
                    i, datas[0])
                updater.states_synced[i] = True
        # mesh-resident weights: states must live on the same mesh —
        # sharded 1/dp under ZeRO-1, replicated otherwise. Layout
        # detection + placement walk every param, so they run only in
        # the cache-(re)build branch below (first step, new param
        # set/dtype, or after set_states_bytes cleared the cache to
        # re-scatter a restore) — never on the per-step hot path.
        zero = None

        def _flat(s, out):
            if isinstance(s, NDArray):
                out.append(s._data)
            elif isinstance(s, (list, tuple)):
                for x in s:
                    _flat(x, out)
            return out

        def _reshape(s, leaves):
            """Rebuild the state structure from flat leaves as NDArrays."""
            if isinstance(s, NDArray):
                return NDArray(leaves.pop(0))
            if isinstance(s, (list, tuple)):
                return tuple(_reshape(x, leaves) for x in s)
            return s

        guard_on = self._guard is not None
        sig = (tuple(indices), opt.__class__,
               tuple(d._data.dtype.name for _, _, _, ds in items
                     for d in ds[:1]),
               guard_on,
               (self._zero_active, self._zero_dp, self._zero_stage))
        cache = getattr(self, '_fused_cache', None)
        if cache is None or cache[0] != sig:
            zero = self._zero_layout(items)
            self._zero_active = zero is not None and zero['zero']
            self._zero_dp = zero['dp'] if zero else 1
            self._zero_stage = zero['stage'] if zero else 0
            if zero is not None:
                self._zero_place_states(items, zero)
            sig = sig[:4] + ((self._zero_active, self._zero_dp,
                              self._zero_stage),)
            structs = [updater.states[i] for i in indices]
            zero_cache = zero

            # wds ride as a STATIC tuple: the ops branch on `if wd` with
            # python control flow, so weight decay must be concrete at
            # trace time (wd changes retrace — they only change via
            # set_wd_mult, not per step). lr/t/rescale are traced.
            def fused(weights, grads, states_flat, lrs, ts, rescale, wds):
                leaves = list(states_flat)
                saved_count = opt._index_update_count
                saved_rescale = opt.rescale_grad
                pos = {idx: n for n, idx in enumerate(indices)}
                # shadow the scalar accessors on the INSTANCE with traced
                # values for the duration of the trace; the class methods
                # come back when the shadows are deleted (restoring bound
                # methods would leave unpicklable attrs in __dict__,
                # breaking save_states(dump_optimizer=True))
                opt._get_lr = lambda idx: lrs[pos[idx]]
                opt._get_wd = lambda idx: wds[pos[idx]]
                opt._update_count = lambda idx: None
                opt._index_update_count = \
                    type('T', (), {'__getitem__':
                                   staticmethod(lambda idx: ts[pos[idx]])})()
                opt.rescale_grad = rescale
                try:
                    new_w, new_s, gs = [], [], []
                    for n, idx in enumerate(indices):
                        w = NDArray(weights[n])
                        gdat = grads[n]
                        if zero_cache is not None and \
                                zero_cache['state_sh'][n] is not None:
                            # the grad is consumed against 1/dp-sharded
                            # moments: constrain it so the partitioner
                            # slices once up front instead of keeping
                            # the full copy live through the update
                            gdat = jax.lax.with_sharding_constraint(
                                gdat, zero_cache['state_sh'][n])
                        gs.append(gdat)
                        g = NDArray(gdat)
                        st = _reshape(structs[n], leaves)
                        opt.update_multi_precision(idx, w, g, st)
                        wd_ = w._data
                        if zero_cache is not None:
                            # all-gather the updated weight back to its
                            # own (replicated / tp) layout
                            wd_ = jax.lax.with_sharding_constraint(
                                wd_, zero_cache['w_sh'][n])
                        new_w.append(wd_)
                        new_s.extend(_flat(st, []))
                finally:
                    for name in ('_get_lr', '_get_wd', '_update_count'):
                        opt.__dict__.pop(name, None)
                    opt._index_update_count = saved_count
                    opt.rescale_grad = saved_rescale
                if guard_on:
                    # non-finite guard, fused into THIS program: one
                    # isfinite reduction over every gradient in its
                    # SHARDED (reduce-scattered) layout where ZeRO is
                    # active — each device scans 1/dp and GSPMD psums
                    # the flag — and the whole writeback gated on it; a
                    # NaN/Inf step keeps the old weights and optimizer
                    # state on device; the host reads the flag a step
                    # later (no extra sync)
                    import functools as _functools
                    ok = _functools.reduce(
                        jnp.logical_and,
                        [jnp.all(jnp.isfinite(g)) for g in gs])
                    new_w = [jnp.where(ok, nw, w)
                             for nw, w in zip(new_w, weights)]
                    new_s = [jnp.where(ok, ns, s)
                             for ns, s in zip(new_s, states_flat)]
                    return new_w, new_s, ok
                return new_w, new_s

            jit_kwargs = {}
            if zero_cache is not None:
                # pin outputs: weights back to their own layout, state
                # leaves to the ZeRO layout they arrived in (donation
                # then reuses the sharded buffers in place)
                leaf_sh = [x.sharding for i in indices
                           for x in _flat(updater.states[i], [])]
                out_sh = ([s for s in zero_cache['w_sh']], leaf_sh)
                if guard_on:
                    out_sh = out_sh + (zero_cache['repl'],)
                jit_kwargs['out_shardings'] = out_sh
            jitted = jax.jit(fused, donate_argnums=(0, 2),
                             static_argnums=(6,), **jit_kwargs)
            self._fused_cache = (sig, fused, jitted)
            self._fused_traced = False
        elif _telem['on']:
            from .. import telemetry as _telemetry
            _telemetry.record_cache_hit('trainer:fused_update')
        _, fused_fn, jitted = self._fused_cache

        # host-side per-step scalars (counts first, as the reference does);
        # snapshot them so a failed trace can roll back before the eager
        # fallback re-counts — and so the guard can rewind the advance
        # if this step's flag comes back non-finite (device no-op)
        count_snapshot = (dict(opt._index_update_count), opt.num_update)
        self._fused_count_snapshot = count_snapshot
        for i in indices:
            opt._update_count(i)
        lrs = jnp.asarray(opt._get_lrs(indices), jnp.float32)
        wds = tuple(float(w) for w in opt._get_wds(indices))
        ts = jnp.asarray([opt._index_update_count[i] for i in indices],
                         jnp.float32)
        rescale = jnp.asarray(opt.rescale_grad, jnp.float32)
        weights = [datas[0]._data for _, _, _, datas in items]
        grads = [g._data for _, _, g, _ in items]
        states_flat = []
        for i in indices:
            _flat(updater.states[i], states_flat)
        was_traced = getattr(self, '_fused_traced', False)
        cctx = None
        if not was_traced:
            # compile ledger window: eval_shape trace probe + the first
            # jitted execution below (where XLA lazily compiles)
            cctx = _compile.begin('trainer:fused_update')
            # probe traceability ABSTRACTLY first: eval_shape consumes no
            # buffers, so a trace failure here can still fall back to the
            # eager loop with every weight/state intact. The real jitted
            # call below donates its inputs — after it dispatches there is
            # nothing to fall back TO, so its errors propagate.
            try:
                import time as _time
                t0 = _time.perf_counter()
                jax.eval_shape(lambda w, g, s, a, b, c: fused_fn(
                    w, g, s, a, b, c, wds), weights, grads, states_flat,
                    lrs, ts, rescale)
                self._fused_traced = True
                if cctx is not None:
                    _compile.set_signature(cctx, _compile.signature(
                        args=[_compile.array_sig(f'w{n}', w, donated=True)
                              for n, w in enumerate(weights[:8])],
                        flags={'optimizer': opt.__class__.__name__,
                               'guard': bool(guard_on),
                               'zero': self._zero_stage
                               if self._zero_active else 0,
                               'dp': self._zero_dp,
                               'params': len(weights),
                               'state_leaves': len(states_flat)}))
                elif _telem['on']:
                    from .. import telemetry as _telemetry
                    _telemetry.record_compile(
                        'trainer:fused_update', repr(sig),
                        _time.perf_counter() - t0)
            except Exception:
                _compile.abort(cctx)
                from .. import config as _config
                if _config.get('MXNET_TPU_FUSED_DEBUG'):
                    import traceback
                    traceback.print_exc()
                import warnings
                warnings.warn(
                    f"Trainer: {opt.__class__.__name__}.update() did not "
                    f"trace; falling back to the eager per-parameter "
                    f"update loop for this trainer.", RuntimeWarning)
                # restore the update counts the eager path will re-apply
                opt._index_update_count, opt.num_update = count_snapshot
                self._fused_disabled = True
                self._fused_cache = None
                return False
        import time as _time
        t0 = _time.perf_counter()
        try:
            with _trace.span('optimizer.fused'):
                out = jitted(weights, grads, states_flat, lrs, ts,
                             rescale, wds)
        except BaseException:
            _compile.abort(cctx)
            raise
        if guard_on:
            new_w, new_s, ok_flag = out
            self._guard.push_flag(ok_flag)
        else:
            new_w, new_s = out
        if not was_traced:
            # first execution after a (re)trace: jit is lazy, so this is
            # where XLA actually compiles — account it as compile time
            if cctx is not None:
                _compile.end(cctx)
            elif _telem['on']:
                from .. import telemetry as _telemetry
                _telemetry.counter('mxnet_tpu_compile_seconds_total').inc(
                    _time.perf_counter() - t0, site='trainer:fused_update')
        for (_, _, _, datas), w in zip(items, new_w):
            datas[0]._data = w
        pos = 0

        def _assign(s):
            nonlocal pos
            if isinstance(s, NDArray):
                s._data = new_s[pos]
                pos += 1
            elif isinstance(s, (list, tuple)):
                for x in s:
                    _assign(x)
        for i in indices:
            _assign(updater.states[i])
        return True

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def get_states_bytes(self):
        """The save_states payload as bytes: optimizer states + the
        pickled optimizer itself (update counts, rescale_grad, schedule
        position). This is what checkpoint.CheckpointManager snapshots on
        the training thread for an async save."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        return self._updater.get_states(dump_optimizer=True)

    def set_states_bytes(self, states):
        """Restore a get_states_bytes() payload (CheckpointManager's
        restore path; load_states is the file-based wrapper)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._updater.set_states(states)
        # a restore rewinds the trajectory: carried error-feedback
        # residuals no longer describe it — deterministic zero reseed
        # (the kvstore compressor keys residuals the same way)
        if getattr(self, '_local_gc', None) is not None:
            self._local_gc.reset()
        if self._kvstore is not None and \
                getattr(self._kvstore, '_compression', None) is not None:
            self._kvstore._compression.reset()
        if hasattr(self._updater, 'optimizer'):
            self._optimizer = self._updater.optimizer
            # re-attach live params: __getstate__ drops param_dict, so
            # per-parameter lr_mult/wd_mult must be rebound after restore
            self._optimizer.param_dict = {
                i: p for i, p in enumerate(self._params)}
        # the restored optimizer replaces the one the fused-update trace
        # closed over — force a retrace against the new instance
        self._fused_cache = None
        self._fused_traced = False

    def save_states(self, fname):
        """Ref: trainer.py:463. Atomic: tmp file + os.replace, so a kill
        mid-write never corrupts the previous states file."""
        from ..serialization import atomic_write_file
        atomic_write_file(fname, self.get_states_bytes())

    def load_states(self, fname):
        """Ref: trainer.py:492."""
        with open(fname, 'rb') as f:
            states = f.read()
        self.set_states_bytes(states)
