"""Compiled sharded training step — the performance path.

This is the TPU-native realisation of the north star (BASELINE.json): the
whole train step (forward + backward + optimizer update + gradient
all-reduce) is ONE pjit-compiled XLA program per step. Parameters are
replicated (DP) or sharded (TP via param_specs) over the mesh; the batch is
sharded over the 'dp' axis; XLA inserts the gradient all-reduce over ICI.
Buffer donation on params/optimizer state gives the reference's
static-alloc in-place update behavior (ref: CachedOp static_alloc,
src/imperative/cached_op.cc:525).

ZeRO-1 (default on whenever the dp axis has >1 devices, gate with
MXTPU_ZERO=0 or zero=False): the fp32 masters and optimizer moments are
dp-SHARDED PartitionSpecs instead of replicated, so the grad all-reduce
becomes a reduce-scatter, each device updates only its 1/dp slice, and
the updated params all-gather back — same wire bytes, 1/dp optimizer
math and state HBM per device. See the mxnet_tpu_comm_* telemetry
contract for the per-run accounting.

ZeRO-3 / FSDP (MXTPU_ZERO=3 or zero=3): the PERSISTENT parameters
themselves (and the fp32 masters) additionally live dp-sharded between
steps (Rajbhandari et al. 2020 stage 3; Zhao et al. 2023 FSDP). Inside
the compiled step each layer's params are all-gathered on first use —
the gathers are chained per layer (``collectives.ordered_barrier``) so
layer k+1's gather overlaps layer k's compute, not one monolithic
up-front gather — and the gathered copies are NOT saved as autodiff
residuals (``jax.checkpoint`` with a ``save_any_names_but_these``
policy on the gather outputs): the backward pass regathers, so full
copies exist only transiently. Gradients reduce-scatter straight into
the shard-local update and the updated params are written back SHARDED
(no trailing all-gather — the next step's per-layer gathers do that
work). Net: param + master + optimizer persistent HBM all drop to
~1/dp, at the cost of one extra all-gather of the params per step (the
backward regather) in ring wire bytes.

Gradient compression + hierarchical collectives (ISSUE 12): with
``compression_params={'type': 'fp16'|'int8'|'2bit'}`` (or
``MXTPU_COMPRESSION``) the gradient exchange gains an error-feedback
quantization epilogue INSIDE the compiled step:
``dec = Q^-1(Q(grad + residual))`` feeds the optimizer and
``residual = grad + residual - dec`` persists per-param as SHARDED
optimizer-side state (donated, checkpointed in the layout-independent
states payload). When the dp axis spans multiple hosts (or
``MXTPU_HIERARCHICAL_DP`` forces a split), the axis decomposes into
(cross-host ``<dp>h``, intra-host ``<dp>i``) sub-axes: ZeRO shards and
the param all-gathers stay on the fast intra-host ICI hop, and only
the (compressed) gradient exchange crosses the slow DCN hop — the
ZeRO++-style hpZ tradeoff: state memory drops 1/h instead of 1/dp in
exchange for zero cross-host param traffic. The non-finite guard
reduces over the DECODED grads (and the residual epilogue), so a
poisoned step still skips on device with the residual writeback gated.
"""
from __future__ import annotations

import functools
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as onp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError, state as _flags, telem_flags as _telem
from ..ndarray.ndarray import NDArray
from ..resilience import faults as _faults
from ..telemetry import trace as _trace, flight as _flight, \
    memory as _memory, compile as _compile
from .. import random as _random
from .. import scopes as _scopes
from ..ops import attention as _attention, rowsparse as _rowsparse
from . import compression as _compression
from .collectives import group_params_by_layer, ordered_barrier
from .mesh import default_mesh


def _devices_span_processes(devices):
    """Does this device set include OTHER processes' devices? A
    process-LOCAL placement (e.g. an elastic survivor training on its
    own devices while jax.distributed is still initialized) must not
    pay — or wedge inside — cross-process collectives."""
    if jax.process_count() <= 1:
        return False
    try:
        me = jax.process_index()
        return any(d.process_index != me for d in devices)
    except Exception:
        return True


def _sharding_spans_processes(sharding):
    try:
        devices = sharding.device_set
    except Exception:
        return jax.process_count() > 1
    return _devices_span_processes(devices)


def _put_replicated(x, sharding):
    """Place parameter/optimizer data with a (possibly multi-host) sharding.
    Process-SPANNING sharding: broadcast process 0's value first, so every
    worker starts from identical parameters regardless of local RNG state —
    the analog of the reference's kvstore.init broadcast from worker 0
    (ref: src/kvstore/kvstore_dist.h InitImpl). A process-LOCAL sharding
    in a multi-process world gets NO broadcast: its step never crosses
    processes (independent replicas — e.g. an elastic survivor beside a
    dead world, or drill workers), so identical init is the caller's
    choice (seed identically, or sync via a dist kvstore), and the
    broadcast collective is exactly what a dead peer would wedge."""
    if _sharding_spans_processes(sharding):
        from jax.experimental import multihost_utils
        # lint: host-sync-ok param (re)placement runs at build/restore/re-form, not per step
        x = multihost_utils.broadcast_one_to_all(onp.asarray(x))
        x = onp.asarray(x)  # lint: host-sync-ok cold path, see above
    return jax.device_put(x, sharding)


def _put_batch(x, sharding):
    """Place a batch with the dp sharding. Single-process: the array is the
    global batch. Multi-process: each process holds its OWN shard (the
    reference's per-worker data partition, tools/launch.py semantics), and
    the global batch is their concatenation over the dp axis."""
    if _sharding_spans_processes(sharding):
        return jax.make_array_from_process_local_data(
            # lint: host-sync-ok the batch arrives host-resident from the io pipeline; h2d staging
            sharding, onp.asarray(x))
    return jax.device_put(x, sharding)


def _local_value(arr):
    """A fully-addressable view of a replicated global array (loss outputs
    span all processes; every device holds the same value)."""
    if jax.process_count() > 1 and not arr.is_fully_addressable:
        return arr.addressable_data(0)
    return arr


def device_nbytes(arr):
    """Bytes of ``arr`` ONE device physically holds: the local shard for
    a sharded global array, the full buffer for replicated/host arrays —
    the unit of the per-device residency accounting (ZeRO gauges)."""
    shards = getattr(arr, 'addressable_shards', None)
    if shards:
        return shards[0].data.nbytes
    return int(arr.size) * jnp.dtype(arr.dtype).itemsize


def compose_zero_spec(shape, base_spec, dp_axis, dp_size):
    """ZeRO layout for an optimizer-state/master tensor: compose a dp
    shard onto the parameter's (tp) PartitionSpec. Picks the first dim
    not already claimed by another mesh axis whose size splits EVENLY
    over dp. None when nothing is shardable (scalars, sub-dp-size and
    ragged tensors stay replicated — the ±slack of the 1/dp footprint;
    ZeRO-3 recovers the ragged ones via flatten+pad, see
    ``zero3_layout``).

    A base spec that itself proposes ``dp_axis`` on a non-divisible dim
    raises MXNetError up front: this jax refuses uneven NamedShardings
    at device_put/jit time with an opaque size error, so composing such
    a spec would only defer the failure."""
    spec = list(base_spec) + [None] * (len(shape) - len(base_spec))
    for i, s in enumerate(spec):
        # already sharded over dp (fsdp-style param_specs): the state
        # inherits the param's own 1/dp layout — composing again would
        # produce an invalid duplicate-axis spec
        if s == dp_axis or (isinstance(s, (tuple, list)) and dp_axis in s):
            if dp_size > 1 and shape[i] % dp_size != 0:
                raise MXNetError(
                    f"compose_zero_spec: spec {tuple(base_spec)!r} shards "
                    f"dim {i} (size {shape[i]}) over the {dp_size}-device "
                    f"'{dp_axis}' axis, but {shape[i]} is not divisible "
                    f"by {dp_size} — XLA refuses uneven shardings. Pad "
                    f"the dim, drop '{dp_axis}' from the spec, or let "
                    f"ZeRO-3 flatten+pad it (zero3_layout).")
            return None
    for i, s in enumerate(spec):
        if s is not None or shape[i] < dp_size \
                or shape[i] % dp_size != 0:
            continue
        spec[i] = dp_axis
        return P(*spec)
    return None


def zero3_layout(shape, base_spec, dp_axis, dp_size):
    """Persistent ZeRO-3 layout for one parameter. Returns a dict:

    - ``{'mode': 'dim', 'spec': P(...), 'gather_spec': P(...)}`` — an
      exactly-divisible free dim shards over dp (composed with any tp
      dims the param already claims); the param/master/moments live in
      logical shape with that spec, and the in-step gather restores
      ``gather_spec`` (the tp-only layout the forward computes in).
    - ``{'mode': 'flat', 'size': s, 'padded': p, 'pad': p - s}`` — no
      dim divides evenly: the fp32 master + moments live as a 1-D
      buffer padded to a dp multiple and sharded ``P(dp)``; the
      compute-dtype param keeps a replicated logical copy (these are
      the ragged stragglers — the pad bytes are reported by
      ``opt_state_bytes_per_device``). Never chosen for tp-sharded
      params (flattening would destroy the tp layout).
    - ``{'mode': 'repl'}`` — too small to shard; fully replicated.
    """
    spec = list(base_spec) + [None] * (len(shape) - len(base_spec))

    def _trim(entries):
        entries = list(entries)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    for i, s in enumerate(spec):
        if s == dp_axis or (isinstance(s, (tuple, list)) and dp_axis in s):
            # user proposed the dp shard (fsdp-style): validate and keep
            compose_zero_spec(shape, base_spec, dp_axis, dp_size)
            gspec = [None if ss == dp_axis else
                     (tuple(a for a in ss if a != dp_axis) or None
                      if isinstance(ss, (tuple, list)) else ss)
                     for ss in spec]
            return {'mode': 'dim', 'spec': P(*spec),
                    'gather_spec': _trim(gspec)}
    composed = compose_zero_spec(shape, base_spec, dp_axis, dp_size)
    if composed is not None:
        return {'mode': 'dim', 'spec': composed,
                'gather_spec': _trim(spec)}
    size = int(onp.prod(shape)) if shape else 1
    if size >= dp_size and all(s is None for s in spec):
        padded = -(-size // dp_size) * dp_size
        return {'mode': 'flat', 'size': size, 'padded': padded,
                'pad': padded - size}
    return {'mode': 'repl'}


def split_dp_mesh(mesh, dp_axis, n_hosts):
    """Rebuild ``mesh`` with its ``dp_axis`` split into
    (``<dp>h`` cross-host, ``<dp>i`` intra-host) sub-axes of extents
    (n_hosts, dp//n_hosts) — dp-major device order, so each host group
    is a contiguous run along the original axis (the order
    ``dist.host_topology`` validated). Other axes are untouched."""
    from jax.sharding import Mesh
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = shape.get(dp_axis, 1)
    if n_hosts <= 1 or dp % n_hosts != 0:
        raise MXNetError(
            f"split_dp_mesh: cannot split the {dp}-device {dp_axis!r} "
            f"axis into {n_hosts} host groups")
    names, dims = [], []
    for name, size in zip(mesh.axis_names, mesh.devices.shape):
        if name == dp_axis:
            names += [dp_axis + 'h', dp_axis + 'i']
            dims += [n_hosts, dp // n_hosts]
        else:
            names.append(name)
            dims.append(size)
    return Mesh(mesh.devices.reshape(tuple(dims)), tuple(names))


def _sgd_init(p):
    return (jnp.zeros_like(p),)


def _sgd_update(p, g, s, lr, momentum=0.9, wd=0.0):
    mom, = s
    g = g + wd * p
    new_mom = momentum * mom - lr * g
    return p + new_mom, (new_mom,)


def _adam_init(p):
    return (jnp.zeros_like(p), jnp.zeros_like(p), jnp.zeros((), jnp.int32))


def _adam_update(p, g, s, lr, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0):
    m, v, t = s
    t = t + 1
    g = g + wd * p
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t.astype(jnp.float32))
    vhat = v / (1 - beta2 ** t.astype(jnp.float32))
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v, t)


def _adamw_update(p, g, s, lr, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01,
                  eta=1.0):
    # reference semantics (src/operator/contrib/adamw.cc, the GluonNLP
    # BERTAdam recipe): NO bias correction, decoupled wd scaled by lr —
    # kept identical to ops/optimizer_ops.py adamw_update so the Trainer
    # and ShardedTrainStep paths produce the same trajectory
    # (tests/test_gradients.py parity check)
    m, v, t = s
    t = t + 1
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    return p - eta * (lr * m / (jnp.sqrt(v) + eps) + wd * lr * p), \
        (m, v, t)


def _lamb_update(p, g, s, lr, beta1=0.9, beta2=0.999, eps=1e-6, wd=0.01):
    m, v, t = s
    t = t + 1
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t.astype(jnp.float32))
    vhat = v / (1 - beta2 ** t.astype(jnp.float32))
    update = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    r1 = jnp.linalg.norm(p.reshape(-1))
    r2 = jnp.linalg.norm(update.reshape(-1))
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    return p - lr * ratio * update, (m, v, t)


_OPTS = {
    'sgd': (_sgd_init, _sgd_update),
    'adam': (_adam_init, _adam_update),
    'adamw': (_adam_init, _adamw_update),
    'lamb': (_adam_init, _lamb_update),
}


class ShardedTrainStep:
    """One-pjit-call training step for a Gluon block over a device mesh.

    Usage:
        step = ShardedTrainStep(net, loss_fn, 'adam',
                                optimizer_params={'lr': 1e-3}, mesh=mesh)
        loss = step(data, label)      # NDArrays; params updated in place
    """

    def __init__(self, block, loss_fn, optimizer='sgd', optimizer_params=None,
                 mesh=None, dp_axis='dp', param_specs=None, donate=True,
                 grad_dtype=None, zero=None, compression_params=None,
                 guard=None, hierarchy=None):
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
        self.donate = donate
        # error-feedback gradient compression (ISSUE 12): routed for
        # real — validated into a codec spec here, applied as the
        # quantize/decode epilogue inside the compiled step; only a
        # genuinely unknown ctype string still raises
        self.compression = _compression.resolve(compression_params)
        self._requested_hierarchy = hierarchy
        self._adopt_mesh(mesh if mesh is not None else default_mesh())
        dp_size = self._dp_size
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
        self.zero_stage = stage if dp_size > 1 else 0
        # MXTPU_REMAT (ISSUE 18): activation-remat policy for the
        # forward, read once at construction so the build signature and
        # the checkpoint seam agree for this step's lifetime
        from .. import config as _remat_cfg
        self._remat_policy = _remat_cfg.get('MXTPU_REMAT')
        self._spans_processes = self._mesh_spans_processes()
        self.zero = self.zero_stage > 0
        self._params = None       # list[(name, Parameter)]
        self._master = None       # fp32 master copies of bf16/fp16 params
        self._opt_state = None
        self._residual = None     # error-feedback residuals (compression)
        self._compiled = None
        self._alias = None        # name-stable jit-boundary key aliases
        self._alias_rev = None
        self._step_count = 0
        self._pending_states = None   # restored blob awaiting first build
        self._cost_args = None        # avals for cost_analysis()
        # resilience.NonFiniteGuard: the pjit step then also reduces
        # isfinite over loss + every grad and gates the whole writeback
        # on device; the guard reads the flag one step deferred
        self._guard = guard
        if guard is not None:
            guard.add_post_restore_hook(self._replace_params_on_mesh)

    def _adopt_mesh(self, mesh):
        """Adopt ``mesh``, decomposing the dp axis into (cross-host,
        intra-host) sub-axes when a hierarchy exists (real multi-host
        process topology, or ``hierarchy=``/``MXTPU_HIERARCHICAL_DP``
        forcing a synthetic split). Sets the axis bookkeeping every
        later layout decision reads:

        - ``_dp_axes``   — axis names the BATCH shards over (the full
          dp extent either way);
        - ``_shard_axis``/``_shard_size`` — the axis ZeRO shards over
          (intra-host under hierarchy: params/masters/moments replicate
          across hosts so no param all-gather ever crosses DCN);
        - ``_cross_axis``/``_cross_size`` — the slow hop the
          (compressible) gradient exchange crosses (None when flat).
        """
        from . import dist as _dist
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = int(shape.get(self.dp_axis, 1))
        H, h = 1, dp
        if dp > 1 and self.dp_axis in shape:
            idx = mesh.axis_names.index(self.dp_axis)
            lead = [0] * len(mesh.axis_names)
            col = []
            for i in range(dp):
                lead[idx] = i
                col.append(mesh.devices[tuple(lead)])
            H, h = _dist.dp_host_split(col, force=self._requested_hierarchy)
        if H > 1:
            for pat, spec in (self.param_specs or {}).items():
                if self.dp_axis in str(spec):
                    raise MXNetError(
                        f"hierarchical dp: param_spec {pat!r} proposes "
                        f"the {self.dp_axis!r} axis, which is split "
                        f"into ({self.dp_axis}h, {self.dp_axis}i) "
                        f"sub-axes under MXTPU_HIERARCHICAL_DP — use "
                        f"{self.dp_axis}i for fsdp-style sharding, or "
                        f"force the flat topology (hierarchy=1).")
            mesh = split_dp_mesh(mesh, self.dp_axis, H)
            self._dp_axes = (self.dp_axis + 'h', self.dp_axis + 'i')
            self._shard_axis = self.dp_axis + 'i'
            self._cross_axis = self.dp_axis + 'h'
        else:
            self._dp_axes = (self.dp_axis,)
            self._shard_axis = self.dp_axis
            self._cross_axis = None
        self.mesh = mesh
        self._dp_size = dp
        self._shard_size = h
        self._cross_size = H
        return mesh

    def _mesh_spans_processes(self):
        """Does this step's mesh include other processes' devices? Then
        every step is a cross-process collective — one that a lost peer
        wedges forever, which is why dispatch refuses to enter it once
        the membership layer has declared a loss."""
        try:
            devices = list(self.mesh.devices.flat)
        except Exception:
            return jax.process_count() > 1
        return _devices_span_processes(devices)

    # ------------------------------------------------------------------
    def _collect(self):
        params = sorted(self.block.collect_params().items())
        trainable = [(n, p) for n, p in params if p.grad_req != 'null']
        frozen = [(n, p) for n, p in params if p.grad_req == 'null']
        return trainable, frozen

    def _resolve_param_specs(self, names):
        """name -> PartitionSpec. A spec key matches a parameter by exact
        name or as a regex via re.search (so plain substrings keep
        working). Unmatched specs and conflicting matches warn; the full
        mapping is kept on self.param_spec_report for inspection."""
        import re
        import warnings
        mapping = {n: P() for n in names}
        matched_by = {n: None for n in names}
        report = {}
        for pat, spec in self.param_specs.items():
            hits = [n for n in names
                    if n == pat or re.search(str(pat), n) is not None]
            report[pat] = hits
            if not hits:
                warnings.warn(
                    f"ShardedTrainStep: param_spec {pat!r} matched no "
                    f"parameter (have e.g. {sorted(names)[:5]})",
                    RuntimeWarning)
            for n in hits:
                if matched_by[n] is not None and mapping[n] != spec:
                    warnings.warn(
                        f"ShardedTrainStep: parameter {n!r} matched both "
                        f"{matched_by[n]!r} and {pat!r}; using {pat!r}",
                        RuntimeWarning)
                mapping[n] = spec
                matched_by[n] = pat
        self.param_spec_report = report
        return mapping

    def _spec_for(self, name):
        if getattr(self, '_spec_map', None) is not None and \
                name in self._spec_map:
            return self._spec_map[name]
        return P()  # replicated

    def _build(self, example_inputs, example_labels):
        trainable, frozen = self._collect()
        t_names = [n for n, _ in trainable]
        f_names = [n for n, _ in frozen]
        self._spec_map = self._resolve_param_specs(t_names + f_names)
        # low-precision trainables keep a persistent fp32 master copy
        # (the reference's create_state_multi_precision,
        # python/mxnet/optimizer/optimizer.py:52): without it, updates
        # below the bf16 ulp of the weight are lost to re-rounding.
        master_names = frozenset(
            n for n, p in trainable
            if jnp.dtype(p.data()._data.dtype).itemsize < 4
            and jnp.issubdtype(p.data()._data.dtype, jnp.floating))
        # mesh axes the param specs shard weights over (tp): where the
        # attention heads divide over them, the flash kernel maps over
        # them as well as over the batch axes (ops/attention.py)
        spec_axes = {a for spec in self._spec_map.values() for e in spec
                     for a in (e if isinstance(e, tuple) else (e,))}
        model_axes = tuple(
            a for a in self.mesh.axis_names
            if a in spec_axes and a not in self._dp_axes
            and self.mesh.shape[a] > 1)
        block = self.block
        loss_fn = self.loss_fn
        opt_update = self._opt_update
        opt_kwargs = self.optimizer_params
        n_inputs = len(example_inputs)

        def forward_loss(t_params, f_params, inputs, labels, key,
                         fault_scale, row_tangents=None):
            all_params = dict(t_params)
            all_params.update(f_params)
            name_to_param = dict(trainable + frozen)
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
                            self.mesh, self._dp_axes, model_axes), \
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

        # ------------------------------------------------------------------
        # RowSparse fast path (ISSUE 19): parameters declared
        # grad_stype='row_sparse' (Embedding(sparse_grad=True)) carry
        # (unique row ids, row-block values) gradients and live-rows-only
        # optimizer updates. Budgets — the static worst-case unique-row
        # counts per lookup — are discovered with one abstract
        # jax.eval_shape trace (no compile, no FLOPs) before the real
        # program is built.
        from .. import config as _cfg
        sparse_on = bool(_cfg.get('MXTPU_SPARSE'))
        sparse_exact = bool(_cfg.get('MXTPU_SPARSE_EXACT')) \
            or not self._lazy_sparse
        sparse_cap = int(_cfg.get('MXTPU_SPARSE_ROWS'))
        table_axis = str(_cfg.get('MXTPU_SPARSE_TABLE_AXIS') or '') or None
        name_to_p = dict(trainable)
        s_candidates = [
            n for n, p in trainable
            if getattr(p, '_grad_stype', 'default') == 'row_sparse'
            and len(tuple(p.data().shape)) == 2]
        sparse_budgets = {}          # name -> [per-lookup row budget]
        sparse_id_counts = {}        # name -> flat ids per step (pre-dedup)
        if sparse_on and s_candidates:
            discovered = {}

            def _discover(t_params, f_params, inputs, labels, key,
                          fault_scale):
                cap = _rowsparse.trace_capture(
                    {n: t_params[n] for n in s_candidates})
                with cap:
                    forward_loss(t_params, f_params, inputs, labels,
                                 key, fault_scale)
                for cn, slot in cap.slots.items():
                    discovered[cn] = list(slot.call_sizes)
                return jnp.zeros(())

            t_avals = {n: jax.ShapeDtypeStruct(
                tuple(p.data().shape), p.data()._data.dtype)
                for n, p in trainable}
            f_avals = {n: jax.ShapeDtypeStruct(
                tuple(p.data().shape), p.data()._data.dtype)
                for n, p in frozen}
            jax.eval_shape(
                _discover, t_avals, f_avals,
                tuple(jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
                      for x in example_inputs),
                tuple(jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
                      for x in example_labels),
                jax.random.PRNGKey(0), jnp.float32(1.0))
            for n in s_candidates:
                sizes = discovered.get(n) or []
                if not sizes:
                    continue     # never looked up through embedding
                vocab = int(name_to_p[n].data().shape[0])
                buds = [min(s, vocab) for s in sizes]
                if sparse_cap and sum(buds) > sparse_cap:
                    continue     # budget over ceiling: dense fallback
                sparse_budgets[n] = buds
                sparse_id_counts[n] = int(sum(sizes))
        s_names = sorted(sparse_budgets)
        self._sparse_names = s_names
        self._sparse_budgets = sparse_budgets
        self._sparse_id_counts = sparse_id_counts
        self._sparse_exact = sparse_exact
        # model-parallel table sharding: a divisible vocab shards
        # P(table_axis) and XLA inserts the all-to-all feature exchange
        # for remote rows; ragged vocabularies keep the replicated
        # compute copy (their fp32 state still shards through ZeRO-3's
        # flat padded stores)
        self._sparse_table_axis = None
        sparse_table_sharded = set()
        if table_axis and s_names:
            if table_axis in (self.dp_axis, self._shard_axis,
                              self._cross_axis):
                raise MXNetError(
                    f"MXTPU_SPARSE_TABLE_AXIS={table_axis!r} collides "
                    f"with the data-parallel axis — pick a model "
                    f"axis (e.g. 'tp').")
            tshape = dict(zip(self.mesh.axis_names,
                              self.mesh.devices.shape))
            tsize = int(tshape.get(table_axis, 0))
            if tsize > 1:
                for n in s_names:
                    vocab = int(name_to_p[n].data().shape[0])
                    if vocab % tsize == 0 and \
                            self._spec_for(n) == P():
                        self._spec_map[n] = P(table_axis)
                        sparse_table_sharded.add(n)
                if sparse_table_sharded:
                    self._sparse_table_axis = table_axis
        self._sparse_sig = {
            'mode': 'exact' if sparse_exact else 'lazy',
            'table_axis': self._sparse_table_axis,
            'tables': {n: int(sum(sparse_budgets[n])) for n in s_names},
        } if s_names else None

        # shardings. The batch shards over the FULL dp extent either
        # way; ZeRO layouts shard over the intra-host sub-axis when the
        # hierarchy is active (see _adopt_mesh), so param traffic never
        # crosses the DCN hop.
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P(self._dp_axes))
        shard_axis, shard_size = self._shard_axis, self._shard_size

        t_shardings = {n: NamedSharding(mesh, self._spec_for(n))
                       for n in t_names}
        f_shardings = {n: NamedSharding(mesh, self._spec_for(n))
                       for n in f_names}
        # ZeRO-1 (Rajbhandari et al., 2020, stage 1): the fp32 masters and
        # Adam moments shard 1/dp over the dp axis (composed with any tp
        # dims the param already shards). The update then reads a
        # dp-SHARDED gradient — the constraint below turns the plain
        # all-reduce into reduce-scatter — and out_shardings all-gather
        # the updated param back to its replicated/tp layout. GSPMD fuses
        # and overlaps both collectives with backward compute.
        shapes = {n: tuple(p.data().shape) for n, p in trainable}
        stage3 = self.zero_stage == 3
        zero_specs = {n: None for n in t_names}
        z3 = {}
        if stage3:
            # ZeRO-3: every trainable gets a persistent layout — dim
            # (sharded in logical shape), flat (fp32 store padded to a
            # dp multiple) or repl (too small)
            for n in t_names:
                z3[n] = zero3_layout(shapes[n], self._spec_for(n),
                                     shard_axis, shard_size)
                if z3[n]['mode'] == 'dim':
                    zero_specs[n] = z3[n]['spec']
        elif self.zero:
            for n in t_names:
                zero_specs[n] = compose_zero_spec(
                    shapes[n], self._spec_for(n), shard_axis,
                    shard_size)
        self.zero_specs = zero_specs
        self.zero3_layouts = z3
        self._shapes = shapes
        self._zero_label = 'zero3' if stage3 else \
            ('zero1' if self.zero else 'off')
        flat_meta = {n: z3[n] for n in t_names
                     if stage3 and z3[n]['mode'] == 'flat'}
        dim_names = [n for n in t_names
                     if stage3 and z3[n]['mode'] == 'dim']
        # flat params: the compute-dtype logical copy stays replicated;
        # the fp32 master IS the (padded, dp-sharded) persistent store,
        # so they join master_names regardless of dtype
        master_names = frozenset(master_names) | frozenset(flat_meta)
        if stage3:
            # persistent params live dp-sharded between steps
            for n in dim_names:
                t_shardings[n] = NamedSharding(mesh, z3[n]['spec'])
        flat_sh = NamedSharding(mesh, P(shard_axis))
        zero_shardings = {
            n: (flat_sh if n in flat_meta else
                NamedSharding(mesh, zero_specs[n])
                if zero_specs[n] is not None else t_shardings[n])
            for n in t_names}
        # optimizer state shards like its parameter (ZeRO: like its
        # slice). ZeRO-3 flat params carry flat (padded) moments — put
        # them in place before the shardings are derived from them.
        for n, fz in flat_meta.items():
            self._opt_state[n] = self._opt_init(
                jnp.zeros((fz['padded'],), jnp.float32))
        state_shardings = {
            n: tuple((repl if s.ndim == 0 else zero_shardings[n])
                     for s in self._opt_state[n])
            for n in t_names}

        master_shardings = {n: zero_shardings[n] for n in master_names}
        shard_constraint = {n: zero_shardings[n] for n in t_names
                            if zero_specs[n] is not None}

        # error-feedback compression: one fp32 residual per trainable,
        # persisted in the SAME layout the grad is consumed in (the
        # zero shard / flat store / replicated) so acc = g + r is a
        # local elementwise add with no extra collective
        comp = self.compression
        comp_on = comp is not None
        ctype = comp['type'] if comp_on else 'none'
        cthreshold = comp['threshold'] if comp_on else 0.0
        cblock = comp['block'] if comp_on else 0
        residual_shapes = {}
        residual_shardings = {}
        if comp_on:
            for n in t_names:
                fz = flat_meta.get(n)
                residual_shapes[n] = (fz['padded'],) if fz is not None \
                    else shapes[n]
                residual_shardings[n] = zero_shardings[n]
        self._residual_shapes = residual_shapes
        self._residual_shardings = residual_shardings

        # ZeRO-3 per-layer gather pipeline: one chained all-gather per
        # layer group, in (heuristic) first-use order
        layer_groups = group_params_by_layer(dim_names) if dim_names \
            else []
        self._layer_groups = layer_groups
        gather_ns = {n: NamedSharding(mesh, z3[n]['gather_spec'])
                     for n in dim_names}

        if stage3 and dim_names:
            def gather_all(t_params):
                """All-gather the dim-sharded params layer by layer:
                each group's gather is barrier-chained to the PREVIOUS
                group's gather (not its compute), so XLA can prefetch
                layer k+1's params while layer k computes; the gathered
                values are checkpoint-named so the remat policy below
                drops them from the autodiff residuals (the backward
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
                                v, gather_ns[n]), 'zero3_gather')
                            for n, v in zip(names, vals)]
                    for n, v in zip(names, vals):
                        gathered[n] = v
                    token = vals[0]
                return gathered

            def forward_sharded(t_params, f_params, inputs, labels, key,
                                fault_scale, row_tangents=None):
                return forward_loss(gather_all(t_params), f_params,
                                    inputs, labels, key, fault_scale,
                                    row_tangents)

            loss_base = forward_sharded
            # ZeRO-3 floor: whatever the remat policy, the gathered
            # params are NEVER kept as autodiff residuals
            base_policy = \
                jax.checkpoint_policies.save_any_names_but_these(
                    'zero3_gather')
        else:
            loss_base = forward_loss
            base_policy = None

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
            loss_forward = jax.checkpoint(
                loss_base,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        elif remat == 'aggressive':
            loss_forward = jax.checkpoint(
                loss_base,
                policy=jax.checkpoint_policies.nothing_saveable)
        elif base_policy is not None:
            loss_forward = jax.checkpoint(loss_base, policy=base_policy)
        else:
            loss_forward = loss_base

        guard_on = self._guard is not None

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
            new_params = {}
            new_master = {}
            new_state = {}
            new_residual = {}
            sparse_stats = {}
            # each parameter's stretch of the program is traced under
            # three names (scopes.py): the gradient's way to where it is
            # consumed, the non-finite check, the update.
            # A dense gradient enters its exchange stretch through an
            # optimization_barrier, as value_and_grad hands it over (bf16
            # for a bf16 parameter: an identity, no rounding is added).
            # It is a fusion boundary. Without it XLA:TPU puts the whole
            # AdamW update (new bf16 weight, master, both moments) into
            # the epilogue of the weight-gradient matmul, and the seven
            # f32 tiles of that epilogue leave the matmul a smaller
            # output window: BERT's FFN2 gradient, 135 GFLOP, took
            # 2.99 ms with the update inside on one v5e and 1.30 ms on
            # each of four, where ZeRO-1's reduce-scatter already stood
            # between the two; 66.2 of bert_base.t512's 297.6 ms step
            # were such fusions (ledger, PR 26). With the boundary the
            # same matmul takes 1.49 ms, the separate updates 4.7 ms a
            # step, and the step 278.9 ms (PERF.md 6, PR 28).
            # phase_mixed_ms_per_step guards this line: a few ms there
            # mean an update is back inside a matmul. Per leaf, not over
            # the gradient tree: a gradient lives from its matmul to its
            # update, and XLA's plan for the step grew by 0.002 GiB. The
            # RowSparse row blocks below have no matmul-shaped gradient
            # and take no barrier.
            exchange = functools.partial(jax.named_scope, _scopes.EXCHANGE)
            guard = functools.partial(jax.named_scope, _scopes.GUARD)
            update = functools.partial(jax.named_scope, _scopes.UPDATE)
            with guard():
                ok = jnp.isfinite(loss_val) if guard_on else None
            for n in t_names:
                srn = srec.get(n)
                if srn is not None:
                    vocab, dim = shapes[n]
                    uids = srn['uids']
                    with exchange():
                        rows = g_rows[n].astype(jnp.float32)
                        if len(sparse_budgets[n]) > 1:
                            # several lookups of the same table in one
                            # step: segment-sum overlapping ids into one
                            # block
                            uids, rows, n_live = \
                                _rowsparse.merge_row_blocks(
                                    uids, rows, vocab)
                        else:
                            n_live = srn['n_live']
                    sparse_stats[n] = n_live
                    if not sparse_exact:
                        # lazy update (reference lazy_update=True /
                        # kvstore row_sparse semantics): gather the live
                        # rows of master + moments, run the SAME
                        # optimizer kernel on the (budget, dim) block,
                        # scatter back. Sentinel slots (uid == vocab)
                        # gather a clipped garbage row whose writeback
                        # XLA's OOB scatter DROPS — dead slots never
                        # touch the table. Moments of absent rows stay
                        # frozen; wd applies to live rows only.
                        fz = flat_meta.get(n)
                        if fz is not None:
                            # zero3 flat padded store: a row is a
                            # contiguous dim-slice of the 1-D buffer
                            fidx = (uids[:, None] * dim + jnp.arange(
                                dim, dtype=jnp.int32)[None, :])

                            def _rget(a, fidx=fidx):
                                return jnp.take(a, fidx, mode='clip')

                            def _rset(a, r, fidx=fidx):
                                return a.at[fidx].set(r, mode='drop')
                        else:
                            def _rget(a, uids=uids):
                                return jnp.take(a, uids, axis=0,
                                                mode='clip')

                            def _rset(a, r, uids=uids):
                                return a.at[uids].set(r, mode='drop')
                        if comp_on:
                            # error-feedback codec on the ROW BLOCK with
                            # per-row scales (block = dim); the residual
                            # stays table-shaped and persistent — only
                            # live rows accumulate/flush error
                            with exchange():
                                acc = rows + _rget(residual[n])
                                dec = _compression.encode_decode(
                                    acc, ctype, cthreshold, dim)
                                new_residual[n] = _rset(residual[n],
                                                        acc - dec)
                            rows = dec
                        if guard_on:
                            with guard():
                                ok = jnp.logical_and(
                                    ok, jnp.all(jnp.isfinite(rows)))
                        with update():
                            if n in master_names:
                                p32 = master[n]
                            else:
                                p32 = t_params[n].astype(jnp.float32)
                            p_rows = _rget(p32)
                            s_rows = tuple(_rget(s) if s.ndim else s
                                           for s in opt_state[n])
                            nr_, nsr_ = opt_update(p_rows, rows, s_rows,
                                                   lr, **opt_kwargs)
                            np_ = _rset(p32, nr_)
                            new_state[n] = tuple(
                                _rset(s, sr) if s.ndim else sr
                                for s, sr in zip(opt_state[n], nsr_))
                            if fz is not None:
                                new_params[n] = np_[:fz['size']].reshape(
                                    shapes[n]).astype(t_params[n].dtype)
                                new_master[n] = np_
                            else:
                                new_params[n] = np_.astype(
                                    t_params[n].dtype)
                                if n in master_names:
                                    new_master[n] = np_
                        continue
                    # exact mode: densify the deduped block into a
                    # table-shaped grad and run the regular dense path —
                    # bit-identical trajectories to dense training (the
                    # parity oracle). The WIRE exchange still happened
                    # on row blocks (the tangent cotangent), only the
                    # local update is dense.
                    with exchange():
                        g32 = jnp.zeros((vocab, dim), jnp.float32) \
                            .at[uids].add(rows, mode='drop')
                else:
                    with exchange():
                        g32 = jax.lax.optimization_barrier(
                            grads[n]).astype(jnp.float32)
                fz = flat_meta.get(n)
                zsh = shard_constraint.get(n)
                with exchange():
                    if fz is not None:
                        # ragged param (ZeRO-3 flatten+pad): the grad
                        # flattens and zero-pads into the flat 1/dp layout
                        g32 = jnp.pad(g32.reshape(-1), (0, fz['pad']))
                        g32 = jax.lax.with_sharding_constraint(
                            g32, zero_shardings[n])
                    elif zsh is not None:
                        # reduce-scatter: the grad is only ever consumed
                        # in this dp-sharded layout, so the partitioner
                        # combines the backward psum + slice into one
                        # reduce-scatter
                        g32 = jax.lax.with_sharding_constraint(g32, zsh)
                    if comp_on:
                        # error-feedback quantized exchange epilogue: the
                        # cross-host hop carries Q(g + r); the decoded
                        # value feeds the update and the quantization
                        # error r' is re-offered next step instead of lost
                        # (Lin et al.; Karimireddy et al.). Elementwise on
                        # the sharded grad — adds no collective of its own.
                        acc = g32 + residual[n]
                        g32 = _compression.encode_decode(
                            acc, ctype, cthreshold, cblock)
                        new_residual[n] = acc - g32
                if guard_on:
                    # isfinite over the SHARDED (and, under compression,
                    # DECODED) grad: each device reduces its slice and
                    # GSPMD psums the scalar — never a full-grad rebuild.
                    # encode_decode propagates non-finite inputs, so a
                    # poisoned gradient cannot hide behind the quantizer.
                    with guard():
                        ok = jnp.logical_and(
                            ok, jnp.all(jnp.isfinite(g32)))
                with update():
                    if n in master_names:
                        p32 = master[n]
                    else:
                        p32 = t_params[n].astype(jnp.float32)
                        if zsh is not None:
                            p32 = jax.lax.with_sharding_constraint(
                                p32, zsh)
                    np_, ns_ = opt_update(p32, g32, opt_state[n], lr,
                                          **opt_kwargs)
                    if fz is not None:
                        # updated flat master -> refresh the replicated
                        # logical compute-dtype copy (slice off the pad)
                        new_params[n] = np_[:fz['size']].reshape(
                            shapes[n]).astype(t_params[n].dtype)
                        new_master[n] = np_
                    else:
                        new_params[n] = np_.astype(t_params[n].dtype)
                        if n in master_names:
                            new_master[n] = np_
                new_state[n] = ns_
            new_f = {n: aux.get(n, f_params[n]) for n in f_names}
            if guard_on:
                # non-finite guard fused into the pjit step: a bad step
                # writes back the OLD params/master/state/aux on device —
                # a no-op update inside the same XLA program, no host
                # round-trip on the happy path. The residual writeback
                # is gated too: a NaN residual must never outlive the
                # skipped step that produced it.
                with guard():
                    new_params = {
                        n: jnp.where(ok, new_params[n], t_params[n])
                        for n in t_names}
                    new_master = {
                        n: jnp.where(ok, new_master[n], master[n])
                        for n in new_master}
                    new_state = {
                        n: tuple(jnp.where(ok, ns_, os_) for ns_, os_ in
                                 zip(new_state[n], opt_state[n]))
                        for n in t_names}
                    new_residual = {n: jnp.where(ok, nr, residual[n])
                                    for n, nr in new_residual.items()}
                    new_f = {n: jnp.where(ok, new_f[n], f_params[n])
                             for n in f_names}
                outs = (new_params, new_f, new_master, new_state,
                        new_residual, loss_val, ok)
            else:
                outs = (new_params, new_f, new_master, new_state,
                        new_residual, loss_val)
            if s_names:
                # per-table live-row counts as a last (replicated)
                # output — the telemetry side reads them one step
                # deferred, never stalling the dispatch
                outs = outs + (sparse_stats,)
            return outs
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
        alias = {n: f'p{i:04d}'
                 for i, n in enumerate(sorted(set(t_names) | set(f_names)))}
        rev = {t: n for n, t in alias.items()}
        self._alias, self._alias_rev = alias, rev

        def _enc(d):
            return {alias[n]: v for n, v in d.items()}

        def _dec(d):
            return {rev[t]: v for t, v in d.items()}

        def stable_step(t_params, f_params, master, opt_state, residual,
                        inputs, labels, key, lr, fault_scale):
            out = train_step(_dec(t_params), _dec(f_params), _dec(master),
                             _dec(opt_state), _dec(residual),
                             inputs, labels, key, lr, fault_scale)
            return tuple(_enc(o) if isinstance(o, dict) else o
                         for o in out)

        in_shardings = (_enc(t_shardings), _enc(f_shardings),
                        _enc(master_shardings), _enc(state_shardings),
                        _enc(residual_shardings),
                        tuple(batch_sh for _ in example_inputs),
                        tuple(batch_sh for _ in example_labels),
                        repl, repl, repl)
        out_shardings = (_enc(t_shardings), _enc(f_shardings),
                         _enc(master_shardings), _enc(state_shardings),
                         _enc(residual_shardings), repl)
        if guard_on:
            out_shardings = out_shardings + (repl,)
        if s_names:
            out_shardings = out_shardings + (
                {alias[n]: repl for n in s_names},)
        donate = (0, 2, 3, 4) if self.donate else ()
        self._compiled = jax.jit(stable_step, in_shardings=in_shardings,
                                 out_shardings=out_shardings,
                                 donate_argnums=donate)
        self._master_names = master_names
        self._master_shardings = master_shardings
        self._t_names = t_names
        self._f_names = f_names
        self._trainable = trainable
        self._frozen = frozen
        self._t_shardings = t_shardings
        self._f_shardings = f_shardings
        self._batch_sh = batch_sh
        self._zero_shardings = zero_shardings
        self._state_shardings = state_shardings
        self._flat_meta = flat_meta
        # Per-step collective accounting (mxnet_tpu_comm_* contract):
        # ring-algorithm wire bytes per device — all_reduce(N) costs
        # 2*(dp-1)/dp*N while reduce_scatter(N)+all_gather(N) cost
        # (dp-1)/dp*N each, so ZeRO-1 provably moves the SAME total as
        # the replicated path. ZeRO-3 is honestly MORE: each dim-sharded
        # param all-gathers twice per step (forward use + backward
        # regather under the remat policy) in the compute dtype, and its
        # fp32 grad reduce-scatters once; flat params reduce-scatter the
        # padded fp32 grad and gather the updated flat master back to
        # the replicated logical copy. Analytic (XLA does not expose
        # per-collective byte counters), recorded once per step in
        # __call__, per-layer in self._gather_plan.
        #
        # Hierarchy decomposition (H hosts x h devices, dp = H*h): the
        # GRADIENT exchange splits into an intra-host reduce-scatter
        # ((h-1)/h * N on the ICI hop) plus a cross-host all-reduce of
        # the 1/h partial (2*(H-1)/H * N/h on the DCN hop — the ONLY
        # cross-host traffic, and the hop the codec shrinks: its
        # operand is the encoded payload). Param writebacks/gathers
        # stay entirely on the intra hop because the ZeRO shard degree
        # is h (states replicate across hosts — ZeRO++-style hpZ).
        # `_comm_plan` keeps the kind-aggregated view (back-compat);
        # `_hop_plan` carries (kind, axis) for per-hop telemetry.
        dp = self._dp_size
        H, h = self._cross_size, self._shard_size
        hier = H > 1

        def _ring(k):
            return (k - 1) / k if k > 1 else 0.0

        ring = _ring(h) if hier else _ring(dp)   # the shard/param hop
        ring_h = _ring(H)
        intra_axis = self._shard_axis
        cross_axis = self._cross_axis or self.dp_axis
        plan = {}
        hop_plan = {}
        comp_raw = 0.0          # fp32 bytes the compressed hop replaces
        comp_enc = 0.0          # encoded bytes it actually carries

        def _add(kind, axis, nbytes, cnt):
            b, c = plan.get(kind, (0.0, 0))
            plan[kind] = (b + nbytes, c + cnt)
            b, c = hop_plan.get((kind, axis), (0.0, 0))
            hop_plan[(kind, axis)] = (b + nbytes, c + cnt)

        # RowSparse side ledger: per-hop sparse wire bytes and the
        # dense-equivalent bytes the same exchange would have moved —
        # the measurable shrink sparse_report()/dryrun assert on
        sparse_hop = {}
        sparse_dense_hop = {}

        def _sadd(axis, nbytes, dense_nbytes):
            sparse_hop[axis] = sparse_hop.get(axis, 0.0) + nbytes
            sparse_dense_hop[axis] = \
                sparse_dense_hop.get(axis, 0.0) + dense_nbytes

        param_nbytes = {}
        for n, p in trainable:
            size = int(onp.prod(p.data().shape)) if p.data().shape else 1
            nbytes = size * jnp.dtype(p.data()._data.dtype).itemsize
            param_nbytes[n] = nbytes
            fz = flat_meta.get(n)
            enc = _compression.wire_bytes(
                shapes[n] if fz is None else (fz['padded'],),
                ctype, cblock) if comp_on else None
            if stage3 and n in gather_ns:
                _add('all_gather', intra_axis, 2 * ring * nbytes, 2)
                grad_raw = size * 4
            elif fz is not None:
                _add('all_gather', intra_axis, ring * fz['padded'] * 4, 1)
                grad_raw = fz['padded'] * 4
            elif zero_specs[n] is not None:
                _add('all_gather', intra_axis, ring * nbytes, 1)
                grad_raw = nbytes
            elif dp > 1:
                grad_raw = nbytes
            else:
                continue
            # the gradient exchange itself
            if n in s_names:
                # RowSparse exchange: the wire carries (int32 ids +
                # row-block values) instead of the table-shaped grad —
                # exchange bytes scale with the live-row budget, not the
                # vocab. Exact mode densifies LOCALLY after the row
                # exchange, so the wire shrink holds for both modes;
                # only the lazy codec re-encodes the rows (per-row
                # scales, block = dim) for the cross-host hop.
                B = sum(sparse_budgets[n])
                dim = shapes[n][1]
                row_raw = B * (dim * 4 + 4)
                row_enc = (_compression.wire_bytes((B, dim), ctype, dim)
                           + B * 4) if comp_on and not sparse_exact \
                    else row_raw
                if hier:
                    if h > 1:
                        _add('reduce_scatter', intra_axis,
                             ring * row_raw, 1)
                        _sadd(intra_axis, ring * row_raw,
                              ring * grad_raw)
                    cross_enc = 2 * ring_h * row_enc / h
                    _add('all_reduce', cross_axis, cross_enc, 1)
                    _sadd(cross_axis, cross_enc,
                          2 * ring_h * (enc if comp_on else grad_raw)
                          / h)
                    comp_raw += 2 * ring_h * row_raw / h
                    comp_enc += cross_enc
                else:
                    _add('all_reduce', intra_axis, 2 * ring * row_enc, 1)
                    _sadd(intra_axis, 2 * ring * row_enc,
                          2 * ring * (enc if comp_on else grad_raw))
                    comp_raw += 2 * ring * row_raw
                    comp_enc += 2 * ring * row_enc
            elif hier:
                if h > 1:
                    _add('reduce_scatter', intra_axis, ring * grad_raw, 1)
                cross_raw = 2 * ring_h * grad_raw / h
                cross_enc = 2 * ring_h * (enc if comp_on else grad_raw) / h
                _add('all_reduce', cross_axis, cross_enc, 1)
                comp_raw += cross_raw
                comp_enc += cross_enc
            elif zero_specs[n] is not None or fz is not None \
                    or (stage3 and n in gather_ns):
                wire = enc if comp_on else grad_raw
                _add('reduce_scatter', intra_axis, ring * wire, 1)
                comp_raw += ring * grad_raw
                comp_enc += ring * wire
            else:
                wire = enc if comp_on else grad_raw
                _add('all_reduce', intra_axis, 2 * ring * wire, 1)
                comp_raw += 2 * ring * grad_raw
                comp_enc += 2 * ring * wire
        # table-axis feature exchange (model-parallel tables): the
        # forward gathers remote rows and the backward scatters their
        # updates — one all-to-all pair per step, bytes proportional to
        # the live-row budget in the compute dtype (+ the id vector)
        for n in sparse_table_sharded:
            tsize = int(dict(zip(self.mesh.axis_names,
                                 self.mesh.devices.shape))[table_axis])
            B = sum(sparse_budgets[n])
            dim = shapes[n][1]
            itemsize = jnp.dtype(
                name_to_p[n].data()._data.dtype).itemsize
            a2a = 2 * _ring(tsize) * B * (dim * itemsize + 4)
            _add('all_to_all', table_axis, a2a, 2)
            _sadd(table_axis, a2a, a2a)
        self._comm_plan = plan
        self._hop_plan = hop_plan
        self._sparse_hop = sparse_hop
        self._sparse_dense_hop = sparse_dense_hop
        self._comp_plan = {
            'codec': ctype, 'raw_bytes': comp_raw, 'encoded_bytes':
            comp_enc, 'axis': cross_axis if hier else intra_axis,
        } if comp_on else None
        # per-layer gather bytes (zero3): [(layer, bytes/step, gathers)]
        self._gather_plan = [
            (gname, 2 * ring * sum(param_nbytes[n] for n in names), 2)
            for gname, names in layer_groups]

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
        batch_spec = None
        try:
            batch_spec = str(getattr(self._batch_sh, 'spec',
                                     self._batch_sh))
        except Exception:
            pass
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
            'zero': self._zero_label,
            'codec': self.compression['type']
            if self.compression is not None else None,
            'guard': self._guard is not None,
            'donate': bool(self.donate),
            'params': len(self._t_names or ()) + len(self._f_names or ()),
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
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        in_datas = tuple(x._data if isinstance(x, NDArray) else x
                         for x in inputs)
        lab_datas = tuple(x._data if isinstance(x, NDArray) else x
                          for x in labels)
        # 1.0 on normal steps (exact-identity multiply on the loss); an
        # injected step.dispatch:nan flips it to NaN inside the compiled
        # step, so loss AND every gradient go non-finite even for
        # int-input models (BERT token ids)
        fault_scale = jnp.asarray(
            float('nan') if fault == 'nan' else 1.0, jnp.float32)
        if self._compiled is None:
            trainable, frozen = self._collect()
            if not trainable and not frozen:
                self.init(*inputs)
                trainable, frozen = self._collect()
            if any(p._data is None for _, p in trainable + frozen):
                self.init(*inputs)
            with _trace.span('optimizer.state_init'):
                self._opt_state = {
                    n: self._opt_init(p.data()._data.astype(jnp.float32))
                    for n, p in trainable}
            self._build(in_datas, lab_datas)
            if cctx is not None:
                _compile.set_signature(
                    cctx, self._build_signature(in_datas, lab_datas))
            # place params on the mesh with their shardings
            with _trace.span('h2d.param_place'), \
                    _memory.oom_guard('h2d.param_place'):
                for n, p in self._trainable:
                    p._data[0]._data = _put_replicated(
                        p.data()._data, self._t_shardings[n])
                for n, p in self._frozen:
                    p._data[0]._data = _put_replicated(
                        p.data()._data, self._f_shardings[n])
                self._master = {
                    n: _put_replicated(
                        self._master_host(n, p.data()._data),
                        self._master_shardings[n])
                    for n, p in self._trainable
                    if n in self._master_names}
                self._opt_state = {
                    n: tuple(_put_replicated(s, sh) for s, sh in
                             zip(self._opt_state[n],
                                 self._state_shardings[n]))
                    for n in self._t_names}
                # error-feedback residuals seed to zero (a restore may
                # overwrite them from the states payload just below)
                self._residual = {
                    n: _put_replicated(
                        onp.zeros(self._residual_shapes[n], onp.float32),
                        self._residual_shardings[n])
                    for n in self._residual_shapes}
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
            in_datas = tuple(_put_batch(x, self._batch_sh)
                             for x in in_datas)
            lab_datas = tuple(_put_batch(x, self._batch_sh)
                              for x in lab_datas)
        if self._cost_args is None:
            # abstract avals of one step call, kept for cost_analysis()
            self._cost_args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)),
                (t_params, f_params, master, opt_state, residual,
                 in_datas, lab_datas, key, lr_val, fault_scale))
        with _trace.span('step.compiled'), \
                _memory.oom_guard('step.dispatch'):
            out = self._compiled(
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
        if self._comm_plan and _trace.enabled():
            # the collectives run INSIDE the compiled program — annotate
            # the trace with the analytic ring-wire plan per step; the
            # stage label separates the zero1 writeback gather from the
            # zero3 per-layer on-use gathers, the axis label separates
            # the intra-host (ici) hop from the cross-host (dcn) hop
            # under the hierarchical decomposition
            for (kind, axis), (nbytes, count) in self._hop_plan.items():
                _trace.instant(f'comm.{kind}', bytes=int(nbytes),
                               count=count, axis=axis,
                               stage=self._zero_label)
            for layer, nbytes, count in self._gather_plan:
                _trace.instant('comm.all_gather', bytes=int(nbytes),
                               count=count, axis=self._shard_axis,
                               stage=self._zero_label, layer=layer)
            if self._comp_plan is not None:
                _trace.instant('comm.compress',
                               bytes=int(self._comp_plan['encoded_bytes']),
                               codec=self._comp_plan['codec'],
                               axis=self._comp_plan['axis'])
                _trace.instant('comm.decompress',
                               bytes=int(self._comp_plan['raw_bytes']),
                               codec=self._comp_plan['codec'],
                               axis=self._comp_plan['axis'])
        if _telem['on'] and self._comm_plan:
            from .. import telemetry as _telemetry
            for (kind, axis), (nbytes, count) in self._hop_plan.items():
                _telemetry.counter(
                    'mxnet_tpu_comm_collective_bytes_total').inc(
                        nbytes, kind=kind, axis=axis,
                        stage=self._zero_label)
                _telemetry.counter('mxnet_tpu_comm_collectives_total').inc(
                    count, kind=kind, axis=axis,
                    stage=self._zero_label)
            if self._comp_plan is not None:
                _telemetry.counter(
                    'mxnet_tpu_comm_compressed_bytes_total').inc(
                        self._comp_plan['encoded_bytes'],
                        codec=self._comp_plan['codec'],
                        axis=self._comp_plan['axis'])
        if sparse_stats is not None:
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
        loss_nd = NDArray(_local_value(loss))
        _memory.on_step(self._step_count)
        _flight.record_step(self._step_count, loss=loss_nd)
        return loss_nd

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
        self.zero_stage = self._requested_stage if self._dp_size > 1 else 0
        self.zero = self.zero_stage > 0
        self._spans_processes = self._mesh_spans_processes()
        self._compiled = None
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
        with _memory.oom_guard('checkpoint.restore'):
            for n, p in self._trainable:
                p._data[0]._data = _put_replicated(
                    onp.asarray(p.data()._data), self._t_shardings[n])
            for n, p in self._frozen:
                p._data[0]._data = _put_replicated(
                    onp.asarray(p.data()._data), self._f_shardings[n])

    # ------------------------------------------------------------------
    # optimizer-state introspection + layout-independent checkpointing
    # ------------------------------------------------------------------
    def cost_analysis(self):
        """{'flops', 'bytes'} of ONE compiled step from XLA's own
        cost_analysis — the deterministic device-side half of the
        per-step attribution report (telemetry.attribution joins it
        with the measured wall-time spans). Lowers/compiles the step
        once more from stored avals (cached by the persistent
        compilation cache when enabled); None before the first step or
        when the backend exposes no cost model."""
        if self._compiled is None or self._cost_args is None:
            return None
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
        XLA's own byte plan). Compiled once more from the stored avals;
        raises before the first step.

        Its ``op_name``s are this process's own. The persistent cache's
        key leaves metadata out, so a hit may hand back an executable
        built from an older source, or from a model under another
        prefix, with *that* program's names in its text: the same
        instructions, scopes that no longer exist. A reader that splits a
        device trace by scope (chipbench/scopes.py) would then split by
        nothing. So this one compile makes the metadata part of the key:
        a persistent-cache hit only on a program traced from the same
        source, a compile of its own otherwise."""
        if self._compiled is None or self._cost_args is None:
            raise MXNetError("compiled_program(): the step has not run yet")
        flag = 'jax_compilation_cache_include_metadata_in_key'
        before = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            return self._compiled.lower(*self._cost_args).compile()
        finally:
            jax.config.update(flag, before)

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
        if self._compiled is None or self._cost_args is None:
            return None
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
        a = onp.asarray(arr, onp.float32)
        fz = getattr(self, '_flat_meta', {}).get(n)
        if fz is not None:
            a = onp.pad(a.reshape(-1), (0, fz['pad']))
        return a

    def _leaf_to_logical(self, n, a):
        """Un-flatten a ZeRO-3 flat master/moment back to the param's
        logical shape for the layout-independent states payload."""
        a = onp.asarray(a)
        fz = getattr(self, '_flat_meta', {}).get(n)
        if fz is not None and a.ndim == 1 and a.shape[0] == fz['padded']:
            a = a[:fz['size']].reshape(self._shapes[n])
        return a

    def _leaf_from_logical(self, n, a):
        """Flatten+pad a logical-shape restored master/moment into this
        step's ZeRO-3 flat layout (identity elsewhere, and for the
        shape-() step counters)."""
        a = onp.asarray(a)  # lint: host-sync-ok checkpoint-restore path, not the step loop
        fz = getattr(self, '_flat_meta', {}).get(n)
        if fz is not None and a.shape == self._shapes[n]:
            a = onp.pad(a.reshape(-1).astype(onp.float32, copy=False),
                        (0, fz['pad']))
        return a

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
            'hierarchy': (self._cross_size, self._shard_size),
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
        dense path it replaced — None when no table took it.

        - ``update_bytes_per_step``: optimizer-touched bytes (param +
          fp32 master + vector moments rows) across sparse tables;
          lazy mode scales with the live-row budget, exact mode is
          honestly dense (it densifies before the kernel).
        - ``exchange_bytes_per_hop``: analytic ring-wire bytes of the
          row-block gradient exchange by mesh hop, with the
          dense-equivalent bytes the same hop would have moved.
        """
        if not getattr(self, '_sparse_names', None):
            return None
        tables = {}
        upd = dense_upd = 0
        for n in self._sparse_names:
            vocab, dim = self._shapes[n]
            budget = min(int(sum(self._sparse_budgets[n])), int(vocab))
            leaves = 1 + sum(
                1 for s in self._opt_state[n] if getattr(s, 'ndim', 0))
            if n in self._master_names:
                leaves += 1
            per_row = dim * 4 * leaves
            touched = vocab if self._sparse_exact else budget
            tables[n] = {'vocab': int(vocab), 'dim': int(dim),
                         'budget': budget,
                         'update_bytes': touched * per_row,
                         'dense_update_bytes': int(vocab) * per_row}
            upd += touched * per_row
            dense_upd += int(vocab) * per_row
        hops = {axis: {'bytes': int(b),
                       'dense_bytes':
                           int(self._sparse_dense_hop.get(axis, 0))}
                for axis, b in (self._sparse_hop or {}).items()}
        return {
            'mode': 'exact' if self._sparse_exact else 'lazy',
            'table_axis': self._sparse_table_axis,
            'tables': tables,
            'update_bytes_per_step': int(upd),
            'dense_update_bytes_per_step': int(dense_upd),
            'update_shrink': dense_upd / max(1, upd),
            'exchange_bytes_per_hop': hops,
        }

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
        states = {n: tuple(self._leaf_to_logical(n, s) for s in st)
                  for n, st in self._opt_state.items()}
        master = {n: self._leaf_to_logical(n, m)
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
            doc['residual'] = {n: self._leaf_to_logical(n, r)
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
                _put_replicated(self._leaf_from_logical(n, s), sh)
                for s, sh in zip(st, self._state_shardings[n]))
        restored_master = doc.get('master', {})
        for n, m in restored_master.items():
            if n in self._master_names:
                self._master[n] = _put_replicated(
                    self._leaf_from_logical(n, m),
                    self._master_shardings[n])
        # zero3 flat masters with no saved counterpart (payload written
        # under zero off/1, where the param carried the value itself):
        # reseed from the CURRENT param so the flat store matches the
        # restored weights instead of keeping a pre-restore value
        for n, p in self._trainable or []:
            if n in self._flat_meta and n not in restored_master \
                    and n in self._master_names:
                self._master[n] = _put_replicated(
                    # lint: host-sync-ok restore-time reseed, runs once per restore
                    self._master_host(n, onp.asarray(p.data()._data)),
                    self._master_shardings[n])
        # error-feedback residuals: restored when the payload carries
        # them (scattered into THIS step's layout), deterministically
        # reseeded to zero otherwise (a payload saved without
        # compression has no error state to carry — documented
        # trajectory note in README "Gradient compression")
        if self._residual is not None and self._residual_shapes:
            restored_res = doc.get('residual', {})
            for n in self._residual_shapes:
                if n in restored_res:
                    self._residual[n] = _put_replicated(
                        self._leaf_from_logical(n, restored_res[n]),
                        self._residual_shardings[n])
                else:
                    self._residual[n] = _put_replicated(
                        onp.zeros(self._residual_shapes[n], onp.float32),
                        self._residual_shardings[n])
        self._step_count = int(doc.get('step_count', self._step_count))
