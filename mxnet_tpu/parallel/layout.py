"""Where everything of a sharded train step lives — from shapes alone.

``mesh_axes`` reads a mesh into one record of its data-parallel axes;
``step_layout`` maps it, the ZeRO stage and the parameters' shapes to a
``StepLayout``: the sharding of every parameter, fp32 master, optimizer
moment, compression residual and batch. Neither takes an array, so the
layout also serves ``ShardedTrainStep.lower()`` on devices only described.
The helpers at the end place arrays to such a layout.

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
the compiled step each layer's params are all-gathered on first use
(``exchange.gather_all``); gradients reduce-scatter straight into the
shard-local update and the updated params are written back SHARDED
(no trailing all-gather — the next step's per-layer gathers do that
work). Net: param + master + optimizer persistent HBM all drop to
~1/dp, at the cost of one extra all-gather of the params per step (the
backward regather) in ring wire bytes.

When the dp axis spans multiple hosts (or ``MXTPU_HIERARCHICAL_DP``
forces a split), the axis decomposes into (cross-host ``<dp>h``,
intra-host ``<dp>i``) sub-axes: ZeRO shards and the param all-gathers
stay on the fast intra-host ICI hop, and only the (compressed) gradient
exchange crosses the slow DCN hop — the ZeRO++-style hpZ tradeoff: state
memory drops 1/h instead of 1/dp in exchange for zero cross-host param
traffic.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .collectives import group_params_by_layer


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


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """The axis bookkeeping every layout decision reads.

    - ``dp_axes`` — axis names the BATCH shards over (the full dp extent,
      ``dp_size``, either way);
    - ``shard_axis``/``shard_size`` — the axis ZeRO shards over
      (intra-host under hierarchy: params/masters/moments replicate
      across hosts so no param all-gather ever crosses DCN);
    - ``cross_axis``/``cross_size`` — the slow hop the (compressible)
      gradient exchange crosses (None and 1 when flat).
    """
    mesh: Mesh
    dp_axis: str
    dp_axes: tuple
    dp_size: int
    shard_axis: str
    shard_size: int
    cross_axis: str | None
    cross_size: int


def mesh_axes(mesh, dp_axis, hierarchy=None, param_specs=None):
    """Read ``mesh`` into a ``MeshAxes``, decomposing the dp axis into
    (cross-host, intra-host) sub-axes when a hierarchy exists (real
    multi-host process topology, or ``hierarchy``/``MXTPU_HIERARCHICAL_DP``
    forcing a synthetic split). The record's mesh is the split one."""
    from . import dist as _dist
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = int(shape.get(dp_axis, 1))
    H, h = 1, dp
    if dp > 1 and dp_axis in shape:
        idx = mesh.axis_names.index(dp_axis)
        lead = [0] * len(mesh.axis_names)
        col = []
        for i in range(dp):
            lead[idx] = i
            col.append(mesh.devices[tuple(lead)])
        H, h = _dist.dp_host_split(col, force=hierarchy)
    if H <= 1:
        return MeshAxes(mesh, dp_axis, (dp_axis,), dp, dp_axis, h, None, H)
    for pat, spec in (param_specs or {}).items():
        if dp_axis in str(spec):
            raise MXNetError(
                f"hierarchical dp: param_spec {pat!r} proposes "
                f"the {dp_axis!r} axis, which is split "
                f"into ({dp_axis}h, {dp_axis}i) "
                f"sub-axes under MXTPU_HIERARCHICAL_DP — use "
                f"{dp_axis}i for fsdp-style sharding, or "
                f"force the flat topology (hierarchy=1).")
    return MeshAxes(split_dp_mesh(mesh, dp_axis, H), dp_axis,
                    (dp_axis + 'h', dp_axis + 'i'), dp, dp_axis + 'i', h,
                    dp_axis + 'h', H)


def resolve_param_specs(names, param_specs):
    """name -> PartitionSpec. A spec key matches a parameter by exact
    name or as a regex via re.search (so plain substrings keep
    working). Unmatched specs and conflicting matches warn. Returns the
    mapping and, for inspection, {spec key: names it matched}."""
    import re
    import warnings
    mapping = {n: P() for n in names}
    matched_by = {n: None for n in names}
    report = {}
    for pat, spec in param_specs.items():
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
    return mapping, report


@dataclasses.dataclass(frozen=True)
class StepLayout:
    """Where one step's arrays live. Dicts are keyed by parameter name;
    ``*_shardings`` hold NamedShardings on ``axes.mesh``."""
    axes: MeshAxes
    stage: int               # effective ZeRO stage: 0, 1 or 3
    label: str               # the stage as telemetry names it
    t_names: list            # trainable parameters, sorted
    f_names: list            # frozen parameters, sorted
    shapes: dict             # logical shape, trainable and frozen
    dtypes: dict             # compute dtype, trainable and frozen
    specs: dict              # base PartitionSpec, table axis applied
    table_axis: str | None   # axis the RowSparse tables in table_sharded
    table_sharded: frozenset  # shard their rows over
    modes: dict              # trainable -> 'dim' | 'flat' | 'shard' | 'repl'
    zero_specs: dict         # trainable -> dp-composed spec, or None
    zero3_layouts: dict      # stage 3: trainable -> zero3_layout()
    flat_meta: dict          # the 'flat' entries of zero3_layouts
    dim_names: list          # the 'dim' parameters of stage 3
    master_names: frozenset  # trainables that keep an fp32 master
    store_shapes: dict       # trainable -> shape of its fp32 store
    state_avals: dict        # trainable -> optimizer state leaves' avals
    t_shardings: dict
    f_shardings: dict
    zero_shardings: dict     # where a trainable's gradient is consumed
    state_shardings: dict    # trainable -> one sharding per state leaf
    master_shardings: dict
    shard_constraint: dict   # trainables whose gradient reduce-scatters
    residual_shapes: dict    # compression on: trainable -> store shape
    residual_shardings: dict
    layer_groups: list       # stage 3: [(layer, [dim names])], use order
    gather_shardings: dict   # dim name -> layout the forward computes in
    batch_sh: NamedSharding
    repl: NamedSharding

    def to_store(self, n, arr):
        """Host-side fp32 array for param ``n`` in its PERSISTENT layout:
        a logical-shape value flattens and zero-pads to the dp multiple
        for a ZeRO-3 flat param; anything else (a leaf already flat, the
        shape-() step counters, every other mode) passes as it is."""
        a = onp.asarray(arr)  # lint: host-sync-ok build/restore path, not the step loop
        fz = self.flat_meta.get(n)
        if fz is not None and a.shape == self.shapes[n]:
            a = onp.pad(a.reshape(-1).astype(onp.float32, copy=False),
                        (0, fz['pad']))
        return a

    def to_logical(self, n, arr):
        """Un-flatten a ZeRO-3 flat master/moment back to the param's
        logical shape for the layout-independent states payload."""
        a = onp.asarray(arr)
        fz = self.flat_meta.get(n)
        if fz is not None and a.ndim == 1 and a.shape[0] == fz['padded']:
            a = a[:fz['size']].reshape(self.shapes[n])
        return a


def step_layout(axes, stage, params, opt_init, compressed=False,
                sparse_names=(), table_axis=None):
    """The ``StepLayout`` of a step over ``axes`` at ZeRO ``stage``.

    ``params`` is a sequence of (name, shape, dtype, base PartitionSpec,
    trainable), each kind in name order; ``opt_init`` the optimizer's
    state constructor, read through ``jax.eval_shape`` only; ``compressed``
    whether error-feedback residuals exist; ``sparse_names`` the RowSparse
    tables and ``table_axis`` the model axis their rows may shard over."""
    mesh = axes.mesh
    shard_axis, shard_size = axes.shard_axis, axes.shard_size
    t_names = [n for n, _s, _d, _p, trainable in params if trainable]
    f_names = [n for n, _s, _d, _p, trainable in params if not trainable]
    shapes = {n: tuple(s) for n, s, _d, _p, _t in params}
    dtypes = {n: jnp.dtype(d) for n, _s, d, _p, _t in params}
    specs = {n: spec for n, _s, _d, spec, _t in params}
    # model-parallel table sharding: a divisible vocab shards
    # P(table_axis) and XLA inserts the all-to-all feature exchange
    # for remote rows; ragged vocabularies keep the replicated
    # compute copy (their fp32 state still shards through ZeRO-3's
    # flat padded stores)
    table_sharded = set()
    if table_axis and sparse_names:
        if table_axis in (axes.dp_axis, shard_axis, axes.cross_axis):
            raise MXNetError(
                f"MXTPU_SPARSE_TABLE_AXIS={table_axis!r} collides "
                f"with the data-parallel axis — pick a model "
                f"axis (e.g. 'tp').")
        tsize = int(dict(zip(mesh.axis_names,
                             mesh.devices.shape)).get(table_axis, 0))
        if tsize > 1:
            for n in sparse_names:
                if shapes[n][0] % tsize == 0 and specs[n] == P():
                    specs[n] = P(table_axis)
                    table_sharded.add(n)
    # low-precision trainables keep a persistent fp32 master copy
    # (the reference's create_state_multi_precision,
    # python/mxnet/optimizer/optimizer.py:52): without it, updates
    # below the bf16 ulp of the weight are lost to re-rounding.
    master_names = frozenset(
        n for n in t_names if dtypes[n].itemsize < 4
        and jnp.issubdtype(dtypes[n], jnp.floating))

    # shardings. The batch shards over the FULL dp extent either
    # way; ZeRO layouts shard over the intra-host sub-axis when the
    # hierarchy is active (see mesh_axes), so param traffic never
    # crosses the DCN hop.
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(axes.dp_axes))
    t_shardings = {n: NamedSharding(mesh, specs[n]) for n in t_names}
    f_shardings = {n: NamedSharding(mesh, specs[n]) for n in f_names}
    # ZeRO-1 (Rajbhandari et al., 2020, stage 1): the fp32 masters and
    # Adam moments shard 1/dp over the dp axis (composed with any tp
    # dims the param already shards). The update then reads a
    # dp-SHARDED gradient — the constraint in exchange.to_store turns the
    # plain all-reduce into reduce-scatter — and out_shardings all-gather
    # the updated param back to its replicated/tp layout. GSPMD fuses
    # and overlaps both collectives with backward compute.
    zero_specs = {n: None for n in t_names}
    z3 = {}
    if stage == 3:
        # ZeRO-3: every trainable gets a persistent layout — dim
        # (sharded in logical shape), flat (fp32 store padded to a
        # dp multiple) or repl (too small)
        for n in t_names:
            z3[n] = zero3_layout(shapes[n], specs[n], shard_axis,
                                 shard_size)
            if z3[n]['mode'] == 'dim':
                zero_specs[n] = z3[n]['spec']
    elif stage:
        for n in t_names:
            zero_specs[n] = compose_zero_spec(
                shapes[n], specs[n], shard_axis, shard_size)
    modes = {n: z3[n]['mode'] if stage == 3 else
             'shard' if zero_specs[n] is not None else 'repl'
             for n in t_names}
    flat_meta = {n: z3[n] for n in t_names if modes[n] == 'flat'}
    dim_names = [n for n in t_names if modes[n] == 'dim']
    # flat params: the compute-dtype logical copy stays replicated;
    # the fp32 master IS the (padded, dp-sharded) persistent store,
    # so they join master_names regardless of dtype
    master_names = master_names | frozenset(flat_meta)
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
    # slice). ZeRO-3 flat params carry flat (padded) moments.
    store_shapes = {n: (flat_meta[n]['padded'],) if n in flat_meta
                    else shapes[n] for n in t_names}
    # (one abstract init per distinct shape: a trace each, and a model
    # has a dozen shapes under its hundreds of parameters)
    state_of = {shape: jax.eval_shape(opt_init, jax.ShapeDtypeStruct(
        shape, jnp.float32)) for shape in set(store_shapes.values())}
    state_avals = {n: state_of[store_shapes[n]] for n in t_names}
    state_shardings = {
        n: tuple((repl if s.ndim == 0 else zero_shardings[n])
                 for s in state_avals[n])
        for n in t_names}
    # error-feedback compression: one fp32 residual per trainable,
    # persisted in the SAME layout the grad is consumed in (the
    # zero shard / flat store / replicated) so acc = g + r is a
    # local elementwise add with no extra collective
    residual_names = t_names if compressed else []
    return StepLayout(
        axes=axes, stage=stage,
        label={0: 'off', 1: 'zero1', 3: 'zero3'}[stage],
        t_names=t_names, f_names=f_names,
        shapes=shapes, dtypes=dtypes, specs=specs,
        table_axis=table_axis if table_sharded else None,
        table_sharded=frozenset(table_sharded), modes=modes,
        zero_specs=zero_specs, zero3_layouts=z3, flat_meta=flat_meta,
        dim_names=dim_names, master_names=master_names,
        store_shapes=store_shapes, state_avals=state_avals,
        t_shardings=t_shardings, f_shardings=f_shardings,
        zero_shardings=zero_shardings, state_shardings=state_shardings,
        master_shardings={n: zero_shardings[n] for n in master_names},
        shard_constraint={n: zero_shardings[n] for n in t_names
                          if zero_specs[n] is not None},
        residual_shapes={n: store_shapes[n] for n in residual_names},
        residual_shardings={n: zero_shardings[n] for n in residual_names},
        # ZeRO-3 per-layer gather pipeline: one chained all-gather per
        # layer group, in (heuristic) first-use order
        layer_groups=group_params_by_layer(dim_names) if dim_names else [],
        gather_shardings={n: NamedSharding(mesh, z3[n]['gather_spec'])
                          for n in dim_names},
        batch_sh=batch_sh, repl=repl)


# placing to the layout ----------------------------------------------------

def devices_span_processes(devices):
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


def sharding_spans_processes(sharding):
    try:
        devices = sharding.device_set
    except Exception:
        return jax.process_count() > 1
    return devices_span_processes(devices)


def put_replicated(x, sharding):
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
    if sharding_spans_processes(sharding):
        from jax.experimental import multihost_utils
        # lint: host-sync-ok param (re)placement runs at build/restore/re-form, not per step
        x = multihost_utils.broadcast_one_to_all(onp.asarray(x))
        x = onp.asarray(x)  # lint: host-sync-ok cold path, see above
    return jax.device_put(x, sharding)


def put_batch(x, sharding):
    """Place a batch with the dp sharding. Single-process: the array is the
    global batch. Multi-process: each process holds its OWN shard (the
    reference's per-worker data partition, tools/launch.py semantics), and
    the global batch is their concatenation over the dp axis."""
    if sharding_spans_processes(sharding):
        return jax.make_array_from_process_local_data(
            # lint: host-sync-ok the batch arrives host-resident from the io pipeline; h2d staging
            sharding, onp.asarray(x))
    return jax.device_put(x, sharding)


def local_value(arr):
    """A fully-addressable view of a replicated global array (loss outputs
    span all processes; every device holds the same value)."""
    if jax.process_count() > 1 and not arr.is_fully_addressable:
        return arr.addressable_data(0)
    return arr
