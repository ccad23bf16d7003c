"""Environment-variable configuration tier (ref: docs/faq/env_var.md +
the dmlc GetEnv calls spread through src/).

The reference configures its runtime through ~60 documented MXNET_* env
vars read at first use. This module is the TPU-native registry: every
supported variable is declared once with a type, default and help string;
`config.get('MXNET_...')` reads the process environment through that
declaration, `describe()` prints the documented surface, and variables
whose CUDA-era meaning has no TPU analog are declared `inert=True` so
user scripts that set them keep working while `describe()` says why they
do nothing here (XLA owns scheduling/memory/fusion).
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple, Optional

from .base import MXNetError

__all__ = ['EnvVar', 'register', 'get', 'set_env', 'describe', 'list_vars']


class EnvVar(NamedTuple):
    name: str
    type: Callable
    default: Any
    help: str
    inert: bool = False     # accepted but a no-op on TPU (documented why)


_REGISTRY: Dict[str, EnvVar] = {}


def register(name, type_, default, help_, inert=False):
    _REGISTRY[name] = EnvVar(name, type_, default, help_, inert)
    return _REGISTRY[name]


def _bool(s):
    return str(s).lower() not in ('0', 'false', 'off', '', 'no', 'n',
                                  'none', 'disabled')


def get(name, default=None):
    """Typed value of a declared env var (process env > declared default >
    `default`)."""
    var = _REGISTRY.get(name)
    if var is None:
        raise MXNetError(
            f"unknown config variable {name!r}; see "
            f"mxnet_tpu.config.list_vars()")
    raw = os.environ.get(name)
    if raw is None:
        return var.default if default is None else default
    try:
        return var.type(raw)
    except (TypeError, ValueError) as e:
        raise MXNetError(
            f"{name}={raw!r} is not a valid {var.type.__name__}") from e


def set_env(name, value):
    """Set a declared variable in the process environment (takes effect at
    the next read — matching the reference's read-at-first-use rule)."""
    if name not in _REGISTRY:
        raise MXNetError(f"unknown config variable {name!r}")
    os.environ[name] = str(value)


def list_vars():
    return sorted(_REGISTRY)


def describe(name: Optional[str] = None):
    """Documentation string for one or all declared variables."""
    names = [name] if name else list_vars()
    lines = []
    for n in names:
        v = _REGISTRY[n]
        cur = os.environ.get(n)
        tag = ' [inert on TPU]' if v.inert else ''
        lines.append(f"{v.name} (type={v.type.__name__}, "
                     f"default={v.default!r}"
                     + (f", set={cur!r}" if cur is not None else '')
                     + f"){tag}\n    {v.help}")
    return '\n'.join(lines)


# ---------------------------------------------------------------------------
# the supported surface
# ---------------------------------------------------------------------------

register('MXNET_HOME', str,
         os.path.join(os.path.expanduser('~'), '.mxnet'),
         'Data directory: model-store cache, datasets.')
register('MXNET_GLUON_REPO', str,
         'https://apache-mxnet.s3-accelerate.dualstack.amazonaws.com/',
         'Base URL (or local directory) for pretrained model downloads.')
register('MXNET_TEST_DEVICE', str, 'cpu',
         'Device used by test_utils.default_context().')
register('MXNET_STORAGE_FALLBACK_LOG_VERBOSE', _bool, True,
         'Log when a sparse op falls back to the dense implementation.')
register('MXNET_ENFORCE_DETERMINISM', _bool, False,
         'Restrict ops to deterministic algorithms. XLA on TPU is '
         'deterministic by default; this additionally pins the framework '
         'RNG seeding of data iterators.')
register('MXNET_SAFE_ACCUMULATION', _bool, True,
         'Accumulate reductions of low-precision inputs in float32 '
         '(layer norm / softmax statistics already do this on TPU).')
register('MXNET_TPU_JAX_TRACE_DIR', str, '',
         'Directory for the XLA device trace started by profiler.start().')
register('MXNET_PROFILER_AUTOSTART', _bool, False,
         'Start the profiler at import time.')
register('MXNET_KVSTORE_BIGARRAY_BOUND', int, 1000000,
         'Arrays above this element count use sharded collectives in the '
         'kvstore reduce path.')
register('MXNET_KVSTORE_USETREE', _bool, False,
         'Reference: tree reduction for multi-GPU. Collective layout on '
         'TPU is chosen by XLA over the ICI topology.', inert=True)
register('MXNET_ENABLE_GPU_P2P', _bool, True,
         'Reference: CUDA peer-to-peer. ICI links are always direct.',
         inert=True)
register('MXNET_ENGINE_TYPE', str, 'ThreadedEnginePerDevice',
         'Reference: dependency-engine selection. The XLA async runtime '
         'is the engine on TPU; accepted for script compatibility.',
         inert=True)
register('MXNET_EXEC_BULK_EXEC_TRAIN', _bool, True,
         'Reference: bulk execution of the graph. jit compilation '
         'subsumes it.', inert=True)
register('MXNET_EXEC_BULK_EXEC_INFERENCE', _bool, True,
         'Reference: bulk execution for inference. jit subsumes it.',
         inert=True)
register('MXNET_EXEC_ENABLE_INPLACE', _bool, True,
         'Reference: in-place graph optimization. XLA buffer donation '
         'subsumes it.', inert=True)
register('MXNET_GPU_MEM_POOL_TYPE', str, 'Naive',
         'Reference: CUDA memory pool strategy. Device memory on TPU is '
         'owned by PJRT/XLA.', inert=True)
register('MXNET_GPU_MEM_POOL_RESERVE', int, 5,
         'Reference: CUDA pool reserve percentage. PJRT-owned on TPU.',
         inert=True)
register('MXNET_CPU_WORKER_NTHREADS', int, 1,
         'Reference: CPU op worker threads. XLA:CPU threadpools are '
         'sized automatically.', inert=True)
register('MXNET_OMP_MAX_THREADS', int, 0,
         'Reference: OpenMP cap. XLA-managed on this stack.', inert=True)
register('MXNET_CUDNN_AUTOTUNE_DEFAULT', int, 1,
         'Reference: cuDNN autotuning. The XLA TPU compiler autotunes '
         'during compilation.', inert=True)
register('MXNET_ENABLE_OPERATOR_TUNING', int, 1,
         'Reference: CPU op tuning. XLA-managed.', inert=True)
register('MXNET_MEMORY_OPT', int, 0,
         'Reference: memory-optimization pass. Use jax.checkpoint / '
         'remat policies instead.', inert=True)
register('MXNET_SUBGRAPH_BACKEND', str, '',
         'Default subgraph partitioner applied by hybridize() when the '
         'call does not name one (see mxnet_tpu.subgraph).')
register('MXNET_SEED', int, 0,
         'Process-wide RNG seed applied at import when set.')
register('MXNET_TPU_COORDINATOR', str, '',
         'host:port of process 0 for multi-process init '
         '(parallel.dist.init / start_membership). Empty: fall back to '
         'the DMLC_PS_ROOT_URI/_PORT drop-in names, then '
         'localhost:12345 with a warning.')
register('MXNET_TPU_NUM_PROCS', int, 0,
         'Total process count for multi-process init. 0 (default): '
         'fall back to DMLC_NUM_WORKER, then single-process.')
register('MXNET_TPU_PROC_ID', int, -1,
         "This process's rank for multi-process init. -1 (default): "
         'fall back to DMLC_WORKER_ID, then 0.')
register('MXNET_TPU_IO_TRANSPORT', str, 'u8',
         "ImageRecordIter host->device transport: 'u8' moves raw uint8 "
         'NHWC and normalizes on device in one cached jitted program '
         "(~4x fewer host bytes); 'f32' materializes normalized "
         'float32 on the host (legacy path).')
register('MXNET_TPU_IO_DECODE_CACHE_MB', float, 256.0,
         'Byte budget (MB) of the cross-epoch decode cache: decoded + '
         'short-side-resized images reused across epochs (crop/mirror/'
         'normalize stay per-epoch). 0 disables the cache.')
register('MXNET_TPU_FUSED_DEBUG', _bool, False,
         "Print the traceback when an optimizer's update() fails to "
         'trace into the fused jitted update (the Trainer then falls '
         'back to the eager per-parameter loop with a warning).')
register('MXTPU_PALLAS_LN', _bool, False,
         'Route the transformer residual+LN epilogue through the fused '
         'Pallas kernel (ops/pallas_layernorm.py) when a TPU is '
         'present and the hidden dim is a multiple of 128. Default: '
         'the XLA path (flag-gated until measured on-chip).')
register('MXNET_TPU_MNIST_DIR', str, '',
         'Directory holding the MNIST idx files for '
         'test_utils.get_mnist(). Empty: a deterministic synthetic '
         'set (zero-egress environments cannot download).')
register('MXNET_TPU_NO_NATIVE_BUILD', _bool, False,
         'Never compile the native IO library on demand: missing '
         'prebuilt .so means the pure-Python pipeline fallback.')
register('MXNET_TPU_TELEMETRY', _bool, False,
         'Enable the runtime telemetry registry (mxnet_tpu.telemetry): '
         'op-dispatch/compile/kvstore/IO/step metrics with Prometheus, '
         'JSON and chrome-trace export. Off: instrumented paths take a '
         'single flag-check fast path.')
register('MXTPU_TRACE', _bool, False,
         'Enable step-level span tracing (mxnet_tpu.telemetry.trace): '
         'nested chrome-trace B/E spans over the step lifecycle (io, '
         'h2d, dispatch, collectives, optimizer, checkpoint) in '
         'lock-free per-thread ring buffers, plus the crash-time '
         'flight recorder. Off: a span is its jax.profiler '
         'TraceAnnotation alone (mxtpu.<name> on the host plane of any '
         'profile; nothing while none is taken) and records nothing '
         'here.')
register('MXTPU_TRACE_RING', int, 16384,
         'Span-trace ring capacity in events PER THREAD. A full ring '
         'overwrites its oldest events (dropped whole spans are '
         'counted in mxnet_tpu_trace_dropped_spans_total).')
register('MXTPU_FLIGHT_STEPS', int, 64,
         'Flight recorder depth: per-step span summaries (+ loss and '
         'guard flags) retained for the crash-time dump.')
register('MXTPU_FLIGHT_DIR', str, '',
         'Directory for flight-recorder post-mortem dumps '
         '(mxtpu_flight-<pid>.json). Empty (default): the system temp '
         'directory — the recorder never litters the CWD. Ignored when '
         'MXTPU_FLIGHT_PATH names an explicit file.')
register('MXTPU_FLIGHT_PATH', str, '',
         'Explicit path of the flight-recorder post-mortem JSON '
         '(watchdog stall, guard rollback, atexit/fatal-signal hook). '
         'Empty (default): MXTPU_FLIGHT_DIR/mxtpu_flight-<pid>.json.')
register('MXNET_TPU_RECOMPILE_WARN_THRESHOLD', int, 3,
         'Telemetry recompile detector: warn (once per compile site) '
         'when one site, e.g. a hybridized block, compiles more than '
         'this many times — churning input shapes/dtypes force an XLA '
         'recompile every step.')
register('MXTPU_FAULT', str, '',
         'Arm deterministic fault injection: comma-separated '
         'site:kind[:prob[:seed[:first-last]]] specs (kinds: raise, '
         'hang, corrupt, nan). See mxnet_tpu.resilience.faults.sites() '
         'for the registered sites. Read once at import; re-arm with '
         'resilience.faults.arm_from_env().')
register('MXTPU_FAULT_HANG_SECONDS', float, 300.0,
         'How long an armed "hang" fault sleeps at its site (long '
         'enough to trip the step watchdog, short enough for tests).')
register('MXTPU_GUARD_MAX_BAD_STEPS', int, 3,
         'NonFiniteGuard policy ladder: after this many CONSECUTIVE '
         'non-finite steps (each already skipped on device), '
         'auto-restore the newest committed checkpoint.')
register('MXTPU_WATCHDOG_SECONDS', float, 300.0,
         'StepWatchdog default deadline: with no training-step '
         'heartbeat for this long, dump all-thread stacks + a telemetry '
         'snapshot to the log (once per stall).')
register('MXTPU_CHECKPOINT_WRITE_RETRIES', int, 2,
         'Bounded retries (with backoff) of a checkpoint payload write '
         'after a transient filesystem error before the failure '
         'surfaces on the training thread.')
register('MXTPU_DATALOADER_WORKER_RETRIES', int, 2,
         'Bounded re-submissions of a gluon DataLoader batch fetch '
         'after a worker crash before a clear error is raised.')
register('MXNET_TPU_IO_CORRUPT_POLICY', str, 'error',
         "What ImageRecordIter does with a corrupt/truncated record "
         "mid-epoch: 'error' raises DataError naming the record index "
         "and file offset; 'skip' substitutes the next good record and "
         "counts mxnet_tpu_io_corrupt_records_total.")
register('MXTPU_ELASTIC', _bool, False,
         'Enable the elastic-training membership layer: dist.init() '
         'starts the rank-0 heartbeat coordinator and a per-process '
         'heartbeat sender on a side-channel TCP socket (never the ICI '
         'collectives), so peer loss is detectable while a collective '
         'is wedged. Pairs with resilience.ElasticController for the '
         'commit -> re-form -> resume path.')
register('MXTPU_ELASTIC_PORT', int, 0,
         'TCP port of the elastic membership side channel on the '
         'coordinator host. 0 (default) derives jax-coordinator port '
         '+ 1000 so launch.py-style multi-job hosts do not collide.')
register('MXTPU_HEARTBEAT_SECONDS', float, 1.0,
         'Elastic membership heartbeat period. Each process beats the '
         'rank-0 coordinator this often over the side channel '
         '(piggybacking its last completed step).')
register('MXTPU_PEER_DEADLINE_SECONDS', float, 10.0,
         'Elastic membership peer deadline: a peer whose last heartbeat '
         'is older than this is declared LOST — the survivors commit a '
         'checkpoint, re-form the mesh at the new world size and '
         'resume. Also the window after which a worker that cannot '
         'reach the coordinator considers the coordinator itself lost.')
register('MXTPU_DIST_INIT_RETRIES', int, 3,
         'Bounded retries (exponential backoff) of '
         'jax.distributed.initialize in dist.init() — workers that '
         'start before the coordinator is listening see a transient '
         'connection error, not a fatal one.')
register('MXTPU_BARRIER_TIMEOUT_SECONDS', float, 60.0,
         'Timeout of the elastic membership barrier (dist.barrier): '
         'how long a rank waits for every live peer to arrive at the '
         'same tag before raising.')
register('MXTPU_JOIN_TIMEOUT_SECONDS', float, 120.0,
         'Timeout of the elastic scale-up admission rendezvous: how '
         'long a joiner (after its JOIN announcement) and the '
         'quiesced survivors wait for each other at the admit barrier '
         'before the admission is abandoned. Also bounds how long an '
         'unadmitted JOIN announcement survives on the coordinator '
         'without joiner heartbeats.')
register('MXTPU_AUTOSCALE_COOLDOWN_SECONDS', float, 30.0,
         'Autoscaler hysteresis: minimum spacing between decisions of '
         'the same kind (per rank for evicts, global for capacity '
         'requests) so one noisy detector window cannot thrash the '
         'fleet.')
register('MXTPU_AUTOSCALE_STRIKES', int, 3,
         'Autoscaler hysteresis: a FleetMonitor detector flag '
         '(chronic straggler, memory imbalance, step regression) must '
         'persist for this many CONSECUTIVE observe() polls before it '
         'escalates to an evict/request-capacity decision; a cleared '
         'flag resets the count.')
register('MXTPU_AUTOSCALE_MAX_WORLD', int, 0,
         'Upper bound on the world size the autoscaler will request '
         'capacity toward (its target is clamped to this). 0 '
         '(default): unbounded — the target is the nominal world '
         'observed at the first poll.')
register('MXTPU_CHECKPOINT_REPLICAS', int, 1,
         'Checkpoint survivability: how many PEER hosts each committed '
         'checkpoint step is replicated to over the membership side '
         'channel (ring order over the live ranks). 0 disables '
         'replication. Replication runs entirely off the training '
         'thread — a dead or slow peer can never stall a commit.')
register('MXTPU_REPLICA_PORT_BASE', int, 0,
         'Base TCP port of the per-rank checkpoint replica servers '
         '(rank r listens on base + r). 0 (default) derives the elastic '
         'side-channel port + 100, so parallel jobs on one host do not '
         'collide.')
register('MXTPU_REPLICA_BANDWIDTH_MBPS', float, 0.0,
         'Cap on checkpoint replication transfer bandwidth in MB/s '
         '(paced per chunk on the sending side, so a replication push '
         'never saturates the NIC a training job shares). 0 (default) '
         'is uncapped.')
register('MXTPU_REPLICA_TIMEOUT_SECONDS', float, 10.0,
         'Socket timeout of every replica-transport op (file_put / '
         'file_get / inventory / commit / delete). Bounds how long a '
         'dead peer can hold a replication worker or a replica-restore '
         'fetch — never the training thread.')
register('MXTPU_COMPRESSION', str, '',
         "Error-feedback gradient compression codec of the GSPMD "
         "sharded step when no explicit compression_params are given: "
         "'' or 'none' (off, the default), 'fp16' (truncate, 2x wire "
         "shrink), 'int8' (per-block scale, ~3.9x) or '2bit' (the "
         "reference kvstore's sign+threshold quantizer, ~15x). The "
         "quantization residual is carried per-param as sharded "
         "optimizer-side state, so the error is re-offered next step "
         "instead of lost.")
register('MXTPU_COMPRESSION_THRESHOLD', float, 0.5,
         "2-bit gradient compression threshold (the reference's "
         "pos_threshold/neg_threshold magnitude): values quantize to "
         "{-t*s, 0, +t*s} against the per-block scale s (s=1 when the "
         "block knob is 0 — absolute-threshold reference semantics).")
register('MXTPU_COMPRESSION_BLOCK', int, 256,
         'Per-block scale granularity (elements along the last dim) of '
         'the int8/2bit gradient codecs. 0: one per-tensor scale '
         '(2bit then uses the absolute threshold with no wire '
         'overhead). Each block adds one fp32 scale to the encoded '
         'payload.')
register('MXTPU_HIERARCHICAL_DP', int, 0,
         'Hierarchy-aware decomposition of the dp axis into (cross-'
         'host, intra-host) sub-axes: ZeRO shards and param '
         'all-gathers then stay on the fast intra-host ICI hop and '
         'only the (compressible) gradient exchange crosses the slow '
         'DCN hop. 0 (default): auto-detect host groups from the '
         'device->process topology; 1: force flat (single hop); N>=2: '
         'force N equal host groups (CPU simulation / drills).')
register('MXTPU_METRICS_PORT', int, 0,
         'Base TCP port of the per-process observability endpoint '
         '(telemetry.server): rank r serves GET /metrics (Prometheus '
         'exposition), /healthz (membership view + stall verdict + '
         'last committed step) and /flight (on-demand flight-recorder '
         'dump) on base + r. 0 (default): no server — the step path is '
         'untouched. The server binds localhost-only unless '
         'MXTPU_METRICS_BIND says otherwise, never touches the ICI '
         'collectives, and answers with bounded handler threads.')
register('MXTPU_METRICS_BIND', str, '127.0.0.1',
         'Bind address of the observability endpoint. The default '
         'stays loopback-only; set 0.0.0.0 deliberately when a fleet '
         'scraper lives off-host.')
register('MXTPU_FLEET_WINDOW', int, 32,
         'Rolling window (snapshots per rank) the fleet anomaly '
         'detectors baseline over: step-time regression and loss-spike '
         'statistics are computed against this many recent snapshots.')
register('MXTPU_FLEET_REGRESSION_FACTOR', float, 2.0,
         "Fleet detector: a rank's step wall time above this multiple "
         'of its own rolling baseline is flagged as a step-time '
         'regression (flight note fleet.step_regression).')
register('MXTPU_FLEET_STRAGGLER_FACTOR', float, 1.5,
         "Fleet detector: a rank's step wall time above this multiple "
         'of the fleet median is flagged as a straggler (flight note '
         'fleet.straggler; the watchdog verdict names the rank).')
register('MXTPU_FLEET_STALE_SECONDS', float, 0.0,
         'Fleet detector: a rank whose newest telemetry snapshot is '
         'older than this is flagged as stale/straggling even if its '
         'last reported step time was healthy. 0 (default): 3x the '
         'membership heartbeat period.')
register('MXTPU_FLEET_LOSS_SPIKE_SIGMA', float, 6.0,
         'Fleet detector: a reported loss above the rolling mean plus '
         'this many rolling standard deviations (window '
         'MXTPU_FLEET_WINDOW, minimum 8 samples) is flagged as a loss '
         'spike (flight note fleet.loss_spike).')
register('MXTPU_FLEET_IMBALANCE_FACTOR', float, 1.5,
         'Fleet detector: max/min ratio of per-rank comm bytes per '
         'step above this is flagged as a collective imbalance '
         '(flight note fleet.comm_imbalance).')
register('MXTPU_MEMORY', _bool, False,
         'Enable memory watermark sampling (telemetry.memory): per-step '
         'live/peak device-memory samples — jax device.memory_stats() '
         'where the backend exposes it, else the deterministic fallback '
         'summing per-device bytes over the tracked live arrays (params, '
         'masters, moments, residuals, device-prefetch leases) — plus '
         'host RSS, into a bounded ring, mxnet_tpu_memory_* gauges, the '
         'flight-recorder step records and the fleet snapshots. Off: '
         'the per-step hook is one dict check and allocates nothing. '
         'The OOM forensics guard is always armed regardless.')
register('MXTPU_MEMORY_RING', int, 256,
         'Watermark ring depth: memory samples retained for the OOM '
         'post-mortem and /healthz (bounded; oldest overwritten).')
register('MXTPU_MEMORY_EVERY', int, 1,
         'Memory sampling cadence: record one watermark sample every '
         'this many steps (1 = every step). Raise it when the fallback '
         'pool walk over very large parameter sets is measurable.')
register('MXTPU_MEMORY_LEAK_STEPS', int, 8,
         'Leak detector: this many CONSECUTIVE samples of monotonic '
         'live-bytes growth (see MXTPU_MEMORY_LEAK_BYTES) latch one '
         'memory.leak_suspected flight note; a non-growing sample '
         'clears the latch.')
register('MXTPU_MEMORY_LEAK_BYTES', int, 1 << 20,
         'Leak detector: minimum total live-bytes growth over the '
         'MXTPU_MEMORY_LEAK_STEPS window before the latch fires (1 MB '
         'default — step-to-step allocator noise must not page anyone).')
register('MXTPU_FLEET_MEMORY_IMBALANCE_FACTOR', float, 1.5,
         'Fleet detector: max/min ratio of per-rank live device memory '
         '(from the heartbeat-piggybacked memory snapshots) above this '
         'is flagged as an HBM imbalance on the fattest rank (flight '
         'note fleet.memory_imbalance).')
register('MXTPU_SCRUB_SECONDS', float, 300.0,
         'Background checkpoint scrubber cadence: every this many '
         'seconds the scrubber re-hashes one pass over the committed '
         'local steps and hosted peer replicas, quarantines mismatches '
         'and repairs them from a healthy replica. 0 disables the '
         'scrubber thread (scrub_once() remains callable).')


def _zero_stage(s):
    """MXTPU_ZERO value -> ZeRO stage int: 0/off/false -> 0, 1/on/true
    -> 1, 3 -> 3 (stage 2 has no separate meaning on the GSPMD path —
    grads already reduce-scatter under stage 1)."""
    raw = str(s).strip().lower()
    if raw in ('3',):
        return 3
    if raw in ('1', 'true', 'on', 'yes', 'y', 'enabled'):
        return 1
    if raw in ('0', 'false', 'off', '', 'no', 'n', 'none', 'disabled'):
        return 0
    raise ValueError(f"MXTPU_ZERO={s!r}: expected 0 (off), 1 (sharded "
                     f"optimizer state) or 3 (sharded params + grads + "
                     f"state / FSDP)")


register('MXTPU_ZERO', _zero_stage, 1,
         'ZeRO stage of the sharded update on the GSPMD data-parallel '
         'path. 1 (default whenever a dp axis with >1 devices is '
         'present): gradients reduce-scatter over dp, each device runs '
         'the optimizer on its 1/dp slice of the fp32 masters and '
         'moments, and updated params all-gather back to the compute '
         'dtype — all inside the one pjit step so XLA overlaps the '
         'collectives with backward compute. 3 (ZeRO-3/FSDP): the '
         'persistent params and masters ALSO live 1/dp-sharded; each '
         "layer's params all-gather on first use inside the step "
         '(prefetched one layer ahead), are rematerialized for '
         'backward instead of kept, and grads reduce-scatter straight '
         'into the shard-local update. 0 forces the fully replicated '
         'update.')

register('MXTPU_COMPILE_LEDGER', str, '',
         'Arm the compile ledger (telemetry.compile): every jit/pjit '
         'build site appends a structured signature + trace/lower/'
         'backend-compile timing entry to a bounded in-memory ring and '
         'an on-disk JSONL ledger. Empty (default): disarmed — build '
         'sites take a single flag-check fast path. "1"/"on": ledger '
         'at MXTPU_FLIGHT_DIR/mxtpu_compile_ledger-<pid>.jsonl; any '
         'other value: an explicit ledger path (share one path across '
         'processes to estimate persistent-cache saved-seconds from '
         'prior runs). Validate with tools/check_compile_ledger.py.')
register('MXTPU_COMPILE_CACHE_DIR', str, '',
         'Persistent XLA compilation-cache directory, wired through '
         'jax.config (jax_compilation_cache_dir + the min-entry-size/'
         'min-compile-time gates dropped to zero so every program is '
         'eligible). Warm processes reuse cold-process binaries: '
         'hit/miss/saved-seconds land in mxnet_tpu_compile_persistent_'
         'cache_* counters and the compile ledger. Loses to '
         'JAX_COMPILATION_CACHE_DIR when that is set. Empty (default): '
         "jax's own defaults (cache off unless configured elsewhere).")

# -- inference serving (mxnet_tpu.serving) ---------------------------------

register('MXTPU_SERVE_BATCH_DEADLINE_MS', float, 5.0,
         'Continuous-batcher formation deadline: a batch dispatches '
         'when its sequence bucket fills to the largest batch bucket '
         'or when its OLDEST request has waited this long, whichever '
         'comes first. 0 dispatches immediately (lowest p50, worst '
         'device efficiency); larger values trade queue latency for '
         'fuller batches.')
register('MXTPU_SERVE_BUCKETS', str, '32,64,128',
         'Sequence-length buckets (comma-separated, ascending). Every '
         'request pads up to the smallest bucket that fits; requests '
         'longer than the largest bucket are rejected with 400. '
         'Together with MXTPU_SERVE_BATCH_BUCKETS this fixes the '
         'compiled-shape universe the warmup pass pre-builds — steady '
         'state never compiles.')
register('MXTPU_SERVE_BATCH_BUCKETS', str, '1,2,4,8',
         'Batch-size buckets (comma-separated, ascending). A formed '
         'batch pads its row count up to the smallest bucket that '
         'fits; the largest bucket is the fill target that dispatches '
         'a batch early.')
register('MXTPU_SERVE_QUEUE_LIMIT', int, 256,
         'Admission bound on total queued predict requests; beyond it '
         'submissions shed with 503 (mxnet_tpu_serving_shed_total, '
         'reason=queue_full) instead of growing an unbounded backlog.')
register('MXTPU_SERVE_PORT', int, 0,
         'Predict-endpoint base port (rank r serves on base + r, the '
         'same collision-avoidance scheme as MXTPU_METRICS_PORT). '
         '0 = serving disarmed.')
register('MXTPU_SERVE_QUANTIZE', str, '',
         "Weight quantization for the predict path: '' (default, "
         "full precision), 'bf16' (cast parameters to bfloat16 — 2x "
         "residency), or 'int8' (snap float weights to the PR 11 "
         "codec's block-scaled int8 value grid — the accuracy of an "
         'int8-weights deployment, stored in float on this backend).')
register('MXTPU_SERVE_MEMORY_LIMIT_MB', float, 0.0,
         'Admission control from memory observability: when live '
         'device bytes (telemetry.memory.health_fields) exceed this, '
         'predicts shed with 503 until pressure clears. 0 = off.')
register('MXTPU_SERVE_WATCHDOG_SECONDS', float, 0.0,
         'Arm a StepWatchdog over the batcher: a dispatch that '
         'produces no completed batch for this long dumps a stall '
         'report (classified COMPILING vs EXECUTING via the compile '
         'window) and notes serving.stuck. 0 = off.')
register('MXTPU_SERVE_EJECT_FAILURES', int, 2,
         'Router ejection threshold: this many CONSECUTIVE failed '
         'predicts (connect refused, 5xx, shed) ejects a replica from '
         'rotation for MXTPU_SERVE_READMIT_SECONDS.')
register('MXTPU_SERVE_READMIT_SECONDS', float, 5.0,
         'How long an ejected replica sits out before the router '
         'probes it back in (the next routed predict is the probe).')
register('MXTPU_SERVE_DRAIN_SECONDS', float, 10.0,
         'Graceful-drain budget: how long a draining replica waits '
         'for in-flight requests to flush before closing.')

# -- kernel autotuning + remat policy (ISSUE 18) ---------------------------

register('MXTPU_FA_G', int, 0,
         'Explicit flash-attention FORWARD head-group size (the G '
         'batch*head slices one kernel invocation processes). Highest '
         'rung of the ops/autotune precedence ladder: env override > '
         'tuning-DB winner > built-in defaults. 0 (default) = unset; '
         'the value is still clamped to a divisor of batch*heads and '
         'to the scoped-VMEM budget.')
register('MXTPU_FA_BQ', int, 0,
         'Explicit flash-attention forward query-sequence block size. '
         '0 = unset (tuning DB, then defaults). Must satisfy the '
         'Mosaic trailing-tile rule (multiple of 8 rows for f32, 16 '
         'for bf16) — autotune.check_candidate validates shapes.')
register('MXTPU_FA_BK', int, 0,
         'Explicit flash-attention forward key-sequence block size. '
         '0 = unset (tuning DB, then defaults).')
register('MXTPU_FA_BWD_G', int, 0,
         'Explicit flash-attention BACKWARD head-group size (the dq '
         'and dk/dv kernels). 0 = unset; same clamps as MXTPU_FA_G.')
register('MXTPU_FA_BWD_BQ', int, 0,
         'Explicit flash-attention backward query block size. '
         '0 = unset (tuning DB, then defaults).')
register('MXTPU_FA_BWD_BK', int, 0,
         'Explicit flash-attention backward key block size. '
         '0 = unset (tuning DB, then defaults).')
register('MXTPU_AUTOTUNE_DIR', str, '',
         'Directory of the kernel-autotuner tuning DB '
         '(mxtpu_autotune.json, atomic JSON keyed by device kind + '
         'kernel + shape signature). When set, _block_sizes consults '
         'the DB winner for each kernel instance (env overrides still '
         'win); populate it with tools/tune_bert_step.py --autotune or '
         'ops.autotune.sweep_flash_attention(). Empty (default): DB '
         'lookups off, built-in defaults apply.')
register('MXTPU_AUTOTUNE_REPS', int, 5,
         'Measured-sweep repetitions per candidate: each surviving '
         'block-shape candidate is AOT-compiled once (compile time '
         'excluded, phases recorded in the compile ledger) and timed '
         'this many times; the median decides the winner.')
register('MXTPU_PALLAS_FFN', _bool, False,
         'Route the BERT FFN1 GELU+bias epilogue through the fused '
         'Pallas matmul kernel (ops/pallas_ffn.py) when a TPU is '
         'present and the hidden/intermediate dims are multiples of '
         '128. Default: the XLA path (flag-gated until measured '
         'on-chip, like MXTPU_PALLAS_LN).')


def _remat_policy(s):
    """MXTPU_REMAT value -> policy name: none (save everything XLA
    wants), layer (save only matmul outputs), aggressive (save nothing
    — recompute the whole forward in backward)."""
    raw = str(s).strip().lower()
    if raw in ('', '0', 'off', 'false', 'no', 'n', 'none', 'disabled'):
        return 'none'
    if raw in ('layer', '1', 'on', 'true', 'yes', 'y'):
        return 'layer'
    if raw in ('aggressive', 'full', '2'):
        return 'aggressive'
    raise ValueError(f"MXTPU_REMAT={s!r}: expected none (default), "
                     f"layer, or aggressive")


register('MXTPU_REMAT', _remat_policy, 'none',
         "Rematerialization policy of the sharded train step's forward "
         "(parallel/step.py): 'none' (default) keeps XLA's own choice "
         'of saved activations (under ZeRO-3 the gathered params are '
         'still always recomputed, never kept); '
         "'layer' wraps the forward in jax.checkpoint saving only "
         'matmul outputs without batch dims '
         '(dots_with_no_batch_dims_saveable — the classic per-layer '
         "checkpoint spend: ~1 extra forward of FLOPs for O(layers) "
         "activation memory); 'aggressive' saves nothing "
         '(nothing_saveable — minimum HBM, maximum recompute). '
         'Sweep + HBM cross-validation: tools/tune_bert_step.py '
         '--autotune.')

# sparse embedding fast path (ISSUE 19) — parallel/step.py RowSparse
# gradients + live-rows-only optimizer updates
register('MXTPU_SPARSE', _bool, True,
         'Enable the RowSparse fast path in the sharded train step: '
         "parameters declared grad_stype='row_sparse' (Embedding("
         'sparse_grad=True)) backpropagate (unique row ids, row-block '
         'values) instead of a dense table-shaped gradient, and the '
         'optimizer updates only the gathered live rows inside the one '
         'pjit step. Off: such tables fall back to the dense path '
         '(identical trajectories under exact mode, see '
         'MXTPU_SPARSE_EXACT).')
register('MXTPU_SPARSE_ROWS', int, 0,
         'Per-table live-row budget ceiling for the sparse fast path. '
         "A table whose worst-case unique-row budget (min(batch ids, "
         'vocab), discovered at trace time) exceeds this falls back to '
         'the dense path — the sparse win only exists when the budget '
         'is well under the vocab. 0 (default) = no ceiling.')
register('MXTPU_SPARSE_EXACT', _bool, False,
         'Force EXACT (non-lazy) sparse semantics: the deduped row '
         'block densifies into a table-shaped gradient and the regular '
         'dense optimizer kernel runs — bit-identical trajectories to '
         'the dense path (the parity oracle; ref lazy_update=False). '
         'Default off = lazy semantics per the reference: momentum/'
         'Adam moments of absent rows stay frozen and weight decay '
         'applies only to live rows.')
register('MXTPU_SPARSE_TABLE_AXIS', str, '',
         "Mesh axis name to model-parallel-shard row_sparse embedding "
         "tables over (e.g. 'tp'): the table rows shard P(axis) and "
         'XLA inserts the all-to-all feature exchange for ids that '
         'hash to remote shards. Tables whose vocab does not divide '
         'the axis extent keep a replicated compute copy and shard '
         "only their fp32 state over ZeRO's flat padded stores. "
         'Empty (default) = tables replicate like other params.')
