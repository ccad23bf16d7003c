"""Shared contract data for the lint rules and the check_* wrappers.

This is the single home of every string-keyed contract the package
relies on reviewers remembering: telemetry metric names (formerly the
private table in check_telemetry_names.py), trace span names, and the
hot-path roots the host-sync rule measures reachability from. The
fault-site registry is NOT duplicated here — resilience/faults.py's
``_SITES`` dict is parsed from its AST so the code stays the registry.
"""
from __future__ import annotations

import re

# ---------------------------------------------------------------------------
# telemetry metric names (registry-drift rule; check_telemetry_names.py
# re-exports these so external callers keep working)
# ---------------------------------------------------------------------------

NAME_RE = re.compile(r'^mxnet_tpu_[a-z][a-z0-9_]*$')

# call name -> metric kind it implies (None: kind-agnostic read)
KINDS = {
    'inc': 'counter', 'counter': 'counter',
    'set_gauge': 'gauge', 'gauge': 'gauge',
    'observe': 'histogram', 'histogram': 'histogram',
    'value': None,
}

# Subsystem contracts: metric sets that dashboards/docs (README,
# PERF_NOTES) reference by name, with their kinds. The lint fails when
# an instrumentation site drops/renames one of these, or adds a new
# metric under the subsystem prefix without declaring it here — keeping
# code, docs and dashboards from drifting apart silently.
SUBSYSTEM_METRICS = {
    'mxnet_tpu_io_': {
        # batch production
        'mxnet_tpu_io_batches_total': 'counter',
        'mxnet_tpu_io_batch_latency_seconds': 'histogram',
        # host-boundary traffic: bytes the python layer pulls out of the
        # pipeline per batch (u8 transport moves ~4x less than f32)
        'mxnet_tpu_io_host_bytes_total': 'counter',
        # zero-copy buffer leases outstanding against the native pipeline
        'mxnet_tpu_io_lease_depth': 'gauge',
        # decode cache (decoded+resized images reused across epochs)
        'mxnet_tpu_io_decode_cache_hits_total': 'counter',
        'mxnet_tpu_io_decode_cache_misses_total': 'counter',
        'mxnet_tpu_io_decode_cache_bytes': 'gauge',
        # decode-prefetch health (PrefetchingIter)
        'mxnet_tpu_io_prefetch_miss_total': 'counter',
        'mxnet_tpu_io_prefetch_stall_seconds_total': 'counter',
        # device prefetch: batches staged on device ahead of the
        # consumer, and the dispatch-to-consume window each host->device
        # copy had to overlap compute in
        'mxnet_tpu_io_device_prefetch_depth': 'gauge',
        'mxnet_tpu_io_h2d_overlap_seconds_total': 'counter',
        # corrupt/truncated records silently substituted under
        # MXNET_TPU_IO_CORRUPT_POLICY=skip (error-policy raises
        # DataError and counts nothing)
        'mxnet_tpu_io_corrupt_records_total': 'counter',
    },
    'mxnet_tpu_resilience_': {
        # fault injection: every armed-site firing, by site + kind
        'mxnet_tpu_resilience_faults_injected_total': 'counter',
        # bounded retry/backoff helper (checkpoint writes, ...), by site
        'mxnet_tpu_resilience_retries_total': 'counter',
        # non-finite guard: bad (skipped-on-device) steps, rollbacks to
        # the last committed checkpoint, and how long recovery took
        'mxnet_tpu_resilience_bad_steps_total': 'counter',
        'mxnet_tpu_resilience_rollbacks_total': 'counter',
        'mxnet_tpu_resilience_last_rollback_step': 'gauge',
        'mxnet_tpu_resilience_recovery_seconds': 'histogram',
        # step watchdog stall dumps and DataLoader worker respawns
        'mxnet_tpu_resilience_watchdog_stalls_total': 'counter',
        'mxnet_tpu_resilience_worker_respawns_total': 'counter',
    },
    'mxnet_tpu_comm_': {
        # collective traffic accounting (ZeRO / GSPMD dp path):
        # ring-algorithm wire bytes per device by collective kind
        # (reduce_scatter / all_gather / all_reduce / broadcast /
        # state_scatter / param_scatter) and mesh axis. The GSPMD step
        # counters additionally carry a `stage` label (off / zero1 /
        # zero3) separating the ZeRO-1 writeback gather from the ZeRO-3
        # per-layer on-use gathers: ZeRO-1 must show the SAME total
        # bytes as the replicated update while the optimizer-state
        # gauge drops to ~1/dp; ZeRO-3 adds the param regather wire
        # bytes while the param gauge also drops to ~1/dp. The per-step
        # trace instants (`comm.all_gather`) carry per-layer bytes via
        # a `layer` arg for gather-vs-compute overlap attribution.
        'mxnet_tpu_comm_collective_bytes_total': 'counter',
        'mxnet_tpu_comm_collectives_total': 'counter',
        # optimizer state (fp32 masters + moments) held by ONE device
        'mxnet_tpu_comm_opt_state_bytes_per_device': 'gauge',
        # persistent params (compute dtype) held by ONE device — the
        # ZeRO-3 1/dp param residency is auditable against it
        'mxnet_tpu_comm_param_bytes_per_device': 'gauge',
        # error-feedback gradient compression (ISSUE 12): encoded bytes
        # the compressed exchange actually carries per step (by codec +
        # hop axis — under the hierarchical decomposition that is the
        # cross-host DCN hop, whose collective_bytes entries already
        # count the encoded size), the per-device residual state the
        # error feedback persists, and the raw/encoded wire ratio
        'mxnet_tpu_comm_compressed_bytes_total': 'counter',
        'mxnet_tpu_comm_residual_bytes_per_device': 'gauge',
        'mxnet_tpu_comm_compression_ratio': 'gauge',
    },
    'mxnet_tpu_elastic_': {
        # elastic multi-host training (membership side channel +
        # commit/re-form/resume controller): heartbeat round-trips
        # sent, peers declared lost past MXTPU_PEER_DEADLINE_SECONDS,
        # completed mesh re-forms, the survivor world size after the
        # newest re-form, and the detect->commit->teardown->restore
        # wall time of each re-form (the MTTR the CPU drill records)
        'mxnet_tpu_elastic_heartbeats_total': 'counter',
        'mxnet_tpu_elastic_peer_losses_total': 'counter',
        'mxnet_tpu_elastic_reforms_total': 'counter',
        'mxnet_tpu_elastic_last_world_size': 'gauge',
        'mxnet_tpu_elastic_reform_seconds': 'histogram',
        # elastic scale-UP (ISSUE 20): JOIN announcements received,
        # the quiesce->rendezvous->restore wall time of each admission
        # re-form, and autoscaler decisions by kind
        # (evict / request_capacity / admit)
        'mxnet_tpu_elastic_joins_total': 'counter',
        'mxnet_tpu_elastic_admission_seconds': 'histogram',
        'mxnet_tpu_elastic_autoscaler_decisions_total': 'counter',
    },
    'mxnet_tpu_trace_': {
        # step-span tracer (MXTPU_TRACE): spans recorded, whole spans
        # dropped by ring overwrite, events currently buffered across
        # every thread ring, and flight-recorder post-mortem dumps
        'mxnet_tpu_trace_spans_total': 'counter',
        'mxnet_tpu_trace_dropped_spans_total': 'counter',
        'mxnet_tpu_trace_ring_depth': 'gauge',
        'mxnet_tpu_trace_flight_dumps_total': 'counter',
    },
    'mxnet_tpu_fleet_': {
        # fleet observability (ISSUE 13): the coordinator's merged view
        # of every rank's heartbeat-piggybacked telemetry snapshot.
        # Per-rank gauges carry a `rank` label; skew is against the
        # fleet median of the last reported step wall times; the
        # comm-bytes counter mirrors each rank's per-hop accounting
        # (axis label) so a fleet dashboard reads one endpoint.
        'mxnet_tpu_fleet_ranks': 'gauge',
        'mxnet_tpu_fleet_last_step': 'gauge',
        'mxnet_tpu_fleet_step_ms': 'gauge',
        'mxnet_tpu_fleet_step_skew_ms': 'gauge',
        'mxnet_tpu_fleet_step_seconds': 'histogram',
        'mxnet_tpu_fleet_loss': 'gauge',
        'mxnet_tpu_fleet_clock_offset_seconds': 'gauge',
        'mxnet_tpu_fleet_snapshot_age_seconds': 'gauge',
        'mxnet_tpu_fleet_snapshots_total': 'counter',
        # mirrors each rank's own cumulative
        # mxnet_tpu_comm_collective_bytes_total by hop axis (gauge: the
        # value IS the remote counter's, so the two scrapes agree
        # exactly — dryrun_multichip asserts it)
        'mxnet_tpu_fleet_comm_bytes': 'gauge',
        # mirrors each rank's live device-memory watermark from the
        # heartbeat-piggybacked memory snapshot (ISSUE 14) — the number
        # the HBM-imbalance detector compares across ranks
        'mxnet_tpu_fleet_memory_bytes': 'gauge',
        # streaming anomaly detectors (kind + rank labels): straggler
        # skew / step-time regression / loss spike / comm imbalance
        'mxnet_tpu_fleet_anomalies_total': 'counter',
    },
    'mxnet_tpu_memory_': {
        # memory observability (ISSUE 14): per-step watermark sampling
        # (MXTPU_MEMORY) — live/peak device bytes by source
        # ('memory_stats' where the backend exposes its allocator,
        # 'fallback' = deterministic per-device sum over the tracked
        # live arrays), host RSS, and the per-pool residency breakdown
        # (params / optimizer_state / residuals / io_leases) the
        # memory_analysis() bucket table reads
        'mxnet_tpu_memory_device_bytes': 'gauge',
        'mxnet_tpu_memory_device_peak_bytes': 'gauge',
        'mxnet_tpu_memory_host_rss_bytes': 'gauge',
        'mxnet_tpu_memory_pool_bytes': 'gauge',
        'mxnet_tpu_memory_samples_total': 'counter',
        # step-over-step growth detector latches + OOM forensics dumps
        # (by dispatch site)
        'mxnet_tpu_memory_leaks_suspected_total': 'counter',
        'mxnet_tpu_memory_oom_dumps_total': 'counter',
    },
    'mxnet_tpu_compile_': {
        # compilation observability (ISSUE 16): the per-site compile
        # counters + the episode-latched recompile detector (PR 1,
        # upgraded), the gluon CachedOp variant-cache hits, and the
        # compile ledger's phase split (trace/lower/backend, attributed
        # via jax.monitoring to the open build site)
        'mxnet_tpu_compile_total': 'counter',
        'mxnet_tpu_compile_seconds_total': 'counter',
        'mxnet_tpu_compile_cache_hits_total': 'counter',
        'mxnet_tpu_compile_phase_seconds_total': 'counter',
        # recompile forensics: one increment per churning axis kind
        # (shape/dtype/sharding/donation/flag/arity, by site) when a
        # logically-same site recompiles with a different signature
        'mxnet_tpu_compile_churn_axes': 'counter',
        # persistent XLA compilation cache (MXTPU_COMPILE_CACHE_DIR):
        # jax's own hit/miss events, the ledger-estimated cold-compile
        # seconds a warm process avoided, and the cache dir's on-disk
        # footprint
        'mxnet_tpu_compile_persistent_cache_hits_total': 'counter',
        'mxnet_tpu_compile_persistent_cache_misses_total': 'counter',
        'mxnet_tpu_compile_persistent_cache_saved_seconds_total':
            'counter',
        'mxnet_tpu_compile_persistent_cache_bytes': 'gauge',
        # ledger bookkeeping: in-memory ring depth + failed JSONL
        # appends (the ledger must never take down training)
        'mxnet_tpu_compile_ledger_entries': 'gauge',
        'mxnet_tpu_compile_ledger_errors_total': 'counter',
    },
    'mxnet_tpu_checkpoint_': {
        'mxnet_tpu_checkpoint_save_seconds': 'histogram',
        'mxnet_tpu_checkpoint_blocked_seconds': 'histogram',
        'mxnet_tpu_checkpoint_restore_seconds': 'histogram',
        'mxnet_tpu_checkpoint_bytes': 'gauge',
        'mxnet_tpu_checkpoint_last_step': 'gauge',
        'mxnet_tpu_checkpoint_saves_total': 'counter',
        'mxnet_tpu_checkpoint_gc_total': 'counter',
        'mxnet_tpu_checkpoint_corrupt_total': 'counter',
        # survivability layer (ISSUE 10): peer replication of committed
        # steps over the membership side channel — successful pushes /
        # wire bytes / bounded-retry-exhausted failures (by peer rank),
        # local-commit-to-replica-commit lag, any-replica restore
        # fetches, and replica retirements (retention GC on the owner,
        # replica_delete on the receiver, orphan GC on a scrub pass)
        'mxnet_tpu_checkpoint_replica_pushes_total': 'counter',
        'mxnet_tpu_checkpoint_replica_bytes_total': 'counter',
        'mxnet_tpu_checkpoint_replica_failures_total': 'counter',
        'mxnet_tpu_checkpoint_replica_lag_seconds': 'histogram',
        'mxnet_tpu_checkpoint_replica_fetches_total': 'counter',
        'mxnet_tpu_checkpoint_replica_gc_total': 'counter',
        # background integrity scrubber: passes completed, committed
        # steps (local or hosted) that failed their re-hash and were
        # quarantined, steps repaired bit-identical from a healthy
        # replica, and the wall cost of one pass
        'mxnet_tpu_checkpoint_scrub_passes_total': 'counter',
        'mxnet_tpu_checkpoint_scrub_corrupt_total': 'counter',
        'mxnet_tpu_checkpoint_scrub_repaired_total': 'counter',
        'mxnet_tpu_checkpoint_scrub_seconds': 'histogram',
    },
    'mxnet_tpu_serving_': {
        # inference serving (ISSUE 17): the continuous-batching engine's
        # throughput counters (requests admitted, batches dispatched,
        # per-bucket hit counts) and its live queue depth
        'mxnet_tpu_serving_requests_total': 'counter',
        'mxnet_tpu_serving_batches_total': 'counter',
        'mxnet_tpu_serving_bucket_hits_total': 'counter',
        'mxnet_tpu_serving_queue_depth': 'gauge',
        # batch quality + latency: fill ratio (rows occupied / bucket
        # capacity — padding waste is 1 - fill) and end-to-end request
        # latency through the engine
        'mxnet_tpu_serving_batch_fill_ratio': 'histogram',
        'mxnet_tpu_serving_latency_seconds': 'histogram',
        # load shedding (queue overflow / admission control / OOM guard,
        # by reason) and lifecycle events: replicas that completed a
        # graceful drain, router-side ejections (by rank)
        'mxnet_tpu_serving_shed_total': 'counter',
        'mxnet_tpu_serving_drained_replicas_total': 'counter',
        'mxnet_tpu_serving_ejections_total': 'counter',
        # AOT warmup: bucket-grid size pre-built at startup and the wall
        # seconds the pass cost (near-zero when the persistent XLA cache
        # is warm)
        'mxnet_tpu_serving_warmup_buckets': 'gauge',
        'mxnet_tpu_serving_warmup_seconds': 'gauge',
    },
    'mxnet_tpu_autotune_': {
        # Pallas kernel autotuner (ISSUE 18): candidates rejected by the
        # static Mosaic legality / VMEM-budget check vs. candidates that
        # made it to the compile+time stage, the wall seconds a sweep
        # cost, and tuning-DB consultation outcomes from _block_sizes
        'mxnet_tpu_autotune_candidates_pruned_total': 'counter',
        'mxnet_tpu_autotune_candidates_timed_total': 'counter',
        'mxnet_tpu_autotune_sweep_seconds_total': 'counter',
        'mxnet_tpu_autotune_db_hits_total': 'counter',
        'mxnet_tpu_autotune_db_misses_total': 'counter',
    },
    'mxnet_tpu_sparse_': {
        # RowSparse embedding fast path (ISSUE 19): per-table live-row
        # count of the previous step (host-read one step deferred), the
        # cumulative row-block gradient payload bytes, the id dedup
        # factor (flat ids per step / unique live rows), and the
        # analytic wire bytes of the row-block exchange per mesh hop
        'mxnet_tpu_sparse_live_rows': 'gauge',
        'mxnet_tpu_sparse_row_bytes_total': 'counter',
        'mxnet_tpu_sparse_dedup_ratio': 'gauge',
        'mxnet_tpu_sparse_exchange_bytes_total': 'counter',
    },
}

# ---------------------------------------------------------------------------
# trace span/instant names (registry-drift rule). A span name not in
# this contract is either a typo or a new subsystem the attribution
# bucketing (telemetry/attribution.py) and docs have never heard of —
# declare it here when adding the instrumentation.
# ---------------------------------------------------------------------------

SPAN_NAMES = frozenset({
    # io pipeline
    'io.batch', 'io.decode', 'io.lease', 'io.prefetch_wait', 'io.wait',
    'io.worker_fetch',
    # host->device staging
    'h2d.batch_put', 'h2d.device_put', 'h2d.normalize',
    'h2d.param_place', 'h2d.pin',
    # step lifecycle
    'step.dispatch', 'step.compiled', 'step.gather',
    # collectives (spans on the gluon path, per-step instants on the
    # GSPMD path carrying analytic ring-wire bytes)
    # (the GSPMD instants interpolate the kind: f'comm.{kind}' — the
    # static rule checks literals, the kind set is declared here)
    'comm.allreduce', 'comm.broadcast', 'comm.all_gather',
    'comm.reduce_scatter', 'comm.all_reduce', 'comm.state_scatter',
    'comm.param_scatter',
    # error-feedback gradient compression: per-step instants carrying
    # the encoded (compress) and decoded-equivalent (decompress) bytes
    # of the cross-host gradient exchange, with codec + hop labels
    'comm.compress', 'comm.decompress',
    # optimizer
    'optimizer.update', 'optimizer.fused', 'optimizer.state_init',
    # checkpointing
    'checkpoint.snapshot', 'checkpoint.write', 'checkpoint.restore',
    # host syncs made visible
    'sync.lease_drain',
    # resilience (elastic.admit: the scale-up admission re-form window,
    # survivors and joiner alike — ISSUE 20)
    'guard.rollback', 'elastic.reform', 'elastic.admit',
    # compilation observability (ISSUE 16): the build-site window span
    # plus the jax.monitoring-attributed phase spans (emitted
    # interpolated as f'compile.{phase}' — the static rule checks
    # literals, the phase set is declared here)
    'compile.build', 'compile.trace', 'compile.lower', 'compile.backend',
    # inference serving (ISSUE 17): the batched bucket dispatch and the
    # server-side predict window (parse -> batch -> respond)
    'serving.dispatch', 'serving.predict',
    # kernel autotuner (ISSUE 18): one sweep = enumerate legal
    # candidates -> compile+time survivors -> persist the winner
    'autotune.sweep',
    # RowSparse embedding fast path (ISSUE 19): per-step instants for
    # the row-block gradient exchange (analytic wire bytes per hop,
    # incl. the table-axis all-to-all) and the live-rows-only optimizer
    # update (mode = lazy | exact)
    'sparse.exchange', 'optimizer.sparse_update', 'comm.all_to_all',
})

# ---------------------------------------------------------------------------
# flight-recorder note kinds (registry-drift rule). A ``flight.note``
# literal not in this contract is either a typo or a new event class
# the post-mortem tooling (watchdog reports, fleet dashboards, docs)
# has never heard of — declare it here when adding the emission site.
# The fleet detector notes are emitted through a variable (the
# detector return tuples in telemetry/fleet.py), so they are declared
# here as the canonical enumeration.
# ---------------------------------------------------------------------------

FLIGHT_NOTE_NAMES = frozenset({
    # fault injection + non-finite guard
    'fault', 'guard.bad_step', 'guard.rollback',
    # watchdog
    'watchdog.stall',
    # elastic membership / re-form controller (+ the ISSUE 20 scale-up
    # path: JOIN announcements, admission re-forms, and the
    # autoscaler's decision ledger)
    'elastic.peer_loss', 'elastic.peer_loss_suspected',
    'elastic.preempt_exit', 'elastic.reform',
    'elastic.join', 'elastic.admit', 'autoscaler.decision',
    # checkpoint replication + scrubbing
    'checkpoint.replicated', 'checkpoint.replica_failed',
    'checkpoint.replica_dropped', 'checkpoint.replica_restore',
    'checkpoint.scrub', 'checkpoint.repair',
    # fleet anomaly detectors (ISSUE 13)
    'fleet.straggler', 'fleet.step_regression', 'fleet.loss_spike',
    'fleet.comm_imbalance',
    # memory observability (ISSUE 14): the leak detector's latched
    # note, the OOM forensics dump marker, and the coordinator-side
    # per-rank HBM-imbalance flag
    'memory.leak_suspected', 'memory.oom', 'fleet.memory_imbalance',
    # compilation observability (ISSUE 16): the recompile-forensics
    # note naming the churning signature axis, and the persistent-cache
    # hit marker with ledger-estimated saved seconds
    'compile.recompiled', 'compile.cache_hit',
    # inference serving (ISSUE 17): shed decisions (with reason), the
    # engine watchdog's stuck-request marker, replica drain/reload
    # lifecycle, router ejections, and fleet-wide weight pushes
    'serving.shed', 'serving.stuck', 'serving.drain', 'serving.reload',
    'serving.eject', 'serving.weight_push',
})

# ---------------------------------------------------------------------------
# hot-path roots (host-sync rule): the dispatch entry points a training
# step flows through. Reachability is measured from these; a host sync
# inside the cone (and inside a hot-path module) blocks the step
# pipeline and must either move, defer, or carry a reasoned
# `# lint: host-sync-ok` marker.
# ---------------------------------------------------------------------------

# (relpath suffix, qualname glob)
HOT_PATH_ROOTS = [
    ('parallel/step.py', 'ShardedTrainStep.__call__'),
    ('parallel/step.py', 'ShardedTrainStep._call_traced'),
    ('gluon/trainer.py', 'Trainer.step'),
    ('gluon/trainer.py', 'Trainer.update'),
    ('gluon/trainer.py', 'Trainer._update'),
    ('gluon/trainer.py', 'Trainer._allreduce_grads'),
    ('gluon/trainer.py', 'Trainer._fused_apply'),
    # span/flight recording runs inside the step on the hot threads
    ('telemetry/trace.py', 'span'),
    ('telemetry/trace.py', 'instant'),
    ('telemetry/trace.py', 'complete'),
    ('telemetry/flight.py', 'FlightRecorder.record_step'),
    ('telemetry/flight.py', 'FlightRecorder.note'),
    ('telemetry/flight.py', 'FlightRecorder.annotate_last'),
]

# host-sync findings are reported only inside these modules (the cone
# from the roots also reaches cold paths — checkpoint restore, error
# formatting — where a host read is fine)
HOT_PATH_FILES = (
    'parallel/step.py',
    'parallel/layout.py',       # the step's placement helpers (put_batch)
    'parallel/exchange.py',     # record_wire runs in every dispatch
    'parallel/collectives.py',
    'gluon/trainer.py',
    'gluon/data/dataloader.py',
    'telemetry/trace.py',
    'telemetry/flight.py',
    'io/io.py',
)

# ---------------------------------------------------------------------------
# hot-lock roots (blocking-under-lock rule): paths that must never wait
# on a contended lock for long — the step-dispatch cone (the same roots
# the host-sync rule measures from) plus the latency-sensitive service
# loops: heartbeat handling (a blocked beat reads as a PEER LOSS to the
# whole fleet) and the metrics/health scrape path (a blocked handler
# slot is how the PR 12 slow-loris class started). A lock acquired
# anywhere in these cones is a HOT lock; blocking unboundedly while
# holding one stalls the hot path for the duration.
# ---------------------------------------------------------------------------

HOT_LOCK_ROOTS = HOT_PATH_ROOTS + [
    # membership heartbeat send + coordinator-side beat handling
    ('parallel/dist.py', 'Membership._beat_loop'),
    ('parallel/dist.py', 'Membership._handle_locked'),
    # metric scrape / health endpoint handler path
    ('telemetry/server.py', 'TelemetryServer._handle_conn'),
    ('telemetry/server.py', 'TelemetryServer._route'),
]

# ---------------------------------------------------------------------------
# lint-registered blocking callees (blocking-under-lock rule): functions
# KNOWN to block unboundedly that the syntactic predicate cannot see
# (the blocking primitive hides behind a C extension or a retry loop
# with no overall deadline). Calling one of these while holding a hot
# lock is a finding even though the call site looks innocent.
# (relpath suffix, qualname glob) — same shape as the root tables.
# ---------------------------------------------------------------------------

BLOCKING_CALLEES = [
    # jax.distributed client construction blocks until the coordinator
    # answers (dist.init wraps it in bounded retries, but the CALL has
    # no deadline of its own)
    ('parallel/dist.py', '_initialize_once'),
]
