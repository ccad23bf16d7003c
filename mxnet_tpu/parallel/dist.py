"""Multi-process distributed init + launcher + elastic membership.

Ref: tools/launch.py + dmlc tracker (scheduler/server/worker env bootstrap
via DMLC_ROLE / DMLC_PS_ROOT_URI). TPU-native: `jax.distributed.initialize`
replaces the tracker; there are no server processes — every process is a
symmetric worker and collectives ride ICI/DCN.

Env protocol (launch-compatible shape):
  MXNET_TPU_COORDINATOR  host:port of process 0
  MXNET_TPU_NUM_PROCS    total processes
  MXNET_TPU_PROC_ID      this process's rank
(Also accepts the DMLC_* names for drop-in use of reference launch scripts.)

Elastic membership (`MXTPU_ELASTIC=1`, ROADMAP item 4): the ps-lite
tracker's worker-churn awareness has no analog in jax.distributed — a
preempted host wedges every peer inside a collective until the job dies.
The ``Membership`` layer closes that gap on a lightweight TCP side
channel (NEVER the ICI collectives, which are exactly what a lost peer
wedges): rank 0 runs a coordinator thread tracking per-peer heartbeat
ages, every process runs a sender thread beating once per
``MXTPU_HEARTBEAT_SECONDS``, and a peer silent for
``MXTPU_PEER_DEADLINE_SECONDS`` is declared LOST — the signal
``resilience.ElasticController`` turns into commit -> re-form -> resume.
"""
from __future__ import annotations

import collections
import json
import logging
import os
import re
import shutil
import socket
import subprocess
import sys
import threading
import time as _time

import jax

from ..base import MXNetError, telem_flags as _telem

_log = logging.getLogger('mxnet_tpu.dist')

_initialized = False
_membership = None
# publication lock for the process-global membership: membership() is
# read from the watchdog/elastic-monitor/endpoint threads while
# start_/stop_membership swap the reference on the main thread. RLock
# by the signal-safety rationale: membership() is reachable from the
# SIGTERM preemption path (manifest `world` metadata).
_membership_lock = threading.RLock()


def _resolve_world(coordinator=None, num_processes=None, process_id=None,
                   need_coordinator=True):
    """One resolution of (coordinator, world, rank) from args/env —
    shared by ``init()`` and ``start_membership()`` so the two can never
    derive different coordinators (the membership side-channel port is
    derived from the coordinator's). MXNET_TPU_* first, the DMLC_*
    drop-in names next. The coordinator (and with it the
    localhost-fallback warning) is only resolved when actually needed —
    a single-process init has nobody to rendezvous with."""
    from .. import config as _config
    num_processes = num_processes \
        or _config.get('MXNET_TPU_NUM_PROCS') \
        or int(os.environ.get('DMLC_NUM_WORKER', '1'))
    if process_id is None:
        pid = _config.get('MXNET_TPU_PROC_ID')
        process_id = pid if pid >= 0 \
            else int(os.environ.get('DMLC_WORKER_ID', '0'))
    if need_coordinator:
        coordinator = coordinator \
            or _config.get('MXNET_TPU_COORDINATOR') \
            or _dmlc_coordinator()
    return coordinator, int(num_processes), int(process_id)


def init(coordinator=None, num_processes=None, process_id=None,
         local_device_ids=None):
    """Initialize jax.distributed from args or env.

    Transient "coordinator not yet listening" races (workers regularly
    start before rank 0's service binds) get a bounded retry with
    exponential backoff (``MXTPU_DIST_INIT_RETRIES``) instead of a fatal
    error. With ``MXTPU_ELASTIC=1`` the membership side channel starts
    here too (see ``Membership``)."""
    global _initialized
    if _initialized:
        return
    from .. import config as _config
    _, num_processes, process_id = _resolve_world(
        None, num_processes, process_id, need_coordinator=False)
    elastic = bool(_config.get('MXTPU_ELASTIC'))
    if num_processes > 1 or elastic:
        # only now is a coordinator address needed (and only now may
        # the localhost-fallback warning fire)
        coordinator, _, _ = _resolve_world(
            coordinator, num_processes, process_id)
    if num_processes > 1:
        from ..resilience.retry import retry_call
        target = _initialize_once if elastic else \
            jax.distributed.initialize

        def _attempt(**kw):
            # jaxlib surfaces BOTH transient connect races (grpc
            # DEADLINE_EXCEEDED / UNAVAILABLE) and permanent mistakes
            # as RuntimeError — classify, so a double init or bad
            # argument fails immediately instead of burning the whole
            # backoff budget behind misleading 'transient' warnings
            try:
                return target(**kw)
            except RuntimeError as e:
                if any(t in str(e) for t in
                       ('only be called once', 'should be defined',
                        'must be defined')):
                    raise MXNetError(
                        f"dist.init: non-transient "
                        f"jax.distributed.initialize failure (not "
                        f"retried): {e}") from e
                raise

        retry_call(
            _attempt,
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
            retries=_config.get('MXTPU_DIST_INIT_RETRIES'),
            backoff_seconds=0.25,
            retry_on=(RuntimeError, ConnectionError, OSError),
            give_up_on=(MXNetError,),
            site='dist.init')
    _initialized = True
    if _config.get('MXTPU_ELASTIC') and _membership is None:
        start_membership(coordinator=coordinator,
                         num_processes=num_processes,
                         process_id=process_id)


_elastic_client = False


def _initialize_once(coordinator_address, num_processes, process_id,
                     local_device_ids=None):
    """Elastic-mode jax.distributed bring-up. Mirrors
    jax._src.distributed.State.initialize but builds the client with the
    knobs the stock wrapper does not expose:

    - ``shutdown_on_destruction=False``: dropping the handle must not
      enter the runtime's shutdown barrier — that barrier waits for
      EVERY peer, the dead one included, which is exactly the wedge
      elastic teardown escapes (``shutdown()`` above relies on this).
    - ``shutdown_timeout=5``: if the orderly barrier IS entered (healthy
      world), give up in seconds, not the 5-minute default.
    """
    from jax._src import config as _jax_config
    from jax._src import distributed as _jd
    from jax._src.lib import xla_extension
    state = _jd.global_state
    if state.client is not None:
        return
    if isinstance(local_device_ids, int):
        local_device_ids = [local_device_ids]
    if local_device_ids:
        # same per-process device pinning stock initialize applies
        visible = ','.join(str(x) for x in local_device_ids)
        _jax_config.update('jax_cuda_visible_devices', visible)
        _jax_config.update('jax_rocm_visible_devices', visible)
    state.coordinator_address = coordinator_address
    bind = '[::]:' + coordinator_address.rsplit(':', 1)[1]
    if process_id == 0 and state.service is None:
        state.service = xla_extension.get_distributed_runtime_service(
            bind, num_processes)
    state.num_processes = num_processes
    state.process_id = process_id
    global _elastic_client
    client = xla_extension.get_distributed_runtime_client(
        coordinator_address, process_id, init_timeout=300,
        shutdown_timeout=5, shutdown_on_destruction=False,
        use_compression=True)
    client.connect()
    state.client = client
    _elastic_client = True
    try:
        state.initialize_preemption_sync_manager()
    except Exception:
        pass


def shutdown(timeout=5.0):
    """Tear down jax.distributed (elastic re-form path).

    The runtime's orderly ``client.shutdown()`` is a BARRIER over every
    peer — including the dead one — and blocks until they all arrive:
    exactly the wedge elastic teardown exists to escape. So with a dead
    peer the elastic path never enters it: the client handle (created
    with ``shutdown_on_destruction=False`` by ``_initialize_once``) is
    dropped, the coordination service is stopped on a daemon thread with
    a bounded join (stopping it aborts the barrier server-side), and the
    distributed bookkeeping is reset so ``process_count()`` and jax's
    own atexit hook see a clean single-process state. Non-elastic
    clients (stock ``jax.distributed.initialize``) still get the orderly
    shutdown, also bounded. Returns True when the teardown completed
    within ``timeout``."""
    global _initialized
    _initialized = False
    try:
        state = jax._src.distributed.global_state
    except Exception:
        return True
    if state.client is None and state.service is None:
        return True
    # hand the live handles to the teardown thread in a box, then reset
    # the bookkeeping FIRST: jax's atexit clean_up consults these same
    # fields — once they are None it cannot re-enter the barrier
    box = [state.client, state.service]
    state.client = None
    state.service = None
    state.process_id = 0
    state.num_processes = 1
    state.preemption_sync_manager = None
    state.coordinator_address = None
    done = threading.Event()
    elastic = _elastic_client

    def _do():
        client, service = box[0], box[1]
        try:
            if not elastic and client is not None:
                client.shutdown()     # orderly barrier: healthy world
            # elastic: NEVER enter the shutdown barrier (it waits for
            # the dead peer) — drop the last client reference instead;
            # shutdown_on_destruction=False makes the destructor stop
            # the agent threads without any peer rendezvous, measured
            # ~20 ms, after which the service stops cleanly
            box[0] = client = None
            if service is not None:
                service.shutdown()
        except Exception as e:
            _log.warning("distributed teardown: %r", e)
        finally:
            box[1] = None
            done.set()

    threading.Thread(target=_do, daemon=True,
                     name='mxtpu-dist-shutdown').start()
    if not done.wait(timeout):
        _log.warning(
            "distributed teardown did not finish within %.1fs; "
            "abandoning it on a daemon thread (bookkeeping already "
            "reset — survivors keep making progress)", timeout)
        return False
    return True


def reinit(coordinator, num_processes, process_id,
           local_device_ids=None):
    """Re-initialize jax.distributed at a NEW world size (after
    ``shutdown()``) — the re-form half of elastic training. World size 1
    needs no distributed runtime at all."""
    global _initialized
    _initialized = False
    if num_processes <= 1:
        _initialized = True
        return
    init(coordinator=coordinator, num_processes=num_processes,
         process_id=process_id, local_device_ids=local_device_ids)


def _dmlc_coordinator():
    uri = os.environ.get('DMLC_PS_ROOT_URI')
    port = os.environ.get('DMLC_PS_ROOT_PORT', '9000')
    if uri:
        return f"{uri}:{port}"
    _log.warning(
        "dist.init: no coordinator address configured — looked for "
        "MXNET_TPU_COORDINATOR, then DMLC_PS_ROOT_URI[:DMLC_PS_ROOT_PORT] "
        "— falling back to localhost:12345 (fine single-host; multi-host "
        "workers will hang at initialize until one of those env vars "
        "names rank 0)")
    return 'localhost:12345'


def rank():
    return jax.process_index()


def num_workers():
    return jax.process_count()


def host_topology(devices):
    """Group ``devices`` (in order) into per-host runs by their owning
    process: ``[(process_index, [device, ...]), ...]``. This is the
    hierarchy query the compressed-collective path builds its
    (cross-host, intra-host) dp decomposition from — the same
    host-level world the elastic membership layer heartbeats over (one
    membership rank per jax process). Contiguous runs only: a device
    order that interleaves processes yields more groups than processes,
    which ``dp_host_split`` treats as "no clean hierarchy"."""
    groups = []
    for d in devices:
        p = getattr(d, 'process_index', 0)
        if groups and groups[-1][0] == p:
            groups[-1][1].append(d)
        else:
            groups.append((p, [d]))
    return groups


def dp_host_split(devices, force=None):
    """(n_hosts, devices_per_host) decomposition of a dp-axis device
    run, or ``(1, len(devices))`` when no clean hierarchy exists.

    ``force`` (or the ``MXTPU_HIERARCHICAL_DP`` knob when None):
    0 auto-detects from the device->process topology via
    ``host_topology``; 1 forces flat; N>=2 forces N equal contiguous
    groups (CPU simulation — single-process meshes have no real host
    boundary to discover). Auto-detection requires equal-size
    contiguous per-process runs; anything else falls back flat rather
    than build a lopsided hierarchy."""
    from .. import config as _config
    n = len(devices)
    if force is None:
        force = int(_config.get('MXTPU_HIERARCHICAL_DP') or 0)
    force = int(force)
    if force == 1 or n <= 1:
        return 1, n
    if force >= 2:
        if n % force != 0:
            raise MXNetError(
                f"MXTPU_HIERARCHICAL_DP={force}: the dp axis has {n} "
                f"devices, not divisible into {force} equal host "
                f"groups — pick a divisor of {n} or 0 (auto).")
        return force, n // force
    groups = host_topology(devices)
    sizes = {len(ds) for _p, ds in groups}
    procs = {p for p, _ds in groups}
    if len(groups) <= 1 or len(sizes) != 1 or len(procs) != len(groups):
        return 1, n
    return len(groups), n // len(groups)


# ---------------------------------------------------------------------------
# elastic membership side channel
# ---------------------------------------------------------------------------

def _elastic_port(coordinator=None):
    """Side-channel port: MXTPU_ELASTIC_PORT, else jax coordinator port
    + 1000 (keeps parallel jobs on one host from colliding)."""
    from .. import config as _config
    port = _config.get('MXTPU_ELASTIC_PORT')
    if port:
        return int(port)
    base = 12345
    coordinator = coordinator or _config.get('MXNET_TPU_COORDINATOR')
    if coordinator and ':' in coordinator:
        try:
            base = int(coordinator.rsplit(':', 1)[1])
        except ValueError:
            pass
    return base + 1000


# the reserved barrier tag of the scale-up admission rendezvous: its
# completion set includes the PENDING joiners (not just the alive
# ranks), and completing it is the admission point — the coordinator
# promotes every pending joiner into the alive set atomically with the
# generation bump (see Membership._handle_locked)
ADMIT_TAG = 'admit'


class Membership:
    """Heartbeat-tracked peer membership over a TCP side channel.

    Rank 0 is the membership coordinator: a server thread answers one
    JSON line per connection (``{'op': 'beat'|'leave'|'view'|'barrier',
    'rank': r, ...}``) with the current view (``{'world', 'alive',
    'ages', 'lost', 'left'}``). Every rank — 0 included — runs a sender
    thread that beats once per ``heartbeat_seconds`` (rank 0 short-
    circuits to a local state update so the coordinator never depends on
    its own socket). A peer whose heartbeat age exceeds
    ``deadline_seconds`` is LOST; a peer that said goodbye (``leave()``,
    the SIGTERM path) is LEFT — departed but not a failure.

    The side channel is deliberately not the collective fabric: a peer
    wedged inside an ICI collective still heartbeats (the sender is a
    daemon thread), while a SIGKILLed/preempted peer goes silent on both
    — which is exactly the distinction the stall classifier needs
    (``resilience.elastic.stall_verdict``)."""

    def __init__(self, rank, world, coordinator_host='127.0.0.1',
                 port=None, heartbeat_seconds=None, deadline_seconds=None,
                 start=True):
        from .. import config as _config
        self.rank = int(rank)
        self.world = int(world)
        self.coordinator_host = coordinator_host
        self.port = int(port) if port else _elastic_port()
        self.heartbeat_seconds = float(
            heartbeat_seconds if heartbeat_seconds is not None
            else _config.get('MXTPU_HEARTBEAT_SECONDS'))
        self.deadline_seconds = float(
            deadline_seconds if deadline_seconds is not None
            else _config.get('MXTPU_PEER_DEADLINE_SECONDS'))
        self.is_coordinator = self.rank == 0
        self.current_step = None      # piggybacked on each beat
        # RLock: view()/lost_peers() are reachable from the checkpoint
        # SIGTERM handler (save() records the membership world in the
        # manifest) — a signal landing while THIS thread holds a plain
        # Lock would self-deadlock the preemption save. Critical
        # sections are tiny and never block, so reentrancy is safe.
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._threads = []
        self._server = None
        # fleet-telemetry piggyback (ISSUE 13): a provider callable
        # yields a compact snapshot dict attached to each beat; the
        # coordinator keeps the newest per rank and hands each one to
        # on_snapshot (the fleet monitor) OUTSIDE the membership lock
        # (and, for remote beats, AFTER the reply is written — the
        # hook must not inflate the sender's measured RTT).
        # on_peers_removed mirrors remove_peers into the monitor so a
        # departed rank cannot haunt the straggler verdict forever.
        self.telemetry_provider = None
        self.on_snapshot = None
        self.on_peers_removed = None
        # coordinator-side: a callable returning the current flagged
        # straggler summary (or None), attached to every reply — so
        # WORKER watchdogs can name the suspect too, not just rank 0
        # ((world-1)/world of wedges happen on a non-coordinator)
        self.verdict_provider = None
        self._telem = {}              # rank -> {'snap','mono','time'}
        # (rtt, offset, when) samples of this clock vs the
        # coordinator's, one per beat round-trip; the min-RTT sample in
        # the window is the clock_offset() estimate (NTP's intuition:
        # the tightest round-trip bounds the asymmetry error best)
        self._off_samples = collections.deque(maxlen=64)
        # coordinator state (rank 0)
        now = _time.monotonic()
        self._last_beat = {r: now for r in range(self.world)}
        self._steps = {}
        self._left = set()
        # JOIN candidates pending admission (scale-up): rank ->
        # announcement time, with liveness tracked separately in
        # _join_beat so a joiner that dies again BEFORE admission is
        # garbage-collected instead of wedging every future admit
        # rendezvous. Promotion into _last_beat happens only when the
        # admission rendezvous (barrier tag ADMIT_TAG) completes.
        self._joining = {}
        self._join_beat = {}
        self._barriers = {}           # tag -> {rank: nonce} arrived this gen
        self._barrier_gen = {}        # tag -> completed-rendezvous count
        self._barrier_done = {}       # tag -> {rank: (nonce, gen)} latest
        self._barrier_calls = 0
        # sender-side state (every rank)
        self._view = None             # last view dict from the coordinator
        self._last_ok = now           # last successful beat round-trip
        self.send_failures = 0
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        # restartable: stop()/leave() set the event — a re-start (or a
        # become_coordinator promotion) must not spawn threads that see
        # it still set and exit on their first wait
        self._stop.clear()
        if self.is_coordinator and self._server is None:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind(('', self.port))
            srv.listen(16)
            srv.settimeout(0.2)
            self._server = srv
            t = threading.Thread(target=self._serve, daemon=True,
                                 name='mxtpu-membership-coord')
            t.start()
            self._threads.append(t)
        if not getattr(self, '_beating', False):
            self._beating = True
            t = threading.Thread(target=self._beat_loop, daemon=True,
                                 name='mxtpu-membership-beat')
            t.start()
            self._threads.append(t)
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=max(1.0, 2 * self.heartbeat_seconds))
        self._threads = []
        self._beating = False
        # retire the socket under the lock: a server thread that
        # outlived its join timeout (wedged handler) reads the handle
        # through the same lock, so it sees either the live socket
        # (accept then raises OSError on the close) or None — never a
        # torn in-between
        with self._lock:
            srv, self._server = self._server, None
        if srv is not None:
            try:
                srv.close()
            except OSError:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- coordinator server (rank 0) ---------------------------------------

    def _serve(self):
        with self._lock:
            srv = self._server
        while srv is not None and not self._stop.is_set():
            try:
                conn, _addr = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            msg = None
            try:
                conn.settimeout(1.0)
                with conn, conn.makefile('rwb') as f:
                    line = f.readline()
                    if not line:
                        continue
                    msg = json.loads(line.decode())
                    reply = self._finish_reply(self._handle_locked(msg))
                    f.write(json.dumps(reply).encode() + b'\n')
                    f.flush()
            except (OSError, ValueError):
                pass
            # hooks AFTER the reply is on the wire (the fleet monitor's
            # detector pass must not inflate the sender's measured beat
            # RTT) — but regardless of whether the write SUCCEEDED:
            # _handle_locked already mutated state, and skipping e.g.
            # the 'remove' mirror on a client disconnect would leave a
            # departed rank haunting the monitor forever
            if msg is not None:
                self._run_hooks(msg)

    def _handle(self, msg):
        reply = self._finish_reply(self._handle_locked(msg))
        self._run_hooks(msg)
        return reply

    def _finish_reply(self, reply):
        """Reply enrichment, outside the membership lock: the
        coordinator wall clock ('now' — stamped as close to the reply
        as possible, the sender's round-trip turns it into a
        clock-offset sample) and the current flagged straggler summary
        (so every rank's cached view can upgrade its own watchdog
        verdict)."""
        if not isinstance(reply, dict):
            return reply
        reply['now'] = _time.time()
        provider = self.verdict_provider
        if provider is not None:
            try:
                s = provider()
                if s is not None:
                    reply['straggler'] = s
            except Exception:
                pass
        return reply

    def _run_hooks(self, msg):
        """Fleet hooks, OUTSIDE the membership lock: the monitor takes
        its own lock and emits flight notes/metrics — nesting those
        acquisitions under self._lock would add a cross-module lock
        edge (tools/mxtpu_lint lock-order rule). Remote requests run
        this after the reply is written (see _serve)."""
        op = msg.get('op')
        if op == 'beat' and msg.get('telem') is not None:
            hook = self.on_snapshot
            if hook is not None:
                try:
                    hook(int(msg.get('rank', -1)), msg['telem'])
                except Exception:
                    _log.exception("membership: on_snapshot hook failed")
        elif op == 'remove':
            hook = self.on_peers_removed
            if hook is not None:
                try:
                    hook([int(r) for r in msg.get('ranks', [])])
                except Exception:
                    _log.exception(
                        "membership: on_peers_removed hook failed")

    def _handle_locked(self, msg):
        op = msg.get('op')
        r = int(msg.get('rank', -1))
        with self._lock:
            if op == 'beat':
                if r in self._joining:
                    # PENDING joiner: liveness only — the rank enters
                    # the alive set at the admission rendezvous, not by
                    # heartbeating at the side channel
                    self._join_beat[r] = _time.monotonic()
                else:
                    self._last_beat[r] = _time.monotonic()
                if msg.get('step') is not None:
                    self._steps[r] = int(msg['step'])
                if msg.get('telem') is not None:
                    self._telem[r] = {'snap': msg['telem'],
                                      'mono': _time.monotonic(),
                                      'time': _time.time()}
            elif op == 'leave':
                self._left.add(r)
            elif op == 'join':
                # JOIN announcement (scale-up): the rank stays PENDING
                # — surfaced under view['joining'] so every survivor's
                # controller quiesces at its next step boundary — and
                # only the admission rendezvous promotes it into the
                # alive set. Stale records of a previous incarnation
                # (LEFT on preemption, LOST on SIGKILL) are discarded
                # so the rejoiner is not instantly re-declared lost
                # off a months-old heartbeat timestamp.
                now = _time.monotonic()
                self._left.discard(r)
                self._last_beat.pop(r, None)
                self._steps.pop(r, None)
                if r not in self._joining:
                    self._joining[r] = now
                self._join_beat[r] = now
            elif op in ('barrier', 'barrier_poll'):
                # generation-counted rendezvous: a reused tag (kvstore's
                # fixed 'kvstore', repeated re-forms) must synchronize
                # EVERY time, so completion bumps the tag's generation
                # and clears the arrival set instead of leaving a
                # permanently-satisfied one behind. Arrivals carry a
                # per-call nonce so a RETRY whose original reply was
                # lost after the rendezvous completed is recognized
                # (replied done) instead of counting toward — and then
                # waiting forever on — the NEXT generation.
                tag = str(msg.get('tag', ''))
                nonce = msg.get('nonce')
                arrived = self._barriers.setdefault(tag, {})  # r -> nonce
                done = self._barrier_done.setdefault(tag, {})
                gen0 = self._barrier_gen.setdefault(tag, 0)
                if op == 'barrier':
                    prev = done.get(r)
                    if prev is not None and prev[0] == nonce:
                        gen0 = prev[1] - 1   # this call already completed
                    else:
                        arrived[r] = nonce
                view = self._view_locked()
                # the ADMISSION rendezvous (tag ADMIT_TAG) completes
                # only when the pending joiners have arrived TOO — and
                # completion is the generation-counted admission
                # point: every pending joiner is promoted into the
                # alive set atomically with the barrier bump, so the
                # completed reply's view already shows the larger
                # world to survivors and joiners alike.
                need = set(view['alive'])
                if tag == ADMIT_TAG:
                    need |= set(self._joining)
                if arrived and need <= set(arrived) | self._left:
                    self._barrier_gen[tag] = self._barrier_gen[tag] + 1
                    for rr, nn in arrived.items():
                        done[rr] = (nn, self._barrier_gen[tag])
                    arrived.clear()
                    if tag == ADMIT_TAG and self._joining:
                        nowm = _time.monotonic()
                        for rr in list(self._joining):
                            self._last_beat[rr] = nowm
                            self._left.discard(rr)
                        self._joining.clear()
                        self._join_beat.clear()
                        view = self._view_locked()
                view['barrier_gen'] = self._barrier_gen[tag]
                view['barrier_baseline'] = gen0
                view['barrier_done'] = self._barrier_gen[tag] > gen0
                return view
            elif op == 'remove':
                for x in msg.get('ranks', []):
                    self._left.add(int(x))
                    self._telem.pop(int(x), None)
                    # a pending JOIN from the removed rank is cancelled
                    # too (it can re-announce after the re-form)
                    self._joining.pop(int(x), None)
                    self._join_beat.pop(int(x), None)
            return self._view_locked()

    def _view_locked(self):
        now = _time.monotonic()
        if self._joining:
            # GC joiners that went silent again before admission — a
            # half-finished JOIN must not wedge future rendezvous
            for r in [r for r, t in self._join_beat.items()
                      if now - t > self.deadline_seconds]:
                self._joining.pop(r, None)
                self._join_beat.pop(r, None)
        ages = {str(r): round(now - t, 3)
                for r, t in self._last_beat.items() if r not in self._left}
        lost = sorted(int(r) for r, age in ages.items()
                      if age > self.deadline_seconds)
        alive = sorted(int(r) for r in ages if int(r) not in lost)
        view = {'world': len(alive), 'alive': alive, 'ages': ages,
                'lost': lost, 'left': sorted(self._left),
                'steps': {str(k): v for k, v in self._steps.items()}}
        if self._joining:
            view['joining'] = {str(r): round(now - t, 3)
                               for r, t in self._joining.items()}
        return view

    # -- sender (every rank) -----------------------------------------------

    def _beat_loop(self):
        from ..resilience import faults as _faults
        while not self._stop.wait(self.heartbeat_seconds):
            try:
                # the fault site: raise drops this beat (enough in a row
                # and the coordinator declares us lost), hang delays it
                _faults.fire('dist.heartbeat')
                self.beat()
            except MXNetError:
                pass    # _request already counted the send failure
            except Exception:
                with self._lock:
                    self.send_failures += 1

    def beat(self, step=None):
        """One heartbeat round-trip (the sender thread's body; callable
        directly from tests and training loops). Updates the cached
        membership view, attaches the fleet telemetry snapshot (when a
        provider is set) and feeds the clock-offset estimator."""
        if step is not None:
            self.current_step = int(step)
        if _telem['on']:
            from .. import telemetry as _telemetry
            _telemetry.inc('mxnet_tpu_elastic_heartbeats_total')
        msg = {'op': 'beat', 'rank': self.rank, 'step': self.current_step}
        provider = self.telemetry_provider
        if provider is not None:
            try:
                snap = provider()
            except Exception:
                _log.exception("membership: telemetry provider failed")
                snap = None
            if snap is not None:
                msg['telem'] = snap
        if self.is_coordinator:
            view = self._handle(msg)
            with self._lock:
                self._view = view
                self._last_ok = _time.monotonic()
            return view
        t0, m0 = _time.time(), _time.monotonic()
        view = self._request(msg)
        t1, m1 = _time.time(), _time.monotonic()
        self._note_offset(t0, t1, view.get('now'), rtt=m1 - m0)
        return view

    def _note_offset(self, t0, t1, coord_now, rtt=None):
        """One clock-offset sample from a beat round-trip: the
        coordinator stamped ``coord_now`` between our send (t0) and
        receive (t1), so offset = coord_now - midpoint with error
        bounded by rtt/2. The rtt MUST come from a monotonic pair: an
        NTP step between send and receive would otherwise fabricate a
        near-zero wall-clock rtt whose poisoned offset wins the
        min-RTT window for the next 64 beats."""
        if coord_now is None:
            return
        rtt = max(0.0, rtt if rtt is not None else t1 - t0)
        with self._lock:
            self._off_samples.append(
                (rtt, float(coord_now) - (t0 + t1) / 2.0, t1))

    def clock_offset(self):
        """(offset_seconds, rtt_seconds) such that ``local wall clock +
        offset ~= coordinator wall clock``, from the minimum-RTT beat in
        the recent sample window (error <= rtt/2) — what
        ``tools/stitch_traces.py`` shifts per-rank trace timestamps by.
        The coordinator is the reference clock: (0.0, 0.0). None before
        the first completed round-trip."""
        if self.is_coordinator:
            return (0.0, 0.0)
        with self._lock:
            if not self._off_samples:
                return None
            rtt, off, _when = min(self._off_samples)
        return (off, rtt)

    def fleet_snapshots(self):
        """{rank: {'snap', 'age_seconds', 'time'}} — the newest
        telemetry snapshot each rank piggybacked on a heartbeat.
        Coordinator-side state: snapshots are stored where beats are
        handled, so workers always see {} (read the merged fleet view
        from the coordinator's /healthz instead)."""
        now = _time.monotonic()
        with self._lock:
            return {int(r): {'snap': e['snap'],
                             'age_seconds': round(now - e['mono'], 3),
                             'time': e['time']}
                    for r, e in self._telem.items()}

    def _request(self, msg, timeout=None):
        timeout = timeout if timeout is not None else \
            max(1.0, self.heartbeat_seconds * 2)
        # snapshot the endpoint under the lock: retarget() (a re-form
        # pointing at the promoted coordinator) updates host+port as a
        # pair, and a beat racing it must not connect to the OLD host
        # with the NEW port
        with self._lock:
            host, port = self.coordinator_host, self.port
        try:
            with socket.create_connection(
                    (host, port), timeout=timeout) as conn:
                with conn.makefile('rwb') as f:
                    f.write(json.dumps(msg).encode() + b'\n')
                    f.flush()
                    line = f.readline()
            view = json.loads(line.decode())
        except (OSError, ValueError) as e:
            with self._lock:
                self.send_failures += 1
            raise MXNetError(
                f"membership: coordinator "
                f"{host}:{port} unreachable: "
                f"{e!r}") from e
        with self._lock:
            self._view = view
            self._last_ok = _time.monotonic()
        return view

    # -- queries -----------------------------------------------------------

    def view(self):
        """Latest membership view (coordinator: computed live; workers:
        the last beat's reply)."""
        if self.is_coordinator:
            with self._lock:
                return self._view_locked()
        with self._lock:
            return dict(self._view) if self._view else None

    def lost_peers(self):
        """Ranks declared lost. On a worker whose COORDINATOR has gone
        silent past the deadline, that is rank 0 — the worker-side half
        of the failure detector."""
        v = self.view()
        lost = list(v['lost']) if v else []
        if not self.is_coordinator:
            with self._lock:
                coord_age = _time.monotonic() - self._last_ok
            if coord_age > self.deadline_seconds and 0 not in lost:
                lost.append(0)
        return sorted(r for r in lost if r != self.rank)

    def peer_ages(self):
        """{rank: seconds-since-last-heartbeat} for the post-mortem
        verdict (watchdog report / flight dump). Finite values only —
        a retired coordinator (``remove_peers``) pins ``_last_ok`` to
        inf, which must not leak -inf ages into JSON dumps."""
        import math
        v = self.view()
        ages = {int(r): a for r, a in (v or {}).get('ages', {}).items()}
        if not self.is_coordinator:
            with self._lock:
                age = _time.monotonic() - self._last_ok
            if math.isfinite(age):
                ages[0] = round(age, 3)
        ages.pop(self.rank, None)
        return ages

    def alive(self):
        """Sorted live ranks (self included unless it left)."""
        v = self.view()
        if not v:
            return [self.rank]
        alive = set(v['alive'])
        if not self.is_coordinator:
            alive -= set(self.lost_peers())
            alive.add(self.rank)
        return sorted(alive)

    def world_size(self):
        return len(self.alive())

    # -- membership ops ----------------------------------------------------

    def leave(self):
        """Graceful goodbye (the SIGTERM/preemption path): peers see a
        departure, not a failure."""
        try:
            if self.is_coordinator:
                self._handle({'op': 'leave', 'rank': self.rank})
            else:
                self._request({'op': 'leave', 'rank': self.rank})
        except MXNetError:
            pass   # coordinator already gone — nothing to tell
        self._stop.set()

    def join(self):
        """Announce this rank as a JOIN candidate (a preempted rank
        coming back, or brand-new capacity granted by the provider).
        The coordinator marks it PENDING — surfaced in every view under
        ``joining`` so the survivors' controllers quiesce at their next
        step boundary — and the admission rendezvous
        (``barrier(ADMIT_TAG)``) promotes it into the alive set. The
        ``dist.join`` fault site drills failed/delayed announcements.
        Returns the coordinator's view."""
        from ..resilience import faults as _faults
        _faults.fire('dist.join')
        if _telem['on']:
            from .. import telemetry as _telemetry
            _telemetry.inc('mxnet_tpu_elastic_joins_total')
            from ..telemetry import flight as _flight
            _flight.note('elastic.join', rank=self.rank)
        msg = {'op': 'join', 'rank': self.rank}
        if self.is_coordinator:
            return self._handle(msg)
        return self._request(msg)

    def joining(self):
        """{rank: seconds-since-announcement} of JOIN candidates pending
        admission (coordinator: computed live; workers: from the last
        beat reply — at most one heartbeat stale)."""
        v = self.view()
        return {int(r): float(a)
                for r, a in (v or {}).get('joining', {}).items()}

    def remove_peers(self, ranks):
        """Retire lost peers from the tracked set (post re-form: the new
        world must not keep re-declaring the same loss)."""
        msg = {'op': 'remove', 'rank': self.rank,
               'ranks': [int(r) for r in ranks]}
        if self.is_coordinator:
            self._handle(msg)
        else:
            try:
                self._request(msg)
            except MXNetError:
                pass
        # worker-side: absorb into the local view too (the coordinator
        # itself may be among the removed) — pruning 'alive' and 'ages'
        # as well, so a stale coordinator-produced view cannot resurrect
        # a removed peer into the next survivor computation
        rs = set(int(r) for r in ranks)
        with self._lock:
            for r in rs:
                self._telem.pop(r, None)
            if self._view:
                self._view['lost'] = [r for r in self._view.get('lost', [])
                                      if int(r) not in rs]
                self._view['alive'] = [
                    r for r in self._view.get('alive', [])
                    if int(r) not in rs]
                self._view['world'] = len(self._view['alive'])
                for r in list(self._view.get('ages', {})):
                    if int(r) in rs:
                        self._view['ages'].pop(r)
            if 0 in rs:
                self._last_ok = float('inf')   # never re-declare rank 0

    def retarget(self, host=None, port=None):
        """Point this worker's sender at a NEW membership coordinator
        (after the old one died and the lowest surviving rank promoted
        itself via ``become_coordinator``). Without ``host`` the current
        one is kept — correct when the survivors share it (single-host
        drills); a multi-host deployment resolves the promoted rank's
        address via ``ElasticController(coordinator_host_fn=...)``."""
        with self._lock:
            if host is not None:
                self.coordinator_host = host
            if port is not None:
                self.port = int(port)
            self._last_ok = _time.monotonic()
        return self

    def become_coordinator(self):
        """Promote this rank to membership coordinator (lowest surviving
        rank after the old coordinator died). Starts the server thread
        on the same side-channel port, seeded with the current survivor
        set."""
        if self.is_coordinator:
            return self
        alive = self.alive()
        with self._lock:
            # lint: lockset-race-ok monotonic False->True promotion latch; a reader seeing the stale False for one beat retries against the dead coordinator once and self-corrects on the next round-trip
            self.is_coordinator = True
            now = _time.monotonic()
            self._last_beat = {r: now for r in alive}
            self._left = set()
            # pending JOINs announced to the dead coordinator are gone
            # with it — joiners re-announce against the promoted one
            self._joining = {}
            self._join_beat = {}
            self._last_ok = now
        self.start()
        # fleet observability followed the OLD coordinator: if this
        # rank was reporting snapshots, the promotion must also make it
        # the merge point — otherwise worker snapshots arriving here
        # are dropped and the degraded fleet goes dark exactly when it
        # most needs watching
        if self.telemetry_provider is not None:
            try:
                from ..telemetry import fleet as _fleet
                _fleet.attach(self)
            except Exception:
                _log.exception("fleet re-attach after promotion failed")
        return self

    def barrier(self, tag, timeout=None):
        """Membership-level rendezvous: block until every LIVE rank has
        arrived at ``tag`` (left/lost peers are not waited for — that is
        the point: a re-form barrier must not wait for the dead). Raises
        MXNetError on timeout."""
        from .. import config as _config
        from ..resilience import faults as _faults
        _faults.fire('dist.barrier')
        timeout = timeout if timeout is not None else \
            _config.get('MXTPU_BARRIER_TIMEOUT_SECONDS')
        deadline = _time.monotonic() + float(timeout)
        # arrive once; the reply's baseline is THIS rendezvous's
        # generation — poll until the coordinator bumps past it (the
        # bump clears the arrival set, so the same tag synchronizes
        # again next time instead of staying permanently satisfied).
        # Transient send failures retry within the deadline: a re-form
        # barrier often races the PROMOTED coordinator's server start,
        # and aborting on the first refused connection would kill a
        # survivor mid-recovery. The nonce makes a retried arrival
        # idempotent — a reply lost AFTER the rendezvous completed
        # must read back as done, not as a fresh arrival.
        with self._lock:
            self._barrier_calls += 1
            nonce = f'{self.rank}.{self._barrier_calls}'
        msg = {'op': 'barrier', 'rank': self.rank, 'tag': str(tag),
               'nonce': nonce}
        view, baseline = None, None
        while True:
            try:
                view = self._handle(msg) if self.is_coordinator \
                    else self._request(msg)
            except MXNetError:
                view = None
            if view is not None:
                if baseline is None and msg['op'] == 'barrier':
                    baseline = view.get('barrier_baseline', 0)
                    msg = {'op': 'barrier_poll', 'rank': self.rank,
                           'tag': str(tag)}
                if view.get('barrier_gen', 0) > (baseline or 0):
                    view['barrier_done'] = True
                    return view
            if _time.monotonic() > deadline:
                raise MXNetError(
                    f"membership barrier {tag!r} timed out after "
                    f"{timeout}s: arrived ranks missing from alive set "
                    f"{(view or {}).get('alive')}")
            _time.sleep(min(0.05, self.heartbeat_seconds / 4))


def membership():
    """The process-global Membership (None unless started)."""
    with _membership_lock:
        return _membership


def start_membership(coordinator=None, num_processes=None, process_id=None,
                     **kwargs):
    """Start (or return) the process-global membership layer. Called by
    ``init()`` under ``MXTPU_ELASTIC=1``; callable directly for custom
    worlds (tests, drills)."""
    global _membership
    if _membership is not None:
        return _membership
    # the SAME resolution init() uses (one shared helper), so the
    # derived side-channel port cannot diverge between init()-started
    # and directly-started layers
    coordinator, num_processes, process_id = _resolve_world(
        coordinator, num_processes, process_id)
    host = coordinator.rsplit(':', 1)[0] if ':' in coordinator \
        else coordinator
    kwargs.setdefault('port', _elastic_port(coordinator))
    ms = Membership(process_id, num_processes,
                    coordinator_host=host, **kwargs)
    with _membership_lock:
        _membership = ms
    # fleet observability (ISSUE 13): heartbeats piggyback telemetry
    # snapshots, the coordinator merges them, and the per-process
    # /metrics//healthz//flight endpoint arms iff MXTPU_METRICS_PORT
    # is set. Never fatal — observability must not take down training.
    try:
        from ..telemetry import fleet as _fleet, server as _tserver
        _fleet.attach(_membership)
        _tserver.maybe_start(rank=_membership.rank,
                             membership=_membership)
    except Exception:
        _log.exception("fleet observability bring-up failed")
    return _membership


def stop_membership():
    global _membership
    with _membership_lock:
        ms, _membership = _membership, None
    if ms is not None:
        ms.stop()


def barrier(tag='barrier', timeout=None):
    """Module-level membership barrier (no-op without a membership —
    single-process jobs have nobody to rendezvous with, but the fault
    site still fires so drills stay deterministic)."""
    if _membership is None:
        from ..resilience import faults as _faults
        _faults.fire('dist.barrier')
        return None
    return _membership.barrier(tag, timeout=timeout)


# ---------------------------------------------------------------------------
# checkpoint replica transport (ISSUE 10)
# ---------------------------------------------------------------------------
#
# Chunked file transfer on the SAME lightweight TCP side-channel design
# as the membership layer — deliberately never the ICI collectives,
# which are exactly what a dead peer wedges. One request per
# connection: a JSON header line, then (file_put) exactly `size` raw
# bytes, then a JSON reply line (file_get replies stream `size` raw
# bytes after the header). The receiver stages every file of a step
# into a ``step_*.tmp-<pid>`` dir and makes it visible only through
# ``replica_commit``'s single os.replace — the same commit protocol as
# a local checkpoint write, so a kill -9 at ANY point mid-transfer
# leaves no partial replica visible.

_REPLICA_CHUNK = 1 << 20          # 1 MiB transfer chunks
_NS_RE = re.compile(r'^[A-Za-z0-9][A-Za-z0-9_.\-]*$')


def _replica_timeout(timeout=None):
    from .. import config as _config
    return float(timeout) if timeout is not None \
        else float(_config.get('MXTPU_REPLICA_TIMEOUT_SECONDS'))


def replica_port(rank, coordinator=None):
    """Replica-server port of ``rank``: MXTPU_REPLICA_PORT_BASE + rank,
    defaulting the base to the elastic side-channel port + 100 (keeps
    parallel jobs on one host from colliding, same scheme as
    ``_elastic_port``)."""
    from .. import config as _config
    base = int(_config.get('MXTPU_REPLICA_PORT_BASE') or 0)
    if not base:
        base = _elastic_port(coordinator) + 100
    return base + int(rank)


def _safe_rel(rel):
    rel = str(rel)
    if not rel or rel.startswith(('/', '\\')) or '..' in rel.split('/') \
            or '\\' in rel:
        raise MXNetError(f"replica transport: unsafe relative path {rel!r}")
    return rel


def _safe_ns(ns):
    ns = str(ns)
    if not _NS_RE.match(ns):
        raise MXNetError(f"replica transport: bad namespace {ns!r}")
    return ns


def _recv_exact(f, n, chunk=_REPLICA_CHUNK):
    out = bytearray()
    while len(out) < n:
        b = f.read(min(chunk, n - len(out)))
        if not b:
            raise OSError(f"replica transport: connection closed after "
                          f"{len(out)}/{n} bytes")
        out += b
    return bytes(out)


class ReplicaServer:
    """Per-rank checkpoint replica endpoint.

    Stores replicas pushed by PEER ranks under
    ``<root>/<ns>/step_*`` (``ns`` names the owner, e.g. ``rank0``) and
    serves reads of both those hosted replicas and — when ``local_dir``
    is given — this host's OWN committed checkpoints (``ns='local'``),
    so a survivor can restore a dead owner's state from any live host.

    Ops (one JSON header line per connection):

    - ``file_put``  {ns, step, rel, size, sha256} + raw bytes: stage one
      payload file into the step's uncommitted tmp dir (hash-verified
      on receipt).
    - ``replica_commit`` {ns, step}: validate the staged dir against its
      manifest and publish it with one os.replace.
    - ``file_get``  {ns, step, rel}: stream one file back.
    - ``replica_inventory`` [{ns}]: committed hosted steps per namespace
      plus the owner's own local committed steps.
    - ``replica_delete`` {ns, step}: retire a hosted replica (retention
      GC from the owner) — counted in
      ``mxnet_tpu_checkpoint_replica_gc_total``.
    """

    def __init__(self, root, local_dir=None, port=0, start=True):
        self.root = os.path.abspath(root)
        self.local_dir = local_dir
        os.makedirs(self.root, exist_ok=True)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._server = None
        self._threads = []
        self.port = int(port)
        self.gc_total = 0
        self._sweep_stale()
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        if self._server is not None:
            return self
        self._stop.clear()
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(('', self.port))
        self.port = srv.getsockname()[1]
        srv.listen(16)
        srv.settimeout(0.2)
        self._server = srv
        t = threading.Thread(target=self._serve, daemon=True,
                             name='mxtpu-replica-server')
        t.start()
        self._threads.append(t)
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []
        # retire the socket under the lock (same discipline as
        # Membership.stop): an accept loop that outlived its join
        # timeout must read the live-socket-or-None pair, never a torn
        # in-between
        with self._lock:
            srv, self._server = self._server, None
        if srv is not None:
            try:
                srv.close()
            except OSError:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def _sweep_stale(self):
        """Sweep staging leftovers of a killed predecessor: nothing is
        in flight when a fresh server starts, so every ``*.tmp-*`` under
        every namespace is a dead write."""
        from ..checkpoint import manifest as mf
        try:
            namespaces = os.listdir(self.root)
        except OSError:
            return
        for ns in namespaces:
            nsdir = os.path.join(self.root, ns)
            if not os.path.isdir(nsdir):
                continue
            for tmp in mf.stale_tmp_dirs(nsdir):
                shutil.rmtree(tmp, ignore_errors=True)

    # -- server loop -------------------------------------------------------

    def _serve(self):
        with self._lock:
            srv = self._server
        while srv is not None and not self._stop.is_set():
            try:
                conn, _addr = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            # one thread per connection: a bandwidth-paced multi-MB put
            # must not block inventory/fetch ops from other peers
            t = threading.Thread(target=self._handle_conn, args=(conn,),
                                 daemon=True, name='mxtpu-replica-conn')
            t.start()

    def _handle_conn(self, conn):
        try:
            conn.settimeout(_replica_timeout())
            with conn, conn.makefile('rwb') as f:
                line = f.readline()
                if not line:
                    return
                try:
                    msg = json.loads(line.decode())
                    reply, payload = self._handle(msg, f)
                except MXNetError as e:
                    reply, payload = {'ok': 0, 'error': str(e)}, None
                except (OSError, ValueError, KeyError, TypeError) as e:
                    reply, payload = {'ok': 0, 'error': repr(e)}, None
                f.write(json.dumps(reply).encode() + b'\n')
                if payload is not None:
                    f.write(payload)
                f.flush()
        except (OSError, ValueError):
            pass

    def _ns_dir(self, ns, create=False):
        d = os.path.join(self.root, _safe_ns(ns))
        if create:
            os.makedirs(d, exist_ok=True)
        return d

    def _step_root(self, ns, step):
        """(namespace dir, final step dir) — ns 'local' reads this
        host's own checkpoint directory (read-only ops)."""
        from ..checkpoint import manifest as mf
        if ns == 'local':
            if self.local_dir is None:
                raise MXNetError("replica server: no local checkpoint "
                                 "dir attached (ns='local' unavailable)")
            base = self.local_dir
        else:
            base = self._ns_dir(ns)
        return base, os.path.join(base, mf.step_dir_name(int(step)))

    def _handle(self, msg, f):
        """Returns (reply dict, optional raw payload bytes)."""
        from ..checkpoint import manifest as mf
        op = msg.get('op')
        if op == 'file_put':
            ns = _safe_ns(msg['ns'])
            if ns == 'local':
                raise MXNetError("replica server: refusing file_put into "
                                 "the local checkpoint dir")
            rel = _safe_rel(msg['rel'])
            size = int(msg['size'])
            data = _recv_exact(f, size)
            digest = mf.sha256_bytes(data)
            if digest != msg.get('sha256'):
                raise MXNetError(
                    f"replica file_put {ns}/{msg.get('step')}/{rel}: "
                    f"content hash mismatch in transfer "
                    f"({digest[:12]}... != "
                    f"{str(msg.get('sha256'))[:12]}...)")
            nsdir = self._ns_dir(ns, create=True)
            staging = os.path.join(
                nsdir, mf.step_dir_name(int(msg['step']))
                + f'.tmp-{os.getpid()}')
            path = os.path.join(staging, rel)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            mf.write_bytes_durable(path, data)
            return {'ok': 1, 'bytes': size}, None
        if op == 'replica_commit':
            ns = _safe_ns(msg['ns'])
            if ns == 'local':
                raise MXNetError("replica server: refusing commit into "
                                 "the local checkpoint dir")
            step = int(msg['step'])
            nsdir = self._ns_dir(ns, create=True)
            final = os.path.join(nsdir, mf.step_dir_name(step))
            staging = final + f'.tmp-{os.getpid()}'
            with self._lock:
                if not os.path.isdir(staging):
                    raise MXNetError(
                        f"replica commit {ns}/{step}: no staged files")
                try:
                    mf.validate_step_dir(staging)
                except mf.CorruptCheckpointError as e:
                    shutil.rmtree(staging, ignore_errors=True)
                    raise MXNetError(
                        f"replica commit {ns}/{step} failed validation "
                        f"(staging discarded): {e}")
                if os.path.isdir(final):
                    old = final + f'.old-{os.getpid()}'
                    if os.path.isdir(old):
                        shutil.rmtree(old)
                    os.replace(final, old)
                    os.replace(staging, final)
                    shutil.rmtree(old, ignore_errors=True)
                else:
                    os.replace(staging, final)
                mf.fsync_dir(nsdir)
            return {'ok': 1, 'step': step}, None
        if op == 'file_get':
            ns = _safe_ns(msg['ns'])
            rel = _safe_rel(msg['rel'])
            _, stepdir = self._step_root(ns, msg['step'])
            path = os.path.join(stepdir, rel)
            try:
                with open(path, 'rb') as pf:
                    data = pf.read()
            except OSError as e:
                raise MXNetError(f"replica file_get "
                                 f"{ns}/{msg.get('step')}/{rel}: {e}")
            return {'ok': 1, 'size': len(data),
                    'sha256': mf.sha256_bytes(data)}, data
        if op == 'replica_inventory':
            want = msg.get('ns')
            hosted = {}
            try:
                namespaces = sorted(os.listdir(self.root))
            except OSError:
                namespaces = []
            for ns in namespaces:
                if not os.path.isdir(os.path.join(self.root, ns)):
                    continue
                if want and ns != want:
                    continue
                hosted[ns] = mf.committed_steps(
                    os.path.join(self.root, ns))
            local = mf.committed_steps(self.local_dir) \
                if self.local_dir else []
            return {'ok': 1, 'hosted': hosted, 'local': local}, None
        if op == 'replica_delete':
            ns = _safe_ns(msg['ns'])
            if ns == 'local':
                raise MXNetError("replica server: refusing delete in "
                                 "the local checkpoint dir")
            _, stepdir = self._step_root(ns, msg['step'])
            removed = 0
            with self._lock:
                if os.path.isdir(stepdir):
                    shutil.rmtree(stepdir, ignore_errors=True)
                    removed = 1
            if removed:
                # one handler thread per connection: the counter bump
                # must not lose updates between concurrent deletes
                with self._lock:
                    self.gc_total += 1
                if _telem['on']:
                    from .. import telemetry as _telemetry
                    _telemetry.inc(
                        'mxnet_tpu_checkpoint_replica_gc_total')
            return {'ok': 1, 'removed': removed}, None
        raise MXNetError(f"replica server: unknown op {op!r}")


def _replica_request(host, port, msg, payload=None, timeout=None,
                     bandwidth_mbps=None, recv_payload=False):
    """One replica-transport round-trip. ``payload`` bytes are streamed
    chunked after the header (paced to ``bandwidth_mbps`` when set);
    ``recv_payload`` reads the reply's ``size`` bytes after the reply
    header. Bounded by the socket timeout at every read/write — a dead
    peer costs one timeout, never a hang."""
    timeout = _replica_timeout(timeout)
    try:
        with socket.create_connection((host, int(port)),
                                      timeout=timeout) as conn:
            conn.settimeout(timeout)
            with conn.makefile('rwb') as f:
                f.write(json.dumps(msg).encode() + b'\n')
                if payload is not None:
                    pace = None
                    if bandwidth_mbps is None:
                        from .. import config as _config
                        bandwidth_mbps = _config.get(
                            'MXTPU_REPLICA_BANDWIDTH_MBPS')
                    if bandwidth_mbps and bandwidth_mbps > 0:
                        pace = 1.0 / (float(bandwidth_mbps) * 1e6)
                    view = memoryview(payload)
                    for off in range(0, len(view), _REPLICA_CHUNK):
                        t0 = _time.perf_counter()
                        chunk = view[off:off + _REPLICA_CHUNK]
                        f.write(chunk)
                        f.flush()
                        if pace:
                            budget = len(chunk) * pace
                            spent = _time.perf_counter() - t0
                            if budget > spent:
                                _time.sleep(budget - spent)
                f.flush()
                line = f.readline()
                if not line:
                    raise OSError("connection closed before reply")
                reply = json.loads(line.decode())
                data = None
                if recv_payload and reply.get('ok'):
                    data = _recv_exact(f, int(reply['size']))
    except (OSError, ValueError) as e:
        raise MXNetError(
            f"replica transport: {host}:{port} {msg.get('op')} failed: "
            f"{e!r}") from e
    if not reply.get('ok'):
        raise MXNetError(
            f"replica transport: {host}:{port} {msg.get('op')} "
            f"rejected: {reply.get('error')}")
    return (reply, data) if recv_payload else reply


def file_put(host, port, ns, step, rel, data, timeout=None,
             bandwidth_mbps=None):
    """Push one payload file of a committed step to a peer's replica
    server (staged — invisible until ``replica_commit``). Fault site
    ``dist.file_put``: raise fails the transfer, corrupt mangles the
    bytes in flight (the receiver's hash check rejects them), hang
    stalls into the socket timeout."""
    from ..resilience import faults as _faults
    kind = _faults.fire('dist.file_put')
    sent = bytes(data)
    if kind == 'corrupt':
        sent = _faults.corrupt_bytes(sent)
    from ..checkpoint import manifest as mf
    return _replica_request(
        host, port,
        {'op': 'file_put', 'ns': ns, 'step': int(step), 'rel': rel,
         'size': len(sent), 'sha256': mf.sha256_bytes(bytes(data))},
        payload=sent, timeout=timeout, bandwidth_mbps=bandwidth_mbps)


def file_get(host, port, ns, step, rel, timeout=None):
    """Fetch one file of a hosted replica (or, with ``ns='local'``, of
    the peer's own committed checkpoint). Returns the raw bytes after
    verifying the transfer hash."""
    from ..checkpoint import manifest as mf
    reply, data = _replica_request(
        host, port,
        {'op': 'file_get', 'ns': ns, 'step': int(step), 'rel': rel},
        timeout=timeout, recv_payload=True)
    if mf.sha256_bytes(data) != reply.get('sha256'):
        raise MXNetError(
            f"replica transport: {ns}/{step}/{rel} from {host}:{port} "
            f"corrupted in transfer (hash mismatch)")
    return data


def replica_commit(host, port, ns, step, timeout=None):
    """Publish a fully staged replica step with one os.replace on the
    receiver (validated against its manifest first)."""
    return _replica_request(
        host, port, {'op': 'replica_commit', 'ns': ns, 'step': int(step)},
        timeout=timeout)


def replica_inventory(host, port, ns=None, timeout=None):
    """{'hosted': {ns: [steps]}, 'local': [steps]} of a peer's replica
    server — the restore-fallback / orphan-GC survey op."""
    msg = {'op': 'replica_inventory'}
    if ns is not None:
        msg['ns'] = ns
    return _replica_request(host, port, msg, timeout=timeout)


def replica_delete(host, port, ns, step, timeout=None):
    """Retire one hosted replica step on a peer (retention GC)."""
    return _replica_request(
        host, port, {'op': 'replica_delete', 'ns': ns, 'step': int(step)},
        timeout=timeout)


def _local_tpu_chips():
    """TPU chips this host exposes, counted from the device files libtpu
    opens (/dev/accel* on older hosts, numbered /dev/vfio groups on the
    v5e machines) — without touching jax, because a launcher that has
    touched jax holds the chips its children need."""
    import glob
    return len(glob.glob('/dev/accel[0-9]*')) \
        or len(glob.glob('/dev/vfio/[0-9]*'))


def launch_local(script, n=2, env=None, coordinator='localhost:29500',
                 raw_command=False):
    """Spawn n local worker processes (the `--launcher local` analog of
    tools/launch.py; the CLI launcher delegates here so the coordinator env
    protocol lives in one place). Returns their exit codes.

    raw_command=True runs `script` verbatim; otherwise it is a python
    script argv run under the current interpreter.

    On a host with TPU chips the workers must be pinned to the CPU
    (``JAX_PLATFORMS=cpu`` in the environment or in ``env``): a TPU
    process opens every local chip, so n of them cannot share a host.
    One process drives all local chips — run the script once, without
    the launcher, and its mesh spans whatever ``jax.devices()`` reports."""
    base = dict(os.environ)
    base.update(env or {})
    chips = _local_tpu_chips()
    if n > 1 and chips \
            and base.get('JAX_PLATFORMS', '').split(',')[0] != 'cpu':
        raise MXNetError(
            f"launch_local: this host exposes {chips} TPU "
            f"chip(s) and a TPU process opens all of them, so {n} local "
            f"workers would collide on the device. One process drives "
            f"all local chips: run the script directly (its mesh spans "
            f"jax.devices()), or set JAX_PLATFORMS=cpu to rehearse the "
            f"multi-process protocol on the CPU.")
    procs = []
    cmd = list(script) if raw_command else [sys.executable] + list(script)
    for i in range(n):
        e = dict(base)
        e['MXNET_TPU_COORDINATOR'] = coordinator
        e['MXNET_TPU_NUM_PROCS'] = str(n)
        e['MXNET_TPU_PROC_ID'] = str(i)
        procs.append(subprocess.Popen(cmd, env=e))
    return [p.wait() for p in procs]
