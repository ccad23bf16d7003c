"""Crash-time flight recorder: the last N steps, always ready to dump.

A crash or a stall at step 48,213 of a multi-hour run is only
debuggable if the process carried its own black box: what the recent
steps spent their time on, what the loss was doing, whether the
non-finite guard was tripping, which faults fired. The flight recorder
is that box — a bounded ring of per-step summaries (span self-times
drained from ``telemetry.trace``, loss, guard flag) plus a bounded log
of notable events (fault injections, guard trips, rollbacks, watchdog
stalls), dumped as ONE atomic JSON:

- by the watchdog when the step heartbeat stalls,
- by the non-finite guard's rollback ladder,
- at interpreter exit (``atexit``) and on fatal signals
  (SIGTERM/SIGABRT, chaining any previously installed handler —
  e.g. the checkpoint preemption hook keeps working),
- on demand via ``flight.dump(reason=...)``.

The dump also embeds the balanced chrome ``traceEvents`` stream and
every thread's currently-OPEN spans, so a hang names the exact frame
each thread was inside (``tools/check_trace.py`` validates the
embedded stream like any other trace dump).

Armed together with tracing (``MXTPU_TRACE=1``): ``record_step()`` is
a no-op while tracing is disarmed, so an untraced run pays one dict
check per step. Recording never waits for the device: a step's loss
joins a short queue of (record, loss) pairs, and each ``record_step()``
reads only those whose array says ``is_ready()`` (a plain float or a
numpy scalar is ready at once). The host may run many steps ahead of
the device; a loss is then in its record a few steps late, and
``snapshot(resolve_loss=True)`` reads what is still queued. No
``float()`` of an unfinished program's output runs on the dispatch
path, so switching the recorder on leaves the loop's pipelining as it
was (until PR 39 it read the previous step's loss in every dispatch,
which held the host one step behind the device).
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import signal as _signal
import threading
import time as _time

from ..base import telem_flags as _telem
from . import compile as _compile
from . import memory as _memory
from . import trace as _trace

__all__ = ['FlightRecorder', 'get', 'record_step', 'note',
           'annotate_last', 'dump', 'default_dump_path',
           'install_crash_hooks']


class FlightRecorder:
    """Bounded ring of step summaries + event log. One process-global
    instance (``flight.get()``); tests may build their own."""

    def __init__(self, capacity=None, event_capacity=256):
        if capacity is None:
            from .. import config as _config
            capacity = _config.get('MXTPU_FLIGHT_STEPS')
        self.capacity = max(1, int(capacity))
        self._steps = collections.deque(maxlen=self.capacity)
        self._events = collections.deque(maxlen=int(event_capacity))
        # RLock, same signal-safety rationale as the module-level
        # _recorder_lock: note() and _pop_pending() run inside the
        # SIGTERM preemption save and the atexit dump — a signal
        # landing while THIS thread holds the ring lock (record_step's
        # critical section) must re-enter, not self-deadlock. Found by
        # mxtpu_lint's signal-safety rule once the call graph learned
        # to resolve `get().note(...)` through the accessor.
        self._lock = threading.RLock()
        self._last_t = None          # perf_counter of the previous step
        # (record, loss) pairs not read yet; a record the ring has
        # dropped need not be filled in, hence the same bound
        self._pending = collections.deque(maxlen=self.capacity)
        self.dumps = 0

    # -- recording ---------------------------------------------------------

    def record_step(self, step, loss=None, guard_ok=None, extra=None):
        """One training step completed. `loss` may be a device scalar
        whose program is still running: it is read by the first
        record_step (or resolving snapshot) that finds it ready, never
        waited for. No-op while tracing is disarmed."""
        if not _trace._state['on']:
            return
        now = _time.perf_counter()
        # this thread runs the step loop: only ITS self-times may be
        # billed against step wall time (attribution); other threads'
        # spans overlap the step and count only in the totals
        rec = {'step': int(step), 'time': _time.time(), 'loss': None,
               'spans_ms': _trace.drain_aggregates(
                   consumer_tid=_trace.tid_for_current_thread())}
        if self._last_t is not None:
            rec['interval_ms'] = round((now - self._last_t) * 1e3, 3)
        self._last_t = now
        if guard_ok is not None:
            rec['guard_ok'] = bool(guard_ok)
        # memory watermark fields (MXTPU_MEMORY): the newest sample's
        # prebuilt dict — disarmed this is one dict check returning the
        # shared None, same no-alloc discipline as the trace gate
        mem = _memory.step_fields()
        if mem is not None:
            rec['mem'] = mem
        # compile-ledger fields: only the first step after a compile
        # carries them (consume-on-read), same no-alloc discipline
        comp = _compile.step_fields()
        if comp is not None:
            rec['compile'] = comp
        if extra:
            rec.update(extra)
        with self._lock:
            self._steps.append(rec)
            if loss is not None:
                self._pending.append((rec, loss))
        self._resolve(self._pop_pending(only_ready=True))
        _trace._sync_metrics()

    @staticmethod
    def _ready(loss):
        """Whether reading `loss` would return at once: a jax array
        (bare or inside an NDArray) answers for itself, anything else (a
        float, a numpy scalar) is ready. Never raises; an array that
        cannot say (deleted, say) counts as ready and reads as None."""
        probe = getattr(getattr(loss, '_data', loss), 'is_ready', None)
        try:
            return probe is None or bool(probe())
        except Exception:
            return True

    def _pop_pending(self, only_ready=False):
        """Take the queued (record, loss) pairs — with `only_ready`
        those a read would not wait for, the others staying queued."""
        taken, left = [], []
        with self._lock:
            for pair in self._pending:
                (left if only_ready and not self._ready(pair[1])
                 else taken).append(pair)
            self._pending.clear()
            self._pending.extend(left)
        return taken

    @staticmethod
    def _resolve(pairs):
        """Read each loss into its step record (a failure records None).
        Called outside the lock: a wedged device must never wedge the
        lock the watchdog's dump needs. The records are already in the
        ring — a concurrent reader sees None or the float, never
        corruption."""
        for rec, loss in pairs:
            try:
                # lint: host-sync-ok only reached for a loss that is_ready() (record_step) or from a snapshot that asked for the wait
                rec['loss'] = float(getattr(loss, '_data', loss))
            except Exception:
                rec['loss'] = None

    def note(self, kind, /, **info):
        """One notable event (fault fired, guard tripped, rollback,
        stall, ...). Bounded; no-op while tracing is disarmed."""
        if not _trace._state['on']:
            return
        ev = {'kind': kind, 'time': _time.time()}
        if info:
            ev.update(info)
        with self._lock:
            self._events.append(ev)

    def annotate_last(self, **fields):
        """Attach fields to the most recent step record (e.g. the
        guard's one-step-deferred verdict: annotate_last(guard_ok=False)
        lands on the step whose flag just drained bad)."""
        if not _trace._state['on']:
            return
        with self._lock:
            if self._steps:
                self._steps[-1].update(fields)

    # -- reading / dumping -------------------------------------------------

    @contextlib.contextmanager
    def _locked_for_dump(self, timeout=2.0):
        """Best-effort lock for the read/dump paths. A crash-time dump
        must never deadlock: same-thread signal re-entry is covered by
        the ring lock being an RLock, but a wedged holder on ANOTHER
        thread must not wedge the watchdog's report. After `timeout`
        we proceed lock-free — safe, because a holder that timed us
        out is interrupted or blocked, not mutating."""
        got = self._lock.acquire(timeout=timeout)
        try:
            yield
        finally:
            if got:
                self._lock.release()

    def steps(self):
        with self._locked_for_dump():
            return [dict(r) for r in self._steps]

    def last_step_record(self):
        """The newest step record (copy), or None — the fleet snapshot
        builder's per-step source; never drains the ring."""
        with self._locked_for_dump():
            return dict(self._steps[-1]) if self._steps else None

    def events(self):
        with self._locked_for_dump():
            return [dict(e) for e in self._events]

    def snapshot(self, resolve_loss=False, signal_safe=False):
        """The full post-mortem document. `resolve_loss=True` reads the
        losses still queued, waiting for their programs; `False` at
        crash time: that wait could be on a wedged device, and the dump
        must never hang. `signal_safe=True` (fatal-
        signal handlers) additionally skips every metrics-registry
        touch: the interrupted frame may hold those locks."""
        if resolve_loss:
            self._resolve(self._pop_pending())    # device read: no lock
        with self._locked_for_dump():
            steps = [dict(r) for r in self._steps]
            events = [dict(e) for e in self._events]
        return {
            'pid': os.getpid(),
            'time': _time.time(),
            'steps': steps,
            'events': events,
            'open_spans': _trace.open_spans(),
            # the open compile window, when a build is mid-flight at
            # crash time — a stall INSIDE compile.backend is forensics
            # gold (which site, which phase, how long)
            'compile_in_flight': _compile.in_flight(),
            'trace_stats': _trace.stats(),
            'faults_armed': self._armed_faults(),
            'traceEvents': _trace.chrome_events(flush_open=True,
                                                metadata=True,
                                                sync=not signal_safe),
        }

    @staticmethod
    def _armed_faults():
        try:
            from ..resilience import faults as _faults
            return _faults.active()
        except Exception:
            return {}

    def dump(self, path=None, reason='', signal_safe=False):
        """Write the post-mortem JSON atomically. Returns the path, or
        None when there is nothing recorded (or tracing is disarmed) —
        an empty flight recorder never shadows a real dump.
        `signal_safe=True` (fatal-signal handlers) skips every
        metrics-registry touch: the interrupted frame may hold the
        registry's non-reentrant lock."""
        if not _trace._state['on']:
            return None
        with self._locked_for_dump():
            empty = not self._steps and not self._events
        if empty and not _trace.stats()['spans_total']:
            return None
        if path is None:
            path = default_dump_path()
        doc = self.snapshot(resolve_loss=False, signal_safe=signal_safe)
        doc['reason'] = reason or 'manual'
        # the watchdog's stall dump and an atexit/SIGTERM dump can
        # overlap; the counter bump rides the same crash-tolerant lock
        # as the ring reads (timeout, then proceed — never wedge a dump)
        with self._locked_for_dump():
            self.dumps += 1
        if _telem['on'] and not signal_safe:
            from . import metrics as _metrics
            _metrics.inc('mxnet_tpu_trace_flight_dumps_total')
        d = os.path.dirname(path)
        if d:
            # a not-yet-created MXTPU_FLIGHT_DIR must not silently lose
            # the post-mortem (same fix as memory.dump_oom)
            os.makedirs(d, exist_ok=True)
        from ..serialization import atomic_write_file
        atomic_write_file(path, json.dumps(doc, default=str).encode())
        return path

    def format_summary(self, last=8):
        """Human-readable tail for log embedding (the watchdog report)."""
        steps = self.steps()[-last:]
        events = self.events()[-last:]
        lines = ['--- flight recorder (last %d steps) ---' % len(steps)]
        for r in steps:
            top = sorted(r['spans_ms'].items(),
                         key=lambda kv: -kv[1]['self_ms'])[:4]
            spans = ' '.join(f"{n}={st['self_ms']:.1f}ms" for n, st in top)
            lines.append(
                f"step {r['step']}: interval={r.get('interval_ms', '?')}ms "
                f"loss={r.get('loss')} guard_ok={r.get('guard_ok', '?')} "
                f"{spans}")
        for e in events:
            lines.append(f"event {e['kind']}: "
                         + ' '.join(f'{k}={v}' for k, v in e.items()
                                    if k not in ('kind', 'time')))
        for s in _trace.open_spans():
            lines.append(f"open span {s['name']} on thread {s['thread']} "
                         f"for {s['age_ms']:.0f}ms")
        return '\n'.join(lines)

    def clear(self):
        with self._lock:
            self._steps.clear()
            self._events.clear()
            self._last_t = None
            self._pending.clear()


def default_dump_path():
    """Where a dump with no explicit path lands: MXTPU_FLIGHT_PATH when
    set, else MXTPU_FLIGHT_DIR (default: the system temp directory —
    never the CWD) + mxtpu_flight-<pid>.json. The pid suffix keeps the
    ranks of a multi-process job from clobbering each other's black
    box."""
    from .. import config as _config
    explicit = _config.get('MXTPU_FLIGHT_PATH')
    if explicit:
        return explicit
    d = _config.get('MXTPU_FLIGHT_DIR')
    if not d:
        import tempfile
        d = tempfile.gettempdir()
    return os.path.join(d, f'mxtpu_flight-{os.getpid()}.json')


_recorder = None
# RLock: get() runs inside the fatal-signal dump hooks — a signal
# interrupting the first-construction critical section on this very
# thread must re-enter, not self-deadlock (the PR-8 SIGTERM bug class;
# now enforced by tools/mxtpu_lint's signal-safety rule)
_recorder_lock = threading.RLock()
_hooks = {'atexit': False, 'signals': False}


def get() -> FlightRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
    return _recorder


def record_step(step, loss=None, guard_ok=None, extra=None):
    get().record_step(step, loss=loss, guard_ok=guard_ok, extra=extra)


def note(kind, /, **info):
    get().note(kind, **info)


def annotate_last(**fields):
    get().annotate_last(**fields)


def dump(path=None, reason='', signal_safe=False):
    return get().dump(path=path, reason=reason, signal_safe=signal_safe)


def _atexit_dump():
    try:
        get().dump(reason='atexit')
    except Exception:
        pass


def _make_signal_handler(signum, prev):
    def handler(sig, frame):
        try:
            get().dump(reason=f'signal:{_signal.Signals(sig).name}',
                       signal_safe=True)
        except Exception:
            pass
        if callable(prev):
            prev(sig, frame)             # chain (e.g. checkpoint SIGTERM)
        elif prev == _signal.SIG_DFL:
            _signal.signal(sig, _signal.SIG_DFL)
            _signal.raise_signal(sig)
    return handler


def install_crash_hooks(signals=(getattr(_signal, 'SIGTERM', None),
                                 getattr(_signal, 'SIGABRT', None))):
    """Register the atexit dump and chain fatal-signal handlers so any
    crash leaves the post-mortem artifact. Idempotent; signal hooks are
    skipped quietly off the main thread (signal.signal would raise)."""
    if not _hooks['atexit']:
        _hooks['atexit'] = True
        atexit.register(_atexit_dump)
    if not _hooks['signals']:
        try:
            for sig in signals:
                if sig is None:
                    continue
                prev = _signal.getsignal(sig)
                _signal.signal(sig, _make_signal_handler(sig, prev))
            _hooks['signals'] = True
        except ValueError:
            pass                         # not the main thread


# armed together with tracing: MXTPU_TRACE=1 runs always leave a black
# box behind (an explicit trace.enable() mid-run can call
# install_crash_hooks itself)
from .. import config as _config_mod  # noqa: E402

if _config_mod.get('MXTPU_TRACE'):
    install_crash_hooks()
