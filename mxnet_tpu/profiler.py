"""Profiler: scoped tracing with chrome://tracing JSON output.

Ref: src/profiler/profiler.h:79,251-299 and python/mxnet/profiler.py. On TPU
the heavy lifting is jax.profiler (XLA/TPU traces viewable in TensorBoard or
Perfetto); this module keeps the reference's API (set_config, start/stop,
scoped Task/Frame/Event/Counter/Marker) and emits a chrome-tracing JSON of
python-level scopes, while optionally also capturing a jax device trace.
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax

from .base import MXNetError, prof_flags as _prof_flags
from .telemetry import trace as _trace_mod

_config = {
    'filename': 'profile.json',
    'profile_all': False,
    'profile_symbolic': False,
    'profile_imperative': False,
    'profile_memory': False,
    'profile_api': False,
    'aggregate_stats': False,
    'continuous_dump': False,
    # block each profiled op to completion before timing it: true device
    # time instead of dispatch time, at the cost of pipelining
    'profile_sync': False,
    # directory for the jax/XLA device trace started by start(); replaces
    # the old MXNET_TPU_JAX_TRACE_DIR env-only path (still honored)
    'jax_trace_dir': None,
}
_state = {'running': False, 'jax_trace_dir': None,
          # whether THIS run has already dumped to the configured file:
          # continuous_dump only extends a file this run wrote — a
          # leftover trace from a previous run/process is overwritten,
          # never merged into the new timeline
          'dumped_in_run': False}
_events = []
_events_lock = threading.Lock()
# op name -> [count, total_us, min_us, max_us] (aggregate_stats)
_op_stats = {}


def record_op(name, dur_us):
    """One per-op profiler row (called from _imperative.invoke when
    profile_imperative/profile_all is active)."""
    now = time.time() * 1e6
    # tid from the shared trace registry: profiler op rows and telemetry
    # spans land in ONE stable small-int tid space (+ thread names)
    ev = {'name': name, 'cat': 'operator', 'ph': 'X',
          'ts': now - dur_us, 'dur': dur_us,
          'pid': os.getpid(), 'tid': _trace_mod.tid_for_current_thread()}
    with _events_lock:
        _events.append(ev)
        st = _op_stats.get(name)
        if st is None:
            _op_stats[name] = [1, dur_us, dur_us, dur_us]
        else:
            st[0] += 1
            st[1] += dur_us
            st[2] = min(st[2], dur_us)
            st[3] = max(st[3], dur_us)


def get_summary(reset=False):
    """Aggregate per-op table (ref: profiler.py dumps(aggregate_stats)):
    name, calls, total/min/max/avg in ms."""
    with _events_lock:
        rows = sorted(_op_stats.items(), key=lambda kv: -kv[1][1])
        if reset:
            _op_stats.clear()
    lines = [f"{'Name':<40s}{'Total Count':>12s}{'Time (ms)':>12s}"
             f"{'Min (ms)':>12s}{'Max (ms)':>12s}{'Avg (ms)':>12s}"]
    for name, (cnt, tot, mn, mx) in rows:
        lines.append(f"{name[:39]:<40s}{cnt:>12d}{tot / 1e3:>12.4f}"
                     f"{mn / 1e3:>12.4f}{mx / 1e3:>12.4f}"
                     f"{tot / cnt / 1e3:>12.4f}")
    return '\n'.join(lines)


def set_config(**kwargs):
    """Ref: python/mxnet/profiler.py set_config. profile_imperative /
    profile_all turn on per-op rows (one entry per imperative op dispatch,
    the analog of the reference wrapping engine pushes,
    src/profiler/profiler.h:299); takes effect immediately if the
    profiler is already running."""
    unknown = [k for k in kwargs if k not in _config]
    if unknown:
        raise MXNetError(
            f"profiler.set_config: unknown keys {unknown!r}")
    _config.update(kwargs)
    _sync_flags()


def _sync_flags():
    _prof_flags['op'] = bool(_state['running'] and (
        _config['profile_imperative'] or _config['profile_all']))
    _prof_flags['sync'] = bool(_config['profile_sync']
                               or _config['aggregate_stats'])


def profiler_set_config(mode='symbolic', filename='profile.json'):
    _config['filename'] = filename


def set_state(state='stop', profile_process='worker'):
    if state == 'run':
        start()
    else:
        stop()


def start(profile_process='worker'):
    _state['running'] = True
    with _events_lock:
        # both clears under the lock: a worker thread appending through
        # record_op/_emit must never interleave with a half-done reset
        _events.clear()
        _op_stats.clear()
    _state['dumped_in_run'] = False
    _sync_flags()
    from . import config as _envcfg
    tdir = _config['jax_trace_dir'] or \
        _envcfg.get('MXNET_TPU_JAX_TRACE_DIR')
    if tdir:
        jax.profiler.start_trace(tdir)
        _state['jax_trace_dir'] = tdir


def stop(profile_process='worker'):
    _state['running'] = False
    _sync_flags()
    if _state['jax_trace_dir']:
        jax.profiler.stop_trace()
        _state['jax_trace_dir'] = None


def pause(profile_process='worker'):
    _state['running'] = False
    _sync_flags()


def resume(profile_process='worker'):
    _state['running'] = True
    _sync_flags()


def _telemetry_events():
    """Telemetry counters/gauges as chrome 'C' events, merged into the
    trace stream so the counter tracks render alongside the op scopes."""
    try:
        from . import telemetry
        if telemetry.enabled():
            return telemetry.chrome_events()
    except Exception:
        pass
    return []


def _span_events():
    """Balanced span events (+ thread-name metadata) from the step
    tracer, merged into the same traceEvents array as the op rows and
    counter tracks — ONE chrome://tracing-loadable stream, one stable
    pid/tid space. Empty when tracing is disarmed or has no spans."""
    try:
        evs = _trace_mod.chrome_events(flush_open=True)
        if not evs:
            return []
        return _trace_mod.thread_metadata() + evs
    except Exception:
        return []


def dump(finished=True, profile_process='worker'):
    """Write chrome://tracing JSON (ref: profiler.h:79 'chrome tracing').

    With continuous_dump set, events already written are cleared from
    memory and the on-disk trace is extended in place, so repeated dumps
    neither re-emit nor unboundedly regrow the same trace. Telemetry 'C'
    counters and step-tracer spans are folded into the same traceEvents
    array (span events dedupe across continuous dumps — the tracer's
    rings are snapshots, not drains)."""
    continuous = _config['continuous_dump']
    with _events_lock:
        new_events = list(_events)
        if continuous:
            _events.clear()
    events = new_events + _telemetry_events() + _span_events()
    if continuous and _state['dumped_in_run'] \
            and os.path.exists(_config['filename']):
        try:
            with open(_config['filename']) as f:
                prev = json.load(f).get('traceEvents', [])
        except (OSError, ValueError):
            prev = []
        seen = {(e.get('name'), e.get('ph'), e.get('ts'), e.get('tid'))
                for e in prev}
        events = prev + [e for e in events
                         if (e.get('name'), e.get('ph'), e.get('ts'),
                             e.get('tid')) not in seen]
    events = _trace_mod.balance_events(events)
    trace = {'traceEvents': events, 'displayTimeUnit': 'ms'}
    with open(_config['filename'], 'w') as f:
        json.dump(trace, f)
    _state['dumped_in_run'] = True


def dumps(reset=False, format='table'):
    """Aggregate-stats table when aggregate_stats is configured (the
    reference's dumps contract, python/mxnet/profiler.py:dumps), else the
    chrome-trace JSON of collected events (incl. per-op rows)."""
    if _config['aggregate_stats'] and format == 'table':
        out = get_summary(reset=reset)
        if reset:
            with _events_lock:
                _events.clear()
        return out
    with _events_lock:
        evs = list(_events)
        if reset:
            _events.clear()
            _op_stats.clear()
    return json.dumps({'traceEvents': _trace_mod.balance_events(
        evs + _telemetry_events() + _span_events())})


def _emit(name, cat, ph, ts=None, args=None, dur=None):
    ev = {'name': name, 'cat': cat, 'ph': ph,
          'ts': (ts if ts is not None else time.time() * 1e6),
          'pid': os.getpid(), 'tid': _trace_mod.tid_for_current_thread()}
    if args:
        ev['args'] = args
    if dur is not None:
        ev['dur'] = dur
    with _events_lock:
        _events.append(ev)


class _Scope:
    def __init__(self, name, cat):
        self.name = name
        self.cat = cat
        self._t0 = None

    def start(self):
        self._t0 = time.time() * 1e6
        if _state['running']:
            _emit(self.name, self.cat, 'B', self._t0)
        return self

    def stop(self):
        if _state['running']:
            _emit(self.name, self.cat, 'E')

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class Task(_Scope):
    def __init__(self, domain, name):
        super().__init__(name, f'task/{domain.name}')


class Frame(_Scope):
    def __init__(self, domain, name):
        super().__init__(name, f'frame/{domain.name}')


class Event(_Scope):
    def __init__(self, name):
        super().__init__(name, 'event')


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self.value = value if value is not None else 0
        if value is not None:
            self._record()

    def _record(self):
        if _state['running']:
            _emit(self.name, f'counter/{self.domain.name}', 'C',
                  args={self.name: self.value})

    def set_value(self, value):
        self.value = value
        self._record()

    def increment(self, delta=1):
        self.value += delta
        self._record()

    def decrement(self, delta=1):
        self.value -= delta
        self._record()

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope='process'):
        if _state['running']:
            _emit(self.name, f'marker/{self.domain.name}', 'I')


def scope(name='<unk>:'):
    return _Scope(name, 'scope')
