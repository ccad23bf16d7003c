"""Compilation observability: compile ledger, recompile forensics, and
persistent-cache telemetry.

Every jit/pjit build site (the ShardedTrainStep step program, gluon
CachedOp per block, the Trainer fused update, the io normalize program)
wraps its build in a :func:`begin`/:func:`end` pair.  While the pair is
open, ``jax.monitoring`` duration events attribute the compile's phase
wall time — ``compile.trace`` (jaxpr trace), ``compile.lower`` (MLIR
lowering), ``compile.backend`` (XLA backend compile) — to that site, and
the phases land as chrome complete events in the PR 6 trace rings.  On
:func:`end` a structured ledger entry (per-arg shape/dtype/sharding/
donation signature + flag knobs + phase seconds, keyed by a signature
fingerprint and the device kind) is appended to a bounded in-memory ring
and, when ``MXTPU_COMPILE_LEDGER`` names a path, to an on-disk JSONL
ledger written with the MXTPU_FLIGHT_DIR atomic-write convention (read,
append, bound, ``os.replace``) — a kill mid-write leaves the previous
ledger, never a truncated hybrid.

Recompile forensics: a second compile at a logically-same site diffs the
new signature against the ledger's last entry and names the churning
axis ("arg 3 `data`: shape (32, 128)→(32, 131)") in the
RecompileWarning, the ``compile.recompiled`` flight note, and the
``mxnet_tpu_compile_churn_axes`` metric.

Persistent cache: one rule says where it lives —
``JAX_COMPILATION_CACHE_DIR`` if set, else a fixed path in the
checkout — and entry points opt in with :func:`use_default_cache`;
``MXTPU_COMPILE_CACHE_DIR`` is the library-level knob and loses to the
JAX variable. Hit/miss/saved-seconds are counted from jax's own cache
events, with saved-seconds additionally estimated from the ledger's
recorded compile time for the hit fingerprint.

Disarmed (the default), every entry point is a single flag/dict check
and allocates nothing.  Validate a ledger file with
``tools/check_compile_ledger.py``.
"""

import collections
import hashlib
import json
import os
import tempfile
import threading
import time as _time

from . import metrics as _metrics
from . import trace as _trace
from .. import config as _config_mod

__all__ = [
    'enable', 'disable', 'enabled', 'clear',
    'begin', 'set_signature', 'end', 'abort', 'watching',
    'signature', 'arg_sig', 'array_sig', 'fingerprint', 'diff_signatures',
    'ledger', 'ledger_path', 'default_ledger_path',
    'in_flight', 'step_fields', 'snapshot_fields', 'health_fields',
    'persistent_cache_stats', 'enable_persistent_cache',
    'use_default_cache',
    'validate_ledger_entry', 'validate_ledger',
    'LEDGER_SCHEMA',
]

LEDGER_SCHEMA = 'mxtpu_compile_ledger_v1'

# required keys of one ledger entry (validate_ledger_entry enforces)
LEDGER_REQUIRED = ('schema', 'time', 'pid', 'site', 'nth', 'fingerprint',
                   'device_kind', 'signature', 'seconds')

_DEFAULT_RING = 256
_LEDGER_MAX_LINES = 512     # on-disk bound: keep the newest entries

_UNSET = object()

_state = {'on': False}
_lock = threading.RLock()
_cfg = {'ring': None, 'ledger': _UNSET, 'cache_dir': _UNSET, 'base': None}

_ring = collections.deque()              # ledger entries, oldest first
_sites = {}          # site -> {'n', 'signature', 'fingerprint'}
_inflight = {}       # tid -> {'site', 'phase', 'since', 'phase_since'}
_tls = threading.local()                 # .ctx: the open build context
_totals = {'n': 0, 'seconds': 0.0}
_last = {'fields': None, 'fresh': False}
_fp_seconds = {}     # fingerprint -> last recorded total compile seconds
_pcache = {'hits': 0, 'misses': 0, 'requests': 0,
           'saved': 0.0, 'saved_est': 0.0}
_hooks = {'armed': False}
_cache_state = {'applied': None}
_device = {'kind': None, 'backend': None}
_seed = {'done': False}
_ledger_err = {'warned': False}

# inferred in-flight phase after each jax.monitoring duration event: the
# event marks the END of its phase, so what runs NEXT is what a stuck
# rank is stuck in.
_EVT_PHASE = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'backend',
}
_NEXT_PHASE = {'trace': 'lower', 'lower': 'backend', 'backend': 'done'}


# ---------------------------------------------------------------------------
# enable / configuration
# ---------------------------------------------------------------------------

def enable():
    _state['on'] = True


def disable():
    _state['on'] = False


def enabled() -> bool:
    return _state['on']


def clear(ring=None, ledger=_UNSET, cache_dir=_UNSET):
    """Drop every sample/site/counter and (optionally) override the ring
    depth, the ledger path ('' disables disk, None restores the
    MXTPU_COMPILE_LEDGER default) and the persistent-cache dir."""
    with _lock:
        _ring.clear()
        _sites.clear()
        _inflight.clear()
        _fp_seconds.clear()
        _pcache.update(hits=0, misses=0, requests=0, saved=0.0,
                       saved_est=0.0)
        _totals.update(n=0, seconds=0.0)
        _last['fields'] = None
        _last['fresh'] = False
        _seed['done'] = False
        _cfg['ring'] = ring
        if ledger is not _UNSET:
            _cfg['ledger'] = ledger
        if cache_dir is not _UNSET:
            _cfg['cache_dir'] = cache_dir
            # keep _cache_state['applied'] — _ensure_persistent_cache
            # compares it against the new dir to re-point jax's cache
            # config (for '': back to the process's own directory, or
            # off if it has none)
    if cache_dir is not _UNSET:
        _ensure_persistent_cache()


def _ring_cap() -> int:
    n = _cfg['ring']
    return _DEFAULT_RING if n is None else max(1, int(n))


def default_ledger_path() -> str:
    d = _config_mod.get('MXTPU_FLIGHT_DIR') or tempfile.gettempdir()
    return os.path.join(d, f'mxtpu_compile_ledger-{os.getpid()}.jsonl')


def ledger_path():
    """The on-disk JSONL ledger path, or None when disk logging is off."""
    if _cfg['ledger'] is not _UNSET:
        return _cfg['ledger'] or None
    raw = _config_mod.get('MXTPU_COMPILE_LEDGER')
    if not raw:
        return None
    if raw.strip().lower() in ('1', 'on', 'true', 'yes'):
        return default_ledger_path()
    return raw


def ledger():
    """Snapshot of the in-memory ledger ring (oldest first)."""
    with _lock:
        return [dict(e) for e in _ring]


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_default_cache():
    """Turn the persistent cache on for this process — the one call
    chip_smoke.py, bench.py, tools/tune_bert_step.py and examples/ make.
    It lives where ``JAX_COMPILATION_CACHE_DIR`` says if that is set
    (jax reads the variable itself and nothing here points it anywhere
    else), otherwise at ``<checkout>/.jax_compile_cache`` — a fixed
    path, because the path is part of what a warm process must find
    again. Returns the directory."""
    with _lock:
        _cfg['base'] = os.path.join(_CHECKOUT, '.jax_compile_cache')
    return _ensure_persistent_cache()


def _cache_dir():
    # an explicit in-process directory (the cold/warm A/B drills hand one
    # to clear()/enable_persistent_cache()) holds until it is cleared;
    # then JAX_COMPILATION_CACHE_DIR, which nothing else may beat; then
    # what use_default_cache() chose; then the MXTPU_ knob
    override = _cfg['cache_dir']
    if override is not _UNSET and override:
        return override
    return os.environ.get('JAX_COMPILATION_CACHE_DIR') \
        or _cfg['base'] \
        or _config_mod.get('MXTPU_COMPILE_CACHE_DIR') or ''


def enable_persistent_cache(path):
    """Point jax's persistent compilation cache at `path` for this
    process (until ``clear(cache_dir='')`` hands it back to
    :func:`_cache_dir`'s rule) and apply it now."""
    with _lock:
        _cfg['cache_dir'] = path
        _cache_state['applied'] = None
    return _ensure_persistent_cache()


def _ensure_persistent_cache():
    d = _cache_dir()
    if (_cache_state['applied'] or '') == d:
        return d
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as _cc)
    if d:
        os.makedirs(d, exist_ok=True)
        # drop jax's eligibility gates so every program (including the
        # tiny ones tests and cold-start smoke runs compile) is cached
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    if (jax.config.jax_compilation_cache_dir or '') != d:
        # d == '' un-points a directory that was applied and is gone
        # (a drill's TemporaryDirectory): left pointed, every later
        # compile warns writing entries into the grave
        jax.config.update('jax_compilation_cache_dir', d or None)
    # jax latches the cache's initialized/disabled state at the FIRST
    # compile of the process — anything jitted before the dir was set
    # (import-time helpers, init ops) leaves it off without this re-init
    _cc.reset_cache()
    _cache_state['applied'] = d
    return d


def persistent_cache_stats():
    """Hit/miss/saved-seconds counters plus the on-disk byte footprint
    of the persistent cache directory (0 when unset/empty)."""
    d = _cache_dir()
    nbytes = 0
    entries = 0
    if d and os.path.isdir(d):
        for root, _dirs, files in os.walk(d):
            for f in files:
                try:
                    nbytes += os.path.getsize(os.path.join(root, f))
                    entries += 1
                except OSError:
                    pass
    with _lock:
        out = {'dir': d or None,
               'hits': _pcache['hits'], 'misses': _pcache['misses'],
               'requests': _pcache['requests'],
               'saved_seconds': round(_pcache['saved'], 6),
               'saved_seconds_est': round(_pcache['saved_est'], 6),
               'bytes': nbytes, 'files': entries}
    if _metrics.enabled():
        _metrics.set_gauge('mxnet_tpu_compile_persistent_cache_bytes',
                           nbytes)
    return out


# ---------------------------------------------------------------------------
# jax.monitoring listeners
# ---------------------------------------------------------------------------

def _arm_hooks():
    if _hooks['armed']:
        return
    with _lock:
        if _hooks['armed']:
            return
        _hooks['armed'] = True     # one attempt; listeners are permanent
        try:
            from jax import monitoring as _mon
            _mon.register_event_duration_secs_listener(_on_duration)
            _mon.register_event_listener(_on_event)
        except Exception:
            pass


def _on_duration(event, duration, **_kw):
    # fires synchronously on the compiling thread at the END of a phase
    phase = _EVT_PHASE.get(event)
    ctx = getattr(_tls, 'ctx', None)
    if phase is None:
        if event == '/jax/compilation_cache/compile_time_saved_sec':
            with _lock:
                _pcache['saved'] += duration
            if ctx is not None:
                ctx['cache']['saved_seconds'] = round(
                    ctx['cache'].get('saved_seconds', 0.0) + duration, 6)
        return
    if ctx is None:
        return
    ctx['phases'][phase] = ctx['phases'].get(phase, 0.0) + duration
    now = _time.time()
    _trace.complete('compile.' + phase, (now - duration) * 1e6,
                    duration * 1e6, site=ctx['site'])
    fl = _inflight.get(ctx['tid'])
    if fl is not None:
        fl['phase'] = _NEXT_PHASE.get(phase, phase)
        fl['phase_since'] = now


def _on_event(event, **_kw):
    if event == '/jax/compilation_cache/cache_hits':
        with _lock:
            _pcache['hits'] += 1
        ctx = getattr(_tls, 'ctx', None)
        if ctx is not None:
            ctx['cache']['hits'] = ctx['cache'].get('hits', 0) + 1
        if _metrics.enabled():
            _metrics.inc('mxnet_tpu_compile_persistent_cache_hits_total')
    elif event == '/jax/compilation_cache/cache_misses':
        with _lock:
            _pcache['misses'] += 1
        ctx = getattr(_tls, 'ctx', None)
        if ctx is not None:
            ctx['cache']['misses'] = ctx['cache'].get('misses', 0) + 1
        if _metrics.enabled():
            _metrics.inc('mxnet_tpu_compile_persistent_cache_misses_total')
    elif event == '/jax/compilation_cache/compile_requests_use_cache':
        with _lock:
            _pcache['requests'] += 1


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------

def arg_sig(name, shape=None, dtype=None, sharding=None, donated=False):
    """One argument's signature row."""
    return {'name': str(name),
            'shape': None if shape is None else [int(s) for s in shape],
            'dtype': None if dtype is None else str(dtype),
            'sharding': None if sharding is None else str(sharding),
            'donated': bool(donated)}


def array_sig(name, x, donated=False):
    """Signature row read off a jax/numpy array (sharding included when
    the array carries one)."""
    sharding = None
    s = getattr(x, 'sharding', None)
    if s is not None:
        try:
            spec = getattr(s, 'spec', None)
            sharding = str(spec) if spec is not None else str(s)
        except Exception:
            sharding = None
    return arg_sig(name, getattr(x, 'shape', None),
                   getattr(x, 'dtype', None), sharding, donated)


def signature(args=(), flags=None):
    """A build site's structured signature: per-arg rows + flag knobs
    (ZeRO stage, compression codec, donation policy, ...)."""
    return {'args': list(args), 'flags': dict(flags or {})}


def fingerprint(sig) -> str:
    """16-hex-digit stable fingerprint of a structured signature."""
    blob = json.dumps(sig, sort_keys=True, separators=(',', ':'),
                      default=str)
    return hashlib.sha256(blob.encode('utf-8')).hexdigest()[:16]


def diff_signatures(old, new):
    """Name every churning axis between two signatures: a list of
    ``{'axis': shape|dtype|sharding|donation|flag|arity, 'detail': ...}``
    rows whose `detail` strings are human-grade ("arg 3 `data`: shape
    (32, 128)→(32, 131)")."""
    out = []
    oa = old.get('args', []) or []
    na = new.get('args', []) or []
    if len(oa) != len(na):
        out.append({'axis': 'arity',
                    'detail': f'arg count {len(oa)}→{len(na)}'})
    for i, (o, n) in enumerate(zip(oa, na)):
        name = n.get('name') or o.get('name') or str(i)
        for key, label in (('shape', 'shape'), ('dtype', 'dtype'),
                           ('sharding', 'sharding'),
                           ('donated', 'donation')):
            ov, nv = o.get(key), n.get(key)
            if ov == nv:
                continue
            if key == 'shape':
                ov = tuple(ov) if ov is not None else None
                nv = tuple(nv) if nv is not None else None
                detail = f'arg {i} `{name}`: shape {ov}→{nv}'
            elif key == 'donated':
                detail = (f'arg {i} `{name}`: donation '
                          f'{bool(ov)}→{bool(nv)}')
            else:
                detail = f'arg {i} `{name}`: {label} {ov}→{nv}'
            out.append({'axis': label, 'arg': i, 'name': name,
                        'detail': detail})
    of = old.get('flags', {}) or {}
    nf = new.get('flags', {}) or {}
    for k in sorted(set(of) | set(nf)):
        if of.get(k) != nf.get(k):
            out.append({'axis': 'flag', 'name': k,
                        'detail': f'flag `{k}`: {of.get(k)!r}→'
                                  f'{nf.get(k)!r}'})
    return out


def _sig_str(sig) -> str:
    try:
        return json.dumps(sig, sort_keys=True, default=str)
    except Exception:
        return repr(sig)


# ---------------------------------------------------------------------------
# build contexts
# ---------------------------------------------------------------------------

def begin(site, _span=True):
    """Open a compile window for `site`.  Returns an opaque ctx to hand
    to :func:`set_signature` / :func:`end` / :func:`abort`, or None when
    the plane is disarmed (the persistent-cache knob is still applied —
    caching must not depend on the ledger being on)."""
    cache_dir = _ensure_persistent_cache()
    armed = _state['on']
    if not armed and not cache_dir:
        return None
    _arm_hooks()
    if not armed:
        return None
    _seed_fp_seconds()
    now = _time.time()
    tid = threading.get_ident()
    ctx = {'site': site, 't0': now, 'mono0': _time.perf_counter(),
           'tid': tid, 'phases': {}, 'cache': {}, 'signature': None,
           'prev': getattr(_tls, 'ctx', None), 'span': None}
    if _span:
        ctx['span'] = _trace.span('compile.build', site=site)
        ctx['span'].__enter__()
    _tls.ctx = ctx
    with _lock:
        _inflight[tid] = {'site': site, 'phase': 'build', 'since': now,
                          'phase_since': now}
    return ctx


def set_signature(ctx, sig):
    if ctx is not None:
        ctx['signature'] = sig


def _close(ctx, exc=False):
    if ctx.get('closed'):
        return
    ctx['closed'] = True
    if ctx.get('span') is not None:
        ctx['span'].__exit__(None, None, None)
        ctx['span'] = None
    _tls.ctx = ctx.get('prev')
    tid = ctx['tid']
    with _lock:
        prev = ctx.get('prev')
        if prev is not None:
            _inflight[tid] = {'site': prev['site'], 'phase': 'build',
                              'since': prev['t0'],
                              'phase_since': _time.time()}
        else:
            _inflight.pop(tid, None)


def abort(ctx):
    """Close a compile window without a ledger entry (trace failed, the
    site fell back to eager, an exception unwound the build)."""
    if ctx is None:
        return
    _close(ctx, exc=True)


def end(ctx):
    """Close the compile window: ledger entry (ring + disk), recompile
    forensics against the site's previous signature, phase metrics, and
    the persistent-cache attribution.  Returns the ledger entry."""
    if ctx is None or ctx.get('closed'):
        return None
    total = _time.perf_counter() - ctx['mono0']
    _close(ctx)
    now = _time.time()
    site = ctx['site']
    sig = ctx['signature'] or signature()
    fp = fingerprint(sig)

    with _lock:
        st = _sites.get(site)
        prev_sig = st['signature'] if st else None
        nth = (st['n'] if st else 0) + 1
        _sites[site] = {'n': nth, 'signature': sig, 'fingerprint': fp}

    axes = diff_signatures(prev_sig, sig) if prev_sig is not None else []
    detail = '; '.join(a['detail'] for a in axes)

    phases = ctx['phases']
    seconds = {'trace': round(phases.get('trace', 0.0), 6),
               'lower': round(phases.get('lower', 0.0), 6),
               'backend': round(phases.get('backend', 0.0), 6),
               'total': round(total, 6)}
    entry = {'schema': LEDGER_SCHEMA, 'time': round(now, 6),
             'pid': os.getpid(), 'site': site, 'nth': nth,
             'fingerprint': fp, 'device_kind': _device_kind(),
             'backend': _backend_name(), 'signature': sig,
             'seconds': seconds}
    if ctx['cache']:
        cache = dict(ctx['cache'])
        # saved-seconds estimate: what this fingerprint cost to compile
        # the last time the (possibly shared cross-process) ledger saw
        # it actually built — jax's own compile_time_saved_sec can go
        # negative for tiny programs, so keep both numbers
        if cache.get('hits'):
            est = _fp_seconds.get(fp)
            if est is not None:
                cache['saved_seconds_est'] = round(est, 6)
                with _lock:
                    _pcache['saved_est'] += est
                if _metrics.enabled():
                    _metrics.counter(
                        'mxnet_tpu_compile_persistent_cache_'
                        'saved_seconds_total').inc(est)
        entry['cache'] = cache
    if axes:
        entry['churn_axes'] = [a['detail'] for a in axes]

    with _lock:
        _ring.append(entry)
        cap = _ring_cap()
        while len(_ring) > cap:
            _ring.popleft()
        _totals['n'] += 1
        _totals['seconds'] += total
        if not entry.get('cache', {}).get('hits'):
            _fp_seconds[fp] = total
        _last['fields'] = {'site': site, 'nth': nth, 'fingerprint': fp,
                           'seconds': seconds['total'],
                           'backend_seconds': seconds['backend']}
        _last['fresh'] = True

    if _metrics.enabled():
        for ph in ('trace', 'lower', 'backend'):
            if seconds[ph]:
                _metrics.counter(
                    'mxnet_tpu_compile_phase_seconds_total').inc(
                        seconds[ph], site=site, phase=ph)
        _metrics.set_gauge('mxnet_tpu_compile_ledger_entries', len(_ring))

    if nth > 1:
        if _metrics.enabled():
            for a in axes:
                _metrics.inc('mxnet_tpu_compile_churn_axes', site=site,
                             axis=a['axis'])
        try:
            from . import flight as _flight
            _flight.note('compile.recompiled', site=site, nth=nth,
                         fingerprint=fp, seconds=seconds['total'],
                         axes=[a['detail'] for a in axes] or
                         ['identical signature (new program instance)'])
        except Exception:
            pass
    if entry.get('cache', {}).get('hits'):
        try:
            from . import flight as _flight
            _flight.note('compile.cache_hit', site=site, fingerprint=fp,
                         hits=entry['cache']['hits'],
                         saved_seconds=entry['cache'].get(
                             'saved_seconds',
                             entry['cache'].get('saved_seconds_est')),
                         saved_seconds_est=entry['cache'].get(
                             'saved_seconds_est'))
        except Exception:
            pass

    # the existing per-site compile counters + the episode-latched
    # RecompileWarning, now naming the exact churning axis
    if _metrics.enabled():
        _metrics.record_compile(site, _sig_str(sig), total, detail=detail)

    path = ledger_path()
    if path:
        _append_ledger(path, entry)
    return entry


class _Watch:
    """Armed `watching` context: a compile window that only records a
    ledger entry when jax actually compiled inside the block (cache-hot
    batches discard for free — no span, no entry)."""
    __slots__ = ('site', 'sig_fn', 'ctx')

    def __init__(self, site, sig_fn):
        self.site = site
        self.sig_fn = sig_fn

    def __enter__(self):
        self.ctx = begin(self.site, _span=False)
        return self

    def __exit__(self, etype, evalue, tb):
        ctx, self.ctx = self.ctx, None
        if ctx is None:
            return False
        if etype is not None or not ctx['phases']:
            abort(ctx)
            return False
        if self.sig_fn is not None:
            try:
                ctx['signature'] = self.sig_fn()
            except Exception:
                pass
        end(ctx)
        return False


class _NullWatch:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_WATCH = _NullWatch()


def watching(site, sig_fn=None):
    """Hot-path compile window (the io normalize program dispatches
    every batch): disarmed it is a shared no-op context; armed it opens
    a window that records only if a compile occurred.  `sig_fn` is
    evaluated lazily, only when an entry is written."""
    if not _state['on']:
        return _NULL_WATCH
    return _Watch(site, sig_fn)


# ---------------------------------------------------------------------------
# ledger disk
# ---------------------------------------------------------------------------

def _append_ledger(path, entry):
    try:
        from ..serialization import atomic_write_file
        old = b''
        try:
            with open(path, 'rb') as f:
                old = f.read()
        except FileNotFoundError:
            pass
        lines = old.splitlines() if old else []
        lines.append(json.dumps(entry, sort_keys=True,
                                default=str).encode('utf-8'))
        if len(lines) > _LEDGER_MAX_LINES:
            lines = lines[-_LEDGER_MAX_LINES:]
        atomic_write_file(path, b'\n'.join(lines) + b'\n')
    except Exception as e:
        if _metrics.enabled():
            _metrics.inc('mxnet_tpu_compile_ledger_errors_total')
        if not _ledger_err['warned']:
            _ledger_err['warned'] = True
            import warnings
            warnings.warn(f'telemetry.compile: ledger append to {path!r} '
                          f'failed ({e!r}); further failures are counted '
                          f'silently', RuntimeWarning, stacklevel=2)


def _seed_fp_seconds():
    """Load fingerprint->seconds from a pre-existing ledger file once,
    so a warm process can estimate persistent-cache saved-seconds from
    the cold process's recorded compile times."""
    if _seed['done']:
        return
    _seed['done'] = True
    path = ledger_path()
    if not path or not os.path.exists(path):
        return
    try:
        with open(path, 'rb') as f:
            for line in f.read().splitlines():
                if not line.strip():
                    continue
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                fp = e.get('fingerprint')
                sec = (e.get('seconds') or {}).get('total')
                if fp and sec and not (e.get('cache') or {}).get('hits'):
                    _fp_seconds.setdefault(fp, float(sec))
    except OSError:
        pass


# ---------------------------------------------------------------------------
# plane integration (flight / fleet / healthz / verdict)
# ---------------------------------------------------------------------------

def in_flight():
    """The oldest open compile window as ``{'site', 'phase',
    'elapsed_seconds'}``, or None.  One dict check when nothing is
    compiling — safe on the watchdog/verdict path."""
    if not _inflight:
        return None
    with _lock:
        if not _inflight:
            return None
        fl = min(_inflight.values(), key=lambda f: f['since'])
        return {'site': fl['site'], 'phase': fl['phase'],
                'elapsed_seconds': round(_time.time() - fl['since'], 3)}


def step_fields():
    """Compact fields for the flight-recorder step record — only on the
    first step after a compile (consume-on-read), so steady-state steps
    carry no compile noise.  Disarmed: one dict check, no allocation."""
    if not _state['on']:
        return None
    if not _last['fresh']:
        return None
    _last['fresh'] = False
    return _last['fields']


def snapshot_fields():
    """The fleet-heartbeat payload: cumulative compile count/seconds and
    the in-flight window (a rank stuck in compile.backend shows up in
    every peer's snapshot table), or None while disarmed."""
    if not _state['on']:
        return None
    out = {'n': _totals['n'], 'seconds': round(_totals['seconds'], 3)}
    fl = in_flight()
    if fl is not None:
        out['in_flight'] = fl
    return out


def health_fields():
    """The /healthz compile document — cold path, computed on demand."""
    out = {'enabled': _state['on'], 'compiles': _totals['n'],
           'seconds': round(_totals['seconds'], 3)}
    with _lock:
        if _ring:
            e = _ring[-1]
            out['last'] = {'site': e['site'], 'nth': e['nth'],
                           'fingerprint': e['fingerprint'],
                           'seconds': e['seconds']['total'],
                           'time': e['time']}
    fl = in_flight()
    if fl is not None:
        out['in_flight'] = fl
    p = ledger_path()
    if p:
        out['ledger_path'] = p
    if _cache_dir():
        out['persistent_cache'] = persistent_cache_stats()
    return out


def _device_kind():
    if _device['kind'] is None:
        try:
            import jax
            _device['kind'] = str(jax.devices()[0].device_kind)
        except Exception:
            return 'unknown'
    return _device['kind']


def _backend_name():
    if _device['backend'] is None:
        try:
            import jax
            _device['backend'] = str(jax.default_backend())
        except Exception:
            return 'unknown'
    return _device['backend']


# ---------------------------------------------------------------------------
# ledger validation (tools/check_compile_ledger.py + tests)
# ---------------------------------------------------------------------------

def validate_ledger_entry(e):
    """Problems with one ledger entry (empty list = valid)."""
    problems = []
    if not isinstance(e, dict):
        return [f'entry is {type(e).__name__}, not an object']
    if e.get('schema') != LEDGER_SCHEMA:
        problems.append(f"schema {e.get('schema')!r} != {LEDGER_SCHEMA!r}")
    for k in LEDGER_REQUIRED:
        if k not in e:
            problems.append(f'missing key {k!r}')
    if problems:
        return problems
    if not isinstance(e['site'], str) or not e['site']:
        problems.append('site must be a non-empty string')
    if not isinstance(e['nth'], int) or e['nth'] < 1:
        problems.append(f"nth {e['nth']!r} must be an int >= 1")
    sec = e['seconds']
    if not isinstance(sec, dict):
        problems.append('seconds must be an object')
    else:
        for k in ('trace', 'lower', 'backend', 'total'):
            v = sec.get(k)
            if not isinstance(v, (int, float)) or v < 0:
                problems.append(f'seconds.{k} {v!r} must be a number >= 0')
    sig = e['signature']
    if not isinstance(sig, dict) or 'args' not in sig:
        problems.append('signature must be an object with an args list')
    else:
        fp = fingerprint(sig)
        if fp != e['fingerprint']:
            problems.append(f"fingerprint {e['fingerprint']!r} does not "
                            f'match its signature (recomputed {fp!r})')
    return problems


def validate_ledger(entries):
    """Problems with a whole ledger: per-entry shape, monotone
    timestamps and nth per (pid, site), and the same-fingerprint ⇒
    same-signature invariant."""
    problems = []
    last_time = {}
    last_nth = {}
    fp_sig = {}
    for i, e in enumerate(entries):
        for p in validate_ledger_entry(e):
            problems.append(f'entry {i}: {p}')
        if not isinstance(e, dict) or 'time' not in e:
            continue
        pid = e.get('pid')
        t = e.get('time')
        if isinstance(t, (int, float)):
            lt = last_time.get(pid)
            if lt is not None and t < lt:
                problems.append(f'entry {i}: time {t} went backwards '
                                f'(previous {lt}) for pid {pid}')
            last_time[pid] = t
        key = (pid, e.get('site'))
        nth = e.get('nth')
        if isinstance(nth, int):
            ln = last_nth.get(key)
            if ln is not None and nth <= ln:
                problems.append(f'entry {i}: nth {nth} not increasing '
                                f'(previous {ln}) for site {key[1]!r}')
            last_nth[key] = nth
        fp = e.get('fingerprint')
        sig = e.get('signature')
        if fp is not None and sig is not None:
            seen = fp_sig.get(fp)
            if seen is None:
                fp_sig[fp] = sig
            elif seen != sig:
                problems.append(f'entry {i}: fingerprint {fp!r} maps to '
                                f'two different signatures')
    return problems


# config gate: MXTPU_COMPILE_LEDGER arms the plane at import (listener
# registration and the jax.config cache wiring both stay lazy — the
# telemetry package never imports jax at module import time)
if _config_mod.get('MXTPU_COMPILE_LEDGER'):
    enable()
