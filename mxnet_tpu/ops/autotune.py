"""Searched, not hardcoded: the Pallas kernel autotuner (ISSUE 18).

The flash-attention block shapes (G head-group, bq/bk sequence tiles)
were hand-picked constants in ``pallas_attention._block_sizes`` with raw
env overrides — exactly how round 3's Mosaic lowering failure (a 2-D
(1, bk) mask block violating the 8×128 trailing-tile rule) shipped.
This module converts that one hand-tuned hot path into a searched one:

1. **Legality enumerator** — :func:`legal_candidates` generates every
   (G, bq, bk) candidate for a (batch·heads, Tq, Tk, D, dtype, kind)
   kernel instance and statically rejects anything Mosaic would refuse
   to lower (the trailing-two-dims (sublane-multiple, 128-multiple)
   tile rule checked per operand block via :func:`tile_legal`), anything
   whose grid would strand head slices (G must divide BH), and anything
   over the ~16 MB scoped-VMEM budget (:func:`vmem_bytes`, the same
   arithmetic ``_block_sizes`` guards with). Illegal shapes are pruned
   BEFORE compile — never attempted.

2. **Measured sweep** — :func:`sweep_flash_attention` ranks survivors
   by the analytic cost model and, on a real TPU, AOT-compiles and
   times the top candidates (median of k reps; compile time excluded by
   timing only the pre-compiled executable, with each compile recorded
   through the PR 15 compile-ledger phases under the
   ``autotune:flash_attention`` site). On CPU backends the sweep
   degrades to legality-check + analytic ranking so the whole plumbing
   is testable chipless. Winners persist in an atomic JSON tuning DB
   keyed by (device_kind, kernel, shape-signature) under
   ``MXTPU_AUTOTUNE_DIR``.

3. **Build-time resolution** — ``_block_sizes`` calls :func:`resolve`,
   which applies the precedence **explicit env override > DB winner >
   caller defaults** (a sweep in progress force-feeds candidates at a
   higher, internal-only precedence), re-validates whatever won against
   the legality rules, clamps to the VMEM budget, and records the
   decision in a process-global registry. ``ShardedTrainStep`` folds
   :func:`decision_flags` into its compile-ledger signature, so a DB
   change that alters a consumed block shape is a named ``flag``
   recompile axis — not silent churn.

Telemetry: ``mxnet_tpu_autotune_*`` counters (candidates pruned/timed,
sweep seconds, DB hits/misses) and the ``autotune.sweep`` span, both
declared in tools/mxtpu_lint/contracts.py.
"""
from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import threading
import time
import warnings

import jax
import jax.numpy as jnp

from ..base import MXNetError, telem_flags as _telem

__all__ = [
    'sublane_min', 'tile_legal', 'fa_block_layouts', 'vmem_bytes',
    'check_candidate', 'legal_candidates', 'analytic_cost', 'shape_sig',
    'db_path', 'load_db', 'db_lookup', 'record_winner', 'resolve',
    'decisions', 'decision_flags', 'clear', 'forced',
    'sweep_flash_attention',
]

KERNEL_FA = 'flash_attention'
DB_BASENAME = 'mxtpu_autotune.json'
DB_VERSION = 1

# What vmem_bytes() may estimate for one kernel instance before a
# candidate is pruned. An estimate, not the compiler's accounting: the
# default blocks (estimated at 11.0 MiB forward, 5.5 MiB backward)
# compile on a v5e under libtpu 0.0.34's default scoped-VMEM limit
# (chip run, PR 23); where the real limit sits above that: not measured.
VMEM_BUDGET = 14 * 2 ** 20

_LANE = 128


def _metrics_mod():
    from ..telemetry import metrics as _metrics
    return _metrics


# ---------------------------------------------------------------------------
# Mosaic legality rules
# ---------------------------------------------------------------------------

def sublane_min(dtype) -> int:
    """Minimum second-to-last (sublane) tile dim for ``dtype``: 8 for
    4-byte types, 16 for bf16/fp16, 32 for 1-byte types."""
    size = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(size, 8)


def tile_legal(array_shape, block_shape, dtype):
    """Mosaic trailing-tile rule for ONE operand: each of the block's
    trailing two dims must be a multiple of the minimum tile (sublane
    for the second-to-last, 128 lanes for the last) OR equal to the
    whole array dim. Returns (ok, reason-or-None).

    Round 3's failure shape is the canonical counterexample: a 2-D
    key-mask block (1, 512) over a (BH, Tk) array — 1 is neither a
    multiple of 8 nor equal to BH, so Mosaic refuses to lower it (the
    fix rides the mask as (N, 1, Tk) with (rows, 1, bk) blocks, whose
    trailing-two dims (1, bk) match the array's (1, Tk) leading dim
    exactly)."""
    if len(array_shape) != len(block_shape):
        return False, (f"rank mismatch: block {block_shape} vs array "
                       f"{array_shape}")
    if len(block_shape) >= 2:
        sub, lane = block_shape[-2], block_shape[-1]
        asub, alane = array_shape[-2], array_shape[-1]
        if sub % sublane_min(dtype) and sub != asub:
            return False, (f"sublane dim {sub} is not a multiple of "
                           f"{sublane_min(dtype)} and != array dim {asub}")
        if lane % _LANE and lane != alane:
            return False, (f"lane dim {lane} is not a multiple of "
                           f"{_LANE} and != array dim {alane}")
    elif block_shape:
        if block_shape[0] % _LANE and block_shape[0] != array_shape[0]:
            return False, (f"lane dim {block_shape[0]} is not a multiple "
                           f"of {_LANE} and != array dim {array_shape[0]}")
    return True, None


def _pad_up(n, b):
    return -(-n // b) * b


def fa_block_layouts(BH, Tq, Tk, D, kind, G, bq, bk, N=None, rep=1):
    """(name, array_shape, block_shape) for every operand block the
    flash kernels of ``kind`` would instantiate at (G, bq, bk), including
    the bq/bk padding of the sequence dims. The kernels address the
    model's (N, T, H*D) arrays in lane blocks of W columns
    (``pallas_attention._lane_block``: 128 where D divides it, W // D
    heads side by side, else D). G heads a grid step are Gn batch rows
    of ``lb`` adjacent lane blocks: rows first, G // (W // D) of them,
    and what a batch of ``N`` rows cannot supply as lane blocks
    (``pallas_attention._step_heads``).
    The softmax row statistics (lse, delta) cross HBM with the queries
    on the lanes, (N, H, 1, Tq) in (Gn, heads of a row, 1, bq) blocks:
    bq, like bk for the key mask, is a multiple of 128 or the whole
    padded sequence. With ``rep`` query heads to a key/value head a
    step's query heads share one key/value lane block: the blocks listed
    are the forward's and dq's; dk/dv's are the whole group wide on the
    query side whatever G is. N is not in the tuning key: without it the
    batch is taken to fill G by rows and the arrays are written one lane
    block wide, their trailing two dims, which the tile rule reads, the
    kernels' own."""
    from . import pallas_attention as pa
    tq, tk = _pad_up(Tq, bq), _pad_up(Tk, bk)
    if N is None:
        W = _LANE if _LANE % D == 0 else D
        hb = heads = W // D
        rows, Gn, lq, lk = max(1, BH // hb), max(1, G // hb), 1, 1
        cq = ck = W
    else:
        rows, heads = N, BH // N
        cq, ck = heads * D, heads * D // rep
        W, hb = pa._lane_block(cq, D)
        Gn, lb = pa._step_heads(N, cq, D, G, rep)
        lq, lk = pa._step_lanes(kind, lb, rep)
    q_side = ((rows, tq, cq), (Gn, bq, lq * W))
    k_side = ((rows, tk, ck), (Gn, bk, lk * W))
    stat = ((rows, heads, 1, tq), (Gn, lq * hb, 1, bq))
    layouts = [('q',) + q_side, ('k',) + k_side, ('v',) + k_side,
               ('kmask', (rows, 1, tk), (Gn, 1, bk)), ('lse',) + stat]
    if kind == 'fwd':
        layouts.append(('out',) + q_side)
    else:
        layouts += [('do',) + q_side, ('delta',) + stat, ('dq',) + q_side,
                    ('dk',) + k_side, ('dv',) + k_side]
    return layouts


def vmem_bytes(G, bq, bk, D, kind, itemsize=4, kv=None):
    """Scoped-VMEM estimate for one kernel invocation of G heads a step,
    whichever way they come (rows or lane blocks): double-buffered IO
    blocks of ``itemsize`` bytes an element + f32 scratch accumulators +
    the live (bq, bk) f32 stack temporaries (~3 forward: s/p/pv; ~6
    backward: s/p/dp/ds/keep/pv). ``kv``: the heads on the key/value
    side of the step, fewer than G where grouped-query heads share a
    key/value block (default: G). The 256 columns a row beside D are two
    128-lane columns of float32: the forward's m and l, dq's lse and
    delta turned into columns; the statistics' own (1, bq) row blocks
    are a few KB and not counted. At 4 bytes and kv = G it is the
    arithmetic ``_block_sizes`` has guarded with since round 4."""
    n_tmp = 3 if kind == 'fwd' else 6
    kv = G if kv is None else kv
    return (2 * (G * bq + 2 * kv * bk) * D * itemsize
            + (G * bq + kv * bk) * (D + 256) * 4
            + n_tmp * bq * bk * 4)


def check_candidate(BH, Tq, Tk, D, dtype, kind, G, bq, bk, N=None, rep=1):
    """Full static legality of one (G, bq, bk) candidate. Returns
    (ok, reason-or-None); every reject reason names the rule so sweep
    reports and tests can assert WHY a shape was pruned. ``N`` and
    ``rep`` (query heads to a key/value head) where the caller knows
    them: :func:`fa_block_layouts`."""
    sub = sublane_min(dtype)
    if G < 1 or BH % G:
        return False, f"G={G} does not divide BH={BH}"
    if bq < 1 or bk < 1:
        return False, f"non-positive block ({bq}, {bk})"
    if bq % sub or bk % sub:
        # padded seq dims are always bq/bk multiples, so a non-multiple
        # block can never equal its array dim — reject outright
        return False, (f"blocks ({bq}, {bk}) not multiples of the "
                       f"{sub}-row sublane tile")
    for name, ashape, bshape in fa_block_layouts(BH, Tq, Tk, D, kind,
                                                 G, bq, bk, N, rep):
        ok, why = tile_legal(ashape, bshape, dtype)
        if not ok:
            return False, f"{name}: {why}"
    vb = vmem_bytes(G, bq, bk, D, kind, jnp.dtype(dtype).itemsize,
                    max(1, G // rep))
    if vb > VMEM_BUDGET:
        return False, (f"VMEM estimate {vb} exceeds the "
                       f"{VMEM_BUDGET}-byte budget")
    return True, None


def legal_candidates(BH, Tq, Tk, D, dtype, kind='fwd'):
    """All statically legal (G, bq, bk) candidates for one kernel
    instance, plus the count of enumerated-but-pruned shapes. The
    candidate space is geometric (powers of two from the sublane
    minimum up to the per-kind cap, plus the exact sequence length when
    it is itself tile-aligned) over every divisor of BH up to 16."""
    sub = sublane_min(dtype)
    cap = 512 if kind == 'fwd' else 256

    def _seq_cands(T):
        vals = set()
        b = sub
        while b <= min(cap, _pad_up(T, sub)):
            vals.add(b)
            b *= 2
        if T % sub == 0 and T <= cap:
            vals.add(T)
        return sorted(vals)

    gs = [g for g in (1, 2, 4, 8, 16) if g <= BH and BH % g == 0]
    out, pruned = [], 0
    for G in gs:
        for bq in _seq_cands(Tq):
            for bk in _seq_cands(Tk):
                ok, _why = check_candidate(BH, Tq, Tk, D, dtype, kind,
                                           G, bq, bk)
                if ok:
                    out.append((G, bq, bk))
                else:
                    pruned += 1
    if _telem['on']:
        _metrics_mod().inc(
            'mxnet_tpu_autotune_candidates_pruned_total', pruned)
    return out, pruned


def analytic_cost(BH, Tq, Tk, D, dtype, kind, G, bq, bk):
    """Deterministic cost estimate (model-seconds) used to rank legal
    candidates: HBM block traffic over ~8e11 B/s + a fixed ~2 µs
    per-grid-step dispatch overhead (the term G amortises) + the
    padding waste of non-dividing blocks. A ranking heuristic, not a
    simulator — on TPU the sweep measures the top of this ranking; on
    CPU it IS the ranking."""
    item = jnp.dtype(dtype).itemsize
    nq, nk = -(-Tq // bq), -(-Tk // bk)
    steps = (BH // G) * nq * nk
    # per grid step: q block + k/v blocks stream in, o writes once per
    # q-row (amortise over nk), mask/lse are noise
    per_step = G * bq * D * item + 2 * G * bk * D * item \
        + (G * bq * D * item) / nk
    hbm_s = steps * per_step / 8e11
    dispatch_s = steps * 2e-6
    waste = (nq * bq * nk * bk) / float(Tq * Tk)
    mult = 2.5 if kind == 'bwd' else 1.0   # bwd ~2 kernels + recompute
    return (hbm_s + dispatch_s) * waste * mult


# ---------------------------------------------------------------------------
# shape signatures + tuning DB
# ---------------------------------------------------------------------------

def shape_sig(BH, Tq, Tk, D, dtype, kind):
    """Canonical shape-signature key: BH{.}Tq{.}Tk{.}D{.}dtype.kind."""
    return (f"BH{int(BH)}.Tq{int(Tq)}.Tk{int(Tk)}.D{int(D)}."
            f"{jnp.dtype(dtype).name}.{kind}")


def device_kind():
    """The tuning DB's device key, as jax reports it."""
    return jax.devices()[0].device_kind.replace(' ', '_')


def db_path(dir_=None):
    """Path of the tuning DB under ``dir_`` (default: the registered
    ``MXTPU_AUTOTUNE_DIR`` knob), or None when no directory is set."""
    if dir_ is None:
        from .. import config as _config
        dir_ = _config.get('MXTPU_AUTOTUNE_DIR')
    if not dir_:
        return None
    return os.path.join(dir_, DB_BASENAME)


_lock = threading.Lock()
_db_cache = {}          # path -> (mtime, size, doc)
_corrupt_warned = set()  # paths already warned about


def load_db(path):
    """Parsed tuning DB at ``path`` ({} when absent). A corrupt or
    truncated DB falls back to {} — defaults stay in force — with ONE
    warning per path per process (an unreadable tuning cache must never
    take down training)."""
    try:
        st = os.stat(path)
    except OSError:
        return {}
    key = (st.st_mtime_ns, st.st_size)
    with _lock:
        cached = _db_cache.get(path)
        if cached is not None and cached[0] == key:
            return cached[1]
    doc = {}
    try:
        with open(path, 'rb') as f:
            raw = json.loads(f.read().decode('utf-8'))
        if not isinstance(raw, dict) or 'entries' not in raw \
                or not isinstance(raw['entries'], dict):
            raise ValueError('missing "entries" table')
        doc = raw
    except Exception as e:
        with _lock:
            first = path not in _corrupt_warned
            _corrupt_warned.add(path)
        if first:
            warnings.warn(
                f"autotune DB {path!r} is corrupt or truncated ({e}); "
                f"falling back to built-in block-size defaults",
                RuntimeWarning)
        return {}
    with _lock:
        _db_cache[path] = (key, doc)
    return doc


def db_lookup(kernel, sig, dir_=None):
    """DB winner blocks (G, bq, bk) for (device_kind, kernel, sig), or
    None. Counts mxnet_tpu_autotune_db_{hits,misses}_total."""
    path = db_path(dir_)
    if path is None:
        return None
    doc = load_db(path)
    entry = doc.get('entries', {}).get(f"{device_kind()}/{kernel}/{sig}")
    if entry is None:
        if _telem['on']:
            _metrics_mod().inc('mxnet_tpu_autotune_db_misses_total')
        return None
    try:
        g, bq, bk = (int(x) for x in entry['blocks'])
    except Exception:
        if _telem['on']:
            _metrics_mod().inc('mxnet_tpu_autotune_db_misses_total')
        return None
    if _telem['on']:
        _metrics_mod().inc('mxnet_tpu_autotune_db_hits_total')
    return g, bq, bk


def record_winner(kernel, sig, blocks, info=None, dir_=None):
    """Atomically merge one winner into the tuning DB (read-modify-
    write through serialization.atomic_write_file, so a concurrent
    reader sees either the old or the new complete file, never a torn
    one). Returns the DB path."""
    path = db_path(dir_)
    if path is None:
        raise MXNetError(
            "autotune: no tuning-DB directory — set MXTPU_AUTOTUNE_DIR "
            "or pass dir_= to record_winner()")
    os.makedirs(os.path.dirname(path) or '.', exist_ok=True)
    doc = load_db(path)
    if not doc:
        doc = {'version': DB_VERSION, 'entries': {}}
    entry = {'blocks': [int(b) for b in blocks]}
    if info:
        entry.update(info)
    doc['entries'][f"{device_kind()}/{kernel}/{sig}"] = entry
    from ..serialization import atomic_write_file
    atomic_write_file(path, json.dumps(doc, indent=1,
                                       sort_keys=True).encode('utf-8'))
    with _lock:
        _db_cache.pop(path, None)
    return path


# ---------------------------------------------------------------------------
# build-time resolution (the _block_sizes seam)
# ---------------------------------------------------------------------------

_forced = {}      # kernel-kind -> (G, bq, bk), sweep-internal precedence
_decisions = {}   # "kernel:sig" -> decision dict, process-global


@contextlib.contextmanager
def forced(kernel, kind, blocks):
    """Sweep-internal context: ``resolve`` returns ``blocks`` for every
    (kernel, kind) instance traced inside — how the sweep compiles each
    candidate without touching the user-facing env/DB precedence."""
    key = (kernel, kind)
    with _lock:
        prev = _forced.get(key)
        _forced[key] = tuple(int(b) for b in blocks)
    try:
        yield
    finally:
        with _lock:
            if prev is None:
                _forced.pop(key, None)
            else:
                _forced[key] = prev


def _env_overrides(kind):
    """Registered MXTPU_FA_{G,BQ,BK} / MXTPU_FA_BWD_* knob values
    (None when unset — 0 and negatives mean unset too, so a knob can be
    explicitly neutralised)."""
    from .. import config as _config
    pre = 'MXTPU_FA_BWD_' if kind == 'bwd' else 'MXTPU_FA_'
    out = {}
    for field in ('G', 'BQ', 'BK'):
        val = _config.get(pre + field)
        out[field.lower()] = int(val) if val and val > 0 else None
    return out


def resolve(kernel, BH, Tq, Tk, D, dtype, kind, default, rep=1):
    """The block shapes a kernel build should use, with precedence
    (sweep-forced) > env override > DB winner > ``default``, followed
    by the safety clamps ``_block_sizes`` has always applied (G to a
    divisor of BH, then down until the VMEM estimate fits the budget;
    with ``rep`` query heads to a key/value head G // rep heads on the
    key/value side). Records the decision — source included — for the
    compile-ledger signature (:func:`decision_flags`)."""
    sig = shape_sig(BH, Tq, Tk, D, dtype, kind)
    with _lock:
        force = _forced.get((kernel, kind))
    env = _env_overrides(kind)
    if force is not None:
        G, bq, bk = force
        source = 'forced'
    elif any(v is not None for v in env.values()):
        base = db_lookup(kernel, sig) or default
        G = env['g'] if env['g'] is not None else base[0]
        bq = env['bq'] if env['bq'] is not None else base[1]
        bk = env['bk'] if env['bk'] is not None else base[2]
        source = 'env'
    else:
        win = db_lookup(kernel, sig)
        if win is not None:
            G, bq, bk = win
            source = 'db'
        else:
            G, bq, bk = default
            source = 'default'
    # clamp G to a divisor of BH — a non-divisor would leave BH % G
    # head slices outside the grid with uninitialized outputs
    G = max(1, min(int(G), BH))
    while BH % G:
        G -= 1
    # scoped-VMEM guard: shrink G (to the next smaller divisor) until
    # the estimate fits — identical to the historical _block_sizes loop
    while G > 1 and vmem_bytes(G, bq, bk, D, kind,
                               jnp.dtype(dtype).itemsize,
                               max(1, G // rep)) > VMEM_BUDGET:
        G -= 1
        while BH % G:
            G -= 1
    decision = {'blocks': (G, bq, bk), 'source': source}
    with _lock:
        _decisions[f"{kernel}:{sig}"] = decision
    return G, bq, bk


def decisions():
    """Snapshot of every block-shape decision made in this process:
    {"kernel:shape-sig": {'blocks': (G, bq, bk), 'source': ...}}."""
    with _lock:
        return {k: dict(v) for k, v in _decisions.items()}


def decision_flags():
    """The decisions as a flat {key: "source:GxBQxBK"} dict — the form
    ShardedTrainStep folds into its compile-ledger signature flags, so
    a DB change that alters a consumed shape surfaces as a named
    ``flag`` recompile axis in the forensics diff."""
    with _lock:
        return {k: f"{v['source']}:{'x'.join(map(str, v['blocks']))}"
                for k, v in sorted(_decisions.items())}


def clear():
    """Reset decision registry, DB cache and corrupt-DB warnings
    (tests; a fresh process starts clean anyway)."""
    with _lock:
        _decisions.clear()
        _db_cache.clear()
        _corrupt_warned.clear()
        _forced.clear()


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _time_candidate(fn, args, reps):
    """(compile_seconds, median_run_seconds) of ``fn`` at ``args``:
    AOT lower+compile first (wrapped in a compile-ledger window so the
    trace/lower/backend phase split lands in the PR 15 ledger), then
    time ``reps`` executions of the pre-compiled program — compile time
    is excluded from the medians by construction."""
    from ..telemetry import compile as _compile
    cctx = _compile.begin(f'autotune:{KERNEL_FA}')
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    except BaseException:
        _compile.abort(cctx)
        raise
    _compile.end(cctx)
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))     # one warm run
    runs = []
    for _ in range(reps):
        t1 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        runs.append(time.perf_counter() - t1)
    runs.sort()
    return compile_s, runs[len(runs) // 2]


def sweep_flash_attention(batch=1, heads=12, seq=512, head_dim=64,
                          dtype=jnp.float32, kinds=('fwd', 'bwd'),
                          reps=5, max_timed=8, db_dir=None, measure=None,
                          causal=False):
    """Sweep the flash-attention block space for one shape and persist
    the winners in the tuning DB.

    measure: None (auto — time candidates only when a real TPU is
    present; CPU interpret-mode timings are meaningless so the sweep
    degrades to the analytic ranking), or an explicit bool. Only the
    ``max_timed`` analytically-best survivors are compiled and timed —
    the legality enumerator has already pruned everything Mosaic would
    reject, so every compile in the sweep is expected to succeed.

    Returns {kind: {winner, source, candidates, pruned, ranking}} plus
    a 'db' entry naming the persisted file."""
    from .pallas_attention import flash_attention, pallas_available
    from ..telemetry import trace as _trace
    if measure is None:
        measure = pallas_available()
    BH = batch * heads
    report = {'shape': {'batch': batch, 'heads': heads, 'seq': seq,
                        'head_dim': head_dim,
                        'dtype': jnp.dtype(dtype).name},
              'device_kind': device_kind(),
              'mode': 'measured' if measure else 'analytic'}
    t_sweep = time.perf_counter()
    with _trace.span('autotune.sweep', kernel=KERNEL_FA,
                     shape=f"b{batch}h{heads}s{seq}d{head_dim}"):
        for kind in kinds:
            cands, pruned = legal_candidates(BH, seq, seq, head_dim,
                                             dtype, kind)
            if not cands:
                raise MXNetError(
                    f"autotune: no legal ({kind}) candidate for "
                    f"BH={BH} T={seq} D={head_dim} — the shape cannot "
                    f"ride the flash kernel at all")
            ranked = sorted(
                cands, key=lambda c: analytic_cost(
                    BH, seq, seq, head_dim, dtype, kind, *c))
            rows = []
            if measure:
                q = jnp.zeros((batch, heads, seq, head_dim), dtype)
                timed = 0
                for cand in ranked[:max_timed]:
                    if kind == 'fwd':
                        def fn(q_, c=cand):
                            with forced(KERNEL_FA, 'fwd', c):
                                return flash_attention(q_, q_, q_,
                                                       causal=causal)
                    else:
                        def fn(q_, c=cand):
                            with forced(KERNEL_FA, 'bwd', c):
                                return jax.grad(
                                    lambda x: flash_attention(
                                        x, x, x,
                                        causal=causal).sum())(q_)
                    try:
                        compile_s, med = _time_candidate(fn, (q,), reps)
                    except Exception as e:  # pragma: no cover - chip only
                        rows.append({'blocks': list(cand),
                                     'error': str(e)[:200]})
                        continue
                    timed += 1
                    rows.append({'blocks': list(cand),
                                 'median_ms': round(med * 1e3, 4),
                                 'compile_s': round(compile_s, 3)})
                if _telem['on']:
                    _metrics_mod().inc(
                        'mxnet_tpu_autotune_candidates_timed_total',
                        timed)
                good = [r for r in rows if 'median_ms' in r]
                if not good:
                    raise MXNetError(
                        f"autotune: every timed ({kind}) candidate "
                        f"failed — see the sweep report rows")
                winner = min(good, key=lambda r: r['median_ms'])
                win_blocks = tuple(winner['blocks'])
                info = {'source': 'measured',
                        'median_ms': winner['median_ms'], 'reps': reps}
            else:
                for cand in ranked[:max_timed]:
                    rows.append({'blocks': list(cand),
                                 'analytic_ms': round(analytic_cost(
                                     BH, seq, seq, head_dim, dtype,
                                     kind, *cand) * 1e3, 4)})
                win_blocks = ranked[0]
                info = {'source': 'analytic',
                        'analytic_ms': rows[0]['analytic_ms']}
            sig = shape_sig(BH, seq, seq, head_dim, dtype, kind)
            path = record_winner(KERNEL_FA, sig, win_blocks, info,
                                 dir_=db_dir)
            report['db'] = path
            report[kind] = {'winner': list(win_blocks),
                            'source': info['source'],
                            'candidates': len(cands), 'pruned': pruned,
                            'signature': sig, 'ranking': rows}
    sweep_s = time.perf_counter() - t_sweep
    if _telem['on']:
        _metrics_mod().inc(
            'mxnet_tpu_autotune_sweep_seconds_total', sweep_s)
    report['sweep_seconds'] = round(sweep_s, 3)
    return report
