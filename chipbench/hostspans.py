"""The program's own host spans in a traced run: the ``mxtpu.``-prefixed
events on the host plane of the run's ``.xplane.pb``, with their nesting.
Shared by the readers ``layer_metrics/step_host_ms_per_step.py`` and
``step_enqueue_ms_per_step.py``.

Since PR 39 every ``mxnet_tpu.telemetry.trace.span(name)`` is a
``jax.profiler.TraceAnnotation('mxtpu.' + name)``, so a profile holds the
program's spans on ``/host:CPU`` beside the benchmark's three
(``chipbench.feed``, ``.dispatch``, ``.read_loss``), on the clock of the
device planes, one line per thread. ``xplane.load`` keeps only the
``chipbench.`` names; this file reads the others from the same file and
counts, as it does, seconds from the profile's start. A train step's are

    mxtpu.step.dispatch          ShardedTrainStep.__call__
      mxtpu.h2d.batch_put        the batch placed on the mesh
      mxtpu.step.compiled        the call of the executable: the enqueue
      mxtpu.step.gather          the donated buffers' views swapped

and where the device's queue is full the dispatch waits in its own Python
before the batch put, not in the executable's call (seen on the chip, PR
39: the key's split and the scalars' puts come first and need the queue
too). So both readers count only the dispatches that provably waited for
nothing (``free_dispatches``).

The span names are the benchmark's own strings, as the scope names of
``scopes.py`` are: a span renamed in the program shows as a metric gone
silent, not as a silently moved yardstick. The file is found as
``scopes.text_path`` finds ``step_program.hlo.txt``: the ``run`` a reader
is handed carries no output directory. A program from before PR 39 leaves
no such event: every function then returns nothing, and none raises.
"""
import collections
import glob
import os
import traceback

from chipbench import manifest, scopes, xplane

PREFIX = 'mxtpu.'
DISPATCH = PREFIX + 'step.dispatch'
ENQUEUE = PREFIX + 'step.compiled'
OUTSIDE = 'no mxtpu. span'

# one span and the spans opened inside it on its thread, in order
Span = collections.namedtuple('Span', 'event children')

_loaded = {}    # path -> [(line name, [Span])], read once a process


def trace_path(run):
    """The newest ``.xplane.pb`` of this run's output directory, as
    harness.profiled_window writes it; None where there is none."""
    found = sorted(glob.glob(os.path.join(
        os.path.dirname(scopes.text_path(run)), 'trace', 'plugins',
        'profile', '*', '*.xplane.pb')), key=os.path.getmtime)
    return found[-1] if found else None


def load(path):
    """[(line name, [Event])]: the ``mxtpu.`` events of each line (one
    thread) of the host plane that has any, by start and, at one start,
    the longer first."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            found = [xplane.Event(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                     for e in line.events if e.name.startswith(PREFIX)]
            if found:
                lines.append((line.name, sorted(
                    found, key=lambda e: (e.start, -e.end))))
    return lines


def forest(events):
    """One thread's events as trees: a span is the child of the innermost
    span that encloses it. ``events`` by start, the longer first."""
    roots, open_ = [], []
    for e in events:
        while open_ and e.start >= open_[-1].event.end:
            open_.pop()
        span = Span(e, [])
        (open_[-1].children if open_ else roots).append(span)
        open_.append(span)
    return roots


def of(run):
    """[(line name, [Span])] of this run's trace, [] where there is no
    file or it cannot be read. Never raises: a reader must not."""
    try:
        path = trace_path(run)
        if path is None:
            return []
        if path not in _loaded:
            _loaded[path] = [(name, forest(found))
                             for name, found in load(path)]
        return _loaded[path]
    except Exception:   # the harness calls read() bare
        print(f"[chipbench] hostspans.of failed:\n{traceback.format_exc()}",
              flush=True)
        return []


def walk(roots):
    for span in roots:
        yield span
        yield from walk(span.children)


def named(lines, name):
    """Every span of that name, on any thread at any depth, by start."""
    return sorted((s for _line, roots in lines for s in walk(roots)
                   if s.event.name == name), key=lambda s: s.event.start)


def seconds(span):
    return span.event.end - span.event.start


def inside(span, name):
    """Seconds of the spans named ``name`` directly inside ``span``."""
    return sum(seconds(c) for c in span.children if c.event.name == name)


def timeline(span):
    """[(what, seconds)] of a span from its start to its end: each child
    under its name less the prefix, and the span's own stretches between
    them under ''."""
    out, at = [], span.event.start
    for c in span.children:
        out.append(('', c.event.start - at))
        out.append((c.event.name[len(PREFIX):], seconds(c)))
        at = c.event.end
    out.append(('', span.event.end - at))
    return out


def free_dispatches(run, lines):
    """The ``mxtpu.step.dispatch`` spans that provably waited for nothing,
    by ``host_dispatch_ms_per_step``'s rule (its ``unblocked``, imported)
    put to the program's span: it began after the last read of the losses,
    when the device's queue was empty, and ended before the first step
    since then was done on any chip. [] without a reduced trace."""
    if run.trace is None:
        return []
    rule = manifest.load_module('layer_metrics', 'host_dispatch_ms_per_step')
    ends = [end for chip in run.trace['per_chip']
            for _start, end in chip['step_runs']]
    reads = [e for e in run.events['host'] if e.name == rule.READ]
    found = []
    for d in named(lines, DISPATCH):
        probe = xplane.Event(rule.DISPATCH, d.event.start, d.event.end)
        if rule.unblocked(sorted(reads + [probe], key=lambda e: e.start),
                          ends):
            found.append(d)
    return found


def _overlap(span, start, end):
    return max(0.0, min(span.event.end, end) - max(span.event.start, start))


def innermost(roots, start, end):
    """The name of the innermost span that covers most of (start, end):
    from the root that overlaps it most, down through each child that
    still covers over half of it. ``OUTSIDE`` where no root covers half."""
    name, level = OUTSIDE, roots
    while level:
        best = max(level, key=lambda s: _overlap(s, start, end))
        if 2 * _overlap(best, start, end) <= end - start:
            break
        name, level = best.event.name, best.children
    return name


def between_programs(run):
    """[(chip, start, end)] of the gaps on each chip's stream in which no
    program was running: the chip waits for the host there. Recomputed
    from the reduced trace's intervals and the loaded module events as
    xplane.reduce_chip does, which keeps only their sum by the
    benchmark's own annotation."""
    chips = run.trace['per_chip']
    ids = sorted(run.events['ops'])
    if len(ids) != len(chips):      # a chip reduced to nothing: which?
        return []
    found = []
    for chip, reduced in zip(ids, chips):
        ran = [x for xs in reduced['intervals'].values() for x in xs]
        if not ran:
            continue
        start, end = min(ran)[0], max(b for _a, b in ran)
        idle = xplane.gaps(xplane.union(ran), start, end)
        running = xplane.union(
            (m.start, m.end) for m in run.events['modules'].get(chip, []))
        found.extend((chip, a, b) for a, b in xplane.minus(idle, running))
    return found


def gaps_by_span(run, lines):
    """{span name: seconds}, mean over chips, of the between-program gaps
    put down to the innermost ``mxtpu.`` span of the step loop's thread
    that covers most of each."""
    roots = max((roots for _line, roots in lines), default=[],
                key=lambda r: sum(s.event.name == DISPATCH for s in walk(r)))
    chips = max(len(run.trace['per_chip']), 1)
    out = collections.Counter()
    for _chip, a, b in between_programs(run):
        out[innermost(roots, a, b)] += (b - a) / chips
    return out
