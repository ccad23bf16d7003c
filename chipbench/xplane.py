"""From a profiler trace to numbers: which planes and lines of the
``.xplane.pb`` are read, how device time splits into the flash kernels,
the collectives and everything else XLA compiled, how much of the window
the chip sat idle, and what the benchmark's loop was doing in each gap.

What the trace of a v5e holds (looked at by hand, PR 24; PERF.md section
3 has the account): one plane ``/device:TPU:<n>`` per chip. Its line
``XLA Ops`` carries one event per executed HLO instruction, in order, on
the core's one stream; an event's name is the instruction's text,
``%fusion.12 = bf16[..] fusion(..), kind=kLoop, ...``, so it begins with
the name the instruction has in the program's optimized HLO. ``Async XLA
Ops`` carries what runs beside that stream (copies between memories,
collectives in flight), from start to done. ``XLA Modules`` carries one
event per program execution, ``jit_stable_step(<fingerprint>)`` for the
step. The host's threads are lines of the plane ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` events appear under the names the
benchmark gave them. All lines count nanoseconds from the profile's start.

Only intervals are reduced here, so the arithmetic can be checked on a
hand-built trace (tests/test_trace_reduction.py).
"""
import bisect
import collections
import re

from chipbench import hlo

LINES = {'XLA Ops': 'ops', 'Async XLA Ops': 'async',
         'XLA Modules': 'modules'}
INSTRUCTION = re.compile(r'^%?([^\s=]+) = ')
DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
HOST_PLANE = '/host:CPU'
ANNOTATION_PREFIX = 'chipbench.'
OTHER_PROGRAMS = 'programs dispatched between steps'

Event = collections.namedtuple('Event', 'name start end')


# ---------------------------------------------------------------------------
# reading the file
# ---------------------------------------------------------------------------

def load(path):
    """{'ops', 'async', 'modules': {chip: [Event]}, 'host': [Event],
    'text': {op name: the instruction's text}, 'planes': [(name, [line
    names])]} from an ``.xplane.pb``. Times are seconds from the profile's
    start; a device op is named by its instruction name alone."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {'ops': {}, 'async': {}, 'modules': {}, 'host': [], 'text': {},
           'planes': []}

    def events(line, keep=lambda name: True, short=False):
        found = []
        for e in line.events:
            name = e.name
            if not keep(name):
                continue
            if short:
                m = INSTRUCTION.match(name)
                if m:
                    out['text'].setdefault(m.group(1), name)
                    name = m.group(1)
            found.append(Event(name, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9))
        return sorted(found, key=lambda e: e.start)

    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        out['planes'].append((plane.name, [ln.name for ln in lines]))
        for line in lines:
            if device and line.name in LINES:
                key = LINES[line.name]
                out[key][int(device.group(1))] = events(
                    line, short=key != 'modules')
            elif plane.name == HOST_PLANE:
                out['host'].extend(events(
                    line, lambda name: name.startswith(ANNOTATION_PREFIX)))
    out['host'].sort(key=lambda e: e.start)
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals):
    """Sorted, disjoint (start, end) pairs covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def length(merged):
    return sum(end - start for start, end in merged)


def minus(merged, other):
    """The part of one disjoint sorted list that another does not cover."""
    out, j = [], 0
    for start, end in merged:
        at = start
        while j < len(other) and other[j][1] <= at:
            j += 1
        k = j
        while k < len(other) and other[k][0] < end:
            if other[k][0] > at:
                out.append((at, other[k][0]))
            at = max(at, other[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def gaps(merged, start, end):
    return minus([(start, end)], merged)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def _host_activity(host, start, end):
    """The annotation covering most of (start, end), or 'unannotated'."""
    best, covered = 'unannotated', 0.0
    for e in host:
        if e.start >= end:
            break
        overlap = min(end, e.end) - max(start, e.start)
        if overlap > covered:
            best, covered = e.name, overlap
    return best


def _inside(runs, at):
    """Whether the instant lies in one of the sorted disjoint intervals."""
    i = bisect.bisect_right(runs, (at, float('inf'))) - 1
    return i >= 0 and at < runs[i][1]


def executions(modules, module):
    """(start, end) of each execution of the program named ``module`` among
    one chip's ``XLA Modules`` events, which are named
    ``<module>(<fingerprint>)``."""
    return [(m.start, m.end) for m in modules
            if m.name.split('(')[0] == module]


def reduce_chip(ops, in_flight, modules, host, program, seen=None):
    """One chip's share of the window: ``ops`` the stream's events,
    ``in_flight`` what ran beside it, ``modules`` the program executions.
    ``seen`` (name -> hlo.Op, from the events' own text) and ``program``
    (hlo.Program) say what an op of the step is; the step is the program
    ``program.module`` names, and an op that ran outside every execution
    of it belongs to one of the small programs dispatched between steps
    and counts as XLA's."""
    seen = seen or {}
    steps = executions(modules, program.module)
    step_runs = union(steps)

    def category(e):
        return program.category(e.name, seen.get(e.name)) \
            if _inside(step_runs, e.start) else OTHER_PROGRAMS

    by = collections.defaultdict(list)
    per_op = collections.Counter()
    for e in ops:
        kind = category(e)
        if kind == 'container':     # the trace shows what it wraps as well
            continue
        if kind == OTHER_PROGRAMS:
            by['xla'].append((e.start, e.end))
            per_op[OTHER_PROGRAMS] += e.end - e.start
        else:
            by[kind].append((e.start, e.end))
            per_op[e.name] += e.end - e.start
    everything = [x for xs in by.values() for x in xs]
    if not everything:
        return None
    start, end = min(everything)[0], max(b for _a, b in everything)
    busy = union(everything)
    compute = union(by['mosaic'] + by['xla'])
    collective = union(by['collective'] + [
        (e.start, e.end) for e in in_flight if category(e) == 'collective'])
    # in a gap inside a running program the chip waits on itself, in one
    # between programs it waits for the host
    running = union((m.start, m.end) for m in modules)
    idle = gaps(busy, start, end)
    between = minus(idle, running)
    waits = collections.Counter()
    waits['inside a program'] = length(idle) - length(between)
    for a, b in between:
        waits['between programs, host in '
              + _host_activity(host, a, b)] += b - a
    return {
        'window_s': end - start,
        'busy_s': length(busy),
        # a kernel's time is the sum of its events
        'mosaic_s': sum(b - a for a, b in by['mosaic']),
        'mosaic_calls': len(by['mosaic']),
        'xla_s': length(union(by['xla'])),
        'collective_s': length(collective),
        'collective_exposed_s': length(minus(collective, compute)),
        'collective_calls': len(by['collective']),
        'steps': len(steps),
        'idle_gaps': waits,
        'longest_gap_s': max((b - a for a, b in idle), default=0.0),
        # for readers that want more than the sums above: seconds by op
        # name, the intervals of each category ('mosaic', 'xla',
        # 'collective') and of the step's executions
        'per_op': per_op,
        'intervals': dict(by),
        'step_runs': steps,
    }


def reduce(trace, program):
    """The window's numbers as means over the chips that ran ops, each
    chip's own under ``per_chip``, and the breakdown the contract's last
    line may carry. None where no op ran on a device or the trace holds no
    execution of ``program.module``: there is then no step to divide by."""
    seen = {name: hlo.describe(text)
            for name, text in trace.get('text', {}).items()}
    chips = [c for c in (
        reduce_chip(ops, trace['async'].get(chip, []),
                    trace['modules'].get(chip, []), trace['host'], program,
                    seen)
        for chip, ops in sorted(trace['ops'].items())) if c]
    if not chips or not any(c['steps'] for c in chips):
        return None
    n = len(chips)
    out = {key: sum(c[key] for c in chips) / n for key in (
        'window_s', 'busy_s', 'mosaic_s', 'xla_s', 'collective_s',
        'collective_exposed_s')}
    out['chips'] = n
    out['per_chip'] = chips
    for key in ('steps', 'mosaic_calls', 'collective_calls',
                'longest_gap_s'):
        out[key] = max(c[key] for c in chips)
    out['idle_share'] = 1.0 - out['busy_s'] / out['window_s']
    waits = collections.Counter()
    # ops grouped by what they are: a step runs the same fusion once per
    # layer under a dozen names
    groups, members = collections.Counter(), collections.defaultdict(set)
    for c in chips:
        for name, seconds in c['idle_gaps'].items():
            waits[name] += seconds / n
        for name, seconds in c['per_op'].items():
            label = name if name == OTHER_PROGRAMS \
                else program.label(name, seen.get(name))
            groups[label] += seconds / n
            members[label].add(name)
    out['breakdown'] = {
        'device_ops': [[f"{label} x{len(members[label])}", seconds]
                       for label, seconds in groups.most_common(10)],
        'idle_gaps': [[name, seconds]
                      for name, seconds in waits.most_common(10)
                      if seconds > 0],
    }
    return out


def main(argv):
    """Reduce a saved trace again, off the chip:
    ``python3 -m chipbench.xplane <file.xplane.pb> <step_program.hlo.txt>``
    from the root of the checkout."""
    import json
    trace = load(argv[0])
    for name, lines in trace['planes']:
        print(f"plane {name}: lines {lines}")
    with open(argv[1]) as f:
        reduced = reduce(trace, hlo.Program(f.read()))
    if reduced:
        del reduced['per_chip']
    print(json.dumps(reduced, indent=1))


if __name__ == '__main__':
    import sys
    main(sys.argv[1:])
