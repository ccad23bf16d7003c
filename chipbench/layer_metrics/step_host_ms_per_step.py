"""Layer: step_dispatch (the host side of ShardedTrainStep.__call__).
The Python the program runs round the call of its executable: median, in
ms, of the program's own ``mxtpu.step.dispatch`` span less its
``mxtpu.step.compiled`` child (chipbench/hostspans.py) -- the guard's and
the fault's look, the argument dictionaries, the key, the batch put, the
swap of the donated buffers' views, the bookkeeping -- over the traced
dispatches that provably waited for nothing (``host_dispatch_ms_per_step``'s
rule, put to the program's span). A dispatch that finds the device's queue
full waits in this very Python, before the batch put (the key's split and
the scalars' puts need the queue too; seen on the chip, PR 39), so the
others would read the device's step time. None where the trace holds no
such span (a program from before PR 39) or reduced to nothing.

``host_dispatch_ms_per_step`` times the same layer from outside, round the
call into the program; this metric and ``step_enqueue_ms_per_step`` are its
two parts, and ``note`` checks that they add up to it. ``note`` also says
what no number here holds: which of the program's spans the host was in
while a chip waited between two programs, and what the longest dispatch of
the window was made of, stretch by stretch."""
import statistics

from chipbench import hostspans, manifest

AGREE_MS = 0.5


def read(run):
    free = hostspans.free_dispatches(run, hostspans.of(run))
    if not free:
        return None
    return 1e3 * statistics.median(
        hostspans.seconds(d) - hostspans.inside(d, hostspans.ENQUEUE)
        for d in free)


def round_it(run, dispatches):
    """Seconds by which the ``chipbench.dispatch`` annotation round each
    of the program's dispatch spans is the longer."""
    outer = [e for e in run.events['host'] if e.name == 'chipbench.dispatch']
    return [e.end - e.start - hostspans.seconds(d)
            for d in dispatches for e in outer
            if e.start <= d.event.start and d.event.end <= e.end] or [0.0]


def note(run):
    lines = hostspans.of(run)
    dispatches = hostspans.named(lines, hostspans.DISPATCH)
    if not dispatches:
        return ('no mxtpu.step.dispatch event on the host plane: a program '
                'from before PR 39, or no trace file at '
                f'{hostspans.trace_path(run)}')
    out = []
    host = read(run)
    enqueue = manifest.load_module(
        'layer_metrics', 'step_enqueue_ms_per_step').read(run)
    outside = manifest.load_module(
        'layer_metrics', 'host_dispatch_ms_per_step').read(run)
    if host is None or enqueue is None or outside is None:
        out.append(f"{len(dispatches)} dispatches, none that provably "
                   f"waited for nothing: nothing to check")
    else:
        off = host + enqueue - outside
        free = hostspans.free_dispatches(run, lines)
        out.append(
            f"over the {len(free)} of {len(dispatches)} dispatches that "
            f"waited for nothing: step_host {host:.3f} + step_enqueue "
            f"{enqueue:.3f} = {host + enqueue:.3f} ms against the unblocked "
            f"chipbench.dispatch median {outside:.3f}: "
            + ('agree' if abs(off) <= AGREE_MS else 'DISAGREE')
            + f" ({off:+.3f} ms, a sum of medians against a median); "
            f"dispatch by dispatch the benchmark's annotation is "
            f"{1e3 * statistics.median(round_it(run, free)):.3f} ms longer "
            f"than the program's span inside it (the harness adds an "
            f"append)")
    if run.trace is not None:
        gaps = hostspans.gaps_by_span(run, lines)
        out.append(
            f"between-program gaps ({sum(gaps.values()):.6f} s a chip) by "
            f"the innermost mxtpu. span over most of each: " + ('; '.join(
                f"{name} {s:.6f} s" for name, s in gaps.most_common())
                or 'none'))
    longest = max(dispatches, key=hostspans.seconds)
    out.append(
        f"longest mxtpu.step.dispatch of the window: "
        f"{1e3 * hostspans.seconds(longest):.3f} ms at "
        f"{longest.event.start:.6f} s = " + ' + '.join(
            f"{name or 'its own Python'} {1e3 * s:.3f}"
            for name, s in hostspans.timeline(longest)))
    return '\n'.join(out)
