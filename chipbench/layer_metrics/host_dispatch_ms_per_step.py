"""Layer: step_dispatch (the host side of ShardedTrainStep.__call__).
What the host needs to dispatch one step when it waits for nothing:
median duration, in ms, of the benchmark's ``chipbench.dispatch``
annotations on the trace's host plane that provably found room in the
device's queue.

A read of the losses ends with every dispatched step done, so the queue is
empty after it (and at the start of the profile, which follows a read). A
dispatch that waits for a slot is released only when a step completes on
the device. So a dispatch that returned before the first step execution
since that read had ended on any chip cannot have waited, and only those
are counted. The rest hold the wait and read the device's step time,
which says nothing of the host.

The program's own ``step.dispatch`` span (telemetry/trace.py) is not used:
switching its recording on makes every dispatch read the previous step's
loss (telemetry/flight.py), so the span reads the device's step time
(PERF.md section 6, PR 24)."""
import statistics

DISPATCH, READ = 'chipbench.dispatch', 'chipbench.read_loss'


def unblocked(host, step_ends):
    """Seconds of each dispatch that ended before the first step since the
    last read (or the start of the trace) had completed. ``host``: the
    annotation events by start; ``step_ends``: when each execution of the
    step ended, on any chip."""
    step_ends = sorted(step_ends)
    found = []
    since = float('-inf')       # the profile opens on an empty queue
    for e in host:
        if e.name == READ:
            since = e.end
        elif e.name == DISPATCH and e.start >= since:
            first_done = next((t for t in step_ends if t > since),
                              float('inf'))
            if e.end <= first_done:
                found.append(e.end - e.start)
    return found


def read(run):
    if run.trace is None:
        return None
    ends = [end for chip in run.trace['per_chip']
            for _start, end in chip['step_runs']]
    free = unblocked(run.events['host'], ends)
    return 1e3 * statistics.median(free) if free else None
