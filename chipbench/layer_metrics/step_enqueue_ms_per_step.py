"""Layer: step_dispatch (the host side of ShardedTrainStep.__call__).
What handing one step to the device takes: median, in ms, of the program's
own ``mxtpu.step.compiled`` span (chipbench/hostspans.py: the call of the
step's executable, from the argument check to the return of the output
arrays) inside the traced dispatches that provably waited for nothing --
the population of ``step_host_ms_per_step``, by
``host_dispatch_ms_per_step``'s rule. None where the trace holds no such
span (a program from before PR 39) or reduced to nothing.

Since PR 39 that executable is the one ``step_program.hlo.txt`` is the
text of: the step runs what ``compiled_program()`` compiled. ``note``
checks it, by every instruction name the step executed."""
import collections
import statistics

from chipbench import hlo, hostspans, scopes, xplane

SHOWN = 12


def read(run):
    free = hostspans.free_dispatches(run, hostspans.of(run))
    if not free:
        return None
    return 1e3 * statistics.median(
        hostspans.inside(d, hostspans.ENQUEUE) for d in free)


def missing_names(run):
    """(how many instruction names the step executed, [(name, what ran)]
    of those ``step_program.hlo.txt`` does not have, [(name, how what the
    trace says ran differs from what the text has)] of those it has as
    another instruction); None where there is no trace or no text of this
    step.

    Two kinds of the latter are no fault of the text (seen on the chip,
    PR 39, the same at the parent). The trace's table keeps one text a
    name (``xplane.load``), the first it meets, so a name the step shares
    with a small program dispatched between steps (``%copy-start`` of the
    key's ``jit__threefry_fold_in``) reads as that program's instruction.
    And ``hlo.describe`` reads 4096 characters of an instruction: an
    event's text spells its operands' shapes out, so a fusion of some
    thirty operands has its ``kind=`` beyond them and reads as kind ''."""
    if run.trace is None:
        return None
    names = scopes.names_of(run)
    if not names:
        return None
    texts = (run.events or {}).get('text', {})
    executed = {name for chip in run.trace['per_chip']
                for name in chip['per_op']} - {xplane.OTHER_PROGRAMS}
    absent, differing = [], []
    for name in sorted(executed):
        seen = hlo.describe(texts[name]) if name in texts else None
        ran = f"{seen.opcode} {seen.shape}" if seen else '?'
        known = run.program.get(name)
        if name not in names:
            absent.append((name, ran))
        elif not scopes.same_instruction(seen, known):
            differing.append((name, ', '.join(
                f"{field} {getattr(seen, field) or 'none'} against "
                f"{getattr(known, field) or 'none'}"
                for field in ('opcode', 'kind', 'target', 'shape')
                if getattr(seen, field) != getattr(known, field))))
    return len(executed), absent, differing


def note(run):
    found = missing_names(run)
    if found is None:
        return (f"no reduced trace, or no {scopes.HLO_FILE} of this step at "
                f"{scopes.text_path(run)}: the names cannot be checked")
    executed, absent, differing = found
    if absent:
        by_opcode = collections.Counter(
            ran.split(' ')[0] for _name, ran in absent)
        said = (f"{len(absent)} of {executed} executed instruction names "
                f"of the step are MISSING from {scopes.HLO_FILE}: by opcode "
                + ', '.join(f"{op} {n}" for op, n in by_opcode.most_common())
                + '; the first: ' + '; '.join(
                    f"{name} ran as {ran}" for name, ran in absent[:SHOWN]))
    else:
        said = (f"no executed instruction name of the step is missing from "
                f"{scopes.HLO_FILE}: all {executed} are in it")
    if differing:
        said += (f"; {len(differing)} the trace describes as another "
                 f"instruction than the text has (a small program between "
                 f"steps has one of that name, the event's text is cut "
                 f"before its kind, or the text is another compile's), "
                 f"trace against text: " + '; '.join(
                     f"{name} {how}" for name, how in differing[:SHOWN]))
    return said
