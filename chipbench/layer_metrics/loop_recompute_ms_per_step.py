"""Layer: loop (models/decoder.py). The part of loop_ms_per_step that is
the forward run a second time: the executed ops under ``ut_loop`` whose
instructions all carry ``rematted_computation``, the name JAX gives what
a ``jax.checkpoint`` region computes again in the backward (read off
``step_program.hlo.txt``). An op that holds recomputed and backward
instructions side by side (XLA fuses a recomputed product into the
gradient matmul that reads it) is not counted; ``note`` prints that part
beside it. Ms a traced step, mean over chips. None where the program has
no such scope or recomputes nothing under it."""
from chipbench import scoped

MARKER = 'rematted_computation'


def _split(run):
    """(ms wholly recomputed, ms partly), or None without a trace."""
    if getattr(run, 'trace', None) is None:
        return None
    names = scoped._names(run)
    chips = run.trace['per_chip']
    wholly = partly = 0.0
    for chip in chips:
        for name, seconds in chip['per_op'].items():
            named = names.get(name)
            own = [n for n in (named.inside or {named.op_name})
                   if scoped._under(n, ('ut_loop',))] if named else []
            marked = sum(MARKER in n.split('/') for n in own)
            if marked and marked == len(own):
                wholly += seconds
            elif marked:
                partly += seconds
    scale = 1e3 / len(chips) / run.trace['steps']
    return wholly * scale, partly * scale


def note(run):
    found, loop = _split(run), scoped.ms_per_step(run, ('ut_loop',))
    if not found or not loop:
        return 'no op under ut_loop'
    return (f"{found[0]:.3f} ms a step wholly recomputed, "
            f"{100 * found[0] / loop:.1f} % of loop_ms_per_step "
            f"{loop:.3f}; {found[1]:.3f} ms more in ops that hold "
            f"recomputed and backward instructions side by side")


def read(run):
    found = _split(run)
    return found[0] or None if found else None
