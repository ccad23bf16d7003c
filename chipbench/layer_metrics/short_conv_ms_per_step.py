"""Layer: short_conv (models/decoder.py:ShortConv, ops/shortconv.py).
Device time of the ops traced under the scope ``short_conv``: the conv
layers' in-projection, gated causal convolution and out-projection,
forward and backward, ms a traced step, mean over chips. None where the
program has no such scope (a parent commit, or a model without conv
layers)."""
from chipbench import scoped


def read(run):
    return scoped.ms_per_step(run, ('short_conv',))


def note(run):
    """The program's counters beside the trace, where its family keeps
    them: the expert layers' and grouped flash kernels' routes as built,
    and each expert layer's balancing bias after the traced steps."""
    counters = getattr(run.family, 'counters', None)
    return 'no counters in this family' if counters is None \
        else f"counters {counters()}"
