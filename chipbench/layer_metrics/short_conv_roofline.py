"""Layer: short_conv (ops/shortconv.py). The least time one chip could
take for a step's gated short convolutions between the projections,
forward + backward -- the larger of their operations over the bf16 peak
and their bytes over the HBM peak, from the family's ``short_conv_cost``
(bytes bound it) -- over the device time of the ops traced under the
scope ``short_conv_core``, whatever computes them, in percent. None where
the program has no such scope or the family no such cost."""
from chipbench import scoped


def read(run):
    if not hasattr(run.family, 'short_conv_cost'):
        return None
    return scoped.roofline_share(
        run, run.family.short_conv_cost(run.config, run.traffic),
        scoped.ms_per_step(run, ('short_conv_core',)))
