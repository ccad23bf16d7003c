"""Layer: kernels (ops/pallas_attention.py). The least time one chip
could take for the window layers' attention forward + backward of one
step -- the larger of the band's matmul operations over the bf16 peak and
the operand bytes over the HBM peak, from the family's
``attention_cost_by_kind`` -- over attn_window_ms_per_step, in percent.
Divided by its own kernels' time, where flash_attn_roofline divides the
whole attention's least time by every Mosaic call's."""
from chipbench import scoped


def read(run):
    if not hasattr(run.family, 'attention_cost_by_kind'):
        return None
    cost = run.family.attention_cost_by_kind(run.config, run.traffic)
    return scoped.roofline_share(run, cost['window'],
                                 scoped.flash_ms_per_step(run, 'attn_swa'))
