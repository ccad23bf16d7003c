"""Layer: moe (ops/moe.py). The least time one chip could take for a
step's grouped matmuls at the balanced share of rows -- the larger of
their operations over the bf16 peak and their bytes over the HBM peak,
from the family's ``expert_cost`` -- over the device time of the Mosaic
calls named ``mxtpu_grouped_matmul`` (forward and both backward
products), in percent. The program pads the rows to a fixed size
(ops/moe.py: CAPACITY, one more tile an expert), which the cost does not
count: the share cannot reach 100 %."""
from chipbench import scoped


def read(run):
    if not hasattr(run.family, 'expert_cost'):
        return None
    return scoped.roofline_share(
        run, run.family.expert_cost(run.config, run.traffic),
        scoped.ms_per_step(run, kernel='mxtpu_grouped_matmul'))
