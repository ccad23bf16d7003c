"""Layer: kernels. The least time one chip could take for the attention
forward + backward of one step -- the larger of its matmul operations
over the chip's bf16 peak and its operand bytes over the HBM peak, both
from the family's ``attention_cost`` -- over the time the Mosaic calls
took (mosaic_ms_per_step), in percent."""


def least_seconds(run):
    """(seconds, which peak binds) for one chip's share of one step."""
    cost = run.family.attention_cost(run.config, run.traffic)
    n = run.traffic['per_chip_batch']
    by_flops = n * cost['flops'] / run.peaks['bf16_flops_per_s']
    by_bytes = n * cost['bytes'] / run.peaks['hbm_bytes_per_s']
    return max(by_flops, by_bytes), \
        'flops' if by_flops >= by_bytes else 'bytes'


def note(run):
    seconds, binds = least_seconds(run)
    return (f"least time {seconds * 1e3:.3f} ms a step a chip, bound by "
            f"{binds} ({run.peaks['bf16_flops_per_s'] / 1e12:g} TFLOP/s, "
            f"{run.peaks['hbm_bytes_per_s'] / 1e9:g} GB/s)")


def read(run):
    if run.trace is None or not run.trace['mosaic_s']:
        return None
    per_step = run.trace['mosaic_s'] / run.trace['steps']
    return 100.0 * least_seconds(run)[0] / per_step
