"""Layer: kernels (ops/pallas_attention.py). Device time of the Mosaic
calls named ``mxtpu_flash_bwd_dq``, the backward kernel that makes dq
(``pallas_call(name=...)``), summed over their trace events, ms a traced
step, mean over chips. With the other two flash metrics it sums to
mosaic_ms_per_step. None where no call carries that name."""
from chipbench import scopes


def read(run):
    return scopes.kernel_ms(run, 'mxtpu_flash_bwd_dq')
