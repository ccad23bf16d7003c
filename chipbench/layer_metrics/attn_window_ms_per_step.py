"""Layer: kernels (ops/pallas_attention.py). Device time of the flash
kernels (forward, dq, dk/dv) called under the scope ``attn_swa``: the
sliding-window layers' attention. Ms a traced step, mean over chips. None
where the program has no such scope."""
from chipbench import scoped


def read(run):
    return scoped.flash_ms_per_step(run, 'attn_swa')
