"""Layer: kernels (ops/pallas_attention.py). Device time of the Mosaic
custom calls (custom_call_target="tpu_custom_call" in the step's HLO:
flash attention forward, dq, dk/dv), summed over their trace events, per
traced step, mean over chips."""


def read(run):
    if run.trace is None or not run.trace['mosaic_calls']:
        return None
    return 1e3 * run.trace['mosaic_s'] / run.trace['steps']
