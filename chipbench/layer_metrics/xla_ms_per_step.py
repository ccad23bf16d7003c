"""Layer: train_step (parallel/step.py, the XLA part of the one pjit
program). Device time in which an op ran that is neither a Mosaic call
nor a collective -- the matmul, LayerNorm/GELU/residual, head, loss and
optimizer fusions, and the small programs dispatched between steps -- per
traced step, mean over chips."""


def read(run):
    if run.trace is None:
        return None
    return 1e3 * run.trace['xla_s'] / run.trace['steps']
