"""Layer: train_step. Device time of the fusions whose instructions were
traced under more than one phase: above all the weight-gradient matmuls
that XLA fused with their AdamW update (backward + optimizer), and
backward fusions that recompute a piece of the forward. Ms a traced
step, mean over chips. The traced run lists the largest by label."""
from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, scopes.MIXED)
