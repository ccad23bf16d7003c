"""Layer: train_step. Device time, inside executions of the step, of the
ops wholly traced under ``mxtpu.exchange``, ``mxtpu.guard``,
``mxtpu.update`` (or ``mxtpu.gather``) that are no collective: what the
step does with the gradients once they exist and XLA did not fuse into
the backward (that part is phase_mixed_ms_per_step). Ms a traced step,
mean over chips."""
from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, 'optimizer')
