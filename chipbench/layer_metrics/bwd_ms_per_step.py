"""Layer: train_step. Device time, inside executions of the step, of the
ops whose every instruction was traced under ``mxtpu.fwd_bwd`` and under
``transpose(``: the backward pass, the Mosaic dq and dk/dv calls
included. Ms a traced step, mean over chips; collectives apart."""
from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, 'bwd')
