"""Layer: train_step. Device time, inside executions of the step, of the
ops whose every instruction was traced under ``mxtpu.fwd_bwd`` and not
under ``transpose(``: the forward pass, the Mosaic forward calls and the
loss's forward included. Ms a traced step, mean over chips. Collectives
are counted apart (collective_ms_per_step). chipbench/scopes.py says
where the names are read from.

Its ``note`` prints what the eight metrics of PR 26 cannot hold: the sum
rules, checked, the block table, the largest mixed and unscoped ops and
the collectives by scope."""
from chipbench import scopes


def note(run):
    return scopes.report(run)


def read(run):
    return scopes.phase_ms(run, 'fwd')
