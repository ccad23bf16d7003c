"""Layer: train_step. The guard on the instrumentation itself: share, in
percent, of the stream's busy time spent in ops that carry no phase scope
or whose name the step's HLO text does not know, the small programs
dispatched between steps included (as xla_ms_per_step includes them).
It rises when a scope is renamed in the program, when XLA stops
carrying the metadata through a pass, or when step_program.hlo.txt is
not found. None where the program has no phase scope at all."""
from chipbench import scopes


def read(run):
    unscoped = scopes.phase_ms(run, scopes.UNSCOPED)
    if unscoped is None:
        return None
    return 100.0 * unscoped / scopes.split(run)['busy']
