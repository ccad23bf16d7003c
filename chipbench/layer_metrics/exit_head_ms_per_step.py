"""Layer: exit_head (models/decoder.py). Device time of the ops traced
under ``lm_head``, ``exit_gate`` or ``mxtpu.loss``: a looped decoder's
head and exit gate after every pass and its objective, forward and
backward; under training the head's matmul runs inside the objective's
chunks, and again in their backward. Ms a traced step, mean over chips.
None where the program has none of the scopes."""
from chipbench import scoped


def read(run):
    return scoped.ms_per_step(run, ('lm_head', 'exit_gate', 'mxtpu.loss'))
