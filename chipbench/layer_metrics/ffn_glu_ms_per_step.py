"""Layer: loop (models/decoder.py:GatedFFN). Device time of the ops
traced under the scope ``ffn_glu``: the dense gated feed-forward's two
matmuls and the gate's product, forward, backward and recomputed; a part
of loop_ms_per_step. Ms a traced step, mean over chips. None where the
program has no such scope."""
from chipbench import scoped


def read(run):
    return scoped.ms_per_step(run, ('ffn_glu',))
