"""Layer: loop (models/decoder.py). Device time of the ops traced under
the scope ``ut_loop``: every pass of a looped decoder's stack and final
norm -- forward, backward, and the forward that ``jax.checkpoint`` runs a
second time inside the backward -- the flash kernels' calls among them.
The heads, the gate and the objective lie outside it
(exit_head_ms_per_step). Ms a traced step, mean over chips. None where
the program has no such scope."""
from chipbench import scoped


def read(run):
    return scoped.ms_per_step(run, ('ut_loop',))
