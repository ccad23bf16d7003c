"""Layer: moe (ops/moe.py). The part of moe_ms_per_step under
``moe_route``: everything the expert layer does that is no grouped matmul
-- router matmul, softmax, top-k, the two sorts, the index arithmetic, the
gather into the experts' row layout and the weighted gather back, forward
and backward. Ms a traced step, mean over chips. A fusion that holds both
scopes' instructions counts here too. None where the program has no such
scope."""
from chipbench import scoped


def read(run):
    return scoped.ms_per_step(run, ('moe_route',))
