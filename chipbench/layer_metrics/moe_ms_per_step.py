"""Layer: moe (ops/moe.py). Device time of the ops traced under the
scopes ``moe_route`` (router matmul, softmax, top-k, the sorts, the
dispatch gather, the combine) or ``moe_experts`` (the grouped matmuls,
``mxtpu_grouped_matmul``, and ReGLU between them), forward and backward,
ms a traced step, mean over chips. None where the program has no such
scope."""
from chipbench import scoped


def read(run):
    return scoped.ms_per_step(run, ('moe_route', 'moe_experts'))
