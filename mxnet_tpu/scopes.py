"""The names mxnet_tpu writes into the programs it builds.

A device trace (``jax.profiler``, ``mx.profiler``) names each executed op
by the ``op_name`` of the HLO instruction it ran, and that is the stack of
``jax.named_scope``s open when the op was traced. The framework opens
three kinds, all at trace time only -- there is no switch, and a compiled
program runs the same instructions with or without them:

* a phase of ``ShardedTrainStep``'s one program (parallel/step.py): the
  constants below. Forward and backward are both under ``FWD_BWD``; JAX
  itself tells them apart, wrapping the first scope inside it in
  ``jvp(...)`` on the way forward and ``transpose(jvp(...))`` on the way
  back, and puts what a ``jax.checkpoint`` region (a looped decoder's
  block applications, a chunk of a chunked loss) computes a second time
  in the backward under ``checkpoint/rematted_computation``: all of a
  chunk, and of a block application all but the flash forward kernel,
  whose two results the region keeps (``FLASH_KEPT`` below);
* a Gluon block (gluon/block.py): every block's forward runs under the
  name its parent knows it by, so an op reads
  ``bertmodel0/encoder/bertlayer3/bertselfattention0/qkv/dot_general``;
* a hand-written stretch that is no block of its own (models/bert.py,
  models/gpt.py, models/decoder.py, ops/attention.py, ops/moe.py): a
  plain word, listed below.

A Pallas kernel is named by ``pallas_call(name=...)``: the name becomes
the custom call's instruction name and the last scope of its ``op_name``.

To give a stretch of your own code a line in the trace::

    with jax.named_scope('my_stretch'):
        y = my_ops(x)

PERF.md section 3 lists which benchmark metric reads which name.
"""

# phases of the train step
FWD_BWD = 'mxtpu.fwd_bwd'       # value_and_grad of the model and its loss
LOSS = 'mxtpu.loss'             # the loss function, inside FWD_BWD
GATHER = 'mxtpu.gather'         # ZeRO-3's per-layer parameter gathers
EXCHANGE = 'mxtpu.exchange'     # fusion boundary, gradient cast, ZeRO layout,
                                # compression
GUARD = 'mxtpu.guard'           # the non-finite check and the gated writeback
UPDATE = 'mxtpu.update'         # optimizer update, cast back, compute copy

# stretches inside blocks that are no child block
ATTN_LAYOUT = 'attn_layout'     # (N,T,H*D) <-> (N,H,T,D) on the XLA and ring
                                # routes; the Pallas route has none
ATTN_CORE = 'attn_core'         # scores, softmax, dropout, weighted sum
FFN1 = 'ffn1'                   # first feed-forward matmul + GELU
LN1, LN2 = 'ln1', 'ln2'         # residual add + LayerNorm
LM_HEAD = 'lm_head'             # the output projection: GPT's tied one,
                                # the decoder's own; a looped decoder's lies
                                # inside LOSS under training
ATTN_SWA = 'attn_swa'           # decoder: a windowed layer's attention core
ATTN_FULL = 'attn_full'         # decoder: a full-attention layer's
ROPE = 'rope'                   # rotary embedding of q and k
RMSNORM = 'rmsnorm'             # RMS normalisation (no block of its own)
MOE_ROUTE = 'moe_route'         # router matmul, softmax, top-k, sort, the
                                # dispatch gather and the combine
MOE_EXPERTS = 'moe_experts'     # the grouped matmuls and ReGLU between them
UT_LOOP = 'ut_loop'             # looped decoder: every pass of the stack and
                                # the final norm, forward, backward and the
                                # forward run again under jax.checkpoint
UT_PASS = 'ut_pass'             # one pass of it, numbered: ut_pass0 ...
FFN_GLU = 'ffn_glu'             # decoder: the dense gated feed-forward
EXIT_GATE = 'exit_gate'         # looped decoder: the exit gate's logit

# Pallas kernels
FLASH_FWD = 'mxtpu_flash_fwd'
FLASH_BWD_DQ = 'mxtpu_flash_bwd_dq'
FLASH_BWD_DKV = 'mxtpu_flash_bwd_dkv'
FFN_GELU = 'mxtpu_ffn_gelu'
ADD_LAYERNORM = 'mxtpu_add_layernorm'
GROUPED_MATMUL = 'mxtpu_grouped_matmul'     # ops/moe.py: forward and both
                                            # backward products

# jax.ad_checkpoint.checkpoint_name: what the flash forward hands its
# backward, named where the custom_vjp's forward rule returns it
# (ops/pallas_attention.py:_flash_fwd), so that a jax.checkpoint region
# whose policy lists the names (models/decoder.py:_recomputed) keeps the
# two arrays and does not run the kernel a second time. Without such a
# policy a name is an identity and lowers to nothing
FLASH_OUT = 'mxtpu_flash_out'   # o, (N, Tq, H*D) in the model's dtype
FLASH_LSE = 'mxtpu_flash_lse'   # the softmax row statistics, float32
                                # (N, H, 1, Tq)
FLASH_KEPT = (FLASH_OUT, FLASH_LSE)
