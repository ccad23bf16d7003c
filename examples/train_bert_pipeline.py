"""Pipeline-parallel BERT pretraining over a 'pp' mesh axis.

Demonstrates the round-5 public pipeline API (beyond the reference —
its model parallelism is manual layer placement with no schedule):

    BertForPretraining  --bert_pipeline_funcs-->  embed/stages/head
    PipelineTrainStep: one jit step, stage params sharded over pp,
    GPipe microbatch schedule as a lax.scan over ppermute.

Runs anywhere: on a CPU-only host use the virtual mesh —

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/train_bert_pipeline.py --pp 2
"""
import argparse

import numpy as onp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--pp', type=int, default=2, help='pipeline stages')
    ap.add_argument('--layers', type=int, default=4)
    ap.add_argument('--hidden', type=int, default=128)
    ap.add_argument('--microbatches', type=int, default=4)
    ap.add_argument('--microbatch-size', type=int, default=2)
    ap.add_argument('--seq', type=int, default=64)
    ap.add_argument('--steps', type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    mx.telemetry.compile.use_default_cache()
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_pipeline_funcs
    from mxnet_tpu.parallel import PipelineTrainStep, make_mesh

    assert args.layers % args.pp == 0, 'layers must divide into stages'
    cfg = dict(vocab_size=1000, hidden=args.hidden, layers=args.layers,
               heads=max(2, args.hidden // 64), intermediate=args.hidden * 4,
               max_len=args.seq, type_vocab=2, dropout=0.0)
    mx.random.seed(0)
    model = BertForPretraining(config=cfg)
    model.initialize(mx.init.Normal(0.02))

    params, embed_fn, stage_fn, head_fn, loss_fn = \
        bert_pipeline_funcs(model, n_stages=args.pp)
    mesh = make_mesh((args.pp,), ('pp',))
    step = PipelineTrainStep(params, embed_fn, stage_fn, head_fn, loss_fn,
                             'adamw', {'learning_rate': 1e-3}, mesh=mesh)

    M, mb, T = args.microbatches, args.microbatch_size, args.seq
    rng = onp.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg['vocab_size'], (M, mb, T)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg['vocab_size'], (M, mb, T)),
                         jnp.int32)
    nsp = jnp.asarray(rng.randint(0, 2, (M, mb)), jnp.int32)

    print(f'mesh {dict(mesh.shape)}  stages={args.pp}  '
          f'microbatches={M}x{mb}  seq={T}')
    for i in range(args.steps):
        loss = float(step(tokens, (labels, nsp)))
        if i % 5 == 0 or i == args.steps - 1:
            print(f'step {i:3d}  loss {loss:.4f}')


if __name__ == '__main__':
    main()
