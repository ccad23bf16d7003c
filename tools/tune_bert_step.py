"""On-chip tuning harness for the flagship BERT train step.

Runs ONE configuration (from env/args) of the fused ShardedTrainStep at
BERT-base scale and prints step time + honest MFU. Used to pick the
batch size / PRNG impl / Pallas block sizes that bench.py then pins.

Usage: python tools/tune_bert_step.py [--batch 32] [--rbg] [--steps 10]
Env: MXTPU_FA_* / MXTPU_FA_BWD_* block-size overrides (ops/pallas_attention).

``--autotune`` (ISSUE 18) replaces the one-configuration run with the
searched pass: the flash-attention candidate sweep at this model's
shape (winners persisted to the MXTPU_AUTOTUNE_DIR tuning DB, which
every later run's _block_sizes consults automatically), then a remat-
policy sweep — one fresh step per MXTPU_REMAT policy, step time next
to memory_analysis()'s activation/temp buckets so the HBM-vs-FLOPs
trade is measured, not guessed.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def _autotune(args):
    """--autotune: kernel sweep -> tuning DB, then the remat-policy
    step-time / HBM table. Prints the PERF_NOTES-ready tables."""
    import json

    import jax
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_base_config, bert_pretrain_loss
    from mxnet_tpu.ops import autotune
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
    from mxnet_tpu.telemetry import attribution

    cfg = bert_base_config()
    db_dir = args.autotune_dir or os.environ.get('MXTPU_AUTOTUNE_DIR') \
        or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, '.mxtpu_autotune')
    os.environ['MXTPU_AUTOTUNE_DIR'] = db_dir

    # 1) flash-attention block sweep at this model's shape. On TPU the
    # candidates are compiled + timed (compile seconds excluded via the
    # ledger window); on CPU the analytic ranking still writes a DB.
    rep = autotune.sweep_flash_attention(
        batch=args.batch, heads=cfg['heads'], seq=args.seq,
        head_dim=cfg['hidden'] // cfg['heads'],
        dtype=jax.numpy.bfloat16 if args.bf16 else jax.numpy.float32,
        db_dir=db_dir)
    print(f"autotune sweep [{rep['mode']}] {rep['shape']} "
          f"({rep['sweep_seconds']}s) -> {rep['db']}")
    for kind in ('fwd', 'bwd'):
        r = rep.get(kind)
        if not r:
            continue
        print(f"  {kind}: winner G,bq,bk={tuple(r['winner'])} "
              f"[{r['source']}] of {r['candidates']} legal "
              f"({r['pruned']} pruned); sig={r['signature']}")
        for row in r['ranking'][:5]:
            print(f"    {row}")

    # 2) remat-policy sweep: fresh model+step per policy (MXTPU_REMAT
    # is read at step construction), same batch, step time next to the
    # memory_analysis() buckets remat actually moves.
    rng = onp.random.RandomState(0)
    batch, seq = args.batch, args.seq
    tokens = rng.randint(0, cfg['vocab_size'],
                         (batch, seq)).astype(onp.int32)
    types = onp.zeros((batch, seq), onp.int32)
    vl = rng.randint(seq // 2, seq + 1, (batch,)).astype(onp.int32)
    nmask = max(8, int(0.15 * seq) // 8 * 8)
    mpos = onp.stack([rng.choice(seq, nmask, replace=False)
                      for _ in range(batch)]).astype(onp.int32)
    labels = rng.randint(0, cfg['vocab_size'],
                         (batch, nmask)).astype(onp.int32)
    nsp = rng.randint(0, 2, (batch,)).astype(onp.int32)

    rows = []
    for policy in args.remat_policies.split(','):
        policy = policy.strip()
        os.environ['MXTPU_REMAT'] = policy
        mx.random.seed(0)
        model = BertForPretraining(cfg)
        model.initialize(mx.init.Normal(0.02))
        if args.bf16:
            model.cast('bfloat16')
        devices = jax.devices()
        mesh = make_mesh((len(devices),), ('dp',), devices=devices)
        step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                                {'learning_rate': 1e-4}, mesh=mesh)
        inputs = [nd.array(tokens), nd.array(types), nd.array(vl),
                  nd.array(mpos)]
        labs = [nd.array(labels), nd.array(nsp)]
        t0 = time.time()
        loss = float(step(inputs, labs).asnumpy())
        compile_s = time.time() - t0
        for _ in range(2):
            step(inputs, labs)
        t0 = time.time()
        for _ in range(args.steps):
            out = step(inputs, labs)
        float(out.asnumpy())
        dt = (time.time() - t0) / args.steps
        mem = step.memory_analysis() or {}
        rows.append({'remat': policy, 'loss': round(loss, 4),
                     'step_ms': round(dt * 1e3, 1),
                     'compile_s': round(compile_s, 1),
                     'memory': mem})
        del step, model

    print("\nremat policy sweep (loss must match across rows — remat "
          "changes what backward recomputes, never the values):")
    for r in rows:
        mem = r['memory']
        # the buckets remat moves: residual/activation HBM (and XLA's
        # own temp accounting as the cross-check)
        buckets = {
            'peak': mem.get('peak_bytes_per_device'),
            'activations_temp':
                (mem.get('buckets_bytes') or {}).get('activations_temp'),
            'xla_temp':
                (mem.get('xla') or {}).get('temp_size_in_bytes'),
        }
        print(f"  remat={r['remat']:<10} loss={r['loss']:<8} "
              f"step={r['step_ms']}ms compile={r['compile_s']}s "
              f"{json.dumps(buckets, default=str)}")
        tbl = attribution.format_memory_table(mem) if mem else None
        if tbl and args.verbose:
            print(tbl)
    losses = {r['loss'] for r in rows}
    if len(losses) > 1:
        print(f"  WARNING: loss drifted across remat policies: {losses}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=32)
    ap.add_argument('--seq', type=int, default=512)
    ap.add_argument('--steps', type=int, default=10)
    ap.add_argument('--rbg', action='store_true',
                    help='use the rbg PRNG (cheap random bits on TPU)')
    ap.add_argument('--autotune', action='store_true',
                    help='searched mode: flash-attention block sweep '
                         'into the MXTPU_AUTOTUNE_DIR tuning DB + '
                         'remat-policy step-time/HBM table')
    ap.add_argument('--autotune-dir', default=None,
                    help='tuning-DB directory (default: '
                         '$MXTPU_AUTOTUNE_DIR or .mxtpu_autotune)')
    ap.add_argument('--remat-policies', default='none,layer,aggressive',
                    help='comma list of MXTPU_REMAT policies to sweep')
    ap.add_argument('--bf16', action='store_true', default=True,
                    help='cast the model to bfloat16 (default)')
    ap.add_argument('--no-bf16', dest='bf16', action='store_false')
    ap.add_argument('--verbose', action='store_true',
                    help='print the full memory table per remat policy')
    ap.add_argument('--trace', metavar='DIR', default=None,
                    help='capture an xprof trace of the timed steps into '
                         'DIR (view with tensorboard --logdir DIR), plus '
                         'the span-level chrome trace (DIR/mxtpu_spans.'
                         'json) and the per-step attribution table '
                         '(telemetry.attribution) over a few extra '
                         'synced steps')
    args = ap.parse_args()

    if args.autotune:
        sys.exit(_autotune(args))

    import jax
    if args.rbg:
        jax.config.update('jax_default_prng_impl', 'rbg')
    from bench import _peak_flops      # the one peak table, keyed by kind
    peak = _peak_flops(jax.devices()[0]) * len(jax.devices())

    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_base_config, bert_pretrain_loss
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
    from mxnet_tpu.telemetry import compile as _compile
    _compile.use_default_cache()

    cfg = bert_base_config()
    batch, seq = args.batch, args.seq
    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    model.cast('bfloat16')
    devices = jax.devices()
    mesh = make_mesh((len(devices),), ('dp',), devices=devices)
    step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                            {'learning_rate': 1e-4}, mesh=mesh)

    rng = onp.random.RandomState(0)
    tokens = nd.array(rng.randint(0, cfg['vocab_size'], (batch, seq))
                      .astype(onp.int32))
    types = nd.array(onp.zeros((batch, seq), onp.int32))
    vl = nd.array(rng.randint(seq // 2, seq + 1, (batch,)).astype(onp.int32))
    nmask = max(8, int(0.15 * seq) // 8 * 8)
    mpos = onp.stack([rng.choice(seq, nmask, replace=False)
                      for _ in range(batch)]).astype(onp.int32)
    labels = nd.array(rng.randint(0, cfg['vocab_size'], (batch, nmask))
                      .astype(onp.int32))
    nsp = nd.array(rng.randint(0, 2, (batch,)).astype(onp.int32))
    inputs = [tokens, types, vl, nd.array(mpos)]

    t0 = time.time()
    v = float(step(inputs, [labels, nsp]).asnumpy())
    print(f"compile+first: {time.time() - t0:.1f}s loss={v:.4f}", flush=True)
    for _ in range(2):
        step(inputs, [labels, nsp])
    import contextlib
    tracer = jax.profiler.trace(args.trace) if args.trace \
        else contextlib.nullcontext()
    with tracer:
        t0 = time.time()
        for _ in range(args.steps):
            loss = step(inputs, [labels, nsp])
        float(loss.asnumpy())
        dt = (time.time() - t0) / args.steps
    if args.trace:
        print(f"xprof trace written to {args.trace}", flush=True)

    params = model.collect_params()
    P = sum(int(onp.prod(p.shape)) for p in params.values())

    def _psize(names):
        return sum(int(onp.prod(p.shape)) for n, p in params.items()
                   if any(s in n for s in names))
    P_embed = _psize(['word_embed', 'pos_embed', 'type_embed', 'embedding'])
    P_head = _psize(['mlm_'])
    P_pool = _psize(['pooler', 'nsp'])
    P_body = P - P_embed - P_head - P_pool
    toks = batch * seq
    flops = (6 * P_body * toks + 6 * P_head * batch * nmask
             + 6 * P_pool * batch
             + 12 * cfg['layers'] * cfg['hidden'] * seq * toks)
    mfu = flops / dt / peak * 100
    knobs = {k: v for k, v in os.environ.items() if 'MXTPU' in k}
    print(f"batch={batch} rbg={args.rbg} env={knobs}")
    print(f"step={dt * 1000:.1f}ms samples/sec={batch / dt:.1f} "
          f"MFU={mfu:.2f}%", flush=True)

    if args.trace:
        # span-level attribution over a few EXTRA per-step-synced steps
        # (so the clean timed number above stays untouched): measured
        # wall buckets + XLA cost_analysis, the honest-MFU decomposition
        # PERF_NOTES.md cites (telemetry/attribution.py)
        from mxnet_tpu.telemetry import attribution, flight, memory, trace
        trace.enable()
        memory.enable()
        flight.get().clear()
        for _ in range(6):
            float(step(inputs, [labels, nsp]).asnumpy())
        comm_plan = getattr(step, '_comm_plan', None) or {}
        rep = attribution.report(
            flight.get().steps(), flops_per_step=flops,
            peak_flops=peak,
            collective_bytes={k: v[0] for k, v in comm_plan.items()})
        xla = step.cost_analysis()
        if xla:
            rep['xla_cost_per_step'] = xla
        print(attribution.format_table(rep), flush=True)
        # the memory half of the same attribution pipeline (ISSUE 14):
        # per-device residency buckets next to the wall-time buckets —
        # what the remat-policy sweep spends is what this measures
        print(attribution.format_memory_table(step.memory_analysis()),
              flush=True)
        span_path = os.path.join(args.trace, 'mxtpu_spans.json')
        trace.dump(span_path)
        print(f"span trace written to {span_path} "
              f"(merge with the xprof view; validate with "
              f"tools/check_trace.py)", flush=True)


if __name__ == '__main__':
    main()
