"""chip_smoke.py — does the system still start on the chip?

One process drives the main path once, at the full width of BERT-base,
through the entry points a user calls, and checks what comes out:

1. device   jax must report a TPU; anything else exits 2 before a model
            is built (``--dry-run-cpu`` is the only CPU path, see below).
2. kernels  every Pallas kernel in mxnet_tpu/ops compiled by Mosaic at
            the BERT-base shapes (flash attention forward / dq / dk-dv
            with padding mask and in-kernel dropout, and causal at
            GPT-2's T=1024; fused add+LayerNorm; fused dense+GELU; one
            mx.rtc user kernel), each compared with a plain float32
            jax.numpy reference under matmul precision "highest".
3. train    BertForPretraining (12 layers, hidden 768, 12 heads, FFN
            3072, vocab 30522), T=512, 32 sequences per chip, bf16 with
            fp32 masters, padding mask, dropout 0.1, MLM on masked
            positions, AdamW, through ShardedTrainStep over a dp mesh of
            every chip: one compiling step plus nine more on a fixed
            seeded batch.
4. dp       (more than one chip) the same step at global batch 32,
            dropout off, depth 2, on one chip and on all of them: the
            losses must agree.
5. gluon    models/lenet.py under ctx=mx.tpu(0): hybridize(),
            gluon.Trainer(..., 'adam'), record/backward/step,
            save_parameters / load_parameters round trip.

Any failed check raises and the exit code is non-zero; nothing is caught
to let a later phase run. The last line of stdout is one JSON object
naming the device as jax reports it. Times are printed as information
and are NOT A BENCHMARK.

``--dry-run-cpu`` rehearses the same code in the sandbox: a 2-layer
hidden-128 model, kernels in interpret mode, the attention route the CPU
takes by design (XLA). Every line it prints says DRY RUN.
"""
import argparse
import json
import os
import re
import statistics
import sys
import tempfile
import time

import numpy as onp

TAG = '[chip_smoke]'


def say(msg):
    print(f"{TAG} {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


# ---------------------------------------------------------------------------
# phase 2: kernels
# ---------------------------------------------------------------------------

def _mosaic_calls(fn, *args):
    """(compiled, number of Mosaic custom calls in its optimized HLO)."""
    compiled = fn.lower(*args).compile()
    return compiled, compiled.as_text().count('tpu_custom_call')


def _close(name, got, ref, rel):
    import jax.numpy as jnp
    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    check(bool(jnp.all(jnp.isfinite(got))), f"{name}: non-finite values")
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    check(err <= rel * scale + 1e-6,
          f"{name}: max|err| {err:.3e} over max|ref| {scale:.3e} "
          f"(limit {rel:g} of it)")
    return err / max(scale, 1e-30)


def _flash_case(name, B, H, T, D, masked, causal, dropout_p, interpret):
    """flash_attention forward + dq/dk/dv against a float32 reference
    that applies the same mask, causal cut and dropout bits."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_attention import (
        _counter_keep, flash_attention)

    rng = onp.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
                   for _ in range(4))
    valid = rng.randint(T // 2, T + 1, (B,))
    kmask = jnp.asarray(onp.arange(T)[None, :] < valid[:, None]) \
        if masked else None
    seed = jnp.full((1, 1), 0x5EED, jnp.uint32)

    def kernel(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, key_mask=kmask, causal=causal, dropout_p=dropout_p,
            dropout_seed=seed if dropout_p else None,
            interpret=interpret), q, k, v)
        return (out,) + vjp(do)

    def reference(q, k, v, do):
        def f(q, k, v):
            s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / (D ** 0.5)
            if masked:
                s = jnp.where(kmask[:, None, None, :], s, -1e30)
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            if dropout_p:
                bh = jnp.arange(B * H, dtype=jnp.uint32).reshape(B, H, 1, 1)
                rows = jnp.arange(T, dtype=jnp.uint32).reshape(1, 1, T, 1)
                cols = jnp.arange(T, dtype=jnp.uint32).reshape(1, 1, 1, T)
                p = p * _counter_keep(seed[0, 0], bh, rows, cols, dropout_p)
            return jnp.einsum('bhqk,bhkd->bhqd', p, v)
        with jax.default_matmul_precision('highest'):
            out, vjp = jax.vjp(f, *(x.astype(jnp.float32)
                                    for x in (q, k, v)))
            return (out,) + vjp(do.astype(jnp.float32))

    t0 = time.perf_counter()
    compiled, n_mosaic = _mosaic_calls(jax.jit(kernel), q, k, v, do)
    compile_s = time.perf_counter() - t0
    check(n_mosaic == (0 if interpret else 3),
          f"{name}: {n_mosaic} Mosaic custom calls in the compiled "
          f"forward+backward, expected {0 if interpret else 3}")
    got = jax.block_until_ready(compiled(q, k, v, do))
    ref = jax.block_until_ready(jax.jit(reference)(q, k, v, do))
    errs = [_close(f"{name} {part}", g, r, 0.03)
            for part, g, r in zip(('out', 'dq', 'dk', 'dv'), got, ref)]
    say(f"kernel {name}: shape ({B},{H},{T},{D}) bf16, {n_mosaic} Mosaic "
        f"calls, compile {compile_s:.1f} s, rel err out/dq/dk/dv = "
        + '/'.join(f"{e:.1e}" for e in errs))


def phase_kernels(dry):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops.pallas_ffn import fused_dense_gelu
    from mxnet_tpu.ops.pallas_layernorm import fused_add_layer_norm

    interpret = dry
    if dry:
        bert, gpt, rows, hidden, ffn = (2, 2, 64, 16), (1, 2, 128, 16), \
            128, 128, 256
    else:
        bert, gpt, rows, hidden, ffn = (32, 12, 512, 64), \
            (8, 12, 1024, 64), 32 * 512, 768, 3072
    _flash_case('flash/mask', *bert, True, False, 0.0, interpret)
    _flash_case('flash/mask+dropout', *bert, True, False, 0.1, interpret)
    _flash_case('flash/causal+dropout', *gpt, False, True, 0.1, interpret)

    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randn(rows, hidden), jnp.bfloat16)
    r = jnp.asarray(rng.randn(rows, hidden), jnp.bfloat16)
    g = jnp.asarray(1 + 0.1 * rng.randn(hidden), jnp.bfloat16)
    b = jnp.asarray(0.1 * rng.randn(hidden), jnp.bfloat16)
    w = jnp.asarray(0.05 * rng.randn(ffn, hidden), jnp.bfloat16)
    wb = jnp.asarray(0.1 * rng.randn(ffn), jnp.bfloat16)
    want = 0 if interpret else 1

    ln, n = _mosaic_calls(jax.jit(lambda x, r, g, b: fused_add_layer_norm(
        x, r, g, b, 1e-5, 256, interpret)), x, r, g, b)
    check(n == want, f"fused_add_layer_norm: {n} Mosaic calls")
    s32 = x.astype(jnp.float32) + r.astype(jnp.float32)
    mean = s32.mean(-1, keepdims=True)
    ref = (s32 - mean) * jax.lax.rsqrt(s32.var(-1, keepdims=True) + 1e-5) \
        * g.astype(jnp.float32) + b.astype(jnp.float32)
    e = _close('fused_add_layer_norm', ln(x, r, g, b), ref, 0.02)
    say(f"kernel fused_add_layer_norm: ({rows},{hidden}) bf16, {n} Mosaic "
        f"call, rel err {e:.1e}")

    fg, n = _mosaic_calls(jax.jit(lambda x, w, wb: fused_dense_gelu(
        x, w, wb, 256, 256, interpret)), x, w, wb)
    check(n == want, f"fused_dense_gelu: {n} Mosaic calls")
    with jax.default_matmul_precision('highest'):
        ref = jax.nn.gelu(x.astype(jnp.float32) @ w.astype(jnp.float32).T
                          + wb.astype(jnp.float32), approximate=False)
    e = _close('fused_dense_gelu', fg(x, w, wb), ref, 0.02)
    say(f"kernel fused_dense_gelu: ({rows},{hidden})x({ffn},{hidden}) "
        f"bf16, {n} Mosaic call, rel err {e:.1e}")

    def scale_add(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + y_ref[...]
    op = mx.rtc.pallas_op(scale_add, out_like=0, interpret=interpret)
    a = mx.nd.array(rng.randn(256, hidden).astype('float32'))
    c = mx.nd.array(rng.randn(256, hidden).astype('float32'))
    got = op(a, c).asnumpy()
    check(onp.allclose(got, a.asnumpy() * 2.0 + c.asnumpy(), atol=1e-5),
          "rtc.pallas_op user kernel disagrees with numpy")
    say(f"kernel rtc.pallas_op scale_add: (256,{hidden}) f32 ok")


# ---------------------------------------------------------------------------
# phase 3/4: the train step
# ---------------------------------------------------------------------------

def _bert_config(dry):
    """(config, T): BERT-base as published, or the dry run's toy."""
    from mxnet_tpu.models.bert import bert_base_config
    if dry:
        return dict(vocab_size=1024, hidden=128, layers=2, heads=4,
                    intermediate=512, max_len=128, type_vocab=2), 128
    return bert_base_config(), 512


def _bert_batch(cfg, batch, seq, seed=0):
    from mxnet_tpu import nd
    rng = onp.random.RandomState(seed)
    nmask = max(8, int(0.15 * seq) // 8 * 8)
    tokens = rng.randint(0, cfg['vocab_size'], (batch, seq))
    valid = rng.randint(seq // 2, seq + 1, (batch,))
    mpos = onp.stack([rng.choice(seq, nmask, replace=False)
                      for _ in range(batch)])
    labels = rng.randint(0, cfg['vocab_size'], (batch, nmask))
    nsp = rng.randint(0, 2, (batch,))
    i32 = lambda a: nd.array(a.astype(onp.int32))          # noqa: E731
    return ([i32(tokens), i32(onp.zeros((batch, seq))), i32(valid),
             i32(mpos)], [i32(labels), i32(nsp)])


def _bert_step(cfg, devices, loss_fn):
    import mxnet_tpu as mx
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    mx.random.seed(0)
    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    model.cast('bfloat16')
    mesh = make_mesh((len(devices),), ('dp',), devices=devices)
    return model, ShardedTrainStep(model, loss_fn, 'adamw',
                                   {'learning_rate': 1e-4}, mesh=mesh)


class _CompileCounter:
    """Backend compile requests jax reports (served from the persistent
    cache or not), whoever made them."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.n += 1


def phase_train(dry, devices, compiles):
    from mxnet_tpu.models.bert import bert_pretrain_loss
    from mxnet_tpu.ops import attention as attn_ops
    from mxnet_tpu.telemetry import compile as _compile

    n = len(devices)
    cfg, seq = _bert_config(dry)
    per_chip = 4 if dry else 32
    batch = per_chip * n
    inputs, targets = _bert_batch(cfg, batch, seq)
    before = dict(attn_ops.route_counts)
    model, step = _bert_step(cfg, devices, bert_pretrain_loss)

    t0 = time.perf_counter()
    losses = [float(step(inputs, targets).asscalar())]
    first_s = time.perf_counter() - t0
    entry = [e for e in _compile.ledger() if e['site'] == 'step:train_step']
    check(len(entry) == 1, f"compile ledger holds {len(entry)} "
                           f"step:train_step entries after step 0")
    sec = entry[0]['seconds']
    say(f"train step 0: loss {losses[0]:.4f}, {first_s:.1f} s of which "
        f"compile trace/lower/backend = {sec['trace']:.1f}/"
        f"{sec['lower']:.1f}/{sec['backend']:.1f} s; cache "
        f"{entry[0].get('cache', {})}")

    # what the backend compiled: the flash kernels on this chip's own
    # (B/dp)·H slices, nothing gathering the global batch for them
    program = step.compiled_program()
    text = program.as_text()
    route = {k: attn_ops.route_counts[k] - before[k] for k in before}
    calls = [ln for ln in text.splitlines() if 'tpu_custom_call' in ln
             and ' custom-call(' in ln]
    heads = cfg['heads']
    if dry:
        check(route['xla'] > 0 and route['pallas'] == 0
              and route['ring'] == 0,
              f"DRY RUN route_counts {route}: the CPU takes XLA by design")
        check(not calls, "DRY RUN: Mosaic custom call on a CPU backend")
    else:
        check(route['pallas'] > 0 and route['xla'] == 0
              and route['ring'] == 0,
              f"route_counts {route}: attention did not take the kernel")
        check(len(calls) >= 3 * cfg['layers'],
              f"{len(calls)} Mosaic custom calls in the compiled step, "
              f"expected 3 per layer")
        lead = {int(m) for ln in calls for m in re.findall(
            r'bf16\[(\d+),%d,%d\]' % (seq, cfg['hidden'] // heads), ln)}
        check(lead == {per_chip * heads},
              f"flash custom calls run on leading dims {sorted(lead)}, "
              f"this chip's own slices are {per_chip * heads}")
    gathers = [ln for ln in text.splitlines()
               if re.search(r' all-gather(-start)?\(', ln)
               and re.search(r'\[(%d|%d),\d+,\d+' % (batch, batch * heads),
                             ln)]
    check(n == 1 or not gathers,
          "an all-gather rebuilds the global batch:\n" + '\n'.join(
              g.strip()[:200] for g in gathers[:3]))
    say(f"route_counts {route}; compiled step: {len(calls)} Mosaic custom "
        f"calls, {text.count(' all-gather')} all-gather / "
        f"{text.count(' reduce-scatter')} reduce-scatter / "
        f"{text.count(' all-reduce')} all-reduce lines, none rebuilding "
        f"the batch")

    ledger0, compiles0 = len(_compile.ledger()), compiles.n
    times = []
    for _ in range(9):
        t0 = time.perf_counter()
        losses.append(float(step(inputs, targets).asscalar()))
        times.append((time.perf_counter() - t0) * 1e3)
    check(all(onp.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the repeated batch: {losses}")
    check(len(_compile.ledger()) == ledger0 and compiles.n == compiles0,
          f"compilation after step 0: ledger {ledger0}->"
          f"{len(_compile.ledger())}, backend compiles {compiles0}->"
          f"{compiles.n}")
    say("train losses " + ' '.join(f"{v:.3f}" for v in losses))
    say(f"train steps 1-9: median {statistics.median(times):.1f} ms/step "
        f"at {per_chip} sequences/chip x {n} chip(s), T={seq} "
        f"(host clock around a synced step; not a benchmark); "
        f"no compilation after step 0")

    want = set(devices)
    for name, p in model.collect_params().items():
        check(p.data()._data.devices() == want,
              f"{name} lives on {p.data()._data.devices()}")
    if not dry:
        check({d.platform for d in want} == {'tpu'}, f"devices {want}")
    if n > 1:
        check(step.zero_stage == 1, f"zero_stage {step.zero_stage}")
        sharded = [nm for nm, st in step._opt_state.items()
                   if st[0].addressable_shards[0].data.size < st[0].size]
        check(sharded, "dp > 1 but no optimizer state is sharded")
        shards = step._opt_state[sharded[0]][0].addressable_shards
        check({s.device for s in shards} == want,
              "optimizer shards do not cover the mesh")
    plan = program.memory_analysis()
    say(f"XLA's byte plan for the step, per device: arguments "
        f"{plan.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
        f"{plan.temp_size_in_bytes / 2**30:.2f} GiB, outputs "
        f"{plan.output_size_in_bytes / 2**30:.2f} GiB "
        f"({plan.alias_size_in_bytes / 2**30:.2f} GiB aliased)")
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            check(dry, f"{d} reports no memory_stats")
            continue
        check(stats['bytes_in_use'] > 0, f"{d} holds no memory")
        say(f"{d}: in use {stats['bytes_in_use'] / 2**30:.2f} GiB, peak "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    return losses


def phase_dp_parity(dry, devices):
    """Global batch 32, dropout off, depth 2, full width: the first loss
    on one chip and on all of them, from the same seed."""
    from mxnet_tpu.models.bert import bert_pretrain_loss

    def loss_f32(mlm, nsp, labels, nsp_labels):
        return bert_pretrain_loss(mlm.astype('float32'),
                                  nsp.astype('float32'), labels, nsp_labels)

    cfg, seq = _bert_config(dry)
    cfg = dict(cfg, layers=2, dropout=0.0)
    batch = 2 * len(devices) if dry else 32
    check(batch % len(devices) == 0, f"batch {batch} over {len(devices)}")
    inputs, targets = _bert_batch(cfg, batch, seq, seed=1)
    got = {}
    for devs in (devices[:1], devices):
        _, step = _bert_step(cfg, devs, loss_f32)
        got[len(devs)] = [float(step(inputs, targets).asscalar())
                          for _ in range(2)]
    one, many = got[1], got[len(devices)]
    say(f"dp parity at global batch {batch}, dropout off: dp=1 losses "
        f"{one[0]:.5f} {one[1]:.5f}; dp={len(devices)} losses "
        f"{many[0]:.5f} {many[1]:.5f}")
    check(abs(one[0] - many[0]) < 1e-2,
          f"first loss differs: dp=1 {one[0]} vs dp={len(devices)} "
          f"{many[0]}")
    check(abs(one[1] - many[1]) < 5e-2,
          f"second loss differs: dp=1 {one[1]} vs dp={len(devices)} "
          f"{many[1]}")


# ---------------------------------------------------------------------------
# phase 5: gluon
# ---------------------------------------------------------------------------

def phase_gluon(dry):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.models import LeNet

    ctx = mx.cpu(0) if dry else mx.tpu(0)
    rng = onp.random.RandomState(0)
    x = rng.rand(64, 1, 28, 28).astype(onp.float32)
    y = (x.mean(axis=(1, 2, 3)) > 0.5).astype(onp.float32)
    xb, yb = nd.array(x, ctx=ctx), nd.array(y, ctx=ctx)

    mx.random.seed(0)
    net = LeNet(classes=2)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-3})
    losses = []
    for _ in range(8):
        with autograd.record():
            loss = loss_fn(net(xb), yb)
        loss.backward()
        trainer.step(x.shape[0])
        losses.append(float(loss.mean().asscalar()))
    check(all(onp.isfinite(losses)) and losses[-1] < losses[0],
          f"gluon loss did not fall: {losses}")
    check(getattr(trainer, '_fused_traced', False)
          and not getattr(trainer, '_fused_disabled', False),
          "gluon.Trainer abandoned its fused update for the eager loop")
    # mx reports any accelerator as gpu(i) so reference scripts that
    # compare against mx.gpu(0) work; what must not happen is cpu
    out = net(xb)
    platform = next(iter(out._data.devices())).platform
    check((out.context.device_type == 'cpu') == dry
          and platform == ('cpu' if dry else 'tpu'),
          f"output reports context {out.context} on a {platform} device")
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, 'lenet.params')
        net.save_parameters(path)
        again = LeNet(classes=2)
        again.load_parameters(path, ctx=ctx)
        again.hybridize()
        check(onp.array_equal(again(xb).asnumpy(), out.asnumpy()),
              "reloaded parameters do not reproduce the output")
    say(f"gluon LeNet on {out.context}: losses "
        + ' '.join(f"{v:.4f}" for v in losses)
        + "; fused update intact; save/load round trip exact")


# ---------------------------------------------------------------------------

def main():
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--dry-run-cpu', action='store_true',
                    help='rehearse on the CPU backend at a tiny size; '
                         'proves nothing about the chip')
    args = ap.parse_args()
    dry = args.dry_run_cpu
    if dry:
        TAG = '[chip_smoke DRY RUN]'

    t_start = time.perf_counter()
    import jax
    devices = jax.devices()
    dev = devices[0]
    want = 'cpu' if dry else 'tpu'
    if dev.platform != want:
        print(f"{TAG} jax reports platform {dev.platform!r} "
              f"({dev.device_kind}, {len(devices)} device(s)); this run "
              f"needs {want!r}. Nothing was built.", file=sys.stderr)
        return 2
    import jaxlib
    from importlib import metadata
    say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"{len(devices)} device(s); jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {metadata.version('libtpu')}")

    from mxnet_tpu.telemetry import compile as _compile
    cache = _compile.use_default_cache()
    _compile.enable()
    compiles = _CompileCounter()
    say(f"compile cache at {cache} "
        f"({_compile.persistent_cache_stats()['files']} files)")

    phase_kernels(dry)
    phase_train(dry, devices, compiles)
    if len(devices) > 1:
        phase_dp_parity(dry, devices)
    else:
        say("dp parity: one device, phase skipped")
    phase_gluon(dry)

    pc = _compile.persistent_cache_stats()
    say(f"compile cache: {pc['hits']} hits, {pc['misses']} misses, "
        f"{compiles.n} compile requests; total "
        f"{time.perf_counter() - t_start:.0f} s (not a benchmark)")
    result = {"ok": True, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind,
                                      "count": len(devices)}}
    if dry:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
