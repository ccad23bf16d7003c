"""Benchmark: BERT-base pretraining MFU (the north-star metric).

Baseline: the driver-defined north star is >=35% MFU for BERT-base
pretraining (BASELINE.md north-star table); vs_baseline = mfu / 35.

This script measures a chip or it measures nothing. The parent stays
off jax (a process that has touched jax holds the chip) and runs ONE
measurement child; the last JSON line the child printed is the result,
and it names the platform, device_kind and device count it came from.
No accelerator -> the child exits 2 before building anything, the
parent prints no metric line and exits 2. A device_kind that is not in
the peak table is an error, not a default. A side report that fails is
recorded in the line's "failed_reports" and the run exits 3; a child
that is cut off or dies after the flagship line leaves that line and a
non-zero exit. Exit 0 means every phase ran and none failed.

The measured step is the framework's hot path: fwd+bwd+AdamW update as ONE
pjit program (ShardedTrainStep), BERT-base seq 512 in bf16 WITH a padding
mask (the flagship config — the Pallas flash kernel handles the mask).
The child also records a pallas-vs-XLA attention timing + parity check
(compiled, not interpreted) in the same JSON. The side reports that
start processes of their own (--zero-probe, --compile-probe, the serving
drill) pin those children to JAX_PLATFORMS=cpu: the chip stays with the
measurement child.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as onp

def _log(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _last_json_line(text):
    """The last line of a child's stdout that parses as a JSON object,
    or None."""
    for line in reversed((text or '').strip().splitlines()):
        line = line.strip()
        if line.startswith('{'):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


# ---------------------------------------------------------------------------
# bf16 peak FLOP/s per chip, keyed on substrings of jax device_kind.
# Source: Google Cloud TPU documentation, the "System architecture" page
# of each generation (v5e: 197 TFLOP/s bf16, 819 GB/s HBM).
# ---------------------------------------------------------------------------
_PEAK_BF16 = [
    ('v6', 918e12), ('trillium', 918e12),
    ('v5p', 459e12),
    ('v5e', 197e12), ('v5 lite', 197e12), ('v5lite', 197e12),
    ('v4', 275e12),
    ('v3', 123e12),
    ('v2', 45e12),
]


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for sub, peak in _PEAK_BF16:
        if sub in kind:
            return peak
    raise RuntimeError(
        f"bench: device_kind {device.device_kind!r} is not in the peak "
        f"table (_PEAK_BF16); add it with its source before measuring "
        f"a utilization on it")


# ---------------------------------------------------------------------------
# pallas-vs-XLA attention micro-benchmark (accel child only)
# ---------------------------------------------------------------------------

def _pallas_report(batch: int) -> dict:
    """Compile the Pallas flash kernels on the real chip at the TRUE
    flagship shape (B=batch, not a cut-down), check fwd parity vs the XLA
    path, and time fwd and fwd+bwd-with-dropout (the training
    configuration) for both paths."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_attention import flash_attention

    B, H, T, D = batch, 12, 512, 64
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    valid = rng.randint(T // 2, T, (B,))
    kmask = jnp.asarray(onp.arange(T)[None, :] < valid[:, None])
    seed = jnp.full((1, 1), 7, jnp.uint32)

    def xla_ref(q, k, v, m):
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                       preferred_element_type=jnp.float32) / (D ** 0.5)
        s = jnp.where(m[:, None, None, :], s, -1e30)
        return jnp.einsum('bhqk,bhkd->bhqd',
                          jax.nn.softmax(s, -1).astype(q.dtype), v)

    def xla_train_loss(q):
        # like-for-like training workload: dropout on the materialized
        # probability tensor, exactly what the Pallas kernel avoids
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                       preferred_element_type=jnp.float32) / (D ** 0.5)
        s = jnp.where(kmask[:, None, None, :], s, -1e30)
        a = jax.nn.softmax(s, -1).astype(q.dtype)
        keep = jax.random.bernoulli(jax.random.PRNGKey(7), 0.9, a.shape)
        a = jnp.where(keep, a / 0.9, 0).astype(q.dtype)
        return jnp.sum(jnp.einsum('bhqk,bhkd->bhqd', a, v)
                       .astype(jnp.float32))

    pall = jax.jit(lambda q: flash_attention(
        q, k, v, key_mask=kmask, interpret=False))
    ref = jax.jit(lambda q: xla_ref(q, k, v, kmask))
    pall_t = jax.jit(jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, key_mask=kmask, dropout_p=0.1, dropout_seed=seed,
        interpret=False).astype(jnp.float32))))
    ref_t = jax.jit(jax.grad(xla_train_loss))

    o_p = jax.block_until_ready(pall(q))
    o_r = jax.block_until_ready(ref(q))
    err = float(jnp.max(jnp.abs(o_p.astype(jnp.float32)
                                - o_r.astype(jnp.float32))))

    def _time(fn, iters=15):
        # dispatches to one device run in order, so the last output's
        # block_until_ready ends the whole window
        jax.block_until_ready(fn(q))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    t_pallas, t_xla = _time(pall), _time(ref)
    t_pallas_t, t_xla_t = _time(pall_t), _time(ref_t)
    return {"shape": [B, H, T, D], "max_abs_err": round(err, 4),
            "fwd_pallas_ms": round(t_pallas, 3),
            "fwd_xla_ms": round(t_xla, 3),
            "train_pallas_ms": round(t_pallas_t, 3),
            "train_xla_ms": round(t_xla_t, 3),
            "train_speedup_vs_xla": round(
                t_xla_t / max(t_pallas_t, 1e-9), 3)}


# ---------------------------------------------------------------------------
# ResNet-50 secondary metric (BASELINE.md: images/sec/chip tracked;
# reference's own headline table is example/image-classification README)
# ---------------------------------------------------------------------------

def _resnet_report(batch=64):
    """ResNet-50 v1 training throughput: hybridized gluon zoo model,
    bf16, fused fwd+bwd+SGD step, batch sliced to the reference's
    224x224 config."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep

    net = resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    net.cast('bfloat16')

    def loss_fn(logits, labels):
        logp = nd.log_softmax(logits, axis=-1)
        return -nd.mean(nd.pick(logp, labels, axis=-1))

    devices = jax.devices()
    mesh = make_mesh((len(devices),), ('dp',), devices=devices)
    step = ShardedTrainStep(net, loss_fn, 'sgd',
                            {'learning_rate': 0.1, 'momentum': 0.9},
                            mesh=mesh)
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(batch, 3, 224, 224).astype(onp.float32))
    y = nd.array(rng.randint(0, 1000, (batch,)).astype(onp.int32))
    for _ in range(2):
        v = float(step([x], [y]).asnumpy())
        assert onp.isfinite(v), "non-finite resnet loss"
    steps = 8
    t0 = time.time()
    for _ in range(steps):
        loss = step([x], [y])
    float(loss.asnumpy())
    dt = (time.time() - t0) / steps
    return {"batch": batch, "step_ms": round(dt * 1000, 1),
            "images_per_sec_per_chip":
                round(batch / dt / len(devices), 1),
            "ref_baseline_images_per_sec": 109,
            "ref_baseline_hw": "1x K80 (example/image-classification)"}


# ---------------------------------------------------------------------------
# Data-IO secondary metric: decode+augment throughput of the native
# libjpeg pipeline (src/io/mxtpu_io.cc). The reference publishes
# ~3000 images/sec for its decode+augment loop
# (ref: docs/static_site/src/pages/api/architecture/note_data_loading.md:181)
# — host-side work.
# ---------------------------------------------------------------------------

def _io_report(n_images=384, src_hw=(360, 480), out_hw=224):
    """images/sec through ImageRecordIter: JPEG decode,
    resize-shorter-side, random crop to out_hw², mirror, mean/std.

    A/B across the host-boundary transports (ISSUE 3):
      f32-copy                 C++ normalizes to f32 NCHW, batch copied out
      u8-lease                 zero-copy uint8 NHWC buffer lease, mean/std
                               + NCHW conversion jitted on device
      u8-lease+device-prefetch same, plus 2 batches kept in flight on
                               device via async device_put
    Bytes through host per image come from the
    mxnet_tpu_io_host_bytes_total counter (u8 moves ~4x less than f32).
    """
    import io as pyio
    import tempfile

    from PIL import Image
    from mxnet_tpu import recordio, telemetry
    from mxnet_tpu.io import ImageRecordIter, DevicePrefetchIter

    with tempfile.TemporaryDirectory() as td:
        rec_path = os.path.join(td, 'bench.rec')
        rec = recordio.MXRecordIO(rec_path, 'w')
        rng = onp.random.RandomState(0)
        for i in range(n_images):
            img = (rng.rand(src_hw[0], src_hw[1], 3) * 255).astype(onp.uint8)
            buf = pyio.BytesIO()
            Image.fromarray(img).save(buf, format='JPEG', quality=90)
            rec.write(recordio.pack(
                recordio.IRHeader(0, float(i % 10), i, 0), buf.getvalue()))
        rec.close()

        batch = 64
        threads = os.cpu_count() or 4
        native = None

        def run(transport, device_prefetch, epochs=3):
            nonlocal native
            it = ImageRecordIter(
                path_imgrec=rec_path, data_shape=(3, out_hw, out_hw),
                batch_size=batch, resize=256, rand_crop=True,
                rand_mirror=True, mean_r=123.68, mean_g=116.78,
                mean_b=103.94, std_r=58.4, std_g=57.1, std_b=57.4,
                preprocess_threads=threads, transport=transport)
            native = getattr(it, '_pipe', None) is not None
            src = DevicePrefetchIter(it, depth=2) if device_prefetch else it
            # cold epoch (thread spin-up, jit trace, decode-cache fill),
            # timed separately — the timed epochs then measure the
            # steady-state transport path, which is what the A/B is
            # about; sync the last batch so async device work is inside
            # the measurement
            t0 = time.time()
            cold_seen = 0
            for batch_data in src:
                cold_seen += batch_data.data[0].shape[0]
            onp.asarray(batch_data.data[0].asnumpy())
            cold_ips = round(cold_seen / (time.time() - t0), 1)
            was_on = telemetry.enabled()
            telemetry.enable()
            bytes0 = telemetry.counter(
                'mxnet_tpu_io_host_bytes_total').value() or 0
            seen = 0
            t0 = time.time()
            for _ in range(epochs):
                src.reset()
                for batch_data in src:
                    seen += batch_data.data[0].shape[0]
            onp.asarray(batch_data.data[0].asnumpy())
            dt = time.time() - t0
            host_bytes = (telemetry.counter(
                'mxnet_tpu_io_host_bytes_total').value() or 0) - bytes0
            if not was_on:
                telemetry.disable()
            out = {"images_per_sec": round(seen / dt, 1),
                   "cold_epoch_images_per_sec": cold_ips,
                   "host_bytes_per_image": round(host_bytes / max(seen, 1))}
            if native:
                hits, misses, cache_bytes = it._pipe.cache_stats()
                out["decode_cache"] = {
                    "hits": int(hits), "misses": int(misses),
                    "bytes": int(cache_bytes)}
            return out

        ab = {"f32-copy": run('f32', False),
              "u8-lease": run('u8', False),
              "u8-lease+device-prefetch": run('u8', True)}
        best = ab["u8-lease+device-prefetch"]["images_per_sec"]
        return {"images_per_sec": best,
                "native_pipeline": native,
                "ab": ab,
                "u8_lease_speedup_vs_f32_copy": round(
                    ab["u8-lease"]["images_per_sec"]
                    / max(ab["f32-copy"]["images_per_sec"], 1e-9), 2),
                "host_bytes_ratio_f32_over_u8": round(
                    ab["f32-copy"]["host_bytes_per_image"]
                    / max(ab["u8-lease"]["host_bytes_per_image"], 1), 2),
                "decode": f"jpeg {src_hw[0]}x{src_hw[1]} -> resize256 -> "
                          f"crop{out_hw} + mirror + mean/std",
                "note": "timed epochs are steady-state (decode cache "
                        "warm); cold_epoch_images_per_sec is the "
                        "decode-bound first epoch",
                "decode_cache_mb": float(os.environ.get(
                    'MXNET_TPU_IO_DECODE_CACHE_MB', '256')),
                "threads": threads,
                "ref_baseline_images_per_sec": 3000}


def _zero_probe_child() -> None:
    """``--zero-probe``: one JSON line with the ZeRO memory trajectory
    on a forced 8-device host-CPU mesh — the tiny-BERT pjit step at
    stage off/1/3, param + master + optimizer bytes per device, gather
    wire bytes per step, and the 3-step loss parity across stages.
    Runs as its own process because the host device count must be fixed
    before jax initializes."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    prev = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in prev:
        os.environ['XLA_FLAGS'] = \
            (prev + ' --xla_force_host_platform_device_count=8').strip()
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_pretrain_loss
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep

    cfg = dict(vocab_size=1024, hidden=128, layers=2, heads=4,
               intermediate=256, max_len=128, type_vocab=2, dropout=0.0)
    mesh = make_mesh((8,), ('dp',))
    rng = onp.random.RandomState(0)
    batch, seq = 8, 64
    tokens = nd.array(rng.randint(0, cfg['vocab_size'], (batch, seq))
                      .astype(onp.int32))
    types = nd.array(onp.zeros((batch, seq), onp.int32))
    labels = onp.full((batch, seq), -1, onp.int32)
    labels[:, :8] = rng.randint(0, cfg['vocab_size'], (batch, 8))
    labels = nd.array(labels)
    nsp = nd.array(rng.randint(0, 2, batch).astype(onp.int32))

    rep, losses = {'dp': 8}, {}
    for stage in (0, 1, 3):
        mx.random.seed(0)
        model = BertForPretraining(cfg)
        model.initialize(mx.init.Normal(0.02))
        step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                                {'learning_rate': 1e-4}, mesh=mesh,
                                zero=stage)
        losses[stage] = [
            float(step([tokens, types], [labels, nsp]).asscalar())
            for _ in range(3)]
        pb = step.param_bytes_per_device()
        sb = step.opt_state_bytes_per_device()
        rep[f'stage{stage}'] = {
            'param_bytes_per_device': pb,
            'opt_state_bytes_per_device': sb,
            'persistent_bytes_per_device': pb + sb,
            'gather_bytes_per_step': step.gather_bytes_per_step(),
            'comm_bytes_per_step': {k: int(v[0]) for k, v in
                                    step._comm_plan.items()},
        }
    rep['loss_max_diff_3v1'] = max(
        abs(a - b) for a, b in zip(losses[3], losses[1]))
    rep['loss_max_diff_3v0'] = max(
        abs(a - b) for a, b in zip(losses[3], losses[0]))
    print(json.dumps(rep), flush=True)


def _zero_report(step, timeout=240.0):
    """The ``"zero"`` field: the live bench step's ZeRO stage and
    residency numbers, plus — when the live mesh has no >1-device dp
    axis (the 1-device CPU smoke) — a ``--zero-probe`` subprocess on a
    forced 8-device mesh so BENCH rounds capture the off/1/3 memory
    trajectory either way."""
    live = {
        'stage': getattr(step, 'zero_stage', 1 if step.zero else 0),
        'dp': step._dp_size,
        'param_bytes_per_device': step.param_bytes_per_device(),
        'opt_state_bytes_per_device': step.opt_state_bytes_per_device(),
        'gather_bytes_per_step': step.gather_bytes_per_step(),
        'comm_bytes_per_step': {k: int(v[0]) for k, v in
                                (step._comm_plan or {}).items()},
    }
    if step._dp_size > 1:
        return live
    # never let the probe blow the child's overall budget (same contract
    # as the resnet report): clamp to the remaining deadline and skip
    # when too little is left for three stage compiles
    child_deadline = float(os.environ.get('BENCH_CHILD_DEADLINE', '0'))
    if child_deadline:
        timeout = min(timeout, child_deadline - time.time() - 30)
        if timeout < 45:
            live['dp8_probe'] = {'skipped': 'child deadline too close'}
            return live
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), '--zero-probe'],
        capture_output=True, text=True, timeout=timeout)
    live['dp8_probe'] = _last_json_line(res.stdout)
    if live['dp8_probe'] is None:
        raise RuntimeError(f'no JSON from zero probe '
                           f'(rc={res.returncode}): {res.stderr[-200:]}')
    return live


def _compile_probe_child() -> None:
    """``--compile-probe``: one JSON line with the compile ledger of a
    tiny-BERT pjit step built FROM SCRATCH in this process, the compile
    plane armed over ``BENCH_COMPILE_LEDGER`` and the persistent XLA
    cache over ``BENCH_COMPILE_CACHE_DIR``. ``_compile_report`` runs it
    twice against one shared cache dir: the first process pays the full
    cold XLA backend compile, the second must hit the cache — the
    process-level cold-vs-warm A/B (a fresh process is the only honest
    cold start: jax's in-memory caches die with it)."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    prev = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in prev:
        os.environ['XLA_FLAGS'] = \
            (prev + ' --xla_force_host_platform_device_count=8').strip()
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_pretrain_loss
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
    from mxnet_tpu.telemetry import compile as _compile

    _compile.enable()
    _compile.clear(
        ledger=os.environ.get('BENCH_COMPILE_LEDGER', ''),
        cache_dir=os.environ.get('BENCH_COMPILE_CACHE_DIR', ''))
    cfg = dict(vocab_size=1024, hidden=128, layers=2, heads=4,
               intermediate=256, max_len=128, type_vocab=2, dropout=0.0)
    mesh = make_mesh((8,), ('dp',))
    rng = onp.random.RandomState(0)
    batch, seq = 8, 64
    tokens = nd.array(rng.randint(0, cfg['vocab_size'], (batch, seq))
                      .astype(onp.int32))
    types = nd.array(onp.zeros((batch, seq), onp.int32))
    labels = onp.full((batch, seq), -1, onp.int32)
    labels[:, :8] = rng.randint(0, cfg['vocab_size'], (batch, 8))
    labels = nd.array(labels)
    nsp = nd.array(rng.randint(0, 2, batch).astype(onp.int32))

    mx.random.seed(0)
    # auto-named: the step jit boundary is name-stable (positional
    # token aliases), so A/B processes share cache entries regardless
    # of where the gluon naming counter sits
    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                            {'learning_rate': 1e-4}, mesh=mesh)
    loss = float(step([tokens, types], [labels, nsp]).asscalar())

    sites = {}
    for e in _compile.ledger():
        sites[e['site']] = round(
            sites.get(e['site'], 0.0) + e['seconds']['total'], 4)
    ent = [e for e in _compile.ledger()
           if e['site'] == 'step:train_step']
    sec = ent[-1]['seconds'] if ent else {}
    pc = _compile.persistent_cache_stats()
    rep = {
        'loss': round(loss, 6),
        'site_seconds': sites,
        'step': {k: round(v, 4) for k, v in sec.items()},
        'cache': {'hits': pc['hits'], 'misses': pc['misses'],
                  'saved_seconds_est': round(pc['saved_seconds_est'], 4),
                  'bytes': pc['bytes'], 'files': pc['files']},
        'ledger_entries': len(_compile.ledger()),
    }
    print(json.dumps(rep), flush=True)


def _run_compile_probe(cache_dir, ledger, timeout):
    """One ``--compile-probe`` child sharing cache_dir + ledger; the
    parsed JSON dict (module-level so the bench contract test can stub
    the subprocess away)."""
    env = dict(os.environ, BENCH_COMPILE_CACHE_DIR=cache_dir,
               BENCH_COMPILE_LEDGER=ledger)
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), '--compile-probe'],
        capture_output=True, text=True, timeout=timeout, env=env)
    doc = _last_json_line(res.stdout)
    if doc is None:
        raise RuntimeError(f'no JSON from compile probe '
                           f'(rc={res.returncode}): {res.stderr[-200:]}')
    return doc


def _compile_report(timeout=240.0):
    """The ``"compile"`` field (ISSUE 16): the live process's per-site
    compile seconds from the in-memory ledger (when the plane is
    armed), plus the cold-vs-warm persistent-cache A/B — two
    ``--compile-probe`` child processes sharing one XLA cache dir and
    one on-disk ledger, so the warm child's saved-seconds estimate is
    priced from the cold child's recorded compile time."""
    import tempfile
    from mxnet_tpu.telemetry import compile as _compile
    out = {'enabled': _compile.enabled(),
           'ledger_path': _compile.ledger_path() or None}
    if _compile.enabled():
        sites = {}
        for e in _compile.ledger():
            sites[e['site']] = round(
                sites.get(e['site'], 0.0) + e['seconds']['total'], 4)
        out['site_seconds'] = sites
    # same deadline contract as the zero/resnet reports: each A/B child
    # gets an equal slice of what's left, and too-little-left skips
    child_deadline = float(os.environ.get('BENCH_CHILD_DEADLINE', '0'))
    if child_deadline:
        timeout = min(timeout, (child_deadline - time.time() - 30) / 2)
        if timeout < 45:
            out['cache_ab'] = {'skipped': 'child deadline too close'}
            return out
    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, 'xla_cache')
        ledger = os.path.join(td, 'ledger.jsonl')
        cold = _run_compile_probe(cache, ledger, timeout)
        warm = _run_compile_probe(cache, ledger, timeout)
    ab = {'cold': cold, 'warm': warm,
          'warm_hit': bool((warm.get('cache') or {}).get('hits'))}
    cb = (cold.get('step') or {}).get('backend')
    wb = (warm.get('step') or {}).get('backend')
    if cb and wb:
        ab['backend_speedup'] = round(cb / max(wb, 1e-9), 1)
    out['cache_ab'] = ab
    return out


def _serving_report(requests=60, deadlines=(0.0, 2.0, 8.0),
                    fleet_timeout=180.0):
    """The ``"serving"`` field (ISSUE 17): measured predict QPS and
    p50/p99 latency vs the batch-formation deadline on one replica
    (same compiled programs across the sweep — the engines share one
    warmed runner), an int8-quantized A/B on the same traffic, and the
    two-replica fleet drill's numbers (failover storm QPS, drain MTTR,
    cold-vs-warm AOT warmup seconds)."""
    import tempfile
    import threading

    import numpy as onp

    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.telemetry import compile as _compile

    _compile.enable()     # the warmup report's compile count reads it

    class _Tok(nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(64, 32)
                self.proj = nn.Dense(8, flatten=False)

        def forward(self, x):
            return self.proj(self.embed(x))

    def _storm(engine, seqs):
        errs = []

        def client(seq):
            try:
                engine.submit(seq, timeout=60.0)
            except Exception as e:                    # noqa: BLE001
                errs.append(repr(e))
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(s,))
                   for s in seqs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        wall = time.perf_counter() - t0
        st = engine.stats()
        return {'qps': round(len(seqs) / max(wall, 1e-9), 1),
                'p50_ms': st['p50_ms'], 'p99_ms': st['p99_ms'],
                'batches': st['batches'],
                'fill': round(len(seqs) / max(st['batches'], 1), 2),
                'errors': errs[:3]}

    rng = onp.random.RandomState(11)
    seqs = [[int(v) for v in rng.randint(0, 64, rng.randint(1, 33))]
            for _ in range(requests)]
    net = _Tok()
    net.initialize()
    runner = serving.BlockRunner(net)
    out = {'requests': requests, 'seq_buckets': [16, 32],
           'batch_buckets': [1, 2, 4, 8]}
    sweep = {}
    for i, dl in enumerate(deadlines):
        eng = serving.InferenceEngine(
            runner, seq_buckets='16,32', batch_buckets='1,2,4,8',
            deadline_ms=dl)
        if i == 0:
            # one warmup covers the whole sweep: every engine rides the
            # same block's CachedOp programs
            warm = serving.warmup(eng)
            out['warmup'] = {'total_seconds': warm['total_seconds'],
                             'compiles': warm['compiles']}
        sweep[f'{dl:g}ms'] = _storm(eng, seqs)
        eng.drain()
    out['deadline_sweep'] = sweep
    # int8 weights A/B on the same traffic (PR 11 codec grid): the
    # latency delta and the worst-case output drift on a fixed probe
    probe = [1, 2, 3, 5, 7]
    base = onp.asarray(runner(onp.asarray(
        [probe + [0] * 11], 'int32')))[0, :5]
    qnet = _Tok()
    qnet.initialize()
    qnet(nd.array(onp.zeros((1, 16), 'int32')))
    fd, tmp = tempfile.mkstemp(suffix='.params')
    os.close(fd)
    try:
        net.save_parameters(tmp)
        qnet.load_parameters(tmp)
    finally:
        os.unlink(tmp)
    serving.quantize_weights(qnet, 'int8')
    qrunner = serving.BlockRunner(qnet)
    qeng = serving.InferenceEngine(qrunner, seq_buckets='16,32',
                                   batch_buckets='1,2,4,8',
                                   deadline_ms=2.0)
    serving.warmup(qeng)
    qab = _storm(qeng, seqs)
    qeng.drain()
    qout = onp.asarray(qrunner(onp.asarray(
        [probe + [0] * 11], 'int32')))[0, :5]
    qab['max_output_drift'] = round(
        float(onp.max(onp.abs(qout - base))), 5)
    out['int8_ab'] = qab
    # the fleet half: 2 replica processes + router, SIGTERM mid-storm
    child_deadline = float(os.environ.get('BENCH_CHILD_DEADLINE', '0'))
    if child_deadline and child_deadline - time.time() < 90:
        out['fleet'] = {'skipped': 'child deadline too close'}
        return out
    from mxnet_tpu.resilience.drill import run_serving_drill
    with tempfile.TemporaryDirectory() as td:
        drill = run_serving_drill(td, timeout=fleet_timeout)
    out['fleet'] = {
        'requests': drill['requests'], 'failed': drill['failed'],
        'failovers': drill['failovers'],
        'mttr_seconds': drill['mttr_seconds'],
        'warmup_cold_seconds': drill['warmup'][1]['total_seconds'],
        'warmup_warm_seconds': drill['warmup'][2]['total_seconds'],
        'warm_cache_hits': drill['warmup'][2]['cache']['hits'],
        'p50_ms': {r: s['p50_ms'] for r, s in drill['stats'].items()},
    }
    return out


def _run_autotune_sweep(db_dir, heads=12, seq=512, head_dim=64):
    """One flash-attention autotune sweep at the flagship BERT shape
    into ``db_dir`` (module-level so the contract tests stub it)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import autotune
    return autotune.sweep_flash_attention(
        batch=1, heads=heads, seq=seq, head_dim=head_dim,
        dtype=jnp.float32, db_dir=db_dir)


def _autotune_report(timeout=120.0):
    """The ``"autotune"`` field (ISSUE 18): the flash-attention block
    sweep at the flagship shape — measured on TPU, analytic ranking on
    CPU — plus the round-trip proof: a fresh ``_block_sizes`` resolve
    consumes the winner the sweep just persisted (source ``db``), which
    is exactly what the compile-ledger signature records in training."""
    import tempfile

    import jax.numpy as jnp

    from mxnet_tpu import config as _mxcfg
    from mxnet_tpu.ops import autotune

    child_deadline = float(os.environ.get('BENCH_CHILD_DEADLINE', '0'))
    if child_deadline and child_deadline - time.time() < 90:
        return {'skipped': 'child deadline too close'}
    out = {'remat_policy': _mxcfg.get('MXTPU_REMAT')}
    prev_dir = os.environ.get('MXTPU_AUTOTUNE_DIR')
    with tempfile.TemporaryDirectory() as td:
        try:
            rep = _run_autotune_sweep(td)
            out['mode'] = rep.get('mode')
            out['sweep_seconds'] = rep.get('sweep_seconds')
            for kind in ('fwd', 'bwd'):
                r = rep.get(kind)
                if r:
                    out[kind] = {'winner': r['winner'],
                                 'source': r['source'],
                                 'candidates': r['candidates'],
                                 'pruned': r['pruned'],
                                 'signature': r['signature']}
            # consumption round trip: a clean resolve state + the DB dir
            # in the env must route _block_sizes to the persisted winner
            os.environ['MXTPU_AUTOTUNE_DIR'] = td
            autotune.clear()
            from mxnet_tpu.ops.pallas_attention import _block_sizes
            got = _block_sizes(12, 512, 512, 64, jnp.float32, 'fwd')
            out['consumed'] = {'blocks': list(got),
                               'decisions': autotune.decision_flags()}
        finally:
            if prev_dir is None:
                os.environ.pop('MXTPU_AUTOTUNE_DIR', None)
            else:
                os.environ['MXTPU_AUTOTUNE_DIR'] = prev_dir
            autotune.clear()
    return out


def _run_sparse_drill(hot_fractions=(1.0, 0.1, 0.02), vocab=20000,
                      dim=32, batch=64, seq=8, steps=3):
    """One dense-vs-sparse embedding drill (module-level so the
    contract tests stub it): build one sparse and one dense step over
    the same wide-table model, then at each hot fraction draw batches
    from the first ``hot_fraction * vocab`` rows and time both paths.
    Returns the sweep rows plus the sparse step's analytic report."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import ShardedTrainStep

    def loss_fn(out, label):
        return (out - label) ** 2

    lab_np = onp.random.RandomState(1).randn(
        batch, seq, 8).astype('float32')
    warm_np = onp.random.RandomState(2).randint(
        0, vocab, size=(batch, seq)).astype('float32')

    def build(sparse):
        # the step builds lazily on its first call, so the env knob
        # must still hold when the warmup step runs — warm up here,
        # inside the knob's scope (also moves compile off the timers)
        os.environ['MXTPU_SPARSE'] = '1' if sparse else '0'
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Embedding(vocab, dim, sparse_grad=True))
        net.add(nn.Dense(8, flatten=False))
        net.initialize()
        step = ShardedTrainStep(net, loss_fn, 'adam',
                                {'learning_rate': 0.01})
        step(nd.array(warm_np), nd.array(lab_np)).asnumpy()
        return step

    prev = os.environ.get('MXTPU_SPARSE')
    try:
        s_step = build(True)
        d_step = build(False)
        lab = nd.array(lab_np)
        sweep = []
        for frac in hot_fractions:
            hot = max(1, int(vocab * frac))
            rng = onp.random.RandomState(3)
            row = {'hot_fraction': frac}
            for tag, st in (('sparse', s_step), ('dense', d_step)):
                times = []
                for _ in range(steps):
                    ids = nd.array(rng.randint(
                        0, hot, size=(batch, seq)).astype('float32'))
                    t0 = time.perf_counter()
                    st(ids, lab).asnumpy()
                    times.append((time.perf_counter() - t0) * 1e3)
                row[f'{tag}_p50_ms'] = sorted(times)[len(times) // 2]
            stats = getattr(s_step, '_sparse_prev_stats', None) or {}
            live = sum(int(v) for v in stats.values())
            row['live_rows'] = live
            row['update_bytes'] = live * dim * 4
            row['dedup_ratio'] = round(batch * seq / max(1, live), 2)
            sweep.append(row)
        return {'report': s_step.sparse_report(), 'sweep': sweep}
    finally:
        if prev is None:
            os.environ.pop('MXTPU_SPARSE', None)
        else:
            os.environ['MXTPU_SPARSE'] = prev


def _sparse_report():
    """The ``"sparse"`` field (ISSUE 19): update-bytes/step and step
    time, sparse vs dense, across hot-fraction sweeps — the RowSparse
    fast path's shrink measured end to end on the live step."""
    child_deadline = float(os.environ.get('BENCH_CHILD_DEADLINE', '0'))
    if child_deadline and child_deadline - time.time() < 90:
        return {'skipped': 'child deadline too close'}
    drill = _run_sparse_drill()
    rep = drill['report'] or {}
    return {
        'mode': rep.get('mode'),
        'tables': rep.get('tables'),
        'update_bytes_per_step': rep.get('update_bytes_per_step'),
        'dense_update_bytes_per_step':
            rep.get('dense_update_bytes_per_step'),
        'update_shrink': rep.get('update_shrink'),
        'exchange_bytes_per_hop': rep.get('exchange_bytes_per_hop'),
        'sweep': drill['sweep'],
    }


def _memory_report(step, run_step, steps=4):
    """The ``"memory"`` field (ISSUE 14): live/peak watermark over a few
    sampled steps (the backend allocator's ``memory_stats`` where it
    exists, the deterministic tracked-array fallback otherwise), the
    ``memory_analysis()`` per-device bucket table whose sum
    reconstructs the measured peak, and whether XLA's compiled-program
    memory analysis was available on this backend — so every BENCH
    round pins the memory trajectory next to the time one."""
    from mxnet_tpu.telemetry import memory

    was = memory.enabled()
    memory.clear()                       # samples only; pools survive
    memory.enable()
    # idempotent re-registration: the report must measure THIS step's
    # residency even if something earlier in the child wiped the
    # registry (clear(pools=True))
    memory.register_provider(step)
    memory.set_analysis_provider(step.memory_analysis, owner=step)
    try:
        for _ in range(steps):
            run_step()
        rep = step.memory_analysis()
        wm = memory.watermarks()
        out = {
            'samples': len(wm),
            'live_bytes_per_device': wm[-1]['device_bytes'] if wm
            else None,
            'peak_bytes_per_device': memory.peak_bytes(),
            'host_rss_bytes': memory.host_rss_bytes(),
            'source': wm[-1]['source'] if wm else None,
            'memory_analysis_available': rep is not None,
            'xla_memory_analysis_available':
                bool(rep and rep.get('xla')),
        }
        if rep:
            out['buckets_bytes'] = rep['buckets_bytes']
            out['bucket_sum_over_peak'] = rep['bucket_sum_over_peak']
            out['measured_fraction'] = rep['measured_fraction']
            out['zero_stage'] = rep['zero_stage']
            if rep.get('xla'):
                out['xla'] = rep['xla']
        return out
    finally:
        memory.clear()
        (memory.enable if was else memory.disable)()


def _attribution_report(step, model, run_step, flops, peak_total,
                        steps=8):
    """Per-step attribution (ISSUE 6): arm span tracing, run a few
    synced steps, and decompose wall time into input / h2d / compute /
    collective / host-sync buckets joined with XLA cost_analysis — so
    BENCH_r06+ carries fractions, not just img/s and step ms.

    When the run itself was launched with MXTPU_TRACE=1, also save one
    checkpoint inside the traced window (covering the checkpoint.*
    spans) and leave `bench_trace.json` behind — a single
    chrome://tracing-loadable timeline of the whole traced segment.
    """
    from mxnet_tpu import config as _mxcfg
    from mxnet_tpu.telemetry import attribution, flight, trace

    armed_by_env = _mxcfg.get('MXTPU_TRACE')
    trace.enable()
    flight.get().clear()
    for _ in range(steps):
        run_step()
    if armed_by_env:
        import tempfile
        from mxnet_tpu.checkpoint import CheckpointManager
        with tempfile.TemporaryDirectory() as td:
            mgr = CheckpointManager(td, params=model, async_save=False)
            mgr.save(steps)
    comm_plan = getattr(step, '_comm_plan', None) or {}
    rep = attribution.report(
        flight.get().steps(), flops_per_step=flops,
        peak_flops=peak_total,
        collective_bytes={k: v[0] for k, v in comm_plan.items()},
        gather_layers=getattr(step, '_gather_plan', None))
    xla = step.cost_analysis()
    if xla:
        rep['xla_cost_per_step'] = xla
    rep['subsystems'] = attribution.subsystems(
        {e['name'] for e in trace.chrome_events()}
        | {n for r in flight.get().steps() for n in r['spans_ms']})
    if armed_by_env:
        rep['trace_dump'] = trace.dump('bench_trace.json')
    else:
        trace.disable()
    return rep


def _fleet_report(run_step, steps=6):
    """Endpoint-armed vs disarmed step-time A/B (ISSUE 13): the same
    step timed with everything observability off, then with telemetry +
    tracing armed, the /metrics //healthz endpoint up AND a scraper
    hammering it concurrently — plus the wire size of one heartbeat
    telemetry snapshot. The PERF_NOTES "what does watching cost" row."""
    import threading
    import urllib.request
    from mxnet_tpu import telemetry
    from mxnet_tpu.base import telem_flags
    from mxnet_tpu.telemetry import fleet, server, trace

    was_telem, was_trace = telem_flags['on'], trace.enabled()

    def timed(n):
        t0 = time.time()
        for _ in range(n):
            run_step()
        return (time.time() - t0) / n * 1e3

    srv = None
    stop = threading.Event()
    scrapes = [0]
    t = None
    try:
        telemetry.disable()
        trace.disable()
        run_step()                               # settle / recompile
        disarmed_ms = timed(steps)
        telemetry.enable()
        trace.enable()
        srv = server.TelemetryServer(port=0)

        def _scrape():
            base = f'http://127.0.0.1:{srv.port}'
            while not stop.is_set():
                try:
                    urllib.request.urlopen(base + '/metrics',
                                           timeout=2).read()
                    urllib.request.urlopen(base + '/healthz',
                                           timeout=2).read()
                    scrapes[0] += 1
                except Exception:
                    pass
                stop.wait(0.05)

        t = threading.Thread(target=_scrape, daemon=True)
        t.start()
        run_step()                               # settle under arming
        armed_ms = timed(steps)
        snap_bytes = fleet.snapshot_bytes()
    finally:
        # a mid-A/B failure must not leave the child's telemetry/trace
        # disarmed (the atexit flight dump would be empty) or leak the
        # scraper + server for the rest of the process
        stop.set()
        if t is not None:
            t.join(timeout=2)
        if srv is not None:
            srv.stop()
        (telemetry.enable if was_telem else telemetry.disable)()
        (trace.enable if was_trace else trace.disable)()
    return {
        'steps': steps,
        'step_ms_disarmed': round(disarmed_ms, 2),
        'step_ms_armed': round(armed_ms, 2),
        'overhead_pct': round(
            (armed_ms - disarmed_ms) / disarmed_ms * 100.0, 2)
        if disarmed_ms else None,
        'snapshot_bytes_per_beat': snap_bytes,
        'scrapes_during_armed_window': scrapes[0],
    }


# ---------------------------------------------------------------------------
# measurement child
# ---------------------------------------------------------------------------

def _child() -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform == 'cpu':
        _log(f"no accelerator: jax reports {len(devices)} "
             f"{devices[0].platform} device(s); nothing to measure")
        sys.exit(2)
    _log(f"child backend={devices[0].platform} "
         f"kind={devices[0].device_kind} n={len(devices)}")
    peak = _peak_flops(devices[0])      # unknown kind: fail before compiling

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_base_config, bert_pretrain_loss
    from mxnet_tpu.parallel import make_mesh, ShardedTrainStep
    from mxnet_tpu.telemetry import compile as _compile
    _compile.use_default_cache()

    cfg = bert_base_config()
    batch = int(os.environ.get('BENCH_BATCH', '32'))
    seq, steps, warmup = 512, 10, 3
    dtype = 'bfloat16'

    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    model.cast(dtype)

    mesh = make_mesh((len(devices),), ('dp',), devices=devices)
    step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                            {'learning_rate': 1e-4}, mesh=mesh)

    rng = onp.random.RandomState(0)
    tokens = nd.array(rng.randint(0, cfg['vocab_size'], (batch, seq))
                      .astype(onp.int32))
    types = nd.array(onp.zeros((batch, seq), onp.int32))
    # flagship config trains WITH a padding mask (sequences padded to 512)
    valid_length = nd.array(rng.randint(seq // 2, seq + 1, (batch,))
                            .astype(onp.int32))
    # GluonNLP recipe: the MLM decoder runs only on the masked positions
    # (max_predictions_per_seq), not all T of them
    nmask = max(8, int(0.15 * seq) // 8 * 8)
    mpos = onp.stack([rng.choice(seq, nmask, replace=False)
                      for _ in range(batch)]).astype(onp.int32)
    masked_positions = nd.array(mpos)
    labels = nd.array(rng.randint(0, cfg['vocab_size'], (batch, nmask))
                      .astype(onp.int32))
    nsp = nd.array(rng.randint(0, 2, (batch,)).astype(onp.int32))

    from mxnet_tpu.ops import attention as attn_ops
    inputs = [tokens, types, valid_length, masked_positions]
    for i in range(warmup):
        v = float(step(inputs, [labels, nsp]).asnumpy())
        _log(f"warmup {i}: loss={v:.4f}")
        assert onp.isfinite(v), "non-finite loss"
    route = dict(attn_ops.route_counts)
    _log(f"attention routing (trace-time): {route}")
    t0 = time.time()
    for _ in range(steps):
        loss = step(inputs, [labels, nsp])
    float(loss.asnumpy())  # sync the whole chain
    dt = (time.time() - t0) / steps

    # Honest MFU accounting: lookup-only embedding tables do no matmul
    # FLOPs; the MLM head (dense+ln+decoder) touches only the nmask masked
    # positions; pooler+nsp touch one position per sequence.
    params = model.collect_params()
    P = sum(int(onp.prod(p.shape)) for p in params.values())
    def _psize(names):
        return sum(int(onp.prod(p.shape)) for n, p in params.items()
                   if any(s in n for s in names))
    P_embed = _psize(['word_embed', 'pos_embed', 'type_embed',
                      'embedding'])
    P_head = _psize(['mlm_'])
    P_pool = _psize(['pooler', 'nsp'])
    P_body = P - P_embed - P_head - P_pool
    tokens_per_step = batch * seq
    # PaLM-appendix accounting: 6*P per processed token (fwd+bwd) + the
    # O(T^2) attention term 12*L*h*T per token
    flops = (6 * P_body * tokens_per_step
             + 6 * P_head * batch * nmask
             + 6 * P_pool * batch
             + 12 * cfg['layers'] * cfg['hidden'] * seq * tokens_per_step)
    sps_chip = batch / dt / len(devices)
    _log(f"params={P / 1e6:.1f}M (matmul-active body={P_body / 1e6:.1f}M "
         f"head={P_head / 1e6:.1f}M embed={P_embed / 1e6:.1f}M) "
         f"step={dt * 1000:.1f}ms samples/sec/chip={sps_chip:.2f}")

    mfu = flops / dt / (peak * len(devices)) * 100.0
    out = {
        "metric": "bert_base_pretrain_mfu",
        "value": round(mfu, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / 35.0, 3),
        "backend": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "samples_per_sec_per_chip": round(sps_chip, 2),
        "step_ms": round(dt * 1000, 1),
        "batch": batch, "seq": seq, "dtype": dtype, "masked": True,
        "mlm_positions": int(nmask),
        "flop_accounting": "honest: embeddings excluded, MLM head "
                           "counted on masked positions only",
        "attn_route": route,
        "peak_flops_assumed": peak,
    }
    # the flagship metric is safe from here on: print it NOW, then
    # enrich with the side reports and print a line after each — the
    # parent takes the LAST parseable JSON line, and if the child is
    # cut off it still finds this one in the partial stdout
    print(json.dumps(out), flush=True)

    def run_step():
        return float(step(inputs, [labels, nsp]).asnumpy())

    def resnet():
        deadline = float(os.environ.get('BENCH_CHILD_DEADLINE', '0'))
        if deadline and time.time() > deadline - 180:
            return {"skipped": "child deadline too close"}
        return _resnet_report()

    # attribution after io: with MXTPU_TRACE=1 the whole child traced
    # from import, so the dumped timeline also carries the io spans
    failed = _run_side_reports(out, [
        ("pallas", lambda: _pallas_report(batch)),
        ("resnet50", resnet),
        ("io", _io_report),
        ("zero", lambda: _zero_report(step)),
        ("memory", lambda: _memory_report(step, run_step)),
        ("attribution", lambda: _attribution_report(
            step, model, run_step, flops, peak * len(devices))),
        ("fleet", lambda: _fleet_report(run_step)),
        ("compile", _compile_report),
        ("serving", _serving_report),
        ("autotune", _autotune_report),
        ("sparse", _sparse_report),
    ])
    if failed:
        sys.exit(3)


def _run_side_reports(out, reports):
    """Run each (name, fn) side report into ``out[name]``, printing the
    enriched line after each. A report that raises is recorded as
    ``{"error": ...}`` and named in ``out["failed_reports"]`` so the
    rest still run — and the caller ends the run non-zero for it.
    Returns the failed names."""
    failed = []
    for name, fn in reports:
        try:
            out[name] = fn()
            _log(f"{name} report: {out[name]}")
        except Exception as e:
            out[name] = {"error": repr(e)[:300]}
            failed.append(name)
            out["failed_reports"] = list(failed)
            _log(f"{name} report failed: {e!r}")
        print(json.dumps(out), flush=True)
    return failed


# ---------------------------------------------------------------------------
# parent: stays off jax, runs the one measurement child
# ---------------------------------------------------------------------------

def _run_child(timeout: float):
    """Run the measurement child. Returns (doc, rc): the last JSON line
    it printed (None if it printed none) and its exit code (124 when it
    was cut off at ``timeout``)."""
    cmd = [sys.executable, os.path.abspath(__file__), '--child']
    env = dict(os.environ,
               BENCH_CHILD_DEADLINE=str(time.time() + timeout))
    try:
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, env=env)
    except subprocess.TimeoutExpired as te:
        partial = te.stdout or b''
        if isinstance(partial, bytes):
            partial = partial.decode(errors='replace')
        _log(f"child cut off at {timeout:.0f}s")
        return _last_json_line(partial), 124
    sys.stderr.write(res.stderr[-4000:])
    return _last_json_line(res.stdout), res.returncode


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == '--zero-probe':
        _zero_probe_child()
        return 0
    if len(sys.argv) >= 2 and sys.argv[1] == '--compile-probe':
        _compile_probe_child()
        return 0
    if len(sys.argv) >= 2 and sys.argv[1] == '--child':
        _child()
        return 0

    doc, rc = _run_child(900.0)
    if doc is None:
        _log(f"no result: measurement child exited {rc} without a "
             f"metric line")
        return rc or 1
    if rc != 0:
        doc['child_rc'] = rc
        _log(f"measurement child exited {rc}: the line below is what it "
             f"had measured by then")
    print(json.dumps(doc), flush=True)
    return rc


if __name__ == '__main__':
    sys.exit(main())
