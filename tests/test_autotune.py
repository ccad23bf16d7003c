"""Pallas kernel autotuner (ISSUE 18): static Mosaic legality, the
tuning-DB round trip through _block_sizes, the precedence ladder, the
remat-policy seam, and the compile-ledger signature integration."""
import json
import os
import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import autotune
from mxnet_tpu.ops.pallas_attention import _block_sizes


@pytest.fixture(autouse=True)
def _clean_autotune(monkeypatch):
    """Every test starts with no env overrides, no DB dir, and a clean
    decision/forced/cache state."""
    for k in ('MXTPU_AUTOTUNE_DIR', 'MXTPU_FA_G', 'MXTPU_FA_BQ',
              'MXTPU_FA_BK', 'MXTPU_FA_BWD_G', 'MXTPU_FA_BWD_BQ',
              'MXTPU_FA_BWD_BK', 'MXTPU_REMAT'):
        monkeypatch.delenv(k, raising=False)
    autotune.clear()
    yield
    autotune.clear()


# ---------------------------------------------------------------------------
# static legality
# ---------------------------------------------------------------------------

def test_r3_postmortem_shape_is_pruned_statically():
    """The r3 on-chip failure — a 2-D (1, 512) key-mask block over a
    (BH, Tk) array, which Mosaic refuses to lower — is rejected by the
    static tile rule; the current 3-D (G, 1, bk) mask layout (the r3
    fix) passes by the block==array-dim equality rule."""
    BH, T = 96, 512
    f32 = jnp.dtype('float32')
    ok, why = autotune.tile_legal((BH, T), (1, T), f32)
    assert not ok and 'sublane dim 1' in why and '96' in why
    ok3, _ = autotune.tile_legal((BH, 1, T), (4, 1, T), f32)
    assert ok3
    # and check_candidate prunes for real: a sublane-misaligned bq and
    # a VMEM-busting giant both carry named reasons
    bad_bq, why_bq = autotune.check_candidate(
        BH, T, T, 64, f32, 'fwd', 4, 12, 128)
    assert not bad_bq and 'sublane' in why_bq
    bad_vm, why_vm = autotune.check_candidate(
        16, 4096, 4096, 256, f32, 'bwd', 16, 4096, 4096)
    assert not bad_vm and 'VMEM' in why_vm
    cands, pruned = autotune.legal_candidates(BH, T, T, 64, f32, 'fwd')
    assert cands and pruned > 0


def test_legal_candidates_are_self_consistent():
    """Every candidate the enumerator emits re-passes the per-candidate
    checker (legality + VMEM budget) for both kernel directions."""
    for dtype in (jnp.dtype('float32'), jnp.dtype(jnp.bfloat16)):
        for kind in ('fwd', 'bwd'):
            cands, _ = autotune.legal_candidates(
                12, 512, 512, 64, dtype, kind)
            assert cands, (dtype, kind)
            for G, bq, bk in cands:
                ok, why = autotune.check_candidate(
                    12, 512, 512, 64, dtype, kind, G, bq, bk)
                assert ok, (dtype, kind, G, bq, bk, why)
                assert autotune.vmem_bytes(G, bq, bk, 64, kind) \
                    <= autotune.VMEM_BUDGET


def test_bf16_raises_sublane_minimum():
    assert autotune.sublane_min(jnp.dtype('float32')) == 8
    assert autotune.sublane_min(jnp.dtype(jnp.bfloat16)) == 16
    # a bq of 8 is legal for f32 but not for bf16: at Tq=8, because the
    # row statistics ride with the queries on the lanes and a bq under
    # 128 has to be the whole sequence
    ok_f32, _ = autotune.check_candidate(
        8, 8, 512, 64, jnp.dtype('float32'), 'fwd', 8, 8, 128)
    ok_bf16, _ = autotune.check_candidate(
        8, 8, 512, 64, jnp.dtype(jnp.bfloat16), 'fwd', 8, 8, 128)
    assert ok_f32 and not ok_bf16
    part, why = autotune.check_candidate(
        8, 512, 512, 64, jnp.dtype('float32'), 'fwd', 8, 8, 128)
    assert not part and why.startswith('lse: lane dim 8')


def test_g_is_heads_a_step_in_lane_blocks():
    """The tuning key stays (BH, Tq, Tk, D) and G heads a grid step: the
    layouts the checker reads are the kernels' lane blocks, G // hb batch
    rows of hb = 128 // D heads side by side, and the VMEM estimate of G
    heads is what it was when each had a block of its own."""
    layouts = {name: (array, block) for name, array, block in
               autotune.fa_block_layouts(672, 512, 512, 64, 'bwd',
                                         4, 256, 256)}
    assert layouts['q'] == ((336, 512, 128), (2, 256, 128))
    assert layouts['dk'] == ((336, 512, 128), (2, 256, 128))
    assert layouts['kmask'] == ((336, 1, 512), (2, 1, 256))
    # the row statistics: queries on the lanes, a row a head
    assert layouts['lse'] == layouts['delta'] == ((336, 2, 1, 512),
                                                  (2, 2, 1, 256))
    # one 128-wide head a block: G rows
    assert dict((n, b) for n, _a, b in autotune.fa_block_layouts(
        32, 512, 512, 128, 'fwd', 4, 512, 512))['q'] == (4, 512, 128)
    # a G under the heads of a lane block still takes one whole block
    assert dict((n, b) for n, _a, b in autotune.fa_block_layouts(
        12, 64, 64, 64, 'fwd', 1, 64, 64))['q'] == (1, 64, 128)
    assert autotune.vmem_bytes(4, 512, 512, 64, 'fwd') == 11534336
    bf16 = jnp.dtype(jnp.bfloat16)
    assert autotune.check_candidate(672, 512, 512, 64, bf16, 'bwd',
                                    4, 256, 256) == (True, None)


@pytest.mark.parametrize('shape', [
    (1, 16, None, 128, 4096), (1, 28, 4, 128, 8192), (2, 16, None, 128, 512),
    (3, 8, None, 64, 512), (56, 12, None, 64, 512)],
    ids=['ouro', 'smallthinker', 'two_rows', 'three_rows_d64', 'bert'])
@pytest.mark.parametrize('kind', ['fwd', 'bwd_dq', 'bwd_dkv'])
@pytest.mark.parametrize('G', [1, 2, 4, 7, 8])
def test_layouts_are_the_blocks_the_kernels_build(shape, kind, G):
    """With N (and the group) known ``fa_block_layouts`` lists the blocks
    ``_block_specs`` builds, G heads a step as rows first and then lane
    blocks, and ``vmem_bytes`` counts the heads on either side of them:
    so ``resolve``'s clamp and ``check_candidate`` judge the real build."""
    from mxnet_tpu.ops import pallas_attention as pa
    N, H, Hkv, D, T = shape
    rep = H // (Hkv or H)
    cap = 512 if kind == 'fwd' else 256
    W, hb = pa._lane_block(H * D, D)
    Gn, lb = pa._step_heads(N, H * D, D, G, rep)
    seq, row, mask = pa._block_specs(kind, Gn, hb, cap, cap, W, True, lb, rep)
    layouts = {name: (array, block) for name, array, block in
               autotune.fa_block_layouts(N * H, T, T, D,
                                         'fwd' if kind == 'fwd' else 'bwd',
                                         G, cap, cap, N=N, rep=rep)}
    if kind == 'bwd_dkv' and rep > 1:
        # the listed blocks are dq's; dk/dv's query side is the group
        assert seq('q').block_shape == (Gn, cap, rep * W)
        assert row.block_shape == (Gn, rep, 1, cap)
    else:
        assert layouts['q'] == ((N, T, H * D), seq('q').block_shape)
        assert layouts['lse'] == ((N, H, 1, T), row.block_shape)
    assert layouts['k'] == layouts['v'] == ((N, T, H * D // rep),
                                            seq('k').block_shape)
    assert layouts['kmask'] == ((N, 1, T), mask.block_shape)
    heads = Gn * layouts['lse'][1][1]
    kv = Gn * layouts['k'][1][2] // D
    assert heads == Gn * lb * hb and kv == max(1, heads // rep)
    # the estimate of that step: operands at their size, the
    # accumulators and statistics in float32, the cell's temporaries
    bf16 = jnp.dtype(jnp.bfloat16)
    assert autotune.vmem_bytes(heads, cap, cap, D, kind[:3], 2, kv) == (
        2 * (heads + 2 * kv) * cap * D * 2
        + (heads + kv) * cap * (D + 256) * 4
        + (3 if kind == 'fwd' else 6) * cap * cap * 4)
    ok, why = autotune.check_candidate(
        N * H, T, T, D, bf16, kind[:3], heads, cap, cap, N=N, rep=rep)
    assert ok == (autotune.vmem_bytes(heads, cap, cap, D, kind[:3], 2, kv)
                  <= autotune.VMEM_BUDGET), why


# ---------------------------------------------------------------------------
# tuning DB: round trip, corruption, precedence
# ---------------------------------------------------------------------------

def test_db_round_trip_through_block_sizes(tmp_path, monkeypatch):
    """A sweep-persisted winner is consumed by a fresh _block_sizes
    resolve (the production seam), with the decision recorded as
    db-sourced for the compile-ledger signature."""
    sig = autotune.shape_sig(4, 64, 64, 64, jnp.dtype('float32'), 'fwd')
    path = autotune.record_winner(autotune.KERNEL_FA, sig, (2, 32, 32),
                                  {'source': 'measured'},
                                  dir_=str(tmp_path))
    doc = json.loads(open(path).read())
    assert doc['version'] == autotune.DB_VERSION
    monkeypatch.setenv('MXTPU_AUTOTUNE_DIR', str(tmp_path))
    autotune.clear()
    assert _block_sizes(4, 64, 64, 64, jnp.float32, 'fwd') == (2, 32, 32)
    flags = autotune.decision_flags()
    assert flags == {f"{autotune.KERNEL_FA}:{sig}": 'db:2x32x32'}
    # an unknown shape still falls through to the defaults
    assert _block_sizes(4, 128, 128, 64, jnp.float32, 'fwd') \
        == (4, 128, 128)
    assert autotune.decisions()[
        f"{autotune.KERNEL_FA}:"
        f"{autotune.shape_sig(4, 128, 128, 64, jnp.dtype('float32'), 'fwd')}"
    ]['source'] == 'default'


def test_corrupt_db_falls_back_with_one_warning(tmp_path, monkeypatch):
    """A truncated/corrupt DB degrades to the built-in defaults with
    exactly ONE RuntimeWarning per path — never an exception."""
    db = tmp_path / autotune.DB_BASENAME
    db.write_text('{"version": 1, "entries": {')     # truncated write
    monkeypatch.setenv('MXTPU_AUTOTUNE_DIR', str(tmp_path))
    autotune.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        first = _block_sizes(4, 64, 64, 64, jnp.float32, 'fwd')
        second = _block_sizes(4, 64, 64, 64, jnp.float32, 'bwd')
    assert first == (4, 64, 64) and second == (4, 64, 64)
    corrupt = [x for x in w if issubclass(x.category, RuntimeWarning)
               and 'corrupt or truncated' in str(x.message)]
    assert len(corrupt) == 1, [str(x.message) for x in w]


def test_env_override_beats_db(tmp_path, monkeypatch):
    """Precedence: an env knob wins over a DB winner, and the decision
    source says so; unset fields fall through to the DB value."""
    sig = autotune.shape_sig(4, 64, 64, 64, jnp.dtype('float32'), 'fwd')
    autotune.record_winner(autotune.KERNEL_FA, sig, (1, 32, 32),
                           dir_=str(tmp_path))
    monkeypatch.setenv('MXTPU_AUTOTUNE_DIR', str(tmp_path))
    monkeypatch.setenv('MXTPU_FA_BQ', '16')
    autotune.clear()
    G, bq, bk = _block_sizes(4, 64, 64, 64, jnp.float32, 'fwd')
    assert (G, bq, bk) == (1, 16, 32)      # bq from env, G/bk from DB
    flags = autotune.decision_flags()
    assert flags[f"{autotune.KERNEL_FA}:{sig}"].startswith('env:')
    # MXTPU_FA_*=0 means unset — back to the DB winner
    monkeypatch.setenv('MXTPU_FA_BQ', '0')
    autotune.clear()
    assert _block_sizes(4, 64, 64, 64, jnp.float32, 'fwd') == (1, 32, 32)


def test_resolve_clamps_illegal_group_to_divisor():
    """Safety clamps survive the ladder: a DB/env G that does not
    divide BH is clamped down to a divisor, never dispatched raw."""
    got = autotune.resolve(autotune.KERNEL_FA, 6, 64, 64, 64,
                           jnp.dtype('float32'), 'fwd', default=(4, 64, 64))
    assert got[0] in (1, 2, 3, 6) and 6 % got[0] == 0


# ---------------------------------------------------------------------------
# CPU sweep -> DB -> ledger signature
# ---------------------------------------------------------------------------

def test_cpu_sweep_writes_db_and_ledger_names_the_source(tmp_path,
                                                         monkeypatch):
    """The analytic CPU sweep persists winners a fresh process-state
    resolve consumes, and the compile-ledger entry's signature carries
    the db-sourced block decision as a flag — the ISSUE 18 acceptance
    path."""
    from mxnet_tpu.ops.pallas_attention import flash_attention
    from mxnet_tpu.telemetry import compile as _compile

    rep = autotune.sweep_flash_attention(
        batch=1, heads=4, seq=64, head_dim=64,
        dtype=jnp.float32, db_dir=str(tmp_path))
    assert rep['mode'] == 'analytic'
    assert rep['fwd']['winner'] and rep['bwd']['winner']
    assert rep['fwd']['pruned'] > 0

    monkeypatch.setenv('MXTPU_AUTOTUNE_DIR', str(tmp_path))
    autotune.clear()
    ledger = tmp_path / 'ledger.jsonl'
    _compile.enable()
    _compile.clear(ledger=str(ledger))
    try:
        ctx = _compile.begin('step:train_step')
        q = jnp.asarray(onp.random.RandomState(0)
                        .randn(1, 4, 64, 64).astype('float32'))
        out = jax.jit(flash_attention)(q, q, q)
        out.block_until_ready()
        flags = autotune.decision_flags()
        assert any(v.startswith('db:') for v in flags.values()), flags
        _compile.set_signature(ctx, _compile.signature(
            args=[], flags={'autotune': flags}))
        _compile.end(ctx)
    finally:
        _compile.clear()
        _compile.disable()
    entries = [json.loads(l) for l in ledger.read_text().splitlines()]
    e = [x for x in entries if x.get('site') == 'step:train_step'][0]
    enc = json.dumps(e['signature'])
    assert 'db:' in enc and 'flash_attention' in enc


# ---------------------------------------------------------------------------
# remat policy
# ---------------------------------------------------------------------------

def test_remat_policy_validation(monkeypatch):
    from mxnet_tpu import config as _cfg
    from mxnet_tpu.base import MXNetError
    assert _cfg.get('MXTPU_REMAT') == 'none'
    monkeypatch.setenv('MXTPU_REMAT', 'layer')
    assert _cfg.get('MXTPU_REMAT') == 'layer'
    monkeypatch.setenv('MXTPU_REMAT', 'full')
    assert _cfg.get('MXTPU_REMAT') == 'aggressive'
    monkeypatch.setenv('MXTPU_REMAT', 'bogus')
    with pytest.raises(MXNetError):
        _cfg.get('MXTPU_REMAT')


def test_remat_policies_keep_loss_parity(monkeypatch):
    """Remat changes what backward recomputes, never the values: the
    same tiny encoder trained under none/layer/aggressive produces the
    same losses to <=1e-6."""
    from mxnet_tpu.models.bert import masked_cross_entropy
    from mxnet_tpu.models.transformer import TransformerEncoder
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh

    def run(policy):
        monkeypatch.setenv('MXTPU_REMAT', policy)
        mx.random.seed(0)
        net = TransformerEncoder(16, hidden=32, layers=1, heads=2,
                                 ffn_hidden=64, max_len=16, dropout=0.0)
        net.initialize(mx.init.Xavier())
        mesh = make_mesh((1,), ('dp',), devices=jax.devices()[:1])
        step = ShardedTrainStep(net, masked_cross_entropy, 'adam',
                                {'learning_rate': 1e-3}, mesh=mesh)
        assert step._remat_policy == policy
        src = onp.random.RandomState(0).randint(
            4, 16, (4, 8)).astype('int32')
        return [float(step([nd.array(src)],
                           [nd.array(src)]).asnumpy())
                for _ in range(2)]

    base = run('none')
    for policy in ('layer', 'aggressive'):
        got = run(policy)
        assert max(abs(a - b) for a, b in zip(base, got)) <= 1e-6, \
            (policy, base, got)


def test_remat_policy_lands_in_step_signature(monkeypatch):
    """The policy is a named flag in the step's build signature — a
    remat change shows up as a flag churn axis, not a mystery
    recompile."""
    from mxnet_tpu.models.bert import masked_cross_entropy
    from mxnet_tpu.models.transformer import TransformerEncoder
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh

    monkeypatch.setenv('MXTPU_REMAT', 'aggressive')
    mx.random.seed(0)
    net = TransformerEncoder(16, hidden=32, layers=1, heads=2,
                             ffn_hidden=64, max_len=16, dropout=0.0)
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((1,), ('dp',), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, masked_cross_entropy, 'adam',
                            {'learning_rate': 1e-3}, mesh=mesh)
    src = onp.random.RandomState(0).randint(4, 16, (4, 8)).astype('int32')
    step([nd.array(src)], [nd.array(src)])
    sig = step._build_signature(
        (onp.asarray(src),), (onp.asarray(src),))
    assert sig['flags']['remat'] == 'aggressive'
    assert 'autotune' in sig['flags']


# ---------------------------------------------------------------------------
# fused FFN epilogue
# ---------------------------------------------------------------------------

def test_fused_dense_gelu_matches_reference():
    """The Pallas FFN1 epilogue (interpret mode on CPU) matches the
    unfused dense+bias+exact-GELU in both values and gradients."""
    from mxnet_tpu.ops.pallas_ffn import fused_dense_gelu

    rng = onp.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 128).astype('float32'))
    w = jnp.asarray((rng.randn(256, 128) * 0.05).astype('float32'))
    b = jnp.asarray(rng.randn(256).astype('float32') * 0.1)

    def ref(x, w, b):
        return jax.nn.gelu(x @ w.T + b, approximate=False)

    got = fused_dense_gelu(x, w, b, 256, 256, True)
    onp.testing.assert_allclose(onp.asarray(got),
                                onp.asarray(ref(x, w, b)),
                                rtol=2e-5, atol=2e-5)
    g_got = jax.grad(lambda *a: fused_dense_gelu(*a, 256, 256, True)
                     .sum(), argnums=(0, 1, 2))(x, w, b)
    g_ref = jax.grad(lambda *a: ref(*a).sum(), argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(g_got, g_ref):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(r),
                                    rtol=2e-4, atol=2e-4)


def test_dense_gelu_default_route_is_unfused(monkeypatch):
    """With MXTPU_PALLAS_FFN unset the seam routes the historical
    Dense-then-GELU path (bit-identical), so the flag is a pure
    opt-in."""
    from mxnet_tpu.ops import nn as nn_ops

    monkeypatch.delenv('MXTPU_PALLAS_FFN', raising=False)
    rng = onp.random.RandomState(5)
    x = jnp.asarray(rng.randn(4, 32).astype('float32'))
    w = jnp.asarray((rng.randn(64, 32) * 0.1).astype('float32'))
    b = jnp.asarray(rng.randn(64).astype('float32') * 0.1)
    got = onp.asarray(nn_ops.dense_gelu(x, w, b))
    ref = onp.asarray(nn_ops.activation(
        nn_ops.fully_connected(x, w, b, num_hidden=64, flatten=False),
        act_type='gelu'))
    onp.testing.assert_array_equal(got, ref)
