"""bench.py driver-artifact contract: the script measures a chip or it
measures nothing. No accelerator -> non-zero exit and no metric line; a
device_kind missing from the peak table is an error, never a default; a
side report that fails ends the run non-zero. The side reports' own
JSON shapes are pinned below with their subprocesses stubbed away —
these are contract tests on the emitted JSON, not benchmarks."""
import importlib.util
import io
import json
import os
import subprocess
import sys

import pytest


@pytest.fixture()
def bench(monkeypatch):
    path = os.path.join(os.path.dirname(__file__), os.pardir, 'bench.py')
    spec = importlib.util.spec_from_file_location('bench_under_test', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod.time, 'sleep', lambda *_a: None)
    monkeypatch.setattr(sys, 'argv', ['bench.py'])
    return mod


def _emitted_line(mod, capsys):
    out = capsys.readouterr().out
    lines = [l for l in out.strip().splitlines() if l.startswith('{')]
    assert lines, f"no JSON line emitted: {out!r}"
    return json.loads(lines[-1])


FLAGSHIP = {'metric': 'bert_base_pretrain_mfu', 'value': 30.1,
            'unit': '% MFU', 'backend': 'tpu', 'device_kind': 'TPU v5 lite',
            'device_count': 1}


def test_no_accelerator_exits_nonzero_without_a_metric_line():
    """The real script on this CPU-only sandbox: the measurement child
    finds no accelerator and exits 2 before building anything; the
    parent prints NO JSON line (a CPU number must never appear under a
    device metric's name) and exits non-zero."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, 'bench.py')
    res = subprocess.run(
        [sys.executable, path], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert res.returncode != 0, res.stdout
    assert not [l for l in res.stdout.splitlines() if l.startswith('{')], \
        res.stdout
    assert 'no accelerator' in res.stderr


def test_unknown_device_kind_is_an_error_not_a_default(bench):
    """A device the peak table does not know has no utilization: the
    lookup raises and names the kind instead of assuming a v5e."""
    class Dev:
        device_kind = 'TPU v9 imaginary'
    with pytest.raises(RuntimeError, match='TPU v9 imaginary'):
        bench._peak_flops(Dev())
    Dev.device_kind = 'TPU v5 lite'
    assert bench._peak_flops(Dev()) == 197e12


def test_failed_side_report_ends_the_run_nonzero(bench, capsys, monkeypatch):
    """A side report that raises is recorded, the rest still run, the
    line names it in "failed_reports" — and the run's exit code is not
    0, in the child and through the parent."""
    def boom():
        raise ValueError('report exploded')
    out = dict(FLAGSHIP)
    failed = bench._run_side_reports(out, [('io', boom),
                                           ('zero', lambda: {'stage': 1})])
    assert failed == ['io'] and out['failed_reports'] == ['io']
    assert 'report exploded' in out['io']['error']
    assert out['zero'] == {'stage': 1}           # later reports still ran
    assert _emitted_line(bench, capsys)['failed_reports'] == ['io']

    monkeypatch.setattr(bench, '_run_child', lambda timeout: (out, 3))
    assert bench.main() == 3
    doc = _emitted_line(bench, capsys)
    assert doc['child_rc'] == 3 and doc['metric'] == FLAGSHIP['metric']
    # and a child that printed nothing leaves nothing to print
    monkeypatch.setattr(bench, '_run_child', lambda timeout: (None, 1))
    assert bench.main() == 1
    assert '{' not in capsys.readouterr().out


def test_compile_report_contract(bench, monkeypatch):
    """The "compile" field (ISSUE 16): cold/warm probe children share
    one cache dir + ledger, the A/B carries warm_hit and the backend
    speedup — pinned with a stubbed probe so no subprocess (and no jax
    compile) runs."""
    calls = []

    def fake_probe(cache_dir, ledger, timeout):
        calls.append((cache_dir, ledger))
        cold = not calls[1:]
        return {
            'loss': 7.5,
            'site_seconds': {'step:train_step': 6.1 if cold else 1.4},
            'step': {'trace': 0.9, 'lower': 0.4,
                     'backend': 4.8 if cold else 0.25,
                     'total': 6.1 if cold else 1.4},
            'cache': ({'hits': 0, 'misses': 17, 'saved_seconds_est': 0.0}
                      if cold else
                      {'hits': 17, 'misses': 0,
                       'saved_seconds_est': 6.1}),
            'ledger_entries': 1 if cold else 2,
        }

    monkeypatch.setattr(bench, '_run_compile_probe', fake_probe)
    monkeypatch.delenv('BENCH_CHILD_DEADLINE', raising=False)
    rep = bench._compile_report()
    # both children must share ONE cache dir and ONE ledger file — the
    # warm process's hit and saved-seconds estimate depend on it
    assert len(calls) == 2 and calls[0] == calls[1]
    ab = rep['cache_ab']
    assert ab['warm_hit'] is True
    assert ab['backend_speedup'] == round(4.8 / 0.25, 1)
    assert ab['cold']['cache']['misses'] == 17
    assert ab['warm']['cache']['saved_seconds_est'] == 6.1
    assert 'enabled' in rep and 'ledger_path' in rep


def test_compile_report_respects_child_deadline(bench, monkeypatch):
    """Too little left on the child budget: the A/B is skipped, never
    started — the flagship metric's deadline wins."""
    def boom(*_a):
        raise AssertionError("probe must not spawn under a tight deadline")
    monkeypatch.setattr(bench, '_run_compile_probe', boom)
    monkeypatch.setenv('BENCH_CHILD_DEADLINE',
                       str(bench.time.time() + 60))
    rep = bench._compile_report()
    assert rep['cache_ab'] == {'skipped': 'child deadline too close'}


def test_serving_report_contract(bench, monkeypatch):
    """The "serving" field (ISSUE 17): a measured deadline sweep with
    QPS + p50/p99 per point, an int8 A/B with bounded output drift, and
    the fleet numbers from the (stubbed) two-replica drill — the
    in-process half runs for real on the tiny model, the subprocess
    drill is pinned."""
    import mxnet_tpu.resilience.drill as drill
    fake = {
        'ok': True, 'requests': 90, 'failed': 0, 'failovers': 2,
        'mttr_seconds': 0.21, 'reloaded_step': 7,
        'warmup': {1: {'total_seconds': 0.9, 'compiles': 19,
                       'cache': {'hits': 0, 'misses': 15}},
                   2: {'total_seconds': 0.5, 'compiles': 19,
                       'cache': {'hits': 15, 'misses': 0}}},
        'stats': {1: {'p50_ms': 5.1}, 2: {'p50_ms': 4.9}},
    }
    monkeypatch.setattr(drill, 'run_serving_drill',
                        lambda td, timeout=180.0: fake)
    monkeypatch.delenv('BENCH_CHILD_DEADLINE', raising=False)
    rep = bench._serving_report(requests=12, deadlines=(0.0, 2.0))
    assert rep['warmup']['compiles'] > 0
    sweep = rep['deadline_sweep']
    assert set(sweep) == {'0ms', '2ms'}
    for point in sweep.values():
        assert point['qps'] > 0 and not point['errors']
        assert point['p99_ms'] >= point['p50_ms']
    assert rep['int8_ab']['max_output_drift'] < 0.1
    fleet = rep['fleet']
    assert fleet['failed'] == 0 and fleet['mttr_seconds'] == 0.21
    assert fleet['warm_cache_hits'] == 15
    assert fleet['warmup_warm_seconds'] < fleet['warmup_cold_seconds']


def test_serving_report_fleet_respects_child_deadline(bench, monkeypatch):
    """Too little left on the child budget: the fleet drill is skipped,
    never spawned — the flagship metric's deadline wins (the same
    contract as the compile A/B)."""
    import mxnet_tpu.resilience.drill as drill

    def boom(*_a, **_k):
        raise AssertionError("drill must not spawn under a tight deadline")
    monkeypatch.setattr(drill, 'run_serving_drill', boom)
    monkeypatch.setenv('BENCH_CHILD_DEADLINE',
                       str(bench.time.time() + 60))
    rep = bench._serving_report(requests=8, deadlines=(2.0,))
    assert rep['fleet'] == {'skipped': 'child deadline too close'}


def test_autotune_report_contract(bench, monkeypatch, tmp_path):
    """The "autotune" field (ISSUE 18): the stubbed sweep's winner
    lands in the report AND the consumption round trip resolves a
    fresh _block_sizes call to the persisted DB winner (source db) —
    the same path the compile-ledger signature records in training."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import autotune

    def fake_sweep(db_dir, heads=12, seq=512, head_dim=64):
        sig = autotune.shape_sig(heads, seq, seq, head_dim,
                                 jnp.dtype(jnp.float32), 'fwd')
        autotune.record_winner(autotune.KERNEL_FA, sig, (2, 256, 128),
                               {'source': 'measured'}, dir_=db_dir)
        return {'mode': 'measured', 'sweep_seconds': 1.2,
                'fwd': {'winner': [2, 256, 128], 'source': 'measured',
                        'candidates': 9, 'pruned': 3,
                        'signature': sig}}

    monkeypatch.setattr(bench, '_run_autotune_sweep', fake_sweep)
    monkeypatch.delenv('BENCH_CHILD_DEADLINE', raising=False)
    monkeypatch.delenv('MXTPU_AUTOTUNE_DIR', raising=False)
    autotune.clear()
    try:
        rep = bench._autotune_report()
    finally:
        autotune.clear()
    assert rep['mode'] == 'measured'
    assert rep['fwd']['winner'] == [2, 256, 128]
    assert rep['consumed']['blocks'] == [2, 256, 128]
    assert any(v.startswith('db:')
               for v in rep['consumed']['decisions'].values())
    # the temp DB dir must not leak into the process env
    import os as _os
    assert 'MXTPU_AUTOTUNE_DIR' not in _os.environ


def test_autotune_report_respects_child_deadline(bench, monkeypatch):
    """Too little left on the child budget: the sweep is skipped, never
    started — the flagship metric's deadline wins (the compile-A/B
    contract)."""
    def boom(*_a, **_k):
        raise AssertionError("sweep must not run under a tight deadline")
    monkeypatch.setattr(bench, '_run_autotune_sweep', boom)
    monkeypatch.setenv('BENCH_CHILD_DEADLINE',
                       str(bench.time.time() + 60))
    rep = bench._autotune_report()
    assert rep == {'skipped': 'child deadline too close'}


def test_sparse_report_contract(bench, monkeypatch):
    """The "sparse" field (ISSUE 19): the stubbed drill's analytic
    report and hot-fraction sweep land in the emitted field — shrink,
    per-hop exchange bytes, and one sweep row per fraction."""
    def fake_drill(*_a, **_k):
        return {
            'report': {
                'mode': 'lazy',
                'tables': {'emb0_weight': {'vocab': 20000, 'dim': 32,
                                           'budget': 512,
                                           'ids_per_step': 512}},
                'update_bytes_per_step': 512 * 32 * 4,
                'dense_update_bytes_per_step': 20000 * 32 * 4,
                'update_shrink': 39.06,
                'exchange_bytes_per_hop': {
                    'dp': {'bytes': 1024, 'dense_bytes': 40960}},
            },
            'sweep': [{'hot_fraction': 0.1, 'sparse_p50_ms': 1.0,
                       'dense_p50_ms': 3.0, 'live_rows': 400,
                       'update_bytes': 51200, 'dedup_ratio': 1.28}],
        }

    monkeypatch.setattr(bench, '_run_sparse_drill', fake_drill)
    monkeypatch.delenv('BENCH_CHILD_DEADLINE', raising=False)
    rep = bench._sparse_report()
    assert rep['mode'] == 'lazy'
    assert rep['update_shrink'] == 39.06
    assert rep['dense_update_bytes_per_step'] == 20000 * 32 * 4
    assert rep['exchange_bytes_per_hop']['dp']['bytes'] == 1024
    assert rep['sweep'][0]['hot_fraction'] == 0.1
    assert rep['sweep'][0]['live_rows'] == 400


def test_sparse_report_respects_child_deadline(bench, monkeypatch):
    """Too little left on the child budget: the drill is skipped, never
    built — the flagship metric's deadline wins."""
    def boom(*_a, **_k):
        raise AssertionError("drill must not build under a tight deadline")
    monkeypatch.setattr(bench, '_run_sparse_drill', boom)
    monkeypatch.setenv('BENCH_CHILD_DEADLINE',
                       str(bench.time.time() + 60))
    rep = bench._sparse_report()
    assert rep == {'skipped': 'child deadline too close'}
