"""BENCHMARK.json resolves to files, is legal, and grows by files alone."""
import json
import os
import shutil
import types

import pytest

from chipbench import hlo, manifest


def test_manifest_has_no_fault():
    assert manifest.check(manifest.load_benchmark()) == []


def test_every_cell_resolves_and_records_its_flop_count():
    for w in manifest.load_benchmark()['workloads']:
        cell = manifest.resolve(w['name'])
        assert cell.traffic['flops_per_sample'] == \
            cell.family.flops_per_sample(cell.config, cell.traffic)
        assert [m['name'] for m in cell.end_to_end].count('setup_s') == 1
        assert cell.per_layer


@pytest.mark.parametrize('name', ['a', 'bert_base.t512', '9x-y_z.0'])
def test_legal_names(name):
    assert manifest.NAME.match(name)


@pytest.mark.parametrize('name', ['', '.hidden', 'a/b', 'a b', 'x' * 65,
                                  '../up'])
def test_illegal_names(name):
    assert not manifest.NAME.match(name)


def test_a_fault_is_named():
    bench = manifest.load_benchmark()
    bench['per_layer'][0] = dict(bench['per_layer'][0], moves='no_such')
    bench['per_layer'][1] = dict(bench['per_layer'][1], layer='train step')
    bench['workloads'][0] = dict(bench['workloads'][0], traffic='no_such')
    faults = manifest.check(bench)
    assert any('no_such' in f and 'moves' in f for f in faults)
    assert any("layer 'train step'" in f for f in faults)
    assert any('no traffic file' in f for f in faults)


# a trace-derived metric none of the sums of xplane.reduce holds (PERF.md
# names it as a follow-up): it reads each chip's per-op seconds and asks
# the step's hlo.Program what each op is
COPIES_READER = '''"""Layer: train_step. Device time of the layout copies
around the kernel, ms a step, mean over chips."""


def read(run):
    if run.trace is None:
        return None
    chips = run.trace['per_chip']
    copies = sum(seconds for chip in chips
                 for name, seconds in chip['per_op'].items()
                 if run.program.label(name).startswith('copy'))
    return 1e3 * copies / len(chips) / run.trace['steps']
'''


def test_a_cell_a_config_and_a_metric_are_added_by_files_alone(tmp_path):
    """bert_large (a configuration of an existing family), a new traffic
    mix, a cell over them and a per-layer metric: new files and new
    entries, no edit to a file that is there."""
    root = str(tmp_path)
    here = os.path.join(root, 'chipbench')
    shutil.copytree(manifest.HERE, here, ignore=shutil.ignore_patterns(
        'tests', '__pycache__'))
    before = {}
    for folder, _dirs, files in os.walk(here):
        for f in files:
            path = os.path.join(folder, f)
            with open(path, 'rb') as fh:
                before[path] = fh.read()

    large = manifest.read_json(os.path.join(here, 'configs',
                                            'bert_base.json'))
    large.update(hidden_size=1024, intermediate_size=4096,
                 num_attention_heads=16, num_hidden_layers=24,
                 source_part='BERT-Large, Uncased')
    with open(os.path.join(here, 'configs', 'bert_large.json'), 'w') as f:
        json.dump(large, f)
    mix = manifest.read_json(os.path.join(here, 'traffic', 't128.json'))
    mix.update(seq_len=256, per_chip_batch=32, labelled_positions=40)
    with open(os.path.join(here, 'traffic', 't256_b32.json'), 'w') as f:
        json.dump(mix, f)
    with open(os.path.join(here, 'layer_metrics',
                           'layout_copy_ms_per_step.py'), 'w') as f:
        f.write(COPIES_READER)
    bench = manifest.load_benchmark()
    bench['configs'].append({
        'name': 'bert_large', 'source': large['source'],
        'file': 'chipbench/configs/bert_large.json', 'reduced': [],
        'why': 'throw-away'})
    bench['workloads'].append({
        'name': 'bert_large.t256', 'config': 'bert_large',
        'traffic': 't256_b32', 'chips': 1, 'why': 'throw-away'})
    bench['per_layer'].append({
        'name': 'layout_copy_ms_per_step', 'unit': 'ms', 'better': 'lower',
        'source': 'device_trace', 'layer': 'train_step',
        'moves': 'samples_per_s_per_chip',
        'workloads': ['bert_large.t256']})
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)

    assert manifest.check(bench, root=root, here=here) == []
    cell = manifest.resolve('bert_large.t256', root=root, here=here)
    assert cell.config['hidden_size'] == 1024
    assert 'layout_copy_ms_per_step' in [m['name'] for m in cell.per_layer]
    # the family's counts follow the new sizes: 24 layers of 1024
    base = manifest.resolve('bert_base.t512')
    assert cell.family.flops_per_sample(cell.config, cell.traffic) > \
        base.family.flops_per_sample(base.config, cell.traffic) * 3
    reader = manifest.load_module('layer_metrics', 'layout_copy_ms_per_step',
                                  here)
    step = hlo.Program(
        'HloModule jit_step\n\nENTRY %main (p: bf16[8,4]) -> bf16[4,8] {\n'
        '  %p = bf16[8,4]{1,0} parameter(0)\n'
        '  %copy.1 = bf16[8,4]{0,1} copy(%p)\n'
        '  ROOT %transpose.2 = bf16[4,8]{1,0} transpose(%copy.1)\n}\n')
    run = types.SimpleNamespace(program=step, trace={
        'steps': 20, 'per_chip': [
            {'per_op': {'copy.1': 0.03, 'transpose.2': 1.0}},
            {'per_op': {'copy.1': 0.05, 'transpose.2': 1.0}}]})
    assert reader.read(run) == pytest.approx(2.0)
    # an old cell does not report the new cell's metric
    old = manifest.resolve('bert_base.t512', root=root, here=here)
    assert 'layout_copy_ms_per_step' not in [m['name'] for m in old.per_layer]
    for path, content in before.items():
        with open(path, 'rb') as fh:
            assert fh.read() == content, f"{path} was edited"
