"""The ``smallthinker`` family's counts by hand at the cell's sizes, its
configuration file against the catalog row, and its readers on a made-up
trace."""
import json
import os
import types

import pytest

from chipbench import hlo, manifest, scoped

CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CELL = 'smallthinker_21b.t8192'
T = 8192


@pytest.fixture(scope='module')
def cell():
    return manifest.resolve(CELL)


def test_flops_per_sample_by_hand(cell):
    # per token and layer: q 2560x3584, k and v 2560x512, o 3584x2560
    attention = 2560 * (3584 + 512 + 512 + 3584)
    assert attention == 20971520
    router = 2560 * 64
    # 6 of 64 experts a token, 16 held: 1.5 expert passes of three
    # 2560 x 768 matrices
    experts = 6 * 16 * (3 * 2560 * 768) // 64
    assert experts == 8847360
    head = 2560 * 37984
    matmuls = 6 * T * (4 * (attention + router + experts) + head)
    # score entries: a full layer the lower triangle, a window layer
    # 4096 rows that grow to the window and 4096 rows of 4096
    full = T * (T + 1) // 2
    band = 4096 * 4097 // 2 + 4096 * 4096
    assert (full, band) == (33558528, 25167872)
    scores = 12 * 28 * 128 * (full + 3 * band)
    want = matmuls + scores
    assert want == 15364880596992
    assert cell.family.flops_per_sample(cell.config, cell.traffic) == want
    assert cell.traffic['flops_per_sample'] == want
    # the sliced head is 31 % of it; 52 layers would make it 3 %
    assert 0.30 < 6 * T * head / want < 0.32
    assert 6 * T * head / (want + 12 * (want - 6 * T * head)) < 0.04


def test_attention_cost_by_hand(cell):
    cost = cell.family.attention_cost(cell.config, cell.traffic)
    kinds = cell.family.attention_cost_by_kind(cell.config, cell.traffic)
    full, band = 33558528, 25167872
    assert kinds['full']['flops'] == 12 * 3584 * full
    assert kinds['window']['flops'] == 3 * 12 * 3584 * band
    # six bf16 passes of (T, 3584) and six of (T, 512), three float32
    # passes of the (T, 28) row statistics, a layer
    layer = 2 * T * 6 * (3584 + 512) + 3 * T * 28 * 4
    assert kinds['full']['bytes'] == layer
    assert kinds['window']['bytes'] == 3 * layer
    assert cost == {'flops': 12 * 3584 * (full + 3 * band),
                    'bytes': 4 * layer}
    # compute-bound by far: 23.8 ms of matmuls against 2 ms of bytes
    assert cost['flops'] / 197e12 > 10 * cost['bytes'] / 819e9


def test_expert_cost_by_hand(cell):
    cost = cell.family.expert_cost(cell.config, cell.traffic)
    rows = T * 6 * 16 // 64
    assert rows == 12288            # 768 an expert
    assert cost['flops'] == 4 * 3 * 2 * rows * 3 * 2560 * 768
    weights = 16 * 3 * 2560 * 768
    assert cost['bytes'] == 4 * 2 * (3 * weights + rows * (
        6 * 2560 + 3 * 1536 + 3 * 768))


def test_the_configuration_against_the_catalog_row(cell):
    if not os.path.isfile(CATALOG):
        pytest.skip('no model catalog on this machine')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'SmallThinker-21BA3B-Instruct')
    config = cell.config
    assert config['source'] == row['source_url']
    differ = sorted(k for k, v in row['config'].items()
                    if config.get(k, 'missing') != v)
    assert differ == sorted(config['reduced'])
    # what is reduced is a count or a layout, never a width, and the file
    # states the published value and why
    for key in config['reduced']:
        assert key in config['published'] and key in config['reduced_why']
    for key in ('num_hidden_layers', 'moe_num_primary_experts', 'vocab_size'):
        assert config['published'][key] == row['config'][key]
    period = row['config']['sliding_window_layout'][:4]
    assert config['sliding_window_layout'] == period == config['rope_layout']
    assert row['config']['sliding_window_layout'] == period * 13
    # the floors of a model_config cut
    assert config['num_hidden_layers'] >= 4
    assert config['moe_num_primary_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['config']['vocab_size']
    # the deployment beside it
    deployment = config['deployment']
    assert deployment['experts_in_model'] == row['config'][
        'moe_num_primary_experts']
    assert deployment['chips_sharing_a_layer'] * config[
        'moe_num_primary_experts'] == deployment['experts_in_model']
    assert deployment['chips_sharing_a_layer'] * config['vocab_size'] == \
        row['config']['vocab_size']
    for key in ('router_input', 'rope', 'bias', 'window',
                'initializer_range', 'dropout'):
        assert key in config['assumed']


def test_the_bytes_of_the_cut(cell):
    config = cell.config
    h, f = config['hidden_size'], config['moe_ffn_hidden_size']
    layer = h * (28 + 4 + 4 + 28) * 128 + 64 * h + 16 * 3 * h * f + 2 * h
    params = 4 * layer + 2 * config['vocab_size'] * h + h
    assert round(params / 1e6, 1) == 656.5
    assert 0.61 < 16 * params / 16909336064 < 0.63


STEP = '''HloModule jit_step

ENTRY %main (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  %mxtpu_flash_fwd.1 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b1/attn_swa/mxtpu_flash_fwd/pallas_call"}
  %mxtpu_flash_bwd_dq.2 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mxtpu.fwd_bwd/transpose(jvp(m))/blocks/b1/attn_swa/mxtpu_flash_bwd_dq/pallas_call"}
  %mxtpu_flash_fwd.3 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b0/attn_full/mxtpu_flash_fwd/pallas_call"}
  %mxtpu_grouped_matmul.4 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b0/experts/moe_experts/mxtpu_grouped_matmul/pallas_call"}
  %sort.5 = bf16[8,4]{1,0} sort(%p), dimensions={0}, metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b0/experts/moe_route/sort"}
  %gather.6 = bf16[8,4]{1,0} copy(%p), metadata={op_name="jit(step)/mxtpu.fwd_bwd/transpose(jvp(m))/blocks/b0/experts/moe_route/gather"}
  ROOT %dot.7 = bf16[8,4]{1,0} copy(%p), metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b0/q/dot_general"}
}
'''


def test_the_readers_on_a_made_up_trace(cell, tmp_path, monkeypatch):
    """Seconds by instruction name, the names' scopes from the HLO text
    the run writes: each reader adds up what lies under its scope."""
    out = tmp_path / 'out'
    out.mkdir()
    (out / 'step_program.hlo.txt').write_text(STEP)
    monkeypatch.setattr('sys.argv', ['run.py', '--out', str(out)])
    per_op = {'mxtpu_flash_fwd.1': 0.010, 'mxtpu_flash_bwd_dq.2': 0.020,
              'mxtpu_flash_fwd.3': 0.004, 'mxtpu_grouped_matmul.4': 0.030,
              'sort.5': 0.002, 'gather.6': 0.006, 'dot.7': 0.100}
    run = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic,
        family=cell.family, program=hlo.Program(STEP), events={'text': {}},
        peaks={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9},
        trace={'steps': 2, 'per_chip': [{'per_op': per_op}]})

    def read(name):
        return manifest.load_module('layer_metrics', name).read(run)
    assert read('moe_ms_per_step') == pytest.approx(19.0)
    assert read('moe_route_ms_per_step') == pytest.approx(4.0)
    assert read('attn_window_ms_per_step') == pytest.approx(15.0)
    assert read('attn_full_ms_per_step') == pytest.approx(2.0)
    kinds = cell.family.attention_cost_by_kind(cell.config, cell.traffic)
    assert read('attn_window_roofline') == pytest.approx(
        100 * kinds['window']['flops'] / 197e12 / 15e-3)
    assert read('expert_matmul_roofline') == pytest.approx(
        100 * cell.family.expert_cost(cell.config, cell.traffic)['flops']
        / 197e12 / 15e-3)
    assert scoped.ms_per_step(run, ('no_such_scope',)) is None


def test_the_readers_find_nothing_in_a_program_without_the_scopes(cell):
    """The parent commit, or an untraced run: None, and nothing raised."""
    bare = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic,
        family=types.SimpleNamespace(), program=hlo.Program(
            'HloModule jit_step\n\nENTRY %main () -> f32[] {\n'
            '  ROOT %c = f32[] constant(0)\n}\n'),
        events={'text': {}}, peaks={}, trace={
            'steps': 2, 'per_chip': [{'per_op': {'fusion.1': 0.01}}]})
    untraced = types.SimpleNamespace(trace=None, family=cell.family,
                                     config=cell.config, traffic=cell.traffic)
    for name in ('moe_ms_per_step', 'moe_route_ms_per_step',
                 'expert_matmul_roofline', 'attn_window_ms_per_step',
                 'attn_full_ms_per_step', 'attn_window_roofline'):
        reader = manifest.load_module('layer_metrics', name)
        assert reader.read(bare) is None
        assert reader.read(untraced) is None
