"""The ``lfm2`` family's counts by hand at the cell's sizes, its
configuration file against the catalog row, and its readers on a made-up
trace."""
import json
import os
import types

import pytest

from chipbench import hlo, manifest, scoped

CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CELL = 'lfm2_24b_a2b.t8192_ep8'
T = 8192


@pytest.fixture(scope='module')
def cell():
    return manifest.resolve(CELL)


def test_flops_per_sample_by_hand(cell):
    h = 2048
    conv = h * 3 * h + h * h                    # in- and out-projection
    attention = h * (2048 + 512 + 512 + 2048)   # q, k, v, o of 64-wide heads
    dense = 3 * h * 11776
    router = h * 64
    # 4 of 64 experts a token, 8 held: half an expert pass of three
    # 2048 x 1536 matrices
    experts = 4 * 8 * (3 * h * 1536) // 64
    assert experts == 4718592
    head = h * 8192
    per_token = 4 * conv + attention + dense + 4 * (router + experts) + head
    scores = 12 * 32 * 64 * T * (T + 1) // 2
    want = 6 * T * per_token + scores
    assert want == 9973014724608
    assert cell.family.flops_per_sample(cell.config, cell.traffic) == want
    assert cell.traffic['flops_per_sample'] == want
    # the conv layers' projections are a third of it (a fifth of the
    # device's work, whose expert rows are padded eight times, ISSUE 41)
    assert 0.32 < 6 * T * 4 * conv / want < 0.34


def test_the_costs_by_hand(cell):
    c, t = cell.config, cell.traffic
    attention = cell.family.attention_cost(c, t)
    assert attention == {
        'flops': 12 * 2048 * T * (T + 1) // 2,
        'bytes': 2 * T * 6 * (2048 + 512) + 3 * T * 32 * 4}
    experts = cell.family.expert_cost(c, t)
    rows = T * 4 * 8 // 64
    assert rows == 4096                 # 512 an expert
    assert experts['flops'] == 4 * 3 * 2 * rows * 3 * 2048 * 1536
    weights = 8 * 3 * 2048 * 1536
    assert experts['bytes'] == 4 * 2 * (3 * weights + rows * (
        6 * 2048 + 3 * 3072 + 3 * 1536))
    conv = cell.family.short_conv_cost(c, t)
    # four conv layers, 11 bf16 rows of h a position forward + backward
    assert conv['bytes'] == 4 * 2 * T * 2048 * 11
    # bound by bytes by far: 1.8 ms of bytes against 0.008 ms of operations
    assert conv['flops'] / 197e12 < conv['bytes'] / 819e9 / 100


def test_the_configuration_against_the_catalog_row(cell):
    if not os.path.isfile(CATALOG):
        pytest.skip('no model catalog on this machine')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'LFM2-24B-A2B')
    config = cell.config
    assert config['source'] == row['source_url']
    differ = sorted(k for k, v in row['config'].items()
                    if config.get(k, 'missing') != v)
    assert differ == sorted(config['reduced'])
    for key in config['reduced']:
        assert key in config['published'] and key in config['reduced_why']
    for key in ('num_hidden_layers', 'num_dense_layers', 'num_experts',
                'vocab_size'):
        assert config['published'][key] == row['config'][key]
    # published layers 1 to 5: the second dense layer and the first whole
    # period after the dense layers
    assert config['layer_types'] == row['config']['layer_types'][1:6]
    assert row['config']['layer_types'][2:6] == [
        'full_attention', 'conv', 'conv', 'conv']
    # the floors of a model_config cut: a whole period and four layers
    # after the dense ones, 8 experts, an eighth of the vocabulary
    assert config['num_hidden_layers'] - config['num_dense_layers'] >= 4
    assert config['num_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['config']['vocab_size']
    deployment = config['deployment']
    assert deployment['experts_in_model'] == row['config']['num_experts']
    assert deployment['chips_sharing_a_layer'] * config['num_experts'] == \
        deployment['experts_in_model']
    assert deployment['chips_sharing_a_layer'] * config['vocab_size'] == \
        row['config']['vocab_size']
    assert config['head_dim'] * config['num_attention_heads'] == \
        config['hidden_size']
    for key in ('head_dim', 'tie_word_embeddings', 'expert_block',
                'router_input', 'bias_update', 'router_logits_dtype',
                'initializer_range', 'qk_norm', 'rope', 'conv'):
        assert key in config['assumed']


def test_the_bytes_of_the_cut(cell):
    h, f, dense = 2048, 1536, 11776
    conv = 4 * h * h + 3 * h
    attention = h * (2048 + 512 + 512 + 2048) + 2 * 64
    moe = 64 * h + 8 * 3 * h * f
    layers = (conv + 3 * h * dense + 2 * h) + (attention + moe + 2 * h) \
        + 3 * (conv + moe + 2 * h)
    params = layers + 8192 * h + h
    assert round(params / 1e6, 1) == 469.3
    assert 0.43 < 16 * params / 2 ** 30 / 15.748 < 0.45


STEP = '''HloModule jit_step

ENTRY %main (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  %dot.1 = bf16[8,4]{1,0} copy(%p), metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b0/conv/short_conv/in_proj/dot_general"}
  %fusion.2 = bf16[8,4]{1,0} copy(%p), metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b0/conv/short_conv/short_conv_core/mul"}
  %fusion.3 = bf16[8,4]{1,0} copy(%p), metadata={op_name="jit(step)/mxtpu.fwd_bwd/transpose(jvp(m))/blocks/b0/conv/short_conv/short_conv_core/mul"}
  %mxtpu_flash_fwd.4 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b1/attn_full/mxtpu_flash_fwd/pallas_call"}
  ROOT %dot.5 = bf16[8,4]{1,0} copy(%p), metadata={op_name="jit(step)/mxtpu.fwd_bwd/jvp(m)/blocks/b1/q/dot_general"}
}
'''


def test_the_readers_on_a_made_up_trace(cell, tmp_path, monkeypatch):
    out = tmp_path / 'out'
    out.mkdir()
    (out / 'step_program.hlo.txt').write_text(STEP)
    monkeypatch.setattr('sys.argv', ['run.py', '--out', str(out)])
    per_op = {'dot.1': 0.010, 'fusion.2': 0.002, 'fusion.3': 0.004,
              'mxtpu_flash_fwd.4': 0.008, 'dot.5': 0.1}
    run = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic,
        family=cell.family, program=hlo.Program(STEP), events={'text': {}},
        peaks={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9},
        trace={'steps': 2, 'per_chip': [{'per_op': per_op}]})

    def reader(name):
        return manifest.load_module('layer_metrics', name)
    assert reader('short_conv_ms_per_step').read(run) == pytest.approx(8.0)
    cost = cell.family.short_conv_cost(cell.config, cell.traffic)
    assert reader('short_conv_roofline').read(run) == pytest.approx(
        100 * 2 * cost['bytes'] / 819e9 / 3e-3)
    assert reader('attn_full_ms_per_step').read(run) == pytest.approx(4.0)
    assert 'counters' in reader('short_conv_ms_per_step').note(run)


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
    for name in ('short_conv_ms_per_step', 'short_conv_roofline'):
        module = manifest.load_module('layer_metrics', name)
        assert module.read(bare) is None
        assert module.read(untraced) is None
    assert manifest.load_module('layer_metrics',
                                'short_conv_ms_per_step').note(bare) == \
        'no counters in this family'
