"""The ``ouro`` family's counts by hand at the cell's sizes, its
configuration file against the catalog row, the bytes of its cut, and its
four readers on a made-up trace."""
import json
import os
import types

import pytest

from chipbench import hlo, manifest

CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
CELL = 'ouro_2_6b.t4096'
T = 4096


@pytest.fixture(scope='module')
def cell():
    return manifest.resolve(CELL)


def test_flops_per_sample_by_hand(cell):
    # a block, per token: q, k, v, o 2048 x 2048 each; gate, up, down
    # 2048 x 5632 each
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert block == 51380224
    head = 2048 * 49152
    # 8 blocks x 4 passes = 32 applications; the head after every pass
    loop = 6 * T * 32 * block
    heads = 6 * T * 4 * head
    # two forward and four backward matmul passes on the causal triangle,
    # 16 heads of 128, every application
    scores = 12 * 16 * 128 * (T * (T + 1) // 2) * 32
    assert (round(loop / 1e12, 2), round(heads / 1e12, 2),
            round(scores / 1e12, 2)) == (40.41, 9.90, 6.60)
    want = loop + heads + scores
    assert want == 56901337350144
    assert cell.family.flops_per_sample(cell.config, cell.traffic) == want
    assert cell.traffic['flops_per_sample'] == want
    # the feed-forward's share, which ffn_glu_ms_per_step times
    assert round(6 * T * 32 * 3 * 2048 * 5632 / 1e12, 1) == 27.2
    # what the depth cut distorts: the heads are 17 % of the matmul work
    # here, 3.4 % with all 48 layers; 83 % lies inside ut_loop
    assert 0.17 < heads / want < 0.18
    assert 0.033 < heads / (6 * (loop + scores) + heads) < 0.035
    assert 0.82 < (loop + scores) / want < 0.83


def test_attention_cost_by_hand(cell):
    cost = cell.family.attention_cost(cell.config, cell.traffic)
    # an application: six bf16 passes of q-sized and six of k-sized
    # (T, 2048) arrays, three float32 passes of the (T, 16) statistics
    layer = 2 * T * 6 * (2048 + 2048) + 3 * T * 16 * 4
    assert cost == {'flops': 32 * 12 * 2048 * (T * (T + 1) // 2),
                    'bytes': 32 * layer}
    # compute-bound: 33.5 ms of matmuls against 7.9 ms of bytes
    assert cost['flops'] / 197e12 > 4 * cost['bytes'] / 819e9


def test_the_configuration_against_the_catalog_row(cell):
    if not os.path.isfile(CATALOG):
        pytest.skip('no model catalog on this machine')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r['name'] == 'Ouro-2.6B')
    config = cell.config
    assert config['source'] == row['source_url']
    differ = sorted(k for k, v in row['config'].items()
                    if config.get(k, 'missing') != v)
    assert differ == sorted(config['reduced']) == [
        'layer_types', 'num_hidden_layers']
    for key in config['reduced']:
        assert key in config['published'] and key in config['reduced_why']
    assert config['published']['num_hidden_layers'] == 48 == len(
        row['config']['layer_types'])
    assert config['layer_types'] == row['config']['layer_types'][:8]
    assert set(row['config']['layer_types']) == {'full_attention'}
    # the loop is never cut, and no width is
    assert config['total_ut_steps'] == row['config']['total_ut_steps'] == 4
    for key in ('hidden_size', 'intermediate_size', 'head_dim',
                'num_attention_heads', 'num_key_value_heads', 'vocab_size'):
        assert config[key] == row['config'][key]
    # the floor of a model_config cut: at least four layers
    assert config['num_hidden_layers'] >= 4
    # what the row does not hold, each said to be from elsewhere
    assumed = config['assumed']
    assert 'not from the catalog row' in assumed['from']
    for key in ('norm_placement', 'final_norm', 'exit_gate',
                'embedding_reinjection', 'objective', 'entropy_weight',
                'rope', 'bias', 'initializer_range', 'dropout',
                'ffn_storage'):
        assert key in assumed, key
    assert assumed['entropy_weight'] == 0.1
    assert assumed['initializer_range'] == 0.02 and assumed['dropout'] == 0.0


def test_the_bytes_of_the_cut(cell):
    config = cell.config
    h, f, v = (config['hidden_size'], config['intermediate_size'],
               config['vocab_size'])
    layer = 4 * h * h + 3 * h * f + 4 * h
    assert layer == 51388416
    params = config['num_hidden_layers'] * layer + 2 * v * h + h + (h + 1)
    assert params == 612438017
    assert '612 438 017' in config['deployment']['bytes']
    assert round(16 * params / 1e9, 2) == 9.80
    assert round(16 * params / 2 ** 30, 2) == 9.13
    assert 0.57 < 16 * params / 16909336064 < 0.59


def _op(name, path, kind='copy'):
    return (f'  %{name} = bf16[8,4]{{1,0}} {kind}(%p), metadata='
            f'{{op_name="jit(step)/mxtpu.fwd_bwd/{path}"}}\n')


_FWD, _BWD = 'jvp(m)/ut_loop/ut_pass1/blocks', \
    'transpose(jvp(m))/ut_loop/ut_pass1/blocks'
_REMAT = _BWD + '/mxtpu.fwd_bwd/jvp(m)/ut_loop/ut_pass1/blocks/checkpoint/' \
    'rematted_computation/b0'
STEP = ('HloModule jit_step\n\n'
        '%fused (q: bf16[8,4]) -> bf16[8,4] {\n'
        '  %q = bf16[8,4]{1,0} parameter(0)\n'
        + _op('mul.20', _REMAT + '/ffn/ffn_glu/mul', 'negate').replace(
            '%p', '%q')
        + _op('dot.21', _BWD + '/b0/ffn/ffn_glu/down/dot_general',
              'negate').replace('%p', '%mul.20').replace('  %dot', '  ROOT %dot')
        + '}\n\n'
        'ENTRY %main (p: bf16[8,4]) -> bf16[8,4] {\n'
        '  %p = bf16[8,4]{1,0} parameter(0)\n'
        + _op('norm.1', _FWD + '/b0/norm1/rmsnorm/mul')
        + _op('ffn.2', _FWD + '/b0/ffn/ffn_glu/gate_up/dot_general')
        + _op('ffn.3', _REMAT + '/ffn/ffn_glu/gate_up/dot_general')
        + _op('attn.4', _REMAT + '/attn_full/mul')
        + _op('ffn.5', _BWD + '/b0/ffn/ffn_glu/down/dot_general')
        + '  %fusion.6 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, '
          'calls=%fused\n'
        + _op('gate.7', 'jvp(m)/exit_gate/dot_general')
        + _op('head.8', 'jvp(mxtpu.loss)/while/body/lm_head/dot_general')
        + _op('loss.9', 'transpose(jvp(mxtpu.loss))/while/body/checkpoint/'
              'rematted_computation/lm_head/dot_general')
        + _op('embed.10', 'jvp(m)/embed/gather')
        + '  ROOT %upd.11 = bf16[8,4]{1,0} copy(%p), metadata={op_name='
          '"jit(step)/mxtpu.update/add"}\n}\n')


def test_the_readers_on_a_made_up_trace(cell, tmp_path, monkeypatch):
    """Seconds by instruction name, the names' scopes from the HLO text
    the run writes: each reader adds up what lies under its scope; a
    fusion that holds a recomputed and a backward instruction is loop
    time and no recomputation."""
    out = tmp_path / 'out'
    out.mkdir()
    (out / 'step_program.hlo.txt').write_text(STEP)
    monkeypatch.setattr('sys.argv', ['run.py', '--out', str(out)])
    per_op = {'norm.1': 0.002, 'ffn.2': 0.010, 'ffn.3': 0.012,
              'attn.4': 0.004, 'ffn.5': 0.020, 'fusion.6': 0.006,
              'gate.7': 0.001, 'head.8': 0.008, 'loss.9': 0.016,
              'embed.10': 0.003, 'upd.11': 0.005}
    run = types.SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic,
        family=cell.family, program=hlo.Program(STEP), events={'text': {}},
        peaks={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9},
        trace={'steps': 2, 'per_chip': [{'per_op': per_op}]})

    def reader(name):
        return manifest.load_module('layer_metrics', name)
    assert reader('loop_ms_per_step').read(run) == pytest.approx(27.0)
    assert reader('ffn_glu_ms_per_step').read(run) == pytest.approx(24.0)
    assert reader('loop_recompute_ms_per_step').read(run) == \
        pytest.approx(8.0)
    assert reader('exit_head_ms_per_step').read(run) == pytest.approx(12.5)
    note = reader('loop_recompute_ms_per_step').note(run)
    assert '8.000 ms' in note and '29.6 %' in note and '3.000 ms more' in note


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
    for name in ('loop_ms_per_step', 'loop_recompute_ms_per_step',
                 'ffn_glu_ms_per_step', 'exit_head_ms_per_step'):
        reader = manifest.load_module('layer_metrics', name)
        assert reader.read(bare) is None
        assert reader.read(untraced) is None
        if hasattr(reader, 'note'):
            assert 'no op' in reader.note(bare)


def test_the_manifest_lists_the_cell_and_its_four_metrics():
    """Looked up by name: a later PR appends after them."""
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    entry = next(w for w in bench['workloads'] if w['name'] == CELL)
    assert (entry['config'], entry['traffic'], entry['chips']) == (
        'ouro_2_6b', 't4096', 1)
    metrics = {m['name']: m for m in bench['per_layer']}
    for name, layer in (('loop_ms_per_step', 'loop'),
                        ('loop_recompute_ms_per_step', 'loop'),
                        ('ffn_glu_ms_per_step', 'loop'),
                        ('exit_head_ms_per_step', 'exit_head')):
        m = metrics[name]
        assert (m['layer'], m['workloads'], m['source'], m['moves']) == (
            layer, [CELL], 'device_trace', 'samples_per_s_per_chip')


def test_the_tiny_preset_keeps_four_passes(cell):
    tiny = cell.family.tiny(cell.config)
    assert tiny['total_ut_steps'] == 4 and tiny['num_hidden_layers'] == 2
    assert tiny['layer_types'] == ['full_attention'] * 2
