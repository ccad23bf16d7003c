"""chipbench/scopes.py and the eight readers of PR 26: from op names to
phases, blocks and kernels on a program built by hand, then on two steps
of bert_base.t512 as the chip traced them with the scopes in."""
import gzip
import json
import os
import sys
import types

import pytest

from chipbench import hlo, manifest, scopes, xplane
from chipbench.xplane import Event

READERS = ('fwd_ms_per_step', 'bwd_ms_per_step', 'optimizer_ms_per_step',
           'phase_mixed_ms_per_step', 'unscoped_share',
           'flash_fwd_ms_per_step', 'flash_bwd_dq_ms_per_step',
           'flash_bwd_dkv_ms_per_step')
DATA = os.path.join(os.path.dirname(__file__), 'data')
TOP = 'jit(stable_step)/mxtpu.fwd_bwd/jvp(bertforpretraining0)/bertmodel0'
BACK = 'jit(stable_step)/mxtpu.fwd_bwd/transpose(jvp(bertforpretraining0))' \
    '/bertmodel0'

HLO = f'''HloModule jit_stable_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[64,128], param_1: bf16[128,256]) -> bf16[64,256] {{
  %param_0 = bf16[64,128]{{1,0}} parameter(0)
  %param_1 = bf16[128,256]{{1,0}} parameter(1)
  %constant.5 = bf16[]{{:T(256)}} constant(0), metadata={{op_name="{BACK}/jit(take_along_axis)"}}
  ROOT %convolution.1 = bf16[64,256]{{1,0}} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={{op_name="{TOP}/encoder/bertlayer3/ffn2/dot_general" stack_frame_id=6}}
}}

%fused_computation.2 (param_0.1: bf16[64,256]) -> (bf16[128,256], f32[128,256]) {{
  %param_0.1 = bf16[64,256]{{1,0}} parameter(0)
  %convolution.2 = f32[128,256]{{1,0}} convolution(%param_0.1, %param_0.1), dim_labels=bf_io->bf, metadata={{op_name="{BACK}/encoder/bertlayer3/ffn2/dot_general"}}
  %multiply.1 = f32[128,256]{{1,0}} multiply(%convolution.2, %convolution.2), metadata={{op_name="jit(stable_step)/mxtpu.update/mul"}}
  %convert.1 = bf16[128,256]{{1,0}} convert(%multiply.1), metadata={{op_name="jit(stable_step)/mxtpu.update/convert_element_type"}}
  ROOT %tuple.1 = (bf16[128,256]{{1,0}}, f32[128,256]{{1,0}}) tuple(%convert.1, %multiply.1)
}}

%fused_computation.3 (param_0.2: f32[128,256]) -> f32[128,256] {{
  %param_0.2 = f32[128,256]{{1,0}} parameter(0)
  ROOT %add.7 = f32[128,256]{{1,0}} add(%param_0.2, %param_0.2), metadata={{op_name="jit(stable_step)/mxtpu.update/add"}}
}}

%fused_computation.4 (param_0.3: bf16[64,256]) -> bf16[64,256] {{
  %param_0.3 = bf16[64,256]{{1,0}} parameter(0)
  ROOT %copy.9 = bf16[64,256]{{0,1}} copy(%param_0.3)
}}

%fused_computation.5 (param_0.4: bf16[64,256]) -> bf16[64,256] {{
  %param_0.4 = bf16[64,256]{{1,0}} parameter(0)
  %subtract.3 = bf16[64,256]{{1,0}} subtract(%param_0.4, %param_0.4), metadata={{op_name="{TOP}/encoder/bertlayer3/ln1/sub"}}
  ROOT %multiply.3 = bf16[64,256]{{1,0}} multiply(%subtract.3, %param_0.4), metadata={{op_name="{BACK}/encoder/bertlayer3/ln1/mul"}}
}}

%async_computation (p: f32[128,256]) -> f32[32,256] {{
  %p = f32[128,256]{{1,0}} parameter(0)
  ROOT %slice.5 = f32[32,256]{{1,0}} slice(%p), slice={{[0:32], [0:256]}}, metadata={{op_name="{BACK}/encoder/bertlayer3/ln1/jit(_var)/mul"}}
}}

%add.clone (x: f32[], y: f32[]) -> f32[] {{
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x, %y)
}}

ENTRY %main.9 (p0: bf16[64,128], p1: bf16[128,256]) -> bf16[64,256] {{
  %p0 = bf16[64,128]{{1,0}} parameter(0), metadata={{op_name="inputs[0]"}}
  %p1 = bf16[128,256]{{1,0}} parameter(1), metadata={{op_name="t_params[\\'p0001\\']"}}
  %fusion.1 = bf16[64,256]{{1,0}} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{TOP}/encoder/bertlayer3/ffn2/dot_general"}}
  %mxtpu_flash_fwd.7 = (bf16[8,64,64]{{2,1,0}}, f32[8,64,1]{{2,1,0}}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="{TOP}/encoder/bertlayer3/bertselfattention0/attn_core/mxtpu_flash_fwd/pallas_call" stack_frame_id=205}}, backend_config={{"custom_call_config":{{"body":"TUzvUgFNTElS op_name=\\"not this\\""}}}}
  %mxtpu_flash_bwd_dq.8 = f32[8,64,64]{{2,1,0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(stable_step)/mxtpu.fwd_bwd/transpose(mxtpu.fwd_bwd)/jvp(bertforpretraining0)/bertmodel0/encoder/bertlayer3/bertselfattention0/attn_core/mxtpu_flash_bwd_dq/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"TUzv"}}}}
  %shard_map.9 = (f32[8,64,64]{{2,1,0}}, f32[8,64,64]{{2,1,0}}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(stable_step)/mxtpu.fwd_bwd/transpose(mxtpu.fwd_bwd)/jvp(bertforpretraining0)/bertmodel0/encoder/bertlayer3/bertselfattention0/attn_core/shard_map/mxtpu_flash_bwd_dkv/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"TUzv"}}}}
  %all-reduce.3 = f32[128,256]{{1,0}} all-reduce(%p1), channel_id=1, replica_groups={{{{0,1}}}}, to_apply=%add.clone, metadata={{op_name="jit(stable_step)/mxtpu.exchange/sharding_constraint"}}
  %fusion.2 = (bf16[128,256]{{1,0}}, f32[128,256]{{1,0}}) fusion(%fusion.1), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="{BACK}/encoder/bertlayer3/ffn2/dot_general"}}
  %fusion.3 = f32[128,256]{{1,0}} fusion(%all-reduce.3), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="jit(stable_step)/mxtpu.update/add"}}
  %fusion.4 = bf16[64,256]{{0,1}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.4
  %fusion.5 = bf16[64,256]{{1,0}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="{BACK}/encoder/bertlayer3/ln1/mul"}}
  %slice-start = ((f32[128,256]{{1,0}}), f32[32,256]{{1,0}}, s32[]) async-start(%fusion.3), calls=%async_computation
  %slice-done = f32[32,256]{{1,0}} async-done(%slice-start)
  ROOT %copy.1 = bf16[64,256]{{1,0}} copy(%fusion.4), metadata={{op_name="{BACK}/encoder/bertlayer3/bertselfattention0/attn_core/attn_layout/transpose"}}
}}
'''


def test_an_op_name_taken_apart():
    fwd = TOP + '/encoder/bertlayer11/ln1/jit(_var)/mul'
    assert scopes.phase_of(fwd) == 'fwd'
    assert scopes.block_of(fwd) == \
        'bertforpretraining0/bertmodel0/encoder/bertlayer11/ln1'
    back = BACK + '/encoder/bertlayer3/dropout0/jit(_bernoulli)/' \
        'jit(_uniform)/while/body/closed_call/add'
    assert scopes.phase_of(back) == 'bwd'
    assert scopes.block_of(back).endswith('bertlayer3/dropout0')
    loss = 'jit(stable_step)/mxtpu.fwd_bwd/transpose(jvp(mxtpu.loss))/' \
        'jit(log_softmax)/reduce_sum'
    assert scopes.phase_of(loss) == 'bwd'
    assert scopes.block_of(loss) == 'mxtpu.loss'
    # a custom_vjp's backward: transpose(<phase>) and jvp(<block>)
    dq = 'jit(stable_step)/mxtpu.fwd_bwd/transpose(mxtpu.fwd_bwd)/' \
        'jvp(bertforpretraining0)/bertmodel0/x/mxtpu_flash_bwd_dq/pallas_call'
    assert scopes.phase_of(dq) == 'bwd'
    assert scopes.block_of(dq) == \
        'bertforpretraining0/bertmodel0/x/mxtpu_flash_bwd_dq'
    for scope in scopes.OPTIMIZER:
        assert scopes.phase_of(f'jit(stable_step)/{scope}/mul') == \
            'optimizer'
        assert scopes.block_of(f'jit(stable_step)/{scope}/mul') == scope
    # ZeRO-3's gathers sit inside value_and_grad and are the optimizer's
    assert scopes.phase_of('jit(stable_step)/mxtpu.fwd_bwd/'
                           'jvp(mxtpu.gather)/sharding_constraint') == \
        'optimizer'
    for bare in ('reduce_sum', "t_params['p0001']", '',
                 'jit(stable_step)/jvp(bertmodel0)/encoder/dot_general'):
        assert scopes.phase_of(bare) is None
        assert scopes.block_of(bare) == ''


def test_only_a_repeated_layer_loses_its_index():
    paths = [f'm0/encoder/bertlayer{i}/{leaf}' for i in range(3)
             for leaf in ('ffn1', 'ffn2', 'att0/qkv')] + ['m0/pooler', '']
    shown = scopes.collapse(paths)
    assert shown['m0/encoder/bertlayer2/ffn1'] == 'm0/encoder/bertlayer*/ffn1'
    assert shown['m0/encoder/bertlayer0/att0/qkv'] == \
        'm0/encoder/bertlayer*/att0/qkv'
    assert shown['m0/pooler'] == 'm0/pooler' and shown[''] == ''
    assert len(set(shown.values())) == 5
    # two layers are two things
    assert scopes.collapse(paths[:6])['m0/encoder/bertlayer1/ffn2'] == \
        'm0/encoder/bertlayer1/ffn2'


def test_one_phase_from_those_an_op_holds():
    assert scopes.phase_among(set()) == scopes.UNSCOPED
    assert scopes.phase_among({'fwd'}) == 'fwd'
    assert scopes.phase_among({'bwd'}) == 'bwd'
    # a backward fusion that computes a piece of the forward again
    assert scopes.phase_among({'fwd', 'bwd'}) == 'bwd'
    assert scopes.phase_among({'optimizer'}) == 'optimizer'
    for found in ({'bwd', 'optimizer'}, {'fwd', 'optimizer'},
                  {'fwd', 'bwd', 'optimizer'}):
        assert scopes.phase_among(found) == scopes.MIXED


def test_a_kernel_by_its_instruction_name_or_its_scope():
    assert scopes.kernel_of('mxtpu_flash_fwd.12') == 'mxtpu_flash_fwd'
    assert scopes.kernel_of('%mxtpu_flash_bwd_dkv') == 'mxtpu_flash_bwd_dkv'
    assert scopes.kernel_of('shard_map.3', 'a/mxtpu_flash_bwd_dq/'
                            'pallas_call') == 'mxtpu_flash_bwd_dq'
    assert scopes.kernel_of('jvp__.12', 'a/jvp()/pallas_call') is None
    assert scopes.kernel_of('mxtpu_flash_fwdx.1') is None


def test_parse_reads_own_and_inner_names():
    names = scopes.parse(HLO)
    assert names['fusion.1'].op_name.endswith('bertlayer3/ffn2/dot_general')
    # its constant(0) carries a backward name: XLA merged equal constants
    assert scopes.phases_of(names['fusion.1']) == {'fwd'}
    assert len(names['fusion.1'].inside) == 1
    assert scopes.phases_of(names['fusion.5']) == {'fwd', 'bwd'}
    # the weight gradient fused with its update carries two phases
    assert scopes.phases_of(names['fusion.2']) == {'bwd', 'optimizer'}
    assert len(names['fusion.2'].inside) == 3
    assert scopes.phases_of(names['fusion.3']) == {'optimizer'}
    assert scopes.phases_of(names['fusion.4']) == set()
    assert names['fusion.4'] == scopes.Named('', frozenset())
    # the kernel's body is never read, whatever it holds
    assert names['mxtpu_flash_fwd.7'].op_name.endswith(
        'mxtpu_flash_fwd/pallas_call')
    assert scopes.phases_of(names['mxtpu_flash_bwd_dq.8']) == {'bwd'}
    # escaped quotes in a parameter's name
    assert names['p1'].op_name == "t_params['p0001']"
    # an async pair: the start holds what it calls, the done takes it over
    assert scopes.phases_of(names['slice-start']) == {'bwd'}
    assert names['slice-done'] == names['slice-start']
    assert scopes.phases_of(names['all-reduce.3']) == {'optimizer'}
    assert scopes.parse('') == {}


def hand_built_run(tmp_path, monkeypatch, text=HLO, rename=str):
    """Two executions of the step on one chip and a small program between
    them, reduced by xplane as a traced run's is."""
    out = tmp_path / 'out'
    out.mkdir()
    if text is not None:
        (out / scopes.HLO_FILE).write_text(text)
    monkeypatch.setattr(sys, 'argv', ['run.py', '--out', str(out)])
    order = ['fusion.1', 'mxtpu_flash_fwd.7', 'mxtpu_flash_bwd_dq.8',
             'shard_map.9', 'all-reduce.3', 'fusion.2', 'fusion.3',
             'fusion.4', 'slice-done', 'copy.1', 'not_in_the_text.5',
             'fusion.5']
    ops, modules, at = [], [], 0.0
    for _step in range(2):
        begin = at
        for i, name in enumerate(order):
            ops.append(Event(rename(name), at, at + 1e-3 * (i + 1)))
            at += 1e-3 * (i + 1)
        modules.append(Event('jit_stable_step(1)', begin, at))
        ops.append(Event('fusion.1', at + 1e-3, at + 2e-3))
        modules.append(Event('jit__fold_in(2)', at + 1e-3, at + 2e-3))
        at += 3e-3
    trace = {'ops': {0: ops}, 'async': {}, 'modules': {0: modules},
             'host': [], 'text': {}}
    program = hlo.Program(text or HLO)
    return types.SimpleNamespace(
        trace=xplane.reduce(trace, program), events=trace, program=program,
        cell=types.SimpleNamespace(name='bert_base.t512'))


def read_all(run):
    return {name: manifest.load_module('layer_metrics', name).read(run)
            for name in READERS}


def test_readers_on_a_hand_built_trace(tmp_path, monkeypatch):
    run = hand_built_run(tmp_path, monkeypatch)
    got = read_all(run)
    # ms a step: op i of the step takes i + 1 ms
    assert got['fwd_ms_per_step'] == pytest.approx(1 + 2)
    assert got['bwd_ms_per_step'] == pytest.approx(3 + 4 + 9 + 10 + 12)
    assert got['optimizer_ms_per_step'] == pytest.approx(7)
    assert got['phase_mixed_ms_per_step'] == pytest.approx(6)
    # fusion.4 has no name, one op is not in the text, and the program
    # between the steps took 1 ms a step; busy 78 + 1
    assert got['unscoped_share'] == pytest.approx(100 * (8 + 11 + 1) / 79)
    assert got['flash_fwd_ms_per_step'] == pytest.approx(2)
    assert got['flash_bwd_dq_ms_per_step'] == pytest.approx(3)
    # named by its scope where shard_map named the instruction
    assert got['flash_bwd_dkv_ms_per_step'] == pytest.approx(4)
    found = scopes.split(run)
    assert found['phase'][scopes.COLLECTIVE] == pytest.approx(5)
    assert dict(found['collectives']) == {
        'all-reduce under optimizer mxtpu.exchange': pytest.approx(5)}
    assert sum(found['phase'].values()) == pytest.approx(
        sum(found['reduced'].values()))
    layer = 'bertforpretraining0/bertmodel0/encoder/bertlayer3/'
    assert found['blocks'][layer + 'ffn2']['fwd'] == pytest.approx(1)
    assert found['blocks'][layer + 'bertselfattention0/attn_core/'
                           'attn_layout']['bwd'] == pytest.approx(10)
    assert found['blocks'][layer + 'ln1']['bwd'] == pytest.approx(9 + 12)
    assert found['bwd_with_fwd'] == pytest.approx(12)
    assert found['blocks']['mxtpu.update']['optimizer'] == pytest.approx(7)
    assert list(found['mixed']) == [
        'bwd+optimizer: fusion kOutput matmul (bf16[128,256], '
        'f32[128,256])']
    assert sorted(found['unscoped']) == [
        'no phase scope: fusion kLoop bf16[64,256]',
        'not in step_program.hlo.txt: not in the step program',
        xplane.OTHER_PROGRAMS]
    text = scopes.report(run)
    assert text.count('holds') == 2 and 'BROKEN' not in text
    assert 'attn_layout' in text \
        and 'all-reduce under optimizer mxtpu.exchange' in text


@pytest.mark.parametrize('case', ['no file', 'another program',
                                  'no scopes in the program', 'no trace'])
def test_readers_return_none_and_never_raise(tmp_path, monkeypatch, case):
    def parent(text):       # the program before PR 26
        return text.replace('mxtpu.', 'other.').replace('mxtpu_flash',
                                                        'jvp__')
    text = {'no file': None,
            'another program': HLO.replace('jit_stable_step', 'jit_other'),
            'no scopes in the program': parent(HLO),
            'no trace': HLO}[case]
    run = hand_built_run(
        tmp_path, monkeypatch, text,
        parent if case == 'no scopes in the program' else str)
    if case == 'another program':   # the step that ran is not the file's
        run.program = hlo.Program(HLO)
        run.trace = xplane.reduce(run.events, run.program)
    if case == 'no trace':
        run.trace = run.events = run.program = None
    expected = dict.fromkeys(READERS)
    if case in ('no file', 'another program'):
        # a kernel is known by its instruction's own name, text or none;
        # the one shard_map named is not
        expected.update(flash_fwd_ms_per_step=pytest.approx(2),
                        flash_bwd_dq_ms_per_step=pytest.approx(3))
    assert read_all(run) == expected
    assert isinstance(scopes.report(run), str)
    # a run object the harness never made: still None
    assert read_all(types.SimpleNamespace(trace={'per_chip': 3})) == \
        dict.fromkeys(READERS)


def test_a_name_that_means_another_op_is_not_known(tmp_path, monkeypatch):
    """On four chips the second compile numbers some instructions
    differently (PR 24): the executed fusion.1 is then not the text's
    fusion.1. The event's own text shows it, and the op is unscoped
    instead of taking another op's phase."""
    run = hand_built_run(tmp_path, monkeypatch)
    run.events['text']['fusion.1'] = \
        '%fusion.1 = f32[7,7]{1,0} fusion(f32[7,7]{1,0} %p), kind=kLoop, ' \
        'calls=%fused_computation.99'
    run.events['text']['fusion.3'] = \
        '%fusion.3 = f32[128,256]{1,0:T(8,128)} fusion(f32[128,256]{1,0} ' \
        '%all-reduce.3), kind=kLoop, calls=%fused_computation.77'
    got = read_all(run)
    assert got['fwd_ms_per_step'] == pytest.approx(2)       # the kernel
    assert got['optimizer_ms_per_step'] == pytest.approx(7)     # the same op
    assert scopes.split(run)['unscoped'][
        'not in step_program.hlo.txt: fusion kLoop matmul f32[7,7]'] == \
        pytest.approx(1)
    known = hlo.describe(HLO.split('ENTRY')[1].splitlines()[3])
    assert known.name == 'fusion.1'
    assert scopes.same_instruction(None, known)
    assert scopes.same_instruction(known, None)
    assert scopes.same_instruction(known, known._replace(calls='x.1'))
    assert not scopes.same_instruction(known, known._replace(shape='f32[1]'))


def test_the_file_is_found_as_run_py_decides(monkeypatch):
    run = types.SimpleNamespace(cell=types.SimpleNamespace(name='a.b'))
    monkeypatch.setattr(sys, 'argv', ['run.py', '--workload', 'a.b'])
    assert scopes.text_path(run) == os.path.join(
        manifest.ROOT, 'chiprun_out', 'a.b', 'step_program.hlo.txt')
    monkeypatch.setattr(sys, 'argv', ['run.py', '--out=/x/y'])
    assert scopes.text_path(run) == '/x/y/step_program.hlo.txt'
    monkeypatch.setattr(sys, 'argv', ['run.py', '--out', '/z'])
    assert scopes.text_path(run) == '/z/step_program.hlo.txt'


def test_the_manifest_accepts_the_new_entries():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    entries = {m['name']: m for m in bench['per_layer']}
    assert [m['name'] for m in bench['per_layer']][-8:] == list(READERS)
    for name in READERS:
        m = entries[name]
        assert m['source'] == 'device_trace' and 'workloads' not in m
        assert m['moves'] == 'samples_per_s_per_chip'
        assert m['layer'] == ('kernels' if name.startswith('flash_')
                              else 'train_step')
        assert m['unit'] == ('%' if name == 'unscoped_share' else 'ms')
        assert manifest.load_module('layer_metrics', name).__doc__
    for w in bench['workloads']:
        reported = [m['name'] for m in manifest.resolve(w['name']).per_layer]
        assert set(READERS) <= set(reported)


# ---------------------------------------------------------------------------
# two steps of bert_base.t512 as the chip traced them, scopes in
# ---------------------------------------------------------------------------

def recorded_run(tmp_path, monkeypatch):
    """t512_scoped_two_steps.json.gz as a traced run hands it to a reader:
    the HLO text (cut to what is read) beside the trace, where run.py
    would have put it."""
    with gzip.open(os.path.join(DATA, 't512_scoped_two_steps.json.gz'),
                   'rt') as f:
        doc = json.load(f)
    (tmp_path / scopes.HLO_FILE).write_text(doc['hlo'])
    monkeypatch.setattr(sys, 'argv', ['run.py', f'--out={tmp_path}'])
    ops, at = [], 0
    for i, gap, duration in doc['ops']:
        at += gap
        ops.append(Event(doc['names'][i], at * 1e-9, (at + duration) * 1e-9))
    events = {'ops': {0: ops}, 'async': {}, 'text': {}, 'modules': {0: [
        Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['modules']]},
        'host': [Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['host']]}
    program = hlo.Program(doc['hlo'])
    return types.SimpleNamespace(
        trace=xplane.reduce(events, program), events=events,
        program=program, cell=types.SimpleNamespace(name='bert_base.t512'))


def test_readers_reproduce_the_traced_run_from_the_recorded_slice(
        tmp_path, monkeypatch):
    """What the chip said over the 20 traced steps of that run (my chip
    run, PR 26): every reader within 0.02 ms of it on these two."""
    run = recorded_run(tmp_path, monkeypatch)
    assert run.trace['steps'] == 2 and run.trace['mosaic_calls'] == 72
    said = {'fwd_ms_per_step': 89.311, 'bwd_ms_per_step': 137.308,
            'optimizer_ms_per_step': 0.992,
            'phase_mixed_ms_per_step': 66.213, 'unscoped_share': 0.930,
            'flash_fwd_ms_per_step': 18.001,
            'flash_bwd_dq_ms_per_step': 22.064,
            'flash_bwd_dkv_ms_per_step': 26.969}
    got = read_all(run)
    for name, value in said.items():
        assert got[name] == pytest.approx(value, abs=0.02), name
    # PR 24's numbers are what they were
    older = {name: manifest.load_module('layer_metrics', name).read(run)
             for name in ('xla_ms_per_step', 'mosaic_ms_per_step')}
    assert older['xla_ms_per_step'] == pytest.approx(229.548, abs=0.02)
    assert older['mosaic_ms_per_step'] == pytest.approx(67.035, abs=0.02)
    # the sum rules, to 0.05 ms
    found = scopes.split(run)
    unscoped_ms = got['unscoped_share'] / 100 * found['busy']
    assert got['fwd_ms_per_step'] + got['bwd_ms_per_step'] \
        + got['optimizer_ms_per_step'] + got['phase_mixed_ms_per_step'] \
        + unscoped_ms == pytest.approx(
            older['xla_ms_per_step'] + older['mosaic_ms_per_step'],
            abs=scopes.SUM_RULE_MS)
    assert got['flash_fwd_ms_per_step'] + got['flash_bwd_dq_ms_per_step'] \
        + got['flash_bwd_dkv_ms_per_step'] == pytest.approx(
            older['mosaic_ms_per_step'], abs=scopes.SUM_RULE_MS)
    text = scopes.report(run)
    assert text.count('holds') == 2 and 'BROKEN' not in text
    # the block table: the layer's index collapsed, ffn1 and ffn2 apart,
    # the layout copies round the kernel a line of their own (15.5 ms by
    # shape in PR 24), head and loss shown
    layer = 'bertforpretraining0/bertmodel0/encoder/bertlayer*/'
    blocks = found['blocks']
    copies = blocks[layer + 'bertselfattention0/attn_core/attn_layout']
    assert copies['fwd'] + copies['bwd'] == pytest.approx(15.6, abs=0.1)
    assert blocks[layer + 'ffn1']['fwd'] == pytest.approx(19.37, abs=0.02)
    assert blocks[layer + 'ffn2']['bwd'] == pytest.approx(22.04, abs=0.02)
    assert blocks['mxtpu.update']['optimizer'] == pytest.approx(0.99,
                                                                abs=0.01)
    for shown in ('mxtpu.loss', 'bertforpretraining0/mlm_decoder',
                  'bertforpretraining0/nsp'):
        assert shown in text
    # the mixed ops are the weight gradients fused with AdamW
    assert all('optimizer' in label.split(':')[0]
               for label in found['mixed'])
    assert max(found['mixed'], key=found['mixed'].get) == \
        'fwd+bwd+optimizer: fusion kOutput matmul (bf16[768,3072], ' \
        'f32[768,3072], +2)'
    assert found['bwd_with_fwd'] == pytest.approx(57.94, abs=0.02)


def test_readers_return_none_on_the_unscoped_slice_of_pr_24(tmp_path,
                                                            monkeypatch):
    """The same two steps as the parent of PR 26 ran them: no phase scope
    in any name, kernels named jvp__.N, no HLO text kept."""
    from test_trace_reduction import Recorded
    with gzip.open(os.path.join(DATA, 't512_two_steps.json.gz'), 'rt') as f:
        doc = json.load(f)
    monkeypatch.setattr(sys, 'argv', ['run.py', f'--out={tmp_path}'])
    events = {'ops': {0: [Event(doc['names'][i], a * 1e-9, b * 1e-9)
                          for i, a, b in doc['ops']]},
              'async': {}, 'text': {}, 'host': [], 'modules': {0: [
                  Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['modules']]}}
    program = Recorded(doc)
    program.get = lambda name: None     # it keeps no instruction
    run = types.SimpleNamespace(
        trace=xplane.reduce(events, program), events=events,
        program=program, cell=types.SimpleNamespace(name='bert_base.t512'))
    assert run.trace['mosaic_calls'] == 72
    assert read_all(run) == dict.fromkeys(READERS)
    assert scopes.report(run).startswith('no phase scope and no kernel name')
    # with the text of a program that has no scope, the same
    (tmp_path / scopes.HLO_FILE).write_text(
        'HloModule jit_stable_step\n\nENTRY %main () -> () {\n' + ''.join(
            f'  %{name} = f32[] add(), metadata={{op_name="jit(stable_step)/'
            f'jvp(bertmodel0)/encoder/add"}}\n' for name in doc['names'])
        + '}\n')
    del run._scope_split
    assert scopes.names_of(run)
    assert read_all(run) == dict.fromkeys(READERS)
