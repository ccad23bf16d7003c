"""The program's own host spans, on a host plane built by hand: four
dispatches of a step whose device takes one at a time -- the first finds
the queue empty, the second waits in its own Python until the first step
is done, the third spends most of its time placing its batch while the
chip sits idle, the fourth follows a read of the losses -- and, end to end,
a rehearsal whose trace file holds the step's spans."""
import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import hlo, hostspans, manifest, xplane
from chipbench.xplane import Event

MS = 1e-3
# (name, start, end) in ms from the profile's start, one thread
HOST = [
    ('mxtpu.step.dispatch', 1.05, 1.95),
    ('mxtpu.h2d.batch_put', 1.10, 1.30),
    ('mxtpu.step.compiled', 1.40, 1.80),
    ('mxtpu.step.gather', 1.85, 1.90),
    ('mxtpu.step.dispatch', 2.15, 5.75),      # waits for step 1's end,
    ('mxtpu.h2d.batch_put', 5.05, 5.25),      # before its batch put
    ('mxtpu.step.compiled', 5.30, 5.60),
    ('mxtpu.step.gather', 5.65, 5.70),
    ('mxtpu.step.dispatch', 5.80, 7.60),
    ('mxtpu.h2d.batch_put', 5.90, 7.00),      # the chip idles from 6.2
    ('mxtpu.step.compiled', 7.10, 7.40),
    ('mxtpu.step.gather', 7.42, 7.45),
    ('mxtpu.step.dispatch', 10.00, 10.80),    # after the read: free again
    ('mxtpu.h2d.batch_put', 10.05, 10.25),
    ('mxtpu.step.compiled', 10.30, 10.60),
    ('mxtpu.step.gather', 10.65, 10.70),
]
# what another thread does meanwhile: never the step loop's
LOADER = [('mxtpu.io.decode', 0.5, 6.5), ('mxtpu.io.collate', 6.6, 8.0)]
BENCH = [Event('chipbench.dispatch', 1.04 * MS, 1.96 * MS),
         Event('chipbench.dispatch', 2.14 * MS, 5.76 * MS),
         Event('chipbench.dispatch', 5.79 * MS, 7.61 * MS),
         Event('chipbench.read_loss', 7.7 * MS, 9.9 * MS),
         Event('chipbench.dispatch', 9.99 * MS, 10.81 * MS)]
STEPS = [(1.8, 5.0), (5.6, 6.2), (7.4, 9.0), (10.6, 12.0)]

HLO = '''HloModule jit_stable_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[64,128]) -> bf16[64,128] {
  %param_0 = bf16[64,128]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[64,128]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(stable_step)/mxtpu.fwd_bwd/mul"}
}

ENTRY %main.3 (p0: bf16[64,128]) -> bf16[64,128] {
  %p0 = bf16[64,128]{1,0} parameter(0)
  ROOT %fusion.1 = bf16[64,128]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.1
}
'''
FUSION = ('%fusion.1 = bf16[64,128]{1,0} fusion(bf16[64,128]{1,0} %p0), '
          'kind=kLoop, calls=%fused_computation.1')


def xspace(lines):
    """A serialized XSpace with one host plane: ``lines`` is [(thread
    name, [(event name, start ms, end ms)])]."""
    from jax.profiler import ProfileData
    ids = {}
    body = []
    for i, (thread, events) in enumerate(lines):
        body.append(f'lines {{ id: {i + 1} name: "{thread}" timestamp_ns: 0')
        for name, start, end in events:
            key = ids.setdefault(name, len(ids) + 1)
            body.append(
                f'  events {{ metadata_id: {key} '
                f'offset_ps: {round(start * 1e9)} '
                f'duration_ps: {round((end - start) * 1e9)} }}')
        body.append('}')
    for name, key in ids.items():
        body.append(f'event_metadata {{ key: {key} value {{ id: {key} '
                    f'name: "{name}" }} }}')
    return ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "/host:CPU"\n' + '\n'.join(body) + '\n}\n'
        'planes { name: "/device:TPU:0" }\n')


@pytest.fixture()
def run(tmp_path, monkeypatch):
    """What a reader is handed, for the trace above written under
    ``--out``: the host plane as a file, the device side as events."""
    where = tmp_path / 'trace' / 'plugins' / 'profile' / '2026_10_05'
    where.mkdir(parents=True)
    (where / 'host.xplane.pb').write_bytes(xspace(
        [('python3', HOST + [('chipbench.dispatch', 1.04, 1.96)]),
         ('loader', LOADER)]))
    (tmp_path / 'step_program.hlo.txt').write_text(HLO)
    monkeypatch.setattr(sys, 'argv', ['run.py', '--out', str(tmp_path)])
    program = hlo.Program(HLO)
    events = {
        'ops': {0: [Event('fusion.1', a * MS, b * MS) for a, b in STEPS]},
        'async': {0: []},
        'modules': {0: [Event('jit_stable_step(7)', a * MS, b * MS)
                        for a, b in STEPS]},
        'host': BENCH, 'text': {'fusion.1': FUSION}}
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name='hand.built'), program=program,
        events=events, trace=xplane.reduce(events, program))


def reader(name):
    return manifest.load_module('layer_metrics', name)


def test_the_file_gives_each_threads_spans_nested(run):
    path = hostspans.trace_path(run)
    assert path.endswith('host.xplane.pb')
    lines = hostspans.load(path)
    assert [name for name, _events in lines] == ['python3', 'loader']
    # the benchmark's own annotation on that thread is xplane.load's
    assert len(lines[0][1]) == len(HOST)
    assert all(e.name.startswith('mxtpu.') for e in lines[0][1])
    (_main, roots), (_other, loader) = hostspans.of(run)
    assert [s.event.name for s in roots] == ['mxtpu.step.dispatch'] * 4
    assert [[c.event.name for c in s.children] for s in roots] == [
        ['mxtpu.h2d.batch_put', 'mxtpu.step.compiled',
         'mxtpu.step.gather']] * 4
    assert [s.event.name for s in loader] == ['mxtpu.io.decode',
                                              'mxtpu.io.collate']
    first = roots[0]
    assert hostspans.seconds(first) == pytest.approx(0.9 * MS)
    assert hostspans.inside(first, hostspans.ENQUEUE) == \
        pytest.approx(0.4 * MS)
    assert [(n, round(s / MS, 2)) for n, s in hostspans.timeline(first)] \
        == [('', 0.05), ('h2d.batch_put', 0.2), ('', 0.1),
            ('step.compiled', 0.4), ('', 0.05), ('step.gather', 0.05),
            ('', 0.05)]
    assert hostspans.of(run) is hostspans.of(run)      # read once


def test_forest_nests_by_enclosure():
    spans = hostspans.forest([
        Event('a', 0, 10), Event('b', 0, 4), Event('c', 1, 2),
        Event('d', 4, 10), Event('e', 10, 11)])
    assert [s.event.name for s in spans] == ['a', 'e']
    a = spans[0]
    assert [c.event.name for c in a.children] == ['b', 'd']
    assert [c.event.name for c in a.children[0].children] == ['c']
    assert hostspans.innermost(spans, 1.2, 1.8) == 'c'
    assert hostspans.innermost(spans, 0.5, 3.5) == 'b'     # c covers a third
    assert hostspans.innermost(spans, 3, 7) == 'd'
    assert hostspans.innermost(spans, 9, 14) == hostspans.OUTSIDE
    assert hostspans.innermost([], 0, 1) == hostspans.OUTSIDE


def test_only_dispatches_that_waited_for_nothing_are_counted(run):
    # the first ended at 1.95, before step 1 was done (5.0); the second
    # at 5.75, after it: it held the wait, in its own Python; the third
    # cannot be told; the fourth began after the read, when the queue was
    # empty, and ended before step 4 was done (12.0)
    free = hostspans.free_dispatches(run, hostspans.of(run))
    assert [round(d.event.start / MS, 2) for d in free] == [1.05, 10.0]
    # 0.9 - 0.4 and 0.8 - 0.3; 0.4 and 0.3
    assert reader('step_host_ms_per_step').read(run) == pytest.approx(0.5)
    assert reader('step_enqueue_ms_per_step').read(run) == \
        pytest.approx(0.35)
    # the rule is host_dispatch_ms_per_step's, which reads the same two
    assert reader('host_dispatch_ms_per_step').read(run) == \
        pytest.approx(0.87)


def test_the_note_checks_the_sum_and_names_the_gaps_and_the_longest(run):
    said = reader('step_host_ms_per_step').note(run)
    assert 'over the 2 of 4 dispatches that waited for nothing: step_host ' \
        '0.500 + step_enqueue 0.350 = 0.850 ms' in said
    assert 'median 0.870: agree (-0.020 ms' in said
    assert "the benchmark's annotation is 0.020 ms longer" in said
    # the chip waited from 5.0 to 5.6 with the host in the second
    # dispatch (no child over half of it), from 6.2 to 7.4, of which the
    # batch put covers 6.2 to 7.0, and from 9.0 to 10.6, mostly in the
    # benchmark's read
    gaps = hostspans.gaps_by_span(run, hostspans.of(run))
    assert gaps == {'mxtpu.h2d.batch_put': pytest.approx(1.2 * MS),
                    'mxtpu.step.dispatch': pytest.approx(0.6 * MS),
                    hostspans.OUTSIDE: pytest.approx(1.6 * MS)}
    assert ('no mxtpu. span 0.001600 s; mxtpu.h2d.batch_put 0.001200 s; '
            'mxtpu.step.dispatch 0.000600 s') in said
    assert ('longest mxtpu.step.dispatch of the window: 3.600 ms at '
            '0.002150 s = its own Python 2.900 + h2d.batch_put 0.200 + '
            'its own Python 0.050 + step.compiled 0.300 + its own Python '
            '0.050 + step.gather 0.050 + its own Python 0.050') in said


def test_every_executed_name_is_looked_up_in_the_text(run):
    peer = reader('step_enqueue_ms_per_step')
    assert peer.missing_names(run) == (1, [], [])
    assert peer.note(run) == ('no executed instruction name of the step is '
                              'missing from step_program.hlo.txt: all 1 '
                              'are in it')
    # the same op under a name the text does not have, and one the trace's
    # table describes as another instruction than the text has
    run.events['ops'][0].append(Event('fusion.9', 8.0 * MS, 8.5 * MS))
    run.events['text']['fusion.9'] = FUSION.replace('fusion.1', 'fusion.9')
    run.events['text']['fusion.1'] = (
        '%fusion.1 = f32[8]{0} all-reduce(f32[8]{0} %p0), to_apply=%add')
    run.trace = xplane.reduce(run.events, run.program)
    assert peer.missing_names(run) == (
        2, [('fusion.9', 'fusion bf16[64,128]')],
        [('fusion.1', 'opcode all-reduce against fusion, kind none against '
          'kLoop, shape f32[8] against bf16[64,128]')])
    said = peer.note(run)
    assert '1 of 2 executed instruction names of the step are MISSING' in said
    assert 'by opcode fusion 1; the first: fusion.9 ran as fusion ' \
        'bf16[64,128]' in said
    assert 'trace against text: fusion.1 opcode all-reduce against fusion, ' \
        'kind none against kLoop' in said


def test_a_program_without_the_spans_reads_nothing(run, tmp_path):
    """The parent of PR 39 annotates nothing: no value, and no raise."""
    for f in tmp_path.glob('trace/plugins/profile/*/*.xplane.pb'):
        f.write_bytes(xspace([('python3', [('chipbench.dispatch', 1, 2)])]))
    hostspans._loaded.clear()
    assert hostspans.of(run) == []
    assert reader('step_host_ms_per_step').read(run) is None
    assert reader('step_enqueue_ms_per_step').read(run) is None
    assert 'before PR 39' in reader('step_host_ms_per_step').note(run)
    # nor does a run with no trace file at all, or no reduced trace
    for f in tmp_path.glob('trace/plugins/profile/*/*.xplane.pb'):
        f.unlink()
    run.trace = None
    assert hostspans.of(run) == []
    assert reader('step_host_ms_per_step').read(run) is None
    assert reader('step_enqueue_ms_per_step').read(run) is None
    assert 'cannot be checked' in reader('step_enqueue_ms_per_step').note(run)


def test_a_rehearsals_trace_holds_the_steps_own_spans(tmp_path):
    """run.py --rehearse --trace 1 on the CPU: the profile it takes holds
    the program's spans on the host plane, nested, with MXTPU_TRACE unset;
    there are as many dispatches as the benchmark's own annotations."""
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=1',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    env.pop('MXTPU_TRACE', None)
    out = tmp_path / 'out'
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, 'run.py'),
         '--workload', 'bert_base.t128', '--seed', '5', '--seconds', '1',
         '--trace', '1', '--rehearse', '--out', str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last['correct'] is True and last['counts']['trace_file'] is True
    (path,) = out.glob('trace/plugins/profile/*/*.xplane.pb')
    lines = hostspans.load(str(path))
    forests = [(name, hostspans.forest(events)) for name, events in lines]
    dispatches = hostspans.named(forests, hostspans.DISPATCH)
    outside = [e for e in xplane.load(str(path))['host']
               if e.name == 'chipbench.dispatch']
    assert len(dispatches) == len(outside) == 20
    for inner, outer in zip(dispatches, outside):
        assert outer.start <= inner.event.start
        assert inner.event.end <= outer.end
        assert [c.event.name for c in inner.children] == [
            'mxtpu.h2d.batch_put', 'mxtpu.step.compiled',
            'mxtpu.step.gather']
