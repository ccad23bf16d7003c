"""The reduction from trace events to numbers, on a trace built by hand:
two chips, a matmul fusion, a Mosaic call, an all-reduce that half hides
behind the kernel, a gap between two program executions while the host
reads the loss."""
import gzip
import json
import os

import types

import pytest

from chipbench import hlo, manifest, xplane
from chipbench.xplane import Event

HLO = '''HloModule jit_stable_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[64,128], param_1: bf16[128,256]) -> bf16[64,256] {
  %param_0 = bf16[64,128]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = bf16[128,256]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = bf16[64,256]{1,0:T(8,128)(2,1)} convolution(%param_0, %param_1), dim_labels=bf_io->bf
}

%fused_computation.2 (param_0.1: bf16[64,256]) -> bf16[64,256] {
  %param_0.1 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %multiply.1 = bf16[64,256]{1,0:T(8,128)(2,1)} multiply(%param_0.1, %param_0.1)
}

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x, %y)
}

ENTRY %main.9 (p0: bf16[64,128], p1: bf16[128,256]) -> bf16[64,256] {
  %p0 = bf16[64,128]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[128,256]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.1 = bf16[64,256]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1
  %jvp__.7 = (bf16[8,64,64]{2,1,0:T(8,128)(2,1)}, f32[8,64,1]{2,1,0:T(8,128)}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"TUzvUgFNTElS"}}
  %all-reduce.3 = f32[128,256]{1,0:T(8,128)} all-reduce(%p1), channel_id=1, replica_groups={{0,1}}, to_apply=%add.clone
  ROOT %fusion.2 = bf16[64,256]{1,0:T(8,128)(2,1)} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
}
'''


STEP = 'jit_stable_step'


def chip(shift):
    ops = [Event('fusion.1', 0.0, 10.0), Event('jvp__.7', 10.0, 14.0),
           Event('all-reduce.3', 12.0, 20.0),
           # a small program of its own between the steps, whose op has
           # the name of one of the step's
           Event('fusion.1', 21.0, 22.0), Event('fusion.2', 25.0, 30.0)]
    modules = [Event('jit_stable_step(123)', 0.0, 20.0),
               Event('jit__threefry_fold_in(9)', 21.0, 22.0),
               Event('jit_stable_step(123)', 25.0, 30.0)]
    move = lambda e: Event(e.name, e.start + shift, e.end + shift)  # noqa
    return [move(e) for e in ops], [move(e) for e in modules]


@pytest.fixture()
def program():
    return hlo.Program(HLO)


def test_hlo_says_what_each_name_is(program):
    assert program.category('fusion.1') == 'xla'
    assert program.category('%fusion.2') == 'xla'
    assert program.category('jvp__.7') == 'mosaic'
    assert program.category('all-reduce.3') == 'collective'
    assert program.category('an_op_of_another_program') == 'xla'
    assert program.label('fusion.1') == \
        'fusion kOutput matmul bf16[64,256]'
    assert program.label('fusion.2') == 'fusion kLoop bf16[64,256]'
    assert program.label('jvp__.7') == \
        'custom-call tpu_custom_call (bf16[8,64,64], f32[8,64,1])'
    assert program.label('all-reduce.3') == 'all-reduce f32[128,256]'


def test_intervals():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert xplane.length(merged) == 6
    assert xplane.minus(merged, [(2, 6), (7.5, 10)]) == [(0, 2), (6, 7.5)]
    assert xplane.gaps(merged, 0, 10) == [(3, 5), (8, 10)]


def test_one_chip(program):
    ops, modules = chip(0.0)
    host = [Event('chipbench.dispatch', 18.0, 19.0),
            Event('chipbench.read_loss', 19.0, 26.0)]
    got = xplane.reduce_chip(ops, [], modules, host, program)
    assert got['window_s'] == 30.0
    assert got['busy_s'] == 26.0
    assert got['mosaic_s'] == 4.0 and got['mosaic_calls'] == 1
    assert got['xla_s'] == 16.0
    assert got['collective_s'] == 8.0
    # the all-reduce runs from 12 to 20, the kernel hides 12 to 14
    assert got['collective_exposed_s'] == 6.0
    assert got['steps'] == 2
    assert got['idle_gaps'] == {
        'inside a program': 0.0,
        'between programs, host in chipbench.read_loss': 4.0}
    assert got['longest_gap_s'] == 3.0
    assert got['per_op'][xplane.OTHER_PROGRAMS] == 1.0
    assert got['per_op']['fusion.1'] == 10.0
    # what a reader of its own may want: the classified intervals
    assert got['step_runs'] == [(0.0, 20.0), (25.0, 30.0)]
    assert got['intervals']['mosaic'] == [(10.0, 14.0)]
    assert got['intervals']['collective'] == [(12.0, 20.0)]


def test_the_step_is_the_program_the_text_names(program):
    """The step's name comes from the compiled program's text, not from a
    constant here: only executions of exactly that module are steps, and a
    trace without one reduces to nothing instead of dividing by zero."""
    assert program.module == STEP
    modules = [Event('jit_stable_step(1)', 0, 4),
               Event('jit_stable_step_2(1)', 5, 6),
               Event('jit__threefry_fold_in(9)', 7, 8)]
    assert xplane.executions(modules, program.module) == [(0, 4)]
    trace = {'ops': {}, 'async': {}, 'modules': {}, 'host': []}
    trace['ops'][0], trace['modules'][0] = chip(0.0)
    assert xplane.reduce(trace, program)['steps'] == 2
    renamed = hlo.Program(HLO.replace('HloModule jit_stable_step',
                                      'HloModule jit_train_step'))
    assert renamed.module == 'jit_train_step'
    assert xplane.reduce(trace, renamed) is None


def test_a_collective_in_flight_beside_the_stream(program):
    """An all-reduce on the async line from 2 to 9 while the stream runs
    the matmul to 6 and then nothing until 9: three of its seven are
    exposed, and it adds nothing to the busy time."""
    ops = [Event('fusion.1', 0.0, 6.0), Event('fusion.2', 9.0, 10.0)]
    modules = [Event('jit_stable_step(123)', 0.0, 10.0)]
    got = xplane.reduce_chip(ops, [Event('all-reduce.3', 2.0, 9.0),
                                   Event('fusion.1', 1.0, 2.0)],
                             modules, [], program)
    assert got['busy_s'] == 7.0 and got['xla_s'] == 7.0
    assert got['collective_s'] == 7.0
    assert got['collective_exposed_s'] == 3.0
    assert got['idle_gaps']['inside a program'] == 3.0


def test_what_ran_decides_over_the_recompiled_text(program):
    """compiled_program() compiles the step a second time, and on four
    chips that text names and fuses some ops differently from the program
    that ran (PERF.md section 6, PR 24). The event's own text wins."""
    seen = {
        # ran as an all-reduce under a name the text does not have
        'all-reduce.77': hlo.describe(
            '%all-reduce.77 = f32[128,256]{1,0} all-reduce(f32[128,256] '
            '%p1), replica_groups={{0,1}}, to_apply=%add.clone'),
        # the text has fusion.2 as a loop fusion; what ran under that
        # name was a Mosaic call
        'fusion.2': hlo.describe(
            '%fusion.2 = bf16[8,64,64]{2,1,0} custom-call(bf16[64,256] '
            '%fusion.1), custom_call_target="tpu_custom_call"'),
    }
    scattered = hlo.describe(
        '%fusion.4056 = (f32[192,768]{1,0}, f32[192,768]{1,0}) fusion('
        'f32[768,768]{1,0} %copy-done.87), kind=kCustom, '
        'calls=%all-reduce-scatter.54')
    assert program.category('fusion.4056', scattered) == 'collective'
    assert program.category('all-reduce.77') == 'xla'
    assert program.category('all-reduce.77', seen['all-reduce.77']) == \
        'collective'
    assert program.category('fusion.2', seen['fusion.2']) == 'mosaic'
    assert program.label('all-reduce.77', seen['all-reduce.77']) == \
        'all-reduce f32[128,256]'
    ops = [Event('fusion.1', 0.0, 4.0), Event('all-reduce.77', 4.0, 6.0),
           Event('fusion.2', 6.0, 9.0)]
    got = xplane.reduce_chip(ops, [], [Event('jit_stable_step(1)', 0, 9)],
                             [], program, seen)
    assert got['collective_s'] == got['collective_exposed_s'] == 2.0
    assert got['mosaic_s'] == 3.0 and got['xla_s'] == 4.0


def test_mean_over_chips_and_breakdown(program):
    trace = {'ops': {}, 'async': {}, 'modules': {}, 'host': [
        Event('chipbench.read_loss', 19.0, 27.0)]}
    trace['ops'][0], trace['modules'][0] = chip(0.0)
    trace['ops'][1], trace['modules'][1] = chip(1.0)
    trace['ops'][1].append(Event('fusion.2', 31.0, 36.0))   # chip 1 runs on
    trace['modules'][1].append(Event('jit_stable_step(123)', 31.0, 36.0))
    got = xplane.reduce(trace, program)
    assert got['chips'] == 2 and got['steps'] == 3
    assert [c['steps'] for c in got['per_chip']] == [2, 3]
    assert got['window_s'] == pytest.approx((30.0 + 35.0) / 2)
    assert got['busy_s'] == pytest.approx((26.0 + 31.0) / 2)
    assert got['idle_share'] == pytest.approx(1 - 28.5 / 32.5)
    assert got['mosaic_s'] == 4.0
    assert got['collective_exposed_s'] == 6.0
    ops = dict(got['breakdown']['device_ops'])
    assert ops['fusion kOutput matmul bf16[64,256] x1'] == 10.0
    assert ops['all-reduce f32[128,256] x1'] == 8.0
    assert ops['fusion kLoop bf16[64,256] x1'] == pytest.approx(7.5)
    assert ops[xplane.OTHER_PROGRAMS + ' x1'] == 1.0
    gaps = dict(got['breakdown']['idle_gaps'])
    assert gaps['between programs, host in chipbench.read_loss'] == 4.0
    assert len(got['breakdown']['device_ops']) <= 10


def test_a_trace_without_device_ops_reduces_to_nothing(program):
    assert xplane.reduce({'ops': {}, 'async': {}, 'modules': {},
                          'host': []}, program) is None


class Recorded:
    """Stands in for hlo.Program on the recorded slice, whose 19 MB of HLO
    text are not kept: the category of each op name as that text gave it."""

    module = STEP

    def __init__(self, doc):
        self._category = doc['category']

    def category(self, name, seen=None):
        return self._category.get(name, 'xla')

    def label(self, name, seen=None):
        return self.category(name)


def test_recorded_slice_of_a_v5e_trace():
    """Two steps of bert_base.t512 as the chip traced them. The stream's
    ops never overlap, so every figure of the reduction can be had a
    second way, by plain sums."""
    path = os.path.join(os.path.dirname(__file__), 'data',
                        't512_two_steps.json.gz')
    with gzip.open(path, 'rt') as f:
        doc = json.load(f)
    names = doc['names']
    ops = [Event(names[i], a * 1e-9, b * 1e-9) for i, a, b in doc['ops']]
    modules = [Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['modules']]
    host = [Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['host']]
    program = Recorded(doc)
    got = xplane.reduce_chip(ops, [], modules, host, program)

    # three while loops (the sorts) wrap ops the line shows as well
    wrapped = [e for e in ops if program.category(e.name) == 'container']
    assert len(wrapped) == 6
    ops = [e for e in ops if e not in wrapped]
    assert all(a.end <= b.start + 1e-12 for a, b in zip(ops, ops[1:]))
    steps = [m for m in modules if m.name.startswith(STEP)]
    in_step = lambda e: any(m.start <= e.start < m.end for m in steps)  # noqa
    busy = sum(e.end - e.start for e in ops)
    mosaic = [e for e in ops if in_step(e)
              and program.category(e.name) == 'mosaic']
    assert got['steps'] == 2
    assert got['busy_s'] == pytest.approx(busy, rel=1e-9)
    assert got['window_s'] == pytest.approx(ops[-1].end - ops[0].start)
    assert got['mosaic_calls'] == len(mosaic) == 72     # 3 a layer a step
    assert got['mosaic_s'] == pytest.approx(
        sum(e.end - e.start for e in mosaic), rel=1e-9)
    assert got['xla_s'] == pytest.approx(busy - got['mosaic_s'], rel=1e-9)
    assert got['collective_calls'] == 0 and got['collective_s'] == 0
    # what the chip said: 297 ms a step, of which the kernels 67
    assert 0.29 < got['window_s'] / 2 < 0.30
    assert 0.06 < got['mosaic_s'] / 2 < 0.07
    idle = got['window_s'] - got['busy_s']
    assert sum(got['idle_gaps'].values()) == pytest.approx(idle, rel=1e-6)
    # the slice opens on the refill after a read: its longest gap is the
    # chip waiting for the first step dispatched after the losses came back
    assert 0.004 < got['longest_gap_s'] < 0.007
    assert max(got['idle_gaps'], key=got['idle_gaps'].get) == \
        'between programs, host in chipbench.dispatch'


# ---------------------------------------------------------------------------
# host_dispatch_ms_per_step: a reader over the loaded trace itself
# ---------------------------------------------------------------------------

@pytest.fixture()
def host_dispatch():
    return manifest.load_module('layer_metrics', 'host_dispatch_ms_per_step')


def test_only_a_dispatch_that_cannot_have_waited_counts(host_dispatch):
    """After a read the queue is empty. Three dispatches return before the
    first step since then has ended (at 40): they found room. The fourth
    returns as that step ends, the fifth later still: they may have waited
    for a slot and are left out. The same again after the second read."""
    host = [Event('chipbench.dispatch', 0, 2),
            Event('chipbench.dispatch', 2, 5),
            Event('chipbench.dispatch', 5, 9),
            Event('chipbench.dispatch', 9, 41),
            Event('chipbench.dispatch', 41, 81),
            Event('chipbench.read_loss', 81, 160),
            Event('chipbench.feed', 160, 160),
            Event('chipbench.dispatch', 160, 166),
            Event('chipbench.dispatch', 166, 205)]
    step_ends = [40, 80, 120, 159, 200, 240]
    assert host_dispatch.unblocked(host, step_ends) == [2, 3, 4, 6]
    run = types.SimpleNamespace(
        trace={'per_chip': [{'step_runs': [(e - 39, e) for e in step_ends]}]},
        events={'host': host})
    assert host_dispatch.read(run) == pytest.approx(3.5e3)
    # on four chips the first chip to finish the step decides
    run.trace['per_chip'].append({'step_runs': [(0, 8)]})
    assert host_dispatch.read(run) == pytest.approx(3.0e3)     # of 2, 3, 6
    run.events = {'host': []}
    assert host_dispatch.read(run) is None
    run.trace = None
    assert host_dispatch.read(run) is None


def test_host_dispatch_on_the_recorded_slice(host_dispatch):
    """The slice holds a read and the nine dispatches after it: seven
    return in about 10 ms each while the first step still runs, the eighth
    waits 245 ms for it to end (what the chip said, PR 24)."""
    path = os.path.join(os.path.dirname(__file__), 'data',
                        't512_two_steps.json.gz')
    with gzip.open(path, 'rt') as f:
        doc = json.load(f)
    host = [Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['host']]
    modules = [Event(n, a * 1e-9, b * 1e-9) for n, a, b in doc['modules']]
    ends = [end for _start, end in xplane.executions(modules, STEP)]
    free = host_dispatch.unblocked(host, ends)
    assert len(free) == 7 and all(0.007 < s < 0.012 for s in free)
    waited = [e.end - e.start for e in host
              if e.name == 'chipbench.dispatch'][7:]
    assert len(waited) == 2 and min(waited) > 0.2
