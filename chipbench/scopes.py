"""From an executed op to the program's own names: which phase of the
train step it belongs to (forward, backward, optimizer), which Gluon
block, and which Pallas kernel it is. Shared by the readers
``layer_metrics/{fwd,bwd,optimizer,phase_mixed}_ms_per_step.py``,
``unscoped_share.py`` and ``flash_{fwd,bwd_dq,bwd_dkv}_ms_per_step.py``.

Where the names are read from (looked at by hand on a v5e trace, PR 26;
PERF.md section 3 has the account):

* The program traces its step under ``jax.named_scope``s and names its
  kernels with ``pallas_call(name=...)``. Both end up in the ``op_name``
  of each HLO instruction's ``metadata={...}``, e.g.
  ``jit(stable_step)/mxtpu.fwd_bwd/transpose(jvp(bertforpretraining0))/
  bertmodel0/encoder/bertlayer3/ffn2/dot_general``.
* A trace event carries the instruction's text *without* its metadata
  (and no stat holds it), so the ``op_name`` is looked up by the event's
  instruction name in the optimized HLO text the traced run writes beside
  the trace, ``step_program.hlo.txt``. ``hlo.Program`` keeps no metadata;
  ``parse`` below reads what is needed. That text comes from compiling
  the step a second time: on one chip it names every op as the executed
  program does, on four chips a few hundred executed ops are not in it
  (PERF.md section 6, PR 24), and those count as unscoped.
* A kernel's name is the custom call's own instruction name
  (``%mxtpu_flash_fwd.12``), which the event does carry, so the kernel
  split needs no text; where the name is not a kernel's, the last scope
  but one of the ``op_name`` (``.../mxtpu_flash_fwd/pallas_call``) is.
* The file: the ``run`` a reader is handed has no output directory, so
  ``text_path`` rebuilds it as run.py does -- ``--out <dir>`` if this
  process was started with one, else ``chiprun_out/<cell>/`` in the
  checkout. No file, or one of another program, and every op is unscoped.

The scope names are strings of the benchmark's own and are not imported
from ``mxnet_tpu``: a scope renamed in the program shows as
``unscoped_share`` rising, not as a silently moved yardstick.
"""
import collections
import os
import re
import sys
import traceback

from chipbench import hlo, manifest, xplane

FWD_BWD = 'mxtpu.fwd_bwd'
LOSS = 'mxtpu.loss'
# after the gradients exist: their exchange, the non-finite guard, the
# update; and ZeRO-3's gathers, which no cell runs yet
OPTIMIZER = ('mxtpu.exchange', 'mxtpu.guard', 'mxtpu.update', 'mxtpu.gather')
BACKWARD = 'transpose('
KERNELS = ('mxtpu_flash_fwd', 'mxtpu_flash_bwd_dq', 'mxtpu_flash_bwd_dkv',
           'mxtpu_ffn_gelu', 'mxtpu_add_layernorm')
PHASES = ('fwd', 'bwd', 'optimizer')
MIXED, UNSCOPED, COLLECTIVE = 'phase_mixed', 'unscoped', 'collective'
HLO_FILE = 'step_program.hlo.txt'
SUM_RULE_MS = 0.05
REPEATED = 3     # so many indices of one name make it a repeated layer

# transformations JAX wraps round the first scope inside them
_WRAPPED = re.compile(r'^(?:jvp|transpose|vmap|remat|checkpoint|custom_jvp|'
                      r'custom_vjp)\((.*)\)$')
# where a block path ends: a nested jit, control flow, a closed call
_INNER = re.compile(r'^jit\(|^(?:while|cond|body|closed_call|checkpoint)$')
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# what a fusion or an async start wraps; while, conditional and call are
# containers whose instructions the trace shows one by one
_CALLS = re.compile(r'\bcalls=%?([^\s,)}]+)')
_DONE = re.compile(r'\b[a-z\-]+-done\((?:[^%)]*)%([^\s,)]+)')
# inside a fusion these compute nothing, and XLA merges equal ones from
# anywhere in the program: a forward fusion's constant(0) may carry a
# backward op_name (seen on the chip, PR 26). Their names are not counted.
NO_WORK = frozenset(('constant', 'parameter', 'iota', 'broadcast', 'bitcast',
                     'tuple', 'get-tuple-element', 'reshape'))
_SUFFIX = re.compile(r'\.\d+$')
_INDEXED = re.compile(r'^(.*?)(\d+)$')


# ---------------------------------------------------------------------------
# an op_name, taken apart
# ---------------------------------------------------------------------------

def phase_of(op_name):
    """'fwd', 'bwd', 'optimizer', or None where no phase scope is in it."""
    if any(scope in op_name for scope in OPTIMIZER):
        return 'optimizer'
    if FWD_BWD in op_name:
        return 'bwd' if BACKWARD in op_name else 'fwd'
    return None


def _unwrap(part):
    while True:
        m = _WRAPPED.match(part)
        if not m:
            return part
        part = m.group(1)


def block_of(op_name):
    """The block path of an op_name: the scopes after the phase scope, up
    to the primitive or the first nested jit or control flow, JAX's
    ``jvp(...)`` wrappers taken off. '' where there is no phase scope or
    no block."""
    parts = [_unwrap(p) for p in op_name.split('/')]
    # after the last mxtpu.fwd_bwd (a custom_vjp's backward repeats it as
    # transpose(mxtpu.fwd_bwd)), or from the optimizer's scope on
    start = max((i + 1 for i, p in enumerate(parts) if p == FWD_BWD),
                default=next((i for i, p in enumerate(parts)
                              if p in OPTIMIZER), None))
    if start is None:
        return ''
    path = []
    for p in parts[start:-1]:
        if _INNER.match(p):
            break
        path.append(p)
    return '/'.join(path)


def _indexed(path):
    """(position, 'parent/stem', stem, index) of each part of a path that
    ends in an index."""
    parts = path.split('/')
    for i, part in enumerate(parts):
        m = _INDEXED.match(part)
        if m:
            yield i, '/'.join(parts[:i] + [m.group(1)]), *m.groups()


def collapse(paths):
    """{path: the path with each repeated layer's index as '*'}: a name
    whose stem has at least ``REPEATED`` indices under one parent
    (``encoder/bertlayer0`` to ``11``) is a repeated layer, ``ffn1`` and
    ``ffn2`` are two things."""
    indices = collections.defaultdict(set)
    for path in paths:
        for _i, key, _stem, index in _indexed(path):
            indices[key].add(index)
    out = {}
    for path in paths:
        parts = path.split('/')
        for i, key, stem, _index in _indexed(path):
            if len(indices[key]) >= REPEATED:
                parts[i] = stem + '*'
        out[path] = '/'.join(parts)
    return out


def kernel_of(name, op_name=''):
    """The Pallas kernel an instruction is, by its own name
    (``mxtpu_flash_fwd.12``) or by the scope ``pallas_call(name=...)``
    left in its op_name; None if neither names one."""
    base = _SUFFIX.sub('', name.lstrip('%'))
    if base in KERNELS:
        return base
    parts = op_name.split('/')
    if len(parts) >= 2 and parts[-2] in KERNELS:
        return parts[-2]
    return None


# ---------------------------------------------------------------------------
# the optimized HLO text, as far as the names need it
# ---------------------------------------------------------------------------

Named = collections.namedtuple('Named', 'op_name inside')


def parse(text):
    """{instruction name: Named(its own op_name, the op_names of the
    instructions of the computations it calls, nested calls included,
    less those of ``NO_WORK``)} for every instruction of every
    computation. An ``*-done`` that carries no name takes those of the
    ``*-start`` it completes."""
    own, calls, waits_for, idle = {}, {}, {}, set()
    members = collections.defaultdict(list)     # computation -> names
    inside = None
    for line in text.splitlines():
        if not line.startswith(' '):
            m = hlo._COMPUTATION.match(line)
            inside = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line) if inside is not None else None
        if not m:
            continue
        name = m.group(1)
        # a Mosaic call's backend_config runs to hundreds of kilobytes,
        # all after what is read here
        cut = line.find(', backend_config=')
        head = line if cut < 0 else line[:cut]
        found = _OP_NAME.search(head)
        own[name] = found.group(1).replace('\\', '') if found else ''
        opcode = hlo._OPCODE.search(head[m.end():m.end() + 4096])
        if opcode and opcode.group(1) in NO_WORK:
            idle.add(name)
        called = _CALLS.findall(head)
        if called:
            calls[name] = called
        done = _DONE.search(head)
        if done:
            waits_for[name] = done.group(1)
        members[inside].append(name)

    held = {}

    def holds(computation):     # the call graph of an HLO module is a DAG
        if computation not in held:
            names = set()
            for name in members.get(computation, ()):
                if own[name] and name not in idle:
                    names.add(own[name])
                for c in calls.get(name, ()):
                    names |= holds(c)
            held[computation] = frozenset(names)
        return held[computation]

    out = {name: Named(op_name, frozenset().union(
        *(holds(c) for c in calls.get(name, ()))))
        for name, op_name in own.items()}
    for name, start in waits_for.items():
        if not out[name].op_name and not out[name].inside and start in out:
            out[name] = out[start]
    return out


def phases_of(named):
    """The phases the op_names inside an instruction carry, or where it
    calls nothing, its own."""
    found = {phase_of(n) for n in named.inside or {named.op_name}}
    return found - {None}


def phase_among(found):
    """One op's phase from those its instructions carry. Optimizer work
    beside forward or backward work is MIXED. Backward beside forward is
    backward: such a fusion needs a cotangent, so it runs on the way back,
    and what it holds of the forward it computes again (dropout bits, the
    normalised input of a LayerNorm, GELU's inner terms)."""
    if not found:
        return UNSCOPED
    if 'optimizer' in found:
        return 'optimizer' if len(found) == 1 else MIXED
    return 'bwd' if 'bwd' in found else 'fwd'


def text_path(run):
    """Where this run's step_program.hlo.txt is, as run.py decides it."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == '--out' and i + 1 < len(argv):
            return os.path.join(argv[i + 1], HLO_FILE)
        if arg.startswith('--out='):
            return os.path.join(arg[len('--out='):], HLO_FILE)
    return os.path.join(manifest.ROOT, 'chiprun_out', run.cell.name,
                        HLO_FILE)


def names_of(run):
    """``parse`` of this run's HLO text, or {} where the file is not there
    or is the text of another program than the step that ran."""
    try:
        with open(text_path(run)) as f:
            text = f.read()
    except OSError:
        return {}
    module = hlo._MODULE.search(text[:4096])
    if not module or module.group(1) != run.program.module:
        return {}
    return parse(text)


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------

def account(trace, events, program, names):
    """The reduced trace's per-op seconds, split by what the names say.
    Everything in ms a step, mean over chips: ``phase`` (fwd, bwd,
    optimizer, phase_mixed, unscoped, collective), ``kernels`` by kernel
    name, ``blocks`` {path: {phase: ms}}, ``mixed`` and ``unscoped``
    {label: ms}, ``collectives`` {'opcode under phase block': ms}, ``busy`` and
    the three sums
    of xplane.reduce the rule is checked against."""
    texts = (events or {}).get('text', {})
    chips, steps = trace['per_chip'], trace['steps']
    scale = 1e3 / len(chips) / steps
    out = {key: collections.Counter()
           for key in ('phase', 'kernels', 'mixed', 'unscoped',
                       'collectives')}
    out['blocks'] = collections.defaultdict(collections.Counter)
    out['bwd_with_fwd'] = 0.0
    what = {}

    def classify(name):
        """(phase or MIXED/UNSCOPED/COLLECTIVE, kernel, block, label,
        whether a backward op holds forward names too)"""
        if name == xplane.OTHER_PROGRAMS:
            return UNSCOPED, None, '', name, False
        seen = hlo.describe(texts[name]) if name in texts else None
        kind = program.category(name, seen)
        label = program.label(name, seen)
        known = name in names and same_instruction(seen, program.get(name))
        named = names[name] if known else Named('', frozenset())
        own = named.op_name or min(named.inside, default='')
        if kind == 'collective':
            # XLA:TPU runs ZeRO-1's reduce-scatter as a kCustom fusion
            # that calls all-reduce-scatter.N
            op = seen or program.get(name)
            which = _SUFFIX.sub('', op.calls) if op.calls.startswith(
                hlo.COLLECTIVES) else op.opcode
            return COLLECTIVE, None, (phase_of(own) or 'no scope',
                                      block_of(own), which), label, False
        kernel = (kernel_of(name, own) or 'unnamed') \
            if kind == 'mosaic' else None
        found = phases_of(named)
        phase = phase_among(found)
        if phase == MIXED:
            return MIXED, kernel, '', '+'.join(
                p for p in PHASES if p in found) + ': ' + label, False
        if phase == UNSCOPED:
            why = 'no phase scope' if known else 'not in ' + HLO_FILE
            return UNSCOPED, kernel, '', why + ': ' + label, False
        return phase, kernel, block_of(own) or '(no block)', label, \
            phase == 'bwd' and 'fwd' in found

    for chip in chips:
        for name, seconds in chip['per_op'].items():
            if name not in what:
                what[name] = classify(name)
            phase, kernel, block, label, recomputes = what[name]
            ms = seconds * scale
            out['phase'][phase] += ms
            if kernel:
                out['kernels'][kernel] += ms
            if phase == COLLECTIVE:
                out['collectives'][block] += ms
            elif phase == MIXED:
                out['mixed'][label] += ms
            elif phase == UNSCOPED:
                out['unscoped'][label] += ms
            else:
                out['blocks'][block][phase] += ms
                if recomputes:
                    out['bwd_with_fwd'] += ms
    shown = collapse(list(out['blocks'])
                     + [path for _phase, path, _which in out['collectives']])
    merged = collections.defaultdict(collections.Counter)
    for path, by_phase in out['blocks'].items():
        merged[shown[path]].update(by_phase)
    out['blocks'] = merged
    together = collections.Counter()
    for (phase, path, which), ms in out['collectives'].items():
        together[f"{which} under {phase} {shown[path]}".rstrip()] += ms
    out['collectives'] = together
    out['busy'] = 1e3 * trace['busy_s'] / steps
    out['reduced'] = {key: 1e3 * trace[key + '_s'] / steps
                      for key in ('xla', 'mosaic', 'collective')}
    return out


def same_instruction(seen, known):
    """Whether the instruction that ran (the event's own text, or None
    where the trace kept none) is the one the HLO text has under that
    name. compiled_program() compiles the step a second time, and on
    four chips that compile numbers some instructions differently (PR
    24): the name is there and means another op."""
    return seen is None or known is None or (
        seen.opcode, seen.kind, seen.target, seen.shape) == (
        known.opcode, known.kind, known.target, known.shape)


def split(run):
    """``account`` of this run, made once and kept on ``run``; None where
    the trace reduced to nothing. Never raises: a reader must not."""
    if getattr(run, 'trace', None) is None:
        return None
    if not hasattr(run, '_scope_split'):
        try:
            run._scope_split = account(run.trace, run.events, run.program,
                                       names_of(run))
        except Exception:   # the harness calls read() bare: a reader that
            # raised would fail the traced run. Say what broke, return None
            run._scope_split = None
            print(f"[chipbench] scopes.split failed:\n"
                  f"{traceback.format_exc()}", flush=True)
    return run._scope_split


def phase_ms(run, phase):
    """One phase's ms a step, or None where there is no split or the
    program carries no phase scope at all (the parent of PR 26)."""
    found = split(run)
    if found is None or not any(found['phase'][p] for p in PHASES + (MIXED,)):
        return None
    return found['phase'][phase]


def kernel_ms(run, kernel):
    """One named kernel's ms a step; None where no call carries its
    name."""
    found = split(run)
    return found['kernels'][kernel] or None if found else None


# ---------------------------------------------------------------------------
# what no metric holds: the tables a traced run prints
# ---------------------------------------------------------------------------

TOP_BLOCKS = 20
ALWAYS_SHOWN = (LOSS, 'mlm_', 'nsp', 'lm_head', 'pooler')


def report(run):
    """The sum rules, checked, and the tables of PERF.md section 5."""
    found = split(run)
    if found is None:
        return 'no reduced trace'
    if phase_ms(run, UNSCOPED) is None and not any(
            found['kernels'][k] for k in KERNELS):
        return ('no phase scope and no kernel name among the executed '
                'ops: a program from before PR 26, or no '
                + HLO_FILE + ' of this step at ' + text_path(run))
    phase, reduced = found['phase'], found['reduced']
    lines = []
    left = sum(phase.values())
    right = sum(reduced.values())
    lines.append(
        'sum rule: ' + ' + '.join(f"{k} {phase[k]:.3f}" for k in PHASES + (
            MIXED, UNSCOPED, COLLECTIVE)) + f" = {left:.3f} ms a step; "
        f"xla {reduced['xla']:.3f} + mosaic {reduced['mosaic']:.3f} + "
        f"collective {reduced['collective']:.3f} = {right:.3f}; "
        + ('holds' if abs(left - right) <= SUM_RULE_MS else
           f'BROKEN by {left - right:+.3f} ms (collectives in flight '
           f'beside the stream, or ops that overlap)'))
    kernels = found['kernels']
    named = sum(kernels[k] for k in KERNELS)
    lines.append(
        'kernels: ' + ', '.join(f"{k} {v:.3f}" for k, v in
                                kernels.most_common()) +
        f"; named {named:.3f} against mosaic {reduced['mosaic']:.3f}: "
        + ('holds' if abs(named - reduced['mosaic']) <= SUM_RULE_MS
           else 'BROKEN'))
    lines.append(
        f"of bwd, {found['bwd_with_fwd']:.3f} ms a step in fusions that "
        f"also hold forward names (they compute pieces of the forward "
        f"again)")
    blocks = found['blocks']
    order = sorted(blocks, key=lambda b: -sum(blocks[b].values()))
    shown = order[:TOP_BLOCKS] + [b for b in order[TOP_BLOCKS:]
                           if any(a in b for a in ALWAYS_SHOWN)]
    lines.append('blocks (ms a step: fwd / bwd / optimizer):')
    lines += [f"  {b:<72} {blocks[b]['fwd']:8.3f} {blocks[b]['bwd']:8.3f} "
              f"{blocks[b]['optimizer']:8.3f}" for b in shown]
    for key, title in (('mixed', 'mixed'), ('unscoped', 'unscoped')):
        rows = found[key].most_common(12)
        lines.append(f"{title}: " + ('; '.join(
            f"{label} {ms:.3f}" for label, ms in rows) or 'none'))
    if found['collectives']:
        lines.append('collectives by scope: ' + '; '.join(
            f"{scope} {ms:.3f}"
            for scope, ms in found['collectives'].most_common(12)))
    return '\n'.join(lines)
