"""Reads the optimized HLO text of a compiled program
(``compiled.as_text()``) far enough to say what each instruction is: its
opcode, a fusion's kind and whether it holds a matmul, a custom call's
target, the shape it produces. The trace names device ops by instruction
name; this is what turns a name into "the flash kernel", "a collective"
or "an XLA fusion".
"""
import collections
import re

Op = collections.namedtuple('Op', 'name opcode kind target calls shape')

COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
               'collective-permute', 'collective-broadcast')
MOSAIC_TARGET = 'tpu_custom_call'
# instructions that only wrap others the trace also shows, or move nothing
CONTAINERS = ('while', 'conditional', 'call')

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s*(.*)$')
_OPCODE = re.compile(r'(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(')
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([^\s(%]+)\s*\(.*\{\s*$')
_SHAPE = re.compile(r'[a-z]+[0-9]*\[[0-9,]*\]')
_KIND = re.compile(r'\bkind=(k[A-Za-z]+)')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_CALLS = re.compile(r'\b(?:calls|to_apply|body)=%?([^\s,)}]+)')
_MODULE = re.compile(r'^HloModule\s+([^\s,]+)', re.MULTILINE)


def describe(text):
    """The Op an instruction's text describes (a line of the HLO text, or
    the name of a trace event), or None if it is no instruction."""
    m = _INSTRUCTION.match(text)
    if not m:
        return None
    name, rhs = m.groups()
    # the attributes follow the operands; a Mosaic call's run to hundreds
    # of kilobytes of kernel body, all after what is read here
    head = rhs[:4096]
    found = _OPCODE.search(head)
    if not found:
        return None
    shapes = _SHAPE.findall(head[:found.start() + 1])
    if len(shapes) > 2:
        shapes[2:] = [f'+{len(shapes) - 2}']
    kind, target, calls = (r.search(head) for r in (_KIND, _TARGET, _CALLS))
    return Op(name, found.group(1), kind.group(1) if kind else '',
              target.group(1) if target else '',
              calls.group(1) if calls else '',
              shapes[0] if len(shapes) == 1
              else '(' + ', '.join(shapes) + ')')


def parse(text):
    """(ops, computations): every instruction of every computation by
    name, and for each computation the opcodes it holds and the
    computations its instructions call."""
    ops, computations = {}, {}
    inside = None
    for line in text.splitlines():
        if not line.startswith(' '):
            m = _COMPUTATION.match(line)
            inside = computations.setdefault(
                m.group(1), {'opcodes': set(), 'calls': set()}) if m else None
            continue
        op = describe(line) if inside is not None else None
        if op is None:
            continue
        inside['opcodes'].add(op.opcode)
        if op.calls:
            inside['calls'].add(op.calls)
        ops[op.name] = op
    return ops, computations


class Program:
    """The instructions of one compiled program, by the names the trace
    uses. ``module`` is the program's own name (``HloModule <name>``): the
    trace names each of its executions ``<name>(<fingerprint>)``."""

    def __init__(self, text):
        self.ops, self._computations = parse(text)
        self._held = {}
        found = _MODULE.search(text)
        self.module = found.group(1) if found else None

    def get(self, name):
        return self.ops.get(name.lstrip('%'))

    def _holds(self, op):
        """The op's own opcode and, where this text knows the op under the
        same name and opcode, those of the computation it calls, nested
        calls included."""
        if op.name not in self._held:
            known = self.get(op.name)
            found, seen = {op.opcode}, set()
            todo = [known.calls] if known and known.opcode == op.opcode \
                else []
            while todo:
                comp = todo.pop()
                if comp and comp not in seen and comp in self._computations:
                    seen.add(comp)
                    found |= self._computations[comp]['opcodes']
                    todo.extend(self._computations[comp]['calls'])
            self._held[op.name] = found
        return self._held[op.name]

    def category(self, name, seen=None):
        """'mosaic', 'collective', 'container' or 'xla'. ``seen`` is the
        Op the trace event itself describes: what ran decides, and this
        text, which comes from compiling the step a second time and can
        name or fuse things differently, adds only what a fusion holds.
        An op neither knows counts as XLA's."""
        op = seen or self.get(name)
        if op is None:
            return 'xla'
        if op.opcode == 'custom-call' and op.target == MOSAIC_TARGET:
            return 'mosaic'
        if op.opcode in CONTAINERS:
            return 'container'
        # a collective by its own opcode, by what the fusion holds where
        # this text knows it, or by what it says it calls (XLA:TPU fuses
        # ZeRO-1's reduce-scatter as kCustom, calls=%all-reduce-scatter.N)
        if any(c.startswith(COLLECTIVES) for c in self._holds(op)) \
                or op.calls.startswith(COLLECTIVES):
            return 'collective'
        return 'xla'

    def label(self, name, seen=None):
        """What to print beside a trace name: 'fusion kOutput matmul
        bf16[28672,3072]', 'custom-call tpu_custom_call (...)', ..."""
        op = seen or self.get(name)
        if op is None:
            return 'not in the step program'
        holds = self._holds(op)
        if holds & {'convolution', 'dot'}:
            what = 'matmul'
        elif holds & {'scatter', 'gather', 'sort'}:
            what = 'gather/scatter/sort'
        elif holds & {'reduce', 'reduce-window'}:
            what = 'reduce'
        else:
            what = ''
        return ' '.join(p for p in (op.opcode, op.kind, op.target, what,
                                    op.shape) if p)
