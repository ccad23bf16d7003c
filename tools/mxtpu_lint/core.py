"""Shared lint machinery: one parse of the tree, all rules over it.

``FileIndex`` walks a package directory, parses every ``.py`` once and
exposes the shared per-file artifacts every rule needs (AST, source
lines, suppression comments, import map) plus the cross-file function
table and best-effort call graph the reachability rules (host-sync,
lock-order, signal-safety) are built on.

The call graph is intentionally static and conservative: names are
resolved lexically (same module first, then explicit imports, then a
unique-across-the-tree fallback), nested ``def``s get an implicit
edge from their enclosing function (a factory "calls" its closure),
and anything unresolvable simply contributes no edge. A linter that
sometimes misses an edge is useful; one that guesses edges is noise.
"""
from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

SUPPRESS_RE = re.compile(
    r'#\s*lint:\s*([a-z][a-z0-9-]*)-ok\b:?[ \t]*(.*?)\s*$')


class Finding:
    """One rule violation at one source location.

    The fingerprint (rule + file + enclosing symbol + message) is what
    the baseline and suppression machinery key on — it survives
    unrelated edits moving the line, which a line-keyed baseline would
    churn on.
    """

    def __init__(self, rule: str, file: 'SourceFile', line: int,
                 message: str, symbol: str = '', severity: str = 'error',
                 data: Optional[dict] = None):
        self.rule = rule
        self.file = file
        self.relpath = file.relpath if file is not None else '<project>'
        self.line = int(line)
        self.message = message
        self.symbol = symbol
        self.severity = severity         # 'error' fails CI; 'warning' reports
        self.data = data or {}           # structured extras (--format json):
        #                                  thread roots, lock keys, ...

    @property
    def fingerprint(self) -> str:
        raw = '\0'.join((self.rule, self.relpath, self.symbol,
                         self.message))
        return hashlib.sha256(raw.encode()).hexdigest()[:16]

    def format(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ''
        sev = '' if self.severity == 'error' else f' {self.severity}:'
        return (f"{self.relpath}:{self.line}: [{self.rule}]{sev}{sym} "
                f"{self.message}")

    def to_json(self) -> dict:
        """Machine-readable form (--format json / the result cache)."""
        out = {'rule': self.rule, 'severity': self.severity,
               'path': self.relpath, 'line': self.line,
               'symbol': self.symbol, 'message': self.message,
               'fingerprint': self.fingerprint}
        if self.data:
            out['data'] = self.data
        return out

    @classmethod
    def from_json(cls, doc: dict, index: 'FileIndex') -> 'Finding':
        """Rebind a cached finding onto the live index (replay path)."""
        return cls(doc['rule'], index.file(doc['path']), doc['line'],
                   doc['message'], symbol=doc.get('symbol', ''),
                   severity=doc.get('severity', 'error'),
                   data=doc.get('data'))

    def __repr__(self):
        return f"Finding({self.format()!r})"


class FuncInfo:
    """One function/method definition in the tree."""

    __slots__ = ('file', 'node', 'name', 'qualname', 'cls', 'parent',
                 'nested', '_body_nodes')

    def __init__(self, file, node, qualname, cls=None, parent=None):
        self.file = file
        self.node = node
        self.name = node.name
        self.qualname = qualname
        self.cls = cls                   # enclosing class name or None
        self.parent = parent             # enclosing FuncInfo or None
        self.nested: List['FuncInfo'] = []
        self._body_nodes = None          # walk_function cache

    @property
    def key(self) -> Tuple[str, str]:
        return (self.file.relpath, self.qualname)

    def __repr__(self):
        return f"FuncInfo({self.file.relpath}::{self.qualname})"


class SourceFile:
    """One parsed source file + the per-line artifacts rules share."""

    def __init__(self, path: str, relpath: str, text: str):
        self.path = path
        self.relpath = relpath
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=path)
        self._nodes = None               # cached ast.walk list
        self.suppressions = self._parse_suppressions()
        self.imports = self._parse_imports()

    def walk(self) -> List[ast.AST]:
        """Every node of this file's tree, cached: each rule used to
        re-run ``ast.walk`` over every file, which dominated the lint
        wall time once the whole-program rules multiplied the passes."""
        if self._nodes is None:
            self._nodes = list(ast.walk(self.tree))
        return self._nodes

    # -- suppression comments ---------------------------------------------
    #
    # Grammar: ``# lint: <rule>-ok <reason>`` (an optional ``:`` after
    # ``-ok`` is accepted). The comment silences findings of <rule> on
    # its own line; a comment-only line additionally silences the next
    # line (for sites too long to share a line with their reason). A
    # suppression WITHOUT a reason does not count — the why is the
    # point of writing one.

    def _parse_suppressions(self) -> Dict[int, Dict[str, str]]:
        out: Dict[int, Dict[str, str]] = {}
        # every suppression COMMENT (one per written marker, keyed by
        # the comment's own line) — the stale-suppression audit walks
        # these; `suppressions` above maps COVERED lines, so a
        # comment-only marker appears there twice
        self.suppression_comments: List[Tuple[int, str, str]] = []
        for i, line in enumerate(self.lines, start=1):
            m = SUPPRESS_RE.search(line)
            if not m:
                continue
            rule, reason = m.group(1), m.group(2).strip()
            if not reason:
                continue                  # reasonless: not a suppression
            self.suppression_comments.append((i, rule, reason))
            out.setdefault(i, {})[rule] = (reason, i)
            if line.lstrip().startswith('#'):
                out.setdefault(i + 1, {})[rule] = (reason, i)
        return out

    def suppressed(self, rule: str, line: int) -> Optional[str]:
        """The suppression reason covering (rule, line), or None."""
        got = self.suppression_at(rule, line)
        return got[0] if got else None

    def suppression_at(self, rule: str, line: int
                       ) -> Optional[Tuple[str, int]]:
        """(reason, comment line) covering (rule, line), or None —
        the comment line is what the stale-suppression audit keys on."""
        ent = self.suppressions.get(line)
        if ent and rule in ent:
            return ent[rule]
        return None

    # -- import map --------------------------------------------------------
    #
    # local name -> dotted path. ``import numpy as np`` maps np ->
    # 'numpy'; ``from jax import random`` maps random -> 'jax.random';
    # ``from . import config as _config`` resolves the relative level
    # against this file's package so the call graph can find the
    # target module's file.

    def _parse_imports(self) -> Dict[str, str]:
        pkg_parts = self.relpath.split('/')[:-1]   # e.g. mxnet_tpu/parallel
        out: Dict[str, str] = {}
        self.star_imports: List[str] = []
        for node in self.walk():
            if isinstance(node, ast.Import):
                for a in node.names:
                    out[a.asname or a.name.split('.')[0]] = a.name
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                    mod = '.'.join(base + ([node.module]
                                           if node.module else []))
                else:
                    mod = node.module or ''
                for a in node.names:
                    if a.name == '*':
                        if mod:
                            self.star_imports.append(mod)
                        continue
                    out[a.asname or a.name] = (mod + '.' + a.name
                                               if mod else a.name)
        return out


# method names every stdlib file / socket / container / thread object
# answers to — excluded from the unique-method call-graph fallback (a
# call through an opaque receiver must not resolve to the one
# user-defined method sharing such a generic name)
_UBIQUITOUS_METHODS = frozenset({
    'read', 'write', 'readline', 'readlines', 'tell', 'seek', 'flush',
    'open', 'close', 'send', 'sendall', 'recv', 'accept', 'connect',
    'get', 'put', 'pop', 'append', 'extend', 'add', 'remove', 'clear',
    'update', 'copy', 'keys', 'values', 'items', 'join', 'split',
    'strip', 'lower', 'encode', 'decode', 'format', 'count', 'index',
    'sort', 'reverse', 'setdefault', 'acquire', 'release', 'wait',
    'notify', 'set', 'start', 'cancel', 'fileno', 'settimeout', 'bind',
    'listen', 'run', 'next',
})


class FileIndex:
    """Every parsed file under one package root, plus the shared
    function table and call graph."""

    def __init__(self, pkg_dir: str, root: Optional[str] = None):
        self.pkg_dir = os.path.abspath(pkg_dir)
        # relpaths are rooted at the package's parent so they read
        # naturally in reports: mxnet_tpu/parallel/step.py
        self.root = os.path.abspath(root or os.path.dirname(self.pkg_dir))
        self.package = os.path.basename(self.pkg_dir)
        self.files: List[SourceFile] = []
        self.errors: List[Tuple[str, str]] = []       # (path, parse error)
        self._by_relpath: Dict[str, SourceFile] = {}
        self._load()
        self.functions: Dict[Tuple[str, str], FuncInfo] = {}
        self._methods_by_name: Dict[str, List[FuncInfo]] = {}
        self._classes: Dict[Tuple[str, str], ast.ClassDef] = {}
        self._build_function_table()
        self._edges: Optional[Dict[Tuple[str, str],
                                   Set[Tuple[str, str]]]] = None

    # -- loading -----------------------------------------------------------

    def _load(self):
        self.file_stats: List[Tuple[str, int, int]] = []
        for dirpath, dirnames, filenames in os.walk(self.pkg_dir):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != '__pycache__')
            for fname in sorted(filenames):
                if not fname.endswith('.py'):
                    continue
                path = os.path.join(dirpath, fname)
                relpath = os.path.relpath(path, self.root).replace(
                    os.sep, '/')
                try:
                    st = os.stat(path)
                    with open(path, encoding='utf-8') as f:
                        text = f.read()
                    sf = SourceFile(path, relpath, text)
                except (SyntaxError, UnicodeDecodeError, OSError) as e:
                    self.errors.append((path, str(e)))
                    continue
                # (relpath, mtime_ns, size): the incremental cache's
                # change-detection vector
                self.file_stats.append(
                    (relpath, st.st_mtime_ns, st.st_size))
                self.files.append(sf)
                self._by_relpath[relpath] = sf

    def file(self, relpath: str) -> Optional[SourceFile]:
        return self._by_relpath.get(relpath)

    def files_matching(self, suffix: str) -> List[SourceFile]:
        return [f for f in self.files if f.relpath.endswith(suffix)]

    def module_file(self, dotted: str) -> Optional[SourceFile]:
        """SourceFile for a dotted module path (package-rooted)."""
        parts = dotted.split('.')
        if parts and parts[0] == self.package:
            parts = parts[1:]
        if not parts:
            rel = f'{self.package}/__init__.py'
        else:
            rel = f"{self.package}/{'/'.join(parts)}.py"
            if rel not in self._by_relpath:
                rel = f"{self.package}/{'/'.join(parts)}/__init__.py"
        return self._by_relpath.get(rel)

    # -- function table ----------------------------------------------------

    def _build_function_table(self):
        for sf in self.files:
            self._index_scope(sf, sf.tree.body, qual='', cls=None,
                              parent=None)

    def _index_scope(self, sf, body, qual, cls, parent):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f'{qual}{node.name}'
                fi = FuncInfo(sf, node, qn, cls=cls, parent=parent)
                self.functions[fi.key] = fi
                self._methods_by_name.setdefault(node.name, []).append(fi)
                if parent is not None:
                    parent.nested.append(fi)
                self._index_scope(sf, node.body,
                                  qual=f'{qn}.<locals>.', cls=cls,
                                  parent=fi)
            elif isinstance(node, ast.ClassDef):
                self._classes[(sf.relpath, node.name)] = node
                self._index_scope(sf, node.body,
                                  qual=f'{qual}{node.name}.',
                                  cls=node.name, parent=parent)
            elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For,
                                   ast.While)):
                # defs under conditional blocks (TYPE_CHECKING guards,
                # import fallbacks) index at the enclosing scope
                self._index_block(sf, node, qual, cls, parent)

    def _index_block(self, sf, node, qual, cls, parent):
        """Defs nested under if/try/with/loop blocks."""
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                self._index_scope(sf, [child], qual, cls, parent)
            elif isinstance(child, (ast.If, ast.Try, ast.With, ast.For,
                                    ast.While)):
                self._index_block(sf, child, qual, cls, parent)

    def function(self, relpath: str, qualname: str) -> Optional[FuncInfo]:
        return self.functions.get((relpath, qualname))

    def methods_named(self, name: str) -> List[FuncInfo]:
        return self._methods_by_name.get(name, [])

    def class_def(self, relpath, name) -> Optional[ast.ClassDef]:
        return self._classes.get((relpath, name))

    # -- call graph --------------------------------------------------------

    def enclosing_function(self, sf: SourceFile,
                           node: ast.AST) -> Optional[FuncInfo]:
        """Innermost FuncInfo whose body lexically contains `node`."""
        best = None
        for fi in self.functions.values():
            if fi.file is not sf:
                continue
            n = fi.node
            end = getattr(n, 'end_lineno', n.lineno)
            if n.lineno <= node.lineno <= end:
                if best is None or n.lineno > best.node.lineno:
                    best = fi
        return best

    def resolve_call(self, sf: SourceFile, cls: Optional[str],
                     func_expr: ast.AST) -> List[FuncInfo]:
        """Best-effort targets of one call expression (possibly [])."""
        if isinstance(func_expr, ast.Name):
            name = func_expr.id
            fi = self.functions.get((sf.relpath, name))
            if fi is not None:
                return [fi]
            cd = self._classes.get((sf.relpath, name))
            if cd is not None:
                init = self.functions.get((sf.relpath, f'{name}.__init__'))
                return [init] if init else []
            dotted = sf.imports.get(name)
            if dotted:
                return self._resolve_dotted(dotted)
            return []
        if isinstance(func_expr, ast.Attribute):
            attr = func_expr.attr
            val = func_expr.value
            if isinstance(val, ast.Name):
                if val.id == 'self' and cls:
                    fi = self.functions.get((sf.relpath, f'{cls}.{attr}'))
                    if fi is not None:
                        return [fi]
                    # same-file base classes
                    hits = [m for m in self.methods_named(attr)
                            if m.file is sf and m.cls]
                    if len(hits) == 1:
                        return hits
                    return []
                if val.id == 'cls' and cls:
                    fi = self.functions.get((sf.relpath, f'{cls}.{attr}'))
                    return [fi] if fi else []
                dotted = sf.imports.get(val.id)
                if dotted:
                    return self._resolve_dotted(f'{dotted}.{attr}')
            # unknown receiver: accept a METHOD name defined exactly
            # once in the whole tree (unique is unambiguous; anything
            # else would be guessing). Module-level functions are
            # excluded — `client.shutdown()` on an opaque receiver must
            # not resolve to a free function that happens to share the
            # name (module functions are reached via their import
            # binding, which the Name branch above already handles) —
            # and so are names every stdlib file/socket/container
            # answers to: `f.tell()` on a file handle must not grow an
            # edge to MXRecordIO.tell just because that is the one
            # user-defined `tell` in the tree
            if attr not in _UBIQUITOUS_METHODS:
                hits = [m for m in self.methods_named(attr) if m.cls]
                if len(hits) == 1:
                    return hits
        return []

    def _resolve_dotted(self, dotted: str,
                        _depth: int = 0) -> List[FuncInfo]:
        mod = self.module_file(dotted)
        if mod is not None:                      # the module itself
            return []
        if '.' not in dotted:
            return []
        mod_path, attr = dotted.rsplit('.', 1)
        mod = self.module_file(mod_path)
        if mod is None:
            return []
        fi = self.functions.get((mod.relpath, attr))
        if fi is not None:
            return [fi]
        cd = self._classes.get((mod.relpath, attr))
        if cd is not None:
            init = self.functions.get((mod.relpath, f'{attr}.__init__'))
            return [init] if init else []
        if _depth < 2:
            # re-exports: `from .metrics import observe` / `from
            # .metrics import *` in a package __init__ forward the
            # name one module over
            fwd = mod.imports.get(attr)
            if fwd:
                return self._resolve_dotted(fwd, _depth + 1)
            for star in getattr(mod, 'star_imports', ()):
                got = self._resolve_dotted(f'{star}.{attr}', _depth + 1)
                if got:
                    return got
        return []

    def call_edges(self) -> Dict[Tuple[str, str], Set[Tuple[str, str]]]:
        """function key -> set of callee keys (cached)."""
        if self._edges is not None:
            return self._edges
        edges: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}
        for fi in self.functions.values():
            out = edges.setdefault(fi.key, set())
            for nested in fi.nested:
                out.add(nested.key)      # a factory "calls" its closure
            for node in self.walk_function(fi):
                if isinstance(node, ast.Call):
                    for target in self.resolve_call(fi.file, fi.cls,
                                                    node.func):
                        out.add(target.key)
                elif isinstance(node, (ast.With, ast.AsyncWith)):
                    # `with X():` implicitly calls __enter__/__exit__
                    for item in node.items:
                        ce = item.context_expr
                        if isinstance(ce, ast.Call):
                            for ee in self._with_protocol_targets(
                                    fi.file, fi.cls, ce):
                                out.add(ee.key)
        self._edges = edges
        return edges

    def _with_protocol_targets(self, sf, cls, call) -> List[FuncInfo]:
        """__enter__/__exit__ reached by ``with <call>:`` — the call
        may be a class constructor, or a factory function whose return
        statements construct the context-manager class (trace.span
        returning _Span)."""
        out = []
        for target in self.resolve_call(sf, cls, call.func):
            inits = [target] if target.name == '__init__' else []
            if not inits:
                for node in self.walk_function(target):
                    if isinstance(node, ast.Return) and \
                            isinstance(node.value, ast.Call):
                        inits += [t for t in self.resolve_call(
                            target.file, target.cls, node.value.func)
                            if t.name == '__init__']
            for init in inits:
                cq = init.qualname.rsplit('.', 1)[0]
                for proto in ('__enter__', '__exit__'):
                    fi = self.functions.get(
                        (init.file.relpath, f'{cq}.{proto}'))
                    if fi is not None:
                        out.append(fi)
        return out

    def walk_function(self, fi: FuncInfo) -> List[ast.AST]:
        """Nodes of a function body EXCLUDING nested function bodies
        (those belong to their own FuncInfo). Cached per function —
        every reachability rule re-walks the same bodies."""
        if fi._body_nodes is not None:
            return fi._body_nodes
        nested_nodes = {id(n.node) for n in fi.nested}
        out = []
        stack = list(ast.iter_child_nodes(fi.node))
        while stack:
            node = stack.pop()
            if id(node) in nested_nodes:
                continue
            out.append(node)
            stack.extend(ast.iter_child_nodes(node))
        fi._body_nodes = out
        return out

    def reachable(self, roots: Iterable[Tuple[str, str]],
                  max_depth: Optional[int] = None
                  ) -> Dict[Tuple[str, str], Tuple[str, str]]:
        """BFS over the call graph. Returns {reached key: root key}."""
        edges = self.call_edges()
        seen: Dict[Tuple[str, str], Tuple[str, str]] = {}
        frontier = [(r, r, 0) for r in roots if r in self.functions]
        for key, root, _d in frontier:
            seen.setdefault(key, root)
        while frontier:
            key, root, depth = frontier.pop()
            if max_depth is not None and depth >= max_depth:
                continue
            for callee in edges.get(key, ()):
                if callee not in seen:
                    seen[callee] = root
                    frontier.append((callee, root, depth + 1))
        return seen


class LintRule:
    """Base class. Subclasses set ``id``/``doc`` (and optionally
    ``severity``) and implement ``run(index) -> [Finding]`` (raw
    findings; suppression and baseline filtering happen in
    ``run_rules``)."""

    id = 'abstract'
    doc = ''
    severity = 'error'       # 'error' fails CI; 'warning' only reports

    def run(self, index: FileIndex) -> List[Finding]:
        raise NotImplementedError

    def finding(self, file, line, message, symbol='',
                severity=None, data=None) -> Finding:
        return Finding(self.id, file, line, message, symbol=symbol,
                       severity=severity or self.severity, data=data)


class Baseline:
    """Grandfathered findings: fingerprint -> entry with a reason.

    New violations (not in the baseline) fail; baselined ones are
    reported as such; baseline entries no longer produced are flagged
    stale so the file gets burned down, not hoarded.
    """

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 path: Optional[str] = None):
        self.entries = dict(entries or {})
        self.path = path

    @classmethod
    def load(cls, path: str) -> 'Baseline':
        if not os.path.exists(path):
            return cls({}, path=path)
        with open(path, encoding='utf-8') as f:
            doc = json.load(f)
        return cls(doc.get('findings', {}), path=path)

    def write(self, path: Optional[str] = None):
        path = path or self.path
        doc = {'version': 1,
               'comment': 'grandfathered mxtpu_lint findings; every '
                          'entry needs a reason. Regenerate: python -m '
                          'tools.mxtpu_lint --write-baseline',
               'findings': dict(sorted(self.entries.items()))}
        with open(path, 'w', encoding='utf-8') as f:
            json.dump(doc, f, indent=2, sort_keys=False)
            f.write('\n')

    def covers(self, finding: Finding) -> bool:
        return finding.fingerprint in self.entries

    def add(self, finding: Finding, reason: str):
        self.entries[finding.fingerprint] = {
            'rule': finding.rule, 'path': finding.relpath,
            'line': finding.line, 'message': finding.message,
            'reason': reason}


class LintResult:
    def __init__(self, new, suppressed, baselined, stale,
                 stale_suppressions=None, raw=None):
        self.new = new                   # [Finding] — these fail CI
        self.suppressed = suppressed     # [(Finding, reason)]
        self.baselined = baselined       # [Finding]
        self.stale = stale               # [fingerprint] unused entries
        # [(relpath, comment line, rule, reason)] — suppression comments
        # whose line no longer triggers their rule (--stale-suppressions)
        self.stale_suppressions = stale_suppressions or []
        self.raw = raw or {}             # {rule id: [Finding]} pre-filter

    @property
    def errors(self):
        return [f for f in self.new if f.severity == 'error']

    @property
    def clean(self) -> bool:
        return not self.errors


def run_rules(index: FileIndex, rules,
              baseline: Optional[Baseline] = None,
              raw: Optional[Dict[str, List[Finding]]] = None
              ) -> LintResult:
    """Run (or, given ``raw`` — the incremental cache's replay path —
    re-filter) the rules. Suppression and baseline filtering always
    happen live so a baseline/comment edit never needs a cold run."""
    baseline = baseline or Baseline()
    new, suppressed, baselined = [], [], []
    seen_fps = set()
    used_comments = set()       # (relpath, comment line, rule)
    raw_out: Dict[str, List[Finding]] = {}
    for rule in rules:
        produced = raw[rule.id] if raw is not None and rule.id in raw \
            else rule.run(index)
        raw_out[rule.id] = produced
        for f in produced:
            ent = (f.file.suppression_at(rule.id, f.line)
                   if f.file is not None else None)
            if ent is not None:
                reason, comment_line = ent
                used_comments.add((f.relpath, comment_line, rule.id))
                suppressed.append((f, reason))
            elif baseline.covers(f):
                baselined.append(f)
                seen_fps.add(f.fingerprint)
            else:
                new.append(f)
    stale = [fp for fp in baseline.entries if fp not in seen_fps]
    # suppression comments for a rule we ran that silenced nothing this
    # run are stale: the code they excused changed (or the rule did) —
    # an unaccountable marker would silently re-arm if the bug returned
    ran_ids = {r.id for r in rules}
    stale_supp = []
    for sf in index.files:
        for line, rule_id, reason in sf.suppression_comments:
            if rule_id in ran_ids and \
                    (sf.relpath, line, rule_id) not in used_comments:
                stale_supp.append((sf.relpath, line, rule_id, reason))
    new.sort(key=lambda f: (f.relpath, f.line, f.rule))
    return LintResult(new, suppressed, baselined, stale,
                      stale_suppressions=sorted(stale_supp), raw=raw_out)


# -- small AST helpers shared by the rules ----------------------------------

def call_name(node: ast.Call) -> str:
    """Dotted textual name of a call target ('' when not name-like)."""
    return dotted_name(node.func)


def dotted_name(expr: ast.AST) -> str:
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return '.'.join(reversed(parts))
    return ''


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def resolves_to_module(sf: SourceFile, expr: ast.AST,
                       module: str) -> bool:
    """Does `expr` (a Name) denote `module` via this file's imports?
    (Handles aliases: ``import time as _time``.)"""
    if not isinstance(expr, ast.Name):
        return False
    return sf.imports.get(expr.id) == module
