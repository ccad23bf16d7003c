"""BENCHMARK.json and the files its names point to.

Everything that belongs to one configuration, one traffic mix, one model
family or one per-layer metric sits in a file of its own, found by name:

    configs/<config>.json         sizes as run, source, policy, "family"
    traffic/<traffic>.json        lengths, batch, mesh, feed, declared route
    families/<family>.py          model, batch, FLOPs, float32 reference
    layer_metrics/<metric>.py     read(run) -> number or None

No list of names lives in code: a later PR adds a cell by adding files and
entries to BENCHMARK.json, and edits nothing that is here.
"""
import importlib.util
import json
import os
import re
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r'^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$')
LAYER = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return read_json(os.path.join(root, 'BENCHMARK.json'))


def load_module(kind, name, here=HERE):
    """Import ``<kind>/<name>.py`` by path (``families`` or
    ``layer_metrics``); the name comes from data, never from a table."""
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a plain name")
    path = os.path.join(here, kind, name + '.py')
    spec = importlib.util.spec_from_file_location(
        f'chipbench_{kind}_{name}'.replace('.', '_').replace('-', '_'), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(bench, section, workload):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
    all of them, less those whose optional "workloads" names other cells."""
    return [m for m in bench[section]
            if 'workloads' not in m or workload in m['workloads']]


def resolve(workload, root=ROOT, here=HERE):
    """One cell: its BENCHMARK.json entry, configuration, traffic mix,
    family module and the metric entries it reports."""
    bench = load_benchmark(root)
    cells = {w['name']: w for w in bench['workloads']}
    if workload not in cells:
        raise KeyError(f"workload {workload!r} is not in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    entry = cells[workload]
    configs = {c['name']: c for c in bench['configs']}
    config = read_json(os.path.join(root, configs[entry['config']]['file']))
    traffic = read_json(os.path.join(here, 'traffic',
                                     entry['traffic'] + '.json'))
    mesh_chips = 1
    for size in traffic['mesh'].values():
        mesh_chips *= int(size)
    if mesh_chips != entry['chips']:
        raise ValueError(
            f"{workload}: traffic {entry['traffic']!r} lays its mesh "
            f"{traffic['mesh']} over {mesh_chips} chip(s), the cell asks "
            f"for {entry['chips']}")
    return types.SimpleNamespace(
        name=workload, entry=entry, chips=entry['chips'], config=config,
        traffic=traffic, family=load_module('families', config['family'],
                                            here),
        end_to_end=metrics_of(bench, 'end_to_end', workload),
        per_layer=metrics_of(bench, 'per_layer', workload))


def check(bench, root=ROOT, here=HERE):
    """Every fault of a manifest against the contract that can be seen
    without running anything, as a list of sentences (empty: none)."""
    faults = []
    seen = set()

    def name_ok(what, name):
        if not isinstance(name, str) or not NAME.match(name):
            faults.append(f"{what} name {name!r} is not legal")
        if name in seen:
            faults.append(f"name {name!r} is used twice")
        seen.add(name)

    paths = bench['paths']
    for arg in bench['command']:
        if os.path.exists(os.path.join(root, arg)) and not any(
                arg == p or arg.startswith(p + '/') for p in paths):
            faults.append(f"command names {arg!r}, outside paths")
    configs = {}
    files = set()
    for c in bench['configs']:
        name_ok('config', c['name'])
        configs[c['name']] = c
        if len(c['why']) > 200:
            faults.append(f"config {c['name']}: why is over 200 characters")
        path = os.path.join(root, c['file'])
        if not any(c['file'].startswith(p + '/') for p in paths):
            faults.append(f"config file {c['file']!r} is outside paths")
        if c['file'] in files:
            faults.append(f"config file {c['file']!r} serves two configs")
        files.add(c['file'])
        if not os.path.isfile(path):
            faults.append(f"config {c['name']}: no file {c['file']}")
            continue
        doc = read_json(path)
        if doc.get('source') != c['source']:
            faults.append(f"config {c['name']}: source differs from its file")
        if sorted(doc.get('reduced', [])) != sorted(c['reduced']):
            faults.append(f"config {c['name']}: reduced differs from its "
                          f"file")
        family = os.path.join(here, 'families', doc.get('family', '') + '.py')
        if not os.path.isfile(family):
            faults.append(f"config {c['name']}: no family file {family}")
    e2e = {}
    for m in bench['end_to_end']:
        name_ok('end_to_end', m['name'])
        e2e[m['name']] = m
        if m['source'] not in ('host_clock', 'device_trace'):
            faults.append(f"{m['name']}: end-to-end source {m['source']!r}")
        if not 0 < m['bound'] <= 0.1:
            faults.append(f"{m['name']}: bound {m['bound']}")
    if 'setup_s' not in e2e:
        faults.append("no setup_s among end_to_end")
    cells = {}
    pairs = set()
    for w in bench['workloads']:
        name_ok('workload', w['name'])
        cells[w['name']] = w
        if w['config'] not in configs:
            faults.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w['traffic']) or not os.path.isfile(os.path.join(
                here, 'traffic', w['traffic'] + '.json')):
            faults.append(f"cell {w['name']}: no traffic file for "
                          f"{w['traffic']!r}")
        if (w['config'], w['traffic']) in pairs:
            faults.append(f"cell {w['name']}: its pair appears twice")
        pairs.add((w['config'], w['traffic']))
        if w['chips'] not in (1, 4):
            faults.append(f"cell {w['name']}: chips {w['chips']}")
        if len(w['why']) > 200:
            faults.append(f"cell {w['name']}: why is over 200 characters")
    if sum(w['chips'] == 4 for w in cells.values()) > max(
            1, len(cells) // 4):
        faults.append("more than a quarter of the cells ask for 4 chips")
    for c in configs:
        if not any(w['config'] == c for w in cells.values()):
            faults.append(f"config {c} is used by no cell")
    for m in bench['per_layer']:
        name_ok('per_layer', m['name'])
        if m['source'] not in SOURCES:
            faults.append(f"{m['name']}: source {m['source']!r}")
        if not isinstance(m['layer'], str) or not LAYER.match(m['layer']):
            faults.append(f"{m['name']}: layer {m['layer']!r} is not a "
                          f"plain name")
        if m['moves'] not in e2e:
            faults.append(f"{m['name']}: moves {m['moves']!r}, which is no "
                          f"end-to-end metric")
        if not os.path.isfile(os.path.join(here, 'layer_metrics',
                                           m['name'] + '.py')):
            faults.append(f"{m['name']}: no reader layer_metrics/"
                          f"{m['name']}.py")
        if m['name'].endswith('_roofline') and m['unit'] != '%':
            faults.append(f"{m['name']}: a roofline share has the unit %")
    for m in bench['end_to_end'] + bench['per_layer']:
        for w in m.get('workloads', ()):
            if w not in cells:
                faults.append(f"{m['name']}: lists unknown cell {w!r}")
    for m in bench['per_layer']:
        moved = e2e.get(m['moves'], {})
        for w in m.get('workloads', cells):
            if 'workloads' in moved and w not in moved['workloads']:
                faults.append(f"{m['name']}: reported in {w}, where "
                              f"{m['moves']} is not")
    for w in cells:
        if len(metrics_of(bench, 'end_to_end', w)) < 2:
            faults.append(f"cell {w}: reports no end-to-end metric besides "
                          f"setup_s")
        if not metrics_of(bench, 'per_layer', w):
            faults.append(f"cell {w}: reports no per-layer metric")
    return faults
