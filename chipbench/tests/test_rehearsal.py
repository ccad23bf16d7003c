"""run.py end to end at the toy preset on the CPU backend: every cell,
both kinds of run, counts only. And without --rehearse: exit 2, no result."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest

RUN = os.path.join(manifest.HERE, 'run.py')
CELLS = [(w['name'], w['chips'])
         for w in manifest.load_benchmark()['workloads']]


def run(name, chips, *extra, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS=f'--xla_force_host_platform_device_count={chips}',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    return subprocess.run(
        [sys.executable, RUN, '--workload', name, '--seed', '4',
         '--seconds', '1', '--out', str(tmp_path / 'out'), *extra],
        env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize('trace', [0, 1])
@pytest.mark.parametrize('name,chips', CELLS)
def test_rehearsal(name, chips, trace, tmp_path):
    done = run(name, chips, '--trace', str(trace), '--rehearse',
               tmp_path=tmp_path)
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last['rehearsal'] is True and last['correct'] is True, \
        done.stdout[-3000:]
    assert last['metrics'] == {} and 'breakdown' not in last
    assert last['device']['platform'] == 'cpu'
    assert last['device']['count'] == chips
    assert last['failed'] == 0
    counts = last['counts']
    assert counts['losses_read'] == counts['steps'] > 32
    assert counts['trace_file'] is bool(trace)
    assert 'REHEARSAL' in done.stdout


def test_on_a_cpu_the_command_refuses(tmp_path):
    done = run('bert_base.t128', 1, '--trace', '0', tmp_path=tmp_path)
    assert done.returncode == 2
    assert '{' not in done.stdout
    assert 'needs 1 of platform' in done.stderr


def test_with_other_than_the_cells_chips_it_refuses(tmp_path):
    done = run('bert_base.dp4_t512', 2, '--trace', '0', '--rehearse',
               tmp_path=tmp_path)
    assert done.returncode == 2 and '{' not in done.stdout
