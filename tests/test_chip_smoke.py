"""chip_smoke.py's sandbox contract, and the one rule for where compiled
programs persist. The on-chip run itself is not a test: it happens
through the chip tool (see .claude/skills/verify/SKILL.md)."""
import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
SMOKE = os.path.join(ROOT, 'chip_smoke.py')


def _run(args, env_extra, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ('JAX_COMPILATION_CACHE_DIR', 'XLA_FLAGS')}
    env.update(JAX_PLATFORMS='cpu', **env_extra)
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_no_flag_on_cpu_exits_nonzero_and_builds_nothing(tmp_path):
    """Without --dry-run-cpu there is no CPU path: the script names the
    platform it found, exits non-zero and never gets as far as a model
    (no loss, no kernel line, no result line)."""
    res = _run([SMOKE], {'JAX_COMPILATION_CACHE_DIR': str(tmp_path)})
    assert res.returncode == 2, (res.stdout, res.stderr[-2000:])
    assert "platform 'cpu'" in res.stderr and 'Nothing was built' in res.stderr
    assert res.stdout.strip() == ''
    assert not os.listdir(tmp_path)              # nothing was compiled


def test_dry_run_passes_and_says_so_on_every_line(tmp_path):
    """The rehearsal: same phases at a tiny size, kernels interpreted,
    the XLA route the CPU takes by design — and DRY RUN on every line
    that could be mistaken for a result. Its cache goes where
    JAX_COMPILATION_CACHE_DIR says, whatever the MXTPU_ knob names."""
    res = _run([SMOKE, '--dry-run-cpu'],
               {'JAX_COMPILATION_CACHE_DIR': str(tmp_path),
                'MXTPU_COMPILE_CACHE_DIR': '/nonexistent/elsewhere'})
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {'ok': True, 'dry_run': True,
                    'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 1}}
    assert all('DRY RUN' in ln for ln in lines[:-1]), lines
    text = res.stdout
    for needle in (f'compile cache at {tmp_path} ',
                   'kernel flash/mask+dropout', 'kernel fused_dense_gelu',
                   'train losses', "'xla': 4", 'no compilation after step 0',
                   'gluon LeNet', 'fused update intact'):
        assert needle in text, needle
    assert os.listdir(tmp_path)       # it cached where the variable said


def test_cache_helper_defaults_to_the_checkout():
    """Without JAX_COMPILATION_CACHE_DIR the one helper points jax at
    <checkout>/.jax_compile_cache (with it: the test above)."""
    prog = ("import jax\n"
            "from mxnet_tpu.telemetry import compile as c\n"
            "assert jax.config.jax_compilation_cache_dir is None\n"
            "c.use_default_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    res = _run(['-c', prog], {})
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.strip() == os.path.join(ROOT, '.jax_compile_cache')
