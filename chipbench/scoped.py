"""Device time of the executed ops that lie under a plain scope of the
program (``jax.named_scope``) or are one of its named kernels: what the
``moe_*`` and ``attn_*`` readers of ``layer_metrics/`` share. The names
come from ``scopes.names_of(run)``, as PERF.md section 3 describes; an op
counts as under a scope when any instruction it holds was traced there,
forward or backward. Where the program has no such scope or kernel (a
parent commit), or the run no trace, every function returns None.
"""
from chipbench import hlo, scopes


def _names(run):
    if not hasattr(run, '_scoped_names'):
        run._scoped_names = scopes.names_of(run)
    return run._scoped_names


def _under(op_name, wanted):
    return any(scopes._unwrap(part) in wanted for part in op_name.split('/'))


def ms_per_step(run, wanted=None, kernel=None):
    """ms a step, mean over chips, of the ops under one of the scopes
    ``wanted`` (None: anywhere) that are, where ``kernel`` is given, Mosaic
    calls of that name, or of one of those names. None where nothing
    matched."""
    if getattr(run, 'trace', None) is None:
        return None
    names = _names(run)
    texts = (run.events or {}).get('text', {})
    chips = run.trace['per_chip']
    kernels = (kernel,) if isinstance(kernel, str) else kernel
    total = None
    for chip in chips:
        for name, seconds in chip['per_op'].items():
            named = names.get(name)
            if named is None:
                continue
            own = named.inside or {named.op_name}
            if wanted is not None and not any(_under(n, wanted) for n in own):
                continue
            if kernels is not None:
                seen = hlo.describe(texts[name]) \
                    if name in texts else None
                if run.program.category(name, seen) != 'mosaic' or (
                        scopes._SUFFIX.sub('', name) not in kernels
                        and not any(set(kernels) & set(n.split('/'))
                                    for n in own)):
                    continue
            total = (total or 0.0) + seconds
    if total is None:
        return None
    return 1e3 * total / len(chips) / run.trace['steps']


FLASH_KERNELS = ('mxtpu_flash_fwd', 'mxtpu_flash_bwd_dq',
                 'mxtpu_flash_bwd_dkv')


def flash_ms_per_step(run, scope):
    """ms a step of the three flash kernels' calls under ``scope``."""
    return ms_per_step(run, (scope,), kernel=FLASH_KERNELS)


def roofline_share(run, cost, ms):
    """100 * least time / measured time: the larger of ``cost``'s
    operations over the chip's bf16 peak and its bytes over the HBM peak,
    for one chip's share of a step, over ``ms``. None without ``ms``."""
    if not ms:
        return None
    n = run.traffic['per_chip_batch']
    least = max(n * cost['flops'] / run.peaks['bf16_flops_per_s'],
                n * cost['bytes'] / run.peaks['hbm_bytes_per_s'])
    return 100.0 * least * 1e3 / ms
