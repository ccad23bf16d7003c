"""Everything the benchmark takes from the program under test, in one
place: the train step a user builds, the arrays behind its NDArrays, and
the program's own compile ledger and counters. The yardstick (traffic,
FLOP counts, peaks, the trace reduction, the references, ``correct``) is
the benchmark's and lives beside this file.
"""
import jax.numpy as jnp

STEP_SITE = 'step:train_step'


def payload(ndarray):
    """The jax array an mxnet_tpu NDArray holds, left on its device."""
    return ndarray._data


def weights_of(model):
    """The model's parameters as float32 device arrays, keyed by their
    names less the model's own auto-numbered prefix."""
    cut = len(model.prefix)
    return {name[cut:]: payload(p.data()).astype(jnp.float32)
            for name, p in model.collect_params().items()}


def seed(value):
    import mxnet_tpu as mx
    mx.random.seed(int(value))


def start_telemetry():
    """Persistent compile cache at the program's fixed path (or where
    JAX_COMPILATION_CACHE_DIR says) and the compile ledger on. Returns the
    cache directory."""
    from mxnet_tpu.telemetry import compile as _compile
    cache = _compile.use_default_cache()
    _compile.enable()
    return cache


def make_step(model, loss_fn, config, traffic, devices):
    """The ShardedTrainStep of a cell: the model, the mesh, the optimizer,
    and nothing else -- every route is the program's default."""
    from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
    axes = tuple(traffic['mesh'])
    mesh = make_mesh(tuple(traffic['mesh'][a] for a in axes), axes,
                     devices=devices)
    policy = config['policy']
    zero = None if traffic['zero'] == 'default' else traffic['zero']
    return ShardedTrainStep(model, loss_fn, policy['optimizer'],
                            dict(policy['optimizer_params']), mesh=mesh,
                            zero=zero)


def route_counts():
    from mxnet_tpu.ops import attention
    return dict(attention.route_counts)


def compile_ledger():
    from mxnet_tpu.telemetry import compile as _compile
    return _compile.ledger()


def cache_stats():
    from mxnet_tpu.telemetry import compile as _compile
    return _compile.persistent_cache_stats()
