"""Layer: compile_cache. Programs the persistent cache did not hold
in this run (jax's own cache-miss events, counted by telemetry/compile.py):
every program on a cell's first run in a checkout, 0 afterwards."""


def read(run):
    return run.cache['misses']
