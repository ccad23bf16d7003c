"""Collective-communication surface.

The reference exposes push/pull (ps-lite) and NCCL allreduce; on TPU the
collectives are XLA ops inside compiled programs. This module provides:
- axis-name bookkeeping so layers (SyncBatchNorm) know which mesh axis is
  the data axis while tracing inside shard_map;
- thin wrappers over lax collectives usable in custom shard_map kernels;
- scheduling helpers for the ZeRO-3 per-layer all-gather pipeline
  (``ordered_barrier``, ``group_params_by_layer``): the gathers inside
  the compiled step are chained to EACH OTHER (layer k+1's gather
  depends on layer k's gather, not on layer k's compute), so XLA's
  latency-hiding scheduler can prefetch the next layer's parameters
  while the current layer computes.
"""
from __future__ import annotations

import re
import threading

from jax import lax

_tls = threading.local()


def _stack():
    if not hasattr(_tls, 'axes'):
        _tls.axes = []
    return _tls.axes


class data_axis:
    """Context manager declaring the active data-parallel axis name while
    tracing inside shard_map/pjit."""

    def __init__(self, name='dp'):
        self.name = name

    def __enter__(self):
        _stack().append(self.name)
        return self

    def __exit__(self, *exc):
        _stack().pop()


def current_data_axis():
    s = _stack()
    return s[-1] if s else None


def psum(x, axis_name):
    return lax.psum(x, axis_name)


def pmean(x, axis_name):
    return lax.pmean(x, axis_name)

def pmax(x, axis_name):
    return lax.pmax(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                            tiled=True)


def ppermute(x, axis_name, perm):
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# ZeRO-3 gather scheduling helpers
# ---------------------------------------------------------------------------

def ordered_barrier(*arrays):
    """Identity on ``arrays`` that makes every output depend on every
    input in the compiled schedule (``lax.optimization_barrier``; its
    transpose fences the cotangents the same way).

    ZeRO-3 uses it to chain per-layer all-gathers: feeding layer k+1's
    sharded params through a barrier together with one leaf of layer
    k's GATHERED params makes gather(k+1) wait for gather(k) — but not
    for layer k's matmuls — so the gathers issue one layer ahead of the
    compute that consumes them."""
    return lax.optimization_barrier(tuple(arrays))


def _natural_key(s):
    """Sort key treating digit runs numerically: layer2 < layer10."""
    return tuple(int(t) if t.isdigit() else t
                 for t in re.split(r'(\d+)', s))


_LAYER_RE = re.compile(r'^(.*?(?:layer|block|stage|cell|stack)\d+)')


def group_params_by_layer(names):
    """[(group_key, [param_name, ...]), ...] — parameters bucketed by
    the layer-ish prefix of their name (``...layerN``/``blockN``/... if
    present, else the name minus its final ``_kind`` token), groups and
    members in natural (digit-aware) order. This is the unit of the
    ZeRO-3 all-gather pipeline: one chained gather per group, ordered
    to approximate first-use order in a sequential model."""
    groups = {}
    for n in names:
        m = _LAYER_RE.match(n)
        key = m.group(1) if m else \
            (n.rsplit('_', 1)[0] if '_' in n else n)
        groups.setdefault(key, []).append(n)
    return [(k, sorted(groups[k], key=_natural_key))
            for k in sorted(groups, key=_natural_key)]
