"""Device/context model over jax devices.

Ref: include/mxnet/base.h:102-115 (Context{kCPU,kGPU,kCPUPinned,kCPUShared})
and python/mxnet/context.py. On TPU, "gpu" maps to a TPU chip so that
unmodified reference scripts (`mx.gpu(0)`) run on TPU; `tpu()` is the
first-class native spelling.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError


class Context:
    """A device context. devtype in {'cpu', 'gpu', 'tpu', 'cpu_pinned', 'cpu_shared'}."""

    devtype2id = {'cpu': 1, 'gpu': 2, 'cpu_pinned': 3, 'tpu': 4, 'cpu_shared': 5}
    devid2type = {v: k for k, v in devtype2id.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devtype2id:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = device_id

    @property
    def device_typeid(self) -> int:
        return self.devtype2id[self.device_type]

    def jax_device(self):
        """Resolve this context to a concrete jax device."""
        if self.device_type in ('cpu', 'cpu_pinned', 'cpu_shared'):
            devs = jax.devices('cpu') if _has_platform('cpu') else jax.devices()
        else:
            # 'gpu' and 'tpu' both resolve to the accelerator platform; on a
            # TPU machine mx.gpu(0) runs on TPU so reference scripts work.
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    f"{self}: no accelerator — jax reports only "
                    f"{jax.default_backend()!r} devices; use mx.cpu() to "
                    f"run there")
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: device_id {self.device_id} out of range ({len(devs)} available)")
        return devs[self.device_id]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        if not hasattr(self._default_ctx, 'stack'):
            self._default_ctx.stack = []
        self._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        self._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, 'stack', None)
        if stack:
            return stack[-1]
        return _DEFAULT


def _has_platform(name: str) -> bool:
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerator_devices():
    devs = [d for d in jax.devices() if d.platform not in ('cpu',)]
    return devs


def cpu(device_id: int = 0) -> Context:
    return Context('cpu', device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context('cpu_pinned', device_id)


def gpu(device_id: int = 0) -> Context:
    return Context('gpu', device_id)


def tpu(device_id: int = 0) -> Context:
    return Context('tpu', device_id)


def num_gpus() -> int:
    """Number of accelerator chips visible (ref: python/mxnet/context.py num_gpus)."""
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


def gpu_memory_info(device_id: int = 0):
    devs = _accelerator_devices()
    if device_id >= len(devs):
        raise MXNetError(f"no accelerator device {device_id}")
    stats = devs[device_id].memory_stats() or {}
    total = stats.get('bytes_limit', 0)
    used = stats.get('bytes_in_use', 0)
    return (total - used, total)


def current_context() -> Context:
    return Context.default_ctx()


_DEFAULT = Context('cpu', 0)
