"""mx.rtc — runtime-compiled user kernels (ref: python/mxnet/rtc.py).

The reference compiles user CUDA C source with NVRTC (`CudaModule`/
`CudaKernel`, ref: src/common/rtc.cc). The TPU equivalent is a user-written
Pallas kernel compiled by Mosaic: `pallas_op` wraps a Pallas kernel function
into an eager framework op over NDArrays, with the same "bring your own
kernel" role. Kernel mode follows ops.pallas_attention.default_interpret
(the interpreter on the CPU backend, Mosaic elsewhere) unless the caller
passes ``interpret=``.

Example:
    def scale_add(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + y_ref[...]
    op = mx.rtc.pallas_op(scale_add, out_like=0)
    z = op(x, y)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray.ndarray import NDArray, _wrap
from .ops.pallas_attention import default_interpret

__all__ = ['pallas_op', 'PallasKernel', 'CudaModule']


class PallasKernel:
    """A compiled user kernel callable on NDArrays
    (the `CudaKernel.launch` analog; grid ≈ launch geometry)."""

    def __init__(self, kernel, out_shape=None, out_like: Optional[int] = None,
                 grid=None, in_specs=None, out_specs=None, interpret=None,
                 name=None):
        from jax.experimental import pallas as pl
        if out_shape is None and out_like is None:
            raise MXNetError(
                "pallas_op needs out_shape=jax.ShapeDtypeStruct(...) or "
                "out_like=<input index>")
        self._pl = pl
        self.kernel = kernel
        self.out_shape = out_shape
        self.out_like = out_like
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.interpret = interpret
        self.name = name or getattr(kernel, '__name__', 'pallas_kernel')
        self._compiled = {}

    def _call_fn(self, shapes_dtypes):
        key = tuple(shapes_dtypes)
        if key not in self._compiled:
            pl = self._pl
            if self.out_shape is not None:
                out_shape = self.out_shape
            else:
                s, d = shapes_dtypes[self.out_like]
                out_shape = jax.ShapeDtypeStruct(s, d)
            kwargs = {}
            if self.grid is not None:
                kwargs['grid'] = self.grid
            if self.in_specs is not None:
                kwargs['in_specs'] = self.in_specs
            if self.out_specs is not None:
                kwargs['out_specs'] = self.out_specs
            interpret = self.interpret
            if interpret is None:
                interpret = default_interpret()
            call = pl.pallas_call(self.kernel, out_shape=out_shape,
                                  interpret=interpret, **kwargs)
            self._compiled[key] = jax.jit(call)
        return self._compiled[key]

    def __call__(self, *inputs):
        datas = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
                 for x in inputs]
        shapes_dtypes = [(tuple(d.shape), d.dtype) for d in datas]
        out = self._call_fn(shapes_dtypes)(*datas)
        if isinstance(out, (list, tuple)):
            return tuple(_wrap(o) for o in out)
        return _wrap(out)

    launch = __call__  # reference CudaKernel.launch parity


def pallas_op(kernel, out_shape=None, out_like=None, grid=None,
              in_specs=None, out_specs=None, interpret=None, name=None):
    """Wrap a Pallas kernel function as an eager framework op
    (the TPU-native `mx.rtc.CudaModule.get_kernel` replacement)."""
    return PallasKernel(kernel, out_shape=out_shape, out_like=out_like,
                        grid=grid, in_specs=in_specs, out_specs=out_specs,
                        interpret=interpret, name=name)


class CudaModule:
    """Unsupported on TPU — kept so reference code fails with guidance
    (ref: python/mxnet/rtc.py CudaModule)."""

    def __init__(self, *args, **kwargs):
        raise MXNetError(
            "CUDA RTC is not available on the TPU backend; write a Pallas "
            "kernel and wrap it with mxnet_tpu.rtc.pallas_op (see "
            "/opt/skills/guides/pallas_guide.md)")
