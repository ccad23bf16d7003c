"""NDArray: the framework tensor, a mutable handle over an immutable jax.Array.

Ref: include/mxnet/ndarray.h:82-1118 and python/mxnet/ndarray/ndarray.py.

Design (TPU-first): the reference NDArray is a ref-counted buffer plus an
engine variable; mutation is in-place writes scheduled on the engine. Here
the payload is an immutable jax.Array and "mutation" rebinds `_data` — views
onto the same Chunk are emulated only where the reference API requires it
(`[:]=` assignment, `+=`). jax's async dispatch provides the engine's
never-block illusion; `wait_to_read()` is `block_until_ready()`.
"""
from __future__ import annotations

import functools
import numbers

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, state, get_op, telem_flags as _telem
from ..context import Context, current_context
from .. import _imperative
from ..ops import (elemwise as _ew, reduce as _red, matrix as _mat, nn as _nn,
                   index as _idx, init as _init)

__all__ = ['NDArray', 'array', 'zeros', 'ones', 'full', 'arange', 'empty',
           'concat', 'stack', 'save', 'load', 'imperative_invoke', 'waitall',
           'from_numpy', 'from_dlpack', 'to_dlpack_for_read']


def _dev_of(ctx):
    return (ctx or current_context()).jax_device()


def _maybe_put(data, ctx):
    """Commit to a device only when the user named a context (directly or
    via a `with ctx:` scope); uncommitted arrays follow their consumers'
    sharding, so eager math composes with mesh-sharded parameters after a
    pjit training step."""
    from ..context import _DEFAULT
    if ctx is None and Context.default_ctx() is _DEFAULT:
        return data
    return jax.device_put(data, _dev_of(ctx))


class NDArray:
    __slots__ = ('_data', '_ctx', '_grad', '_grad_req', '_in_graph',
                 '_stype', '__weakref__')

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data
        self._ctx = ctx
        self._grad = None
        self._grad_req = 'write'
        self._in_graph = False
        self._stype = 'default'

    def __deepcopy__(self, memo):
        import copy as _copy
        new = object.__new__(type(self))
        memo[id(self)] = new
        # walk the MRO: self.__slots__ alone would miss inherited slots
        # on sparse subclasses
        for klass in type(self).__mro__:
            for k in getattr(klass, '__slots__', ()):
                if k == '__weakref__':
                    continue
                v = getattr(self, k, None)
                # jax.Arrays are immutable: share the buffer; caches
                # (weakref-keyed) reset instead of deep-copying
                if k == '_data':
                    setattr(new, k, v)
                elif k.endswith('_cache'):
                    setattr(new, k, None)
                else:
                    setattr(new, k, _copy.deepcopy(v, memo))
        return new

    # ---- basic properties -------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return onp.dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def stype(self):
        return self._stype

    @property
    def context(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        if isinstance(self._data, jax.core.Tracer):
            # mid-trace there is no buffer to ask: the array will live
            # wherever the program runs, i.e. the context in force
            return Context.default_ctx()
        dev = min(self._data.devices(), key=lambda d: d.id)
        if dev.platform != 'cpu':
            accel = [d for d in jax.devices() if d.platform != 'cpu']
            idx = accel.index(dev) if dev in accel else 0
            return Context('gpu', idx)
        cpus = jax.devices('cpu')
        idx = cpus.index(dev) if dev in cpus else 0
        return Context('cpu', idx)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return self.transpose()

    # ---- host interop -----------------------------------------------------
    def asnumpy(self) -> onp.ndarray:
        if _telem['on']:
            # device->host materialization is the dominant sync point in
            # real training loops (loss.asnumpy() every step)
            _timed_sync(self._data)
        return onp.asarray(self._data)

    def asscalar(self):
        return self.asnumpy().item()

    def item(self):
        return self.asnumpy().item()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        return bool(self.asnumpy())

    def __len__(self):
        return self.shape[0]

    def wait_to_read(self):
        if _telem['on']:
            _timed_sync(self._data)
            return
        jax.block_until_ready(self._data)

    def wait_to_write(self):
        if _telem['on']:
            _timed_sync(self._data)
            return
        jax.block_until_ready(self._data)

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"

    # ---- data movement ----------------------------------------------------
    def as_in_context(self, ctx) -> "NDArray":
        if ctx == self.context:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device()), ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, _dev_of(other._ctx)) \
                if other._ctx else self._data
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), other)
        raise MXNetError("copyto expects NDArray or Context")

    def copy(self):
        return NDArray(self._data + 0 if jnp.issubdtype(self._data.dtype, jnp.number)
                       else jnp.array(self._data), self._ctx)

    def astype(self, dtype, copy=True):
        return _invoke(_ew.cast, self, dtype=onp.dtype(dtype).name)

    def to_dlpack_for_read(self):
        return jax.dlpack.to_dlpack(self._data)

    # ---- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req='write', stype=None):
        """Ref: python/mxnet/ndarray/ndarray.py attach_grad. A non-default
        ``stype`` makes the gradient a real sparse NDArray so the sparse
        API (indices/data/retain) and stype-dispatching optimizers work."""
        grad = NDArray(jnp.zeros_like(self._data))
        if stype not in (None, 'default'):
            from .sparse import cast_storage
            grad = cast_storage(grad, stype)
        self._grad = grad
        self._grad_req = grad_req
        self._in_graph = True

    def detach(self):
        out = NDArray(self._data, self._ctx)
        out._in_graph = False
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _imperative.backward([self], [out_grad], retain_graph, train_mode)

    # ---- shape ops (methods mirroring the reference API) -------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get('shape', shape)
        return _invoke(_mat.reshape, self, shape=shape,
                       reverse=kwargs.get('reverse', False))

    def reshape_like(self, other):
        return _invoke(_mat.reshape, self, shape=other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _invoke(_mat.transpose, self, axes=axes or None)

    def flatten(self):
        return _invoke(_mat.flatten, self)

    def expand_dims(self, axis):
        return _invoke(_mat.expand_dims, self, axis=axis)

    def squeeze(self, axis=None):
        return _invoke(_mat.squeeze, self, axis=axis)

    def swapaxes(self, dim1, dim2):
        return _invoke(_mat.swapaxes, self, dim1=dim1, dim2=dim2)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return _invoke(_mat.split, self, num_outputs=num_outputs, axis=axis,
                       squeeze_axis=squeeze_axis)

    def tile(self, reps):
        return _invoke(_mat.tile, self, reps=reps)

    def repeat(self, repeats, axis=None):
        return _invoke(_mat.repeat, self, repeats=repeats, axis=axis)

    def broadcast_to(self, shape):
        return _invoke(_red.broadcast_to, self, shape=shape)

    def broadcast_like(self, other):
        return _invoke(_red.broadcast_like, self, other)

    def slice_axis(self, axis, begin, end):
        return _invoke(_mat.slice_axis, self, axis=axis, begin=begin, end=end)

    # ---- math methods ------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return _invoke(_red.sum, self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        return _invoke(_red.mean, self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return _invoke(_red.prod, self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return _invoke(_red.max, self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return _invoke(_red.min, self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return _invoke(_red.argmax, self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return _invoke(_red.argmin, self, axis=axis, keepdims=keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return _invoke(_red.norm, self, ord=ord, axis=axis, keepdims=keepdims)

    def abs(self):
        return _invoke(_ew.abs, self)

    def sqrt(self):
        return _invoke(_ew.sqrt, self)

    def square(self):
        return _invoke(_ew.square, self)

    def exp(self):
        return _invoke(_ew.exp, self)

    def log(self):
        return _invoke(_ew.log, self)

    def relu(self):
        return _invoke(_ew.relu, self)

    def sigmoid(self):
        return _invoke(_ew.sigmoid, self)

    def tanh(self):
        return _invoke(_ew.tanh, self)

    def softmax(self, axis=-1):
        return _invoke(_nn.softmax, self, axis=axis)

    def log_softmax(self, axis=-1):
        return _invoke(_nn.log_softmax, self, axis=axis)

    def clip(self, a_min=None, a_max=None):
        return _invoke(_ew.clip, self, a_min=a_min, a_max=a_max)

    def dot(self, other, transpose_a=False, transpose_b=False):
        return _invoke(_mat.dot, self, other, transpose_a=transpose_a,
                       transpose_b=transpose_b)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return _invoke(_nn.one_hot, self, depth=depth, on_value=on_value,
                       off_value=off_value)

    def topk(self, axis=-1, k=1, ret_typ='indices', is_ascend=False):
        return _invoke(_mat.topk, self, axis=axis, k=k, ret_typ=ret_typ,
                       is_ascend=is_ascend)

    def sort(self, axis=-1, is_ascend=True):
        return _invoke(_mat.sort, self, axis=axis, is_ascend=is_ascend)

    def argsort(self, axis=-1, is_ascend=True):
        return _invoke(_mat.argsort, self, axis=axis, is_ascend=is_ascend)

    def take(self, indices, axis=0, mode='clip'):
        return _invoke(_idx.take, self, indices, axis=axis, mode=mode)

    def tostype(self, stype):
        if stype == 'default':
            return self
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)

    def as_np_ndarray(self):
        from ..numpy import ndarray as np_nd
        return np_nd(self._data)

    # ---- arithmetic dunders -------------------------------------------------
    def _binop(self, other, fn, scalar_fn):
        if isinstance(other, NDArray):
            return _invoke(fn, self, other)
        if isinstance(other, numbers.Number):
            return _invoke(scalar_fn, self, scalar=other)
        if isinstance(other, (onp.ndarray, jax.Array)):
            return _invoke(fn, self, NDArray(jnp.asarray(other)))
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, _ew.broadcast_add, _ew.plus_scalar)

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop(other, _ew.broadcast_sub, _ew.minus_scalar)

    def __rsub__(self, other):
        return self._binop(other, _ew.broadcast_sub, _ew.rminus_scalar) \
            if isinstance(other, numbers.Number) else NotImplemented

    def __mul__(self, other):
        return self._binop(other, _ew.broadcast_mul, _ew.mul_scalar)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop(other, _ew.broadcast_div, _ew.div_scalar)

    def __rtruediv__(self, other):
        return self._binop(other, _ew.broadcast_div, _ew.rdiv_scalar) \
            if isinstance(other, numbers.Number) else NotImplemented

    def __mod__(self, other):
        return self._binop(other, _ew.broadcast_mod, _ew.mod_scalar)

    def __pow__(self, other):
        return self._binop(other, _ew.broadcast_power, _ew.power_scalar)

    def __rpow__(self, other):
        return self._binop(other, _ew.broadcast_power, _ew.rpower_scalar) \
            if isinstance(other, numbers.Number) else NotImplemented

    def __neg__(self):
        return _invoke(_ew.negative, self)

    def __abs__(self):
        return _invoke(_ew.abs, self)

    def __eq__(self, other):
        if other is None:
            return False
        return self._binop(other, _ew.broadcast_equal, _ew.equal_scalar)

    def __ne__(self, other):
        if other is None:
            return True
        return self._binop(other, _ew.broadcast_not_equal, _ew.not_equal_scalar)

    def __gt__(self, other):
        return self._binop(other, _ew.broadcast_greater, _ew.greater_scalar)

    def __ge__(self, other):
        return self._binop(other, _ew.broadcast_greater_equal, _ew.greater_equal_scalar)

    def __lt__(self, other):
        return self._binop(other, _ew.broadcast_lesser, _ew.lesser_scalar)

    def __le__(self, other):
        return self._binop(other, _ew.broadcast_lesser_equal, _ew.lesser_equal_scalar)

    __hash__ = object.__hash__

    # in-place: rebind _data (engine-free mutation)
    def __iadd__(self, other):
        out = self.__add__(other)
        self._data = out._data
        return self

    def __isub__(self, other):
        out = self.__sub__(other)
        self._data = out._data
        return self

    def __imul__(self, other):
        out = self.__mul__(other)
        self._data = out._data
        return self

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._data = out._data
        return self

    # ---- indexing -----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data
            if jnp.issubdtype(key.dtype, jnp.floating):
                key = key.astype(jnp.int32)
            return _invoke(lambda d, k: jnp.take(d, k, axis=0), self,
                           NDArray(key))
        return _invoke(lambda d: d[key], self)

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, slice) and key == slice(None):
            # x[:] = v — full overwrite preserving shape/dtype
            self._data = jnp.broadcast_to(
                jnp.asarray(value).astype(self._data.dtype), self.shape)
            return
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        self._data = self._data.at[key].set(
            jnp.asarray(value, dtype=self._data.dtype))

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]


def _wrap(data) -> NDArray:
    return NDArray(data)


def _invoke(fn, *args, **kwargs):
    """Eager dispatch of a registered compute fn on NDArray args.

    Storage-driven dispatch (the reference's FComputeEx,
    op_attr_types.h:304): when a positional argument carries a sparse
    stype and a storage-specific implementation is registered for the
    op's stype signature, that kernel runs instead of the dense one."""
    stypes = tuple(getattr(a, '_stype', 'default') or 'default'
                   for a in args if isinstance(a, NDArray))
    if any(st != 'default' for st in stypes):
        from ..base import lookup_sparse_impl
        impl = lookup_sparse_impl(getattr(fn, '__name__', ''), stypes)
        if impl is not None:
            # eager pre-compute hook: host-side facts (e.g. the nnz
            # budget) must come from the CONCRETE payloads here — inside
            # invoke the args may be autograd tracers
            prepare = getattr(impl, '__sparse_prepare__', None)
            if prepare is not None:
                import functools
                fn = functools.wraps(impl)(
                    functools.partial(impl, **prepare(args, kwargs)))
            else:
                fn = impl
    out_data, tensor_inputs, vjp_fn, gfn = _imperative.invoke(fn, args, kwargs)
    if isinstance(out_data, tuple):
        outs = [NDArray(o) for o in out_data]
        if vjp_fn is not None:
            _imperative.record_node(tensor_inputs, outs, vjp_fn, gfn,
                                    getattr(fn, '__name__', 'op'),
                                    tuple_out=True)
        return tuple(outs)
    out = NDArray(out_data)
    if vjp_fn is not None:
        _imperative.record_node(tensor_inputs, [out], vjp_fn, gfn,
                                getattr(fn, '__name__', 'op'))
    return out


def imperative_invoke(op_name, *args, **kwargs):
    """Invoke a registered op by name (the MXImperativeInvokeEx analog,
    ref: include/mxnet/c_api.h:1251)."""
    opdef = get_op(op_name)
    return _invoke(opdef.fn, *args, **kwargs)


# ---- creation -----------------------------------------------------------

def _to_jax_dtype(dtype):
    return jnp.dtype(onp.dtype(dtype)) if dtype is not None else jnp.float32


def array(source_array, ctx=None, dtype=None) -> NDArray:
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    arr = onp.asarray(source_array, dtype=onp.dtype(dtype) if dtype else None)
    if arr.dtype == onp.float64 and dtype is None:
        arr = arr.astype(onp.float32)
    if arr.dtype == onp.int64 and dtype is None:
        arr = arr.astype(onp.int32)
    data = _maybe_put(jnp.asarray(arr), ctx)
    return NDArray(data, ctx)


def empty(shape, ctx=None, dtype='float32') -> NDArray:
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype='float32', **kwargs) -> NDArray:
    data = _maybe_put(jnp.zeros(shape, _to_jax_dtype(dtype)), ctx)
    return NDArray(data, ctx)


def ones(shape, ctx=None, dtype='float32', **kwargs) -> NDArray:
    data = _maybe_put(jnp.ones(shape, _to_jax_dtype(dtype)), ctx)
    return NDArray(data, ctx)


def full(shape, val, ctx=None, dtype='float32') -> NDArray:
    data = _maybe_put(jnp.full(shape, val, _to_jax_dtype(dtype)), ctx)
    return NDArray(data, ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype='float32'):
    return _wrap(_init.arange(start=start, stop=stop, step=step, repeat=repeat,
                              dtype=dtype))


def concat(*args, dim=1):
    return _invoke(_mat.concat, *args, dim=dim)


def stack(*args, axis=0):
    return _invoke(_mat.stack, *args, axis=axis)


def from_numpy(a, zero_copy=False):
    return array(a)


def from_dlpack(dl):
    return NDArray(jax.dlpack.from_dlpack(dl))


def to_dlpack_for_read(arr):
    return arr.to_dlpack_for_read()


def _timed_sync(data):
    """block_until_ready with the stall reported to telemetry (the analog
    of the reference engine's WaitForVar accounting)."""
    import time as _time
    from .. import telemetry as _telemetry
    t0 = _time.perf_counter()
    jax.block_until_ready(data)
    _telemetry.inc('mxnet_tpu_sync_total')
    _telemetry.counter('mxnet_tpu_sync_seconds_total').inc(
        _time.perf_counter() - t0)


def waitall():
    """Ref: Engine::WaitForAll — barrier on all outstanding async work."""
    try:
        if _telem['on']:
            import time as _time
            from .. import telemetry as _telemetry
            t0 = _time.perf_counter()
            jax.effects_barrier()
            _telemetry.inc('mxnet_tpu_sync_total')
            _telemetry.counter('mxnet_tpu_sync_seconds_total').inc(
                _time.perf_counter() - t0)
            return
        jax.effects_barrier()
    except Exception:
        pass


# ---- serialization (ref: src/ndarray/ndarray.cc Save/Load + python save/load)

def save(fname, data):
    """Writes the reference's dmlc binary container (ref:
    src/ndarray/ndarray.cc NDArray::Save, kMXAPINDArrayListMagic) so files
    interchange with the reference ecosystem."""
    from ..serialization import atomic_write_file, save_ndarray_file
    if isinstance(data, NDArray):
        payload = [data.asnumpy()]
    elif isinstance(data, (list, tuple)):
        if not all(isinstance(d, NDArray) for d in data):
            raise MXNetError("save expects a list of NDArrays")
        payload = [d.asnumpy() for d in data]
    elif isinstance(data, dict):
        payload = {k: v.asnumpy() for k, v in data.items()}
    else:
        raise MXNetError("save expects NDArray, list, or dict")
    atomic_write_file(fname, save_ndarray_file(payload))


def _decode_loaded(entry):
    """Binary-loader entry → NDArray (densifying sparse payloads — this
    build keeps the sparse API over dense storage)."""
    from ..serialization import sparse_to_dense
    if isinstance(entry, tuple):
        return array(sparse_to_dense(*entry))
    if entry is None:
        return None
    return array(entry)


def load(fname):
    """Reads reference-format binary files; round-1 pickle files are still
    readable through a restricted (numpy-only) unpickler."""
    from ..serialization import (is_ndarray_file, load_ndarray_file,
                                 safe_pickle_load)
    with open(fname, 'rb') as f:
        buf = f.read()
    if is_ndarray_file(buf):
        arrays, names = load_ndarray_file(buf)
        if names:
            return {k: _decode_loaded(v) for k, v in zip(names, arrays)}
        return [_decode_loaded(a) for a in arrays]
    import io as _io
    kind, payload = safe_pickle_load(_io.BytesIO(buf))
    if kind == 'single':
        return array(payload)
    if kind == 'list':
        return [array(p) for p in payload]
    return {k: array(v) for k, v in payload.items()}


def load_frombuffer(buf):
    """Ref: mx.nd.load_frombuffer (c_api MXNDArrayLoadFromBuffer)."""
    from ..serialization import is_ndarray_file, load_ndarray_file
    if not is_ndarray_file(buf):
        raise MXNetError("buffer is not an NDArray file")
    arrays, names = load_ndarray_file(buf)
    if names:
        return {k: _decode_loaded(v) for k, v in zip(names, arrays)}
    return [_decode_loaded(a) for a in arrays]
