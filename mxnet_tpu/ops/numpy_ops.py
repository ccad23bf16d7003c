"""The numpy (`_npi_*`/`_np_*`) operator namespace as registered ops.

Ref: src/operator/numpy/ (98 files — np_elemwise_broadcast_op.cc,
np_broadcast_reduce_op_value.cc, np_einsum_op.cc, np_insert_op_*.cc,
np_delete_op.cc, np_matrix_op.cc, np_init_op.cc, np_window_op.cc,
linalg/np_*.cc, random/np_*_op.cc ...). The reference implements each op
as a CUDA/CPU kernel pair with shape/type inference; here each op is a
jnp/lax lowering (XLA supplies the kernels, fusion and autodiff) behind
the same internal op name, and the `mx.np` frontend dispatches through
this registry exactly like `mx.nd` dispatches through the legacy one.

Pure-backward helper nodes of the reference (`_npi_backward_nan_to_num`,
`_npi_backward_polyval`, `_npi_hsplit_backward`) are deliberately absent:
gradients come from jax.vjp on the forward lowering.

Ops whose output shape depends on VALUES (`_npi_unique`, `_npi_nonzero`,
`_npi_delete`, boolean-mask assign) are eager-only under jit, exactly as
data-dependent shapes are unsupported by XLA; the reference pays a device
sync for them too (ref: np_unique_op.cc SyncCopyToCPU).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import register_op
from .. import random as _random

__all__ = []


def _reg(name, num_outputs=1, nograd=False):
    def deco(fn):
        register_op(name, num_outputs=num_outputs, nograd=nograd)(fn)
        __all__.append(name)
        return fn
    return deco


def _dt(dtype, default='float32'):
    return jnp.dtype(dtype if dtype is not None else default)


def _shape(shape):
    return (shape,) if isinstance(shape, int) else tuple(shape)


# ---------------------------------------------------------------------------
# elemwise broadcast binary (+ scalar and reflected-scalar variants)
# ref: np_elemwise_broadcast_op.cc, np_elemwise_broadcast_op_extended.cc,
#      np_elemwise_broadcast_logic_op.cc
# ---------------------------------------------------------------------------

_BINARY = {
    'add': jnp.add, 'subtract': jnp.subtract, 'multiply': jnp.multiply,
    'mod': jnp.mod, 'power': jnp.power, 'true_divide': jnp.true_divide,
    'floor_divide': jnp.floor_divide, 'arctan2': jnp.arctan2,
    'hypot': jnp.hypot, 'copysign': jnp.copysign, 'ldexp':
        lambda a, b: a * jnp.power(2.0, b),
    'lcm': jnp.lcm, 'gcd': jnp.gcd,
    'bitwise_and': jnp.bitwise_and, 'bitwise_or': jnp.bitwise_or,
    'bitwise_xor': jnp.bitwise_xor,
    'bitwise_left_shift': jnp.left_shift,
    'bitwise_right_shift': jnp.right_shift,
    'maximum': jnp.maximum, 'minimum': jnp.minimum,
    'fmax': jnp.fmax, 'fmin': jnp.fmin, 'fmod': jnp.fmod,
}
_LOGIC = {
    'equal': jnp.equal, 'not_equal': jnp.not_equal,
    'greater': jnp.greater, 'greater_equal': jnp.greater_equal,
    'less': jnp.less, 'less_equal': jnp.less_equal,
    'logical_and': jnp.logical_and, 'logical_or': jnp.logical_or,
    'logical_xor': jnp.logical_xor,
}

for _n, _f in _BINARY.items():
    _reg(f'_npi_{_n}')(lambda lhs, rhs, _f=_f: _f(lhs, rhs))
    _reg(f'_npi_{_n}_scalar')(
        lambda data, scalar=1.0, _f=_f: _f(data, scalar))
for _n in ('subtract', 'mod', 'power', 'true_divide', 'floor_divide',
           'arctan2', 'copysign', 'ldexp'):
    _f = _BINARY[_n]
    _reg(f'_npi_r{_n}_scalar')(
        lambda data, scalar=1.0, _f=_f: _f(scalar, data))
for _n, _f in _LOGIC.items():
    _reg(f'_npi_{_n}', nograd=True)(lambda lhs, rhs, _f=_f: _f(lhs, rhs))
    _reg(f'_npi_{_n}_scalar', nograd=True)(
        lambda data, scalar=0.0, _f=_f: _f(data, scalar))


# ---------------------------------------------------------------------------
# elemwise unary (ref: np_elemwise_unary_op_basic.cc)
# ---------------------------------------------------------------------------

_UNARY = {
    'abs': jnp.abs, 'absolute': jnp.abs, 'negative': jnp.negative,
    'reciprocal': jnp.reciprocal, 'sign': jnp.sign, 'rint': jnp.rint,
    'ceil': jnp.ceil, 'floor': jnp.floor, 'trunc': jnp.trunc,
    'fix': jnp.trunc, 'square': jnp.square, 'sqrt': jnp.sqrt,
    'cbrt': jnp.cbrt, 'exp': jnp.exp, 'expm1': jnp.expm1, 'log': jnp.log,
    'log2': jnp.log2, 'log10': jnp.log10, 'log1p': jnp.log1p,
    'degrees': jnp.degrees, 'radians': jnp.radians, 'deg2rad': jnp.deg2rad,
    'rad2deg': jnp.rad2deg, 'sin': jnp.sin, 'cos': jnp.cos,
    'tan': jnp.tan, 'arcsin': jnp.arcsin, 'arccos': jnp.arccos,
    'arctan': jnp.arctan, 'sinh': jnp.sinh, 'cosh': jnp.cosh,
    'tanh': jnp.tanh, 'arcsinh': jnp.arcsinh, 'arccosh': jnp.arccosh,
    'arctanh': jnp.arctanh, 'invert': jnp.invert,
    'bitwise_not': jnp.invert, 'exp2': jnp.exp2,
    'positive': jnp.positive, 'conjugate': jnp.conjugate,
}
for _n, _f in _UNARY.items():
    _reg(f'_npi_{_n}')(lambda data, _f=_f: _f(data))
_reg('_npi_logical_not', nograd=True)(lambda data: jnp.logical_not(data))
for _n in ('isnan', 'isinf', 'isfinite', 'isposinf', 'isneginf'):
    _reg(f'_npi_{_n}', nograd=True)(
        lambda data, _f=getattr(jnp, _n): _f(data))


@_reg('_npi_around')
def _npi_around(data, decimals=0):
    return jnp.round(data, decimals)


@_reg('_npi_nan_to_num')
def _npi_nan_to_num(data, copy=True, nan=0.0, posinf=None, neginf=None):
    return jnp.nan_to_num(data, nan=nan, posinf=posinf, neginf=neginf)


@_reg('_np_copy')
def _np_copy(a):
    return jnp.asarray(a)


# ---------------------------------------------------------------------------
# reductions (ref: np_broadcast_reduce_op_value.cc, *_boolean.cc, *_index.cc)
# ---------------------------------------------------------------------------

def _red(name, fn, nograd=False):
    @_reg(name, nograd=nograd)
    def op(a, axis=None, dtype=None, keepdims=False, initial=None,
           where=None, fn=fn):
        kw = {}
        if dtype is not None:
            kw['dtype'] = jnp.dtype(dtype)
        if initial is not None:
            kw['initial'] = initial
        if where is not None:
            kw['where'] = where
        return fn(a, axis=axis, keepdims=keepdims, **kw)
    return op


_red('_np_sum', jnp.sum)
_red('_np_prod', jnp.prod)
_red('_np_max', lambda a, axis=None, keepdims=False: jnp.max(
    a, axis=axis, keepdims=keepdims))
_red('_np_min', lambda a, axis=None, keepdims=False: jnp.min(
    a, axis=axis, keepdims=keepdims))
_red('_np_any', lambda a, axis=None, keepdims=False: jnp.any(
    a, axis=axis, keepdims=keepdims), nograd=True)
_red('_np_all', lambda a, axis=None, keepdims=False: jnp.all(
    a, axis=axis, keepdims=keepdims), nograd=True)


@_reg('_npi_mean')
def _npi_mean(a, axis=None, dtype=None, keepdims=False):
    kw = {'dtype': jnp.dtype(dtype)} if dtype is not None else {}
    return jnp.mean(a, axis=axis, keepdims=keepdims, **kw)


@_reg('_npi_std')
def _npi_std(a, axis=None, dtype=None, ddof=0, keepdims=False):
    kw = {'dtype': jnp.dtype(dtype)} if dtype is not None else {}
    return jnp.std(a, axis=axis, ddof=ddof, keepdims=keepdims, **kw)


@_reg('_npi_var')
def _npi_var(a, axis=None, dtype=None, ddof=0, keepdims=False):
    kw = {'dtype': jnp.dtype(dtype)} if dtype is not None else {}
    return jnp.var(a, axis=axis, ddof=ddof, keepdims=keepdims, **kw)


@_reg('_npi_average')
def _npi_average(a, axis=None, weights=None, returned=False):
    if weights is None:
        avg = jnp.mean(a, axis=axis)
        scl = jnp.asarray(a.size if axis is None
                          else a.shape[axis], jnp.float32)
    else:
        scl = jnp.sum(weights, axis=axis)
        avg = jnp.sum(a * weights, axis=axis) / scl
    if returned:
        return avg, jnp.broadcast_to(scl, avg.shape)
    return avg


@_reg('_npi_norm')
def _npi_norm(a, ord=2, axis=None, keepdims=False, flag=0):
    return jnp.linalg.norm(a, ord=None if flag == 0 else ord,
                           axis=axis, keepdims=keepdims)


@_reg('_npi_argmax', nograd=True)
def _npi_argmax(a, axis=None, keepdims=False):
    out = jnp.argmax(a, axis=axis)
    if keepdims and axis is not None:
        out = jnp.expand_dims(out, axis)
    return out


@_reg('_npi_argmin', nograd=True)
def _npi_argmin(a, axis=None, keepdims=False):
    out = jnp.argmin(a, axis=axis)
    if keepdims and axis is not None:
        out = jnp.expand_dims(out, axis)
    return out


@_reg('_npi_percentile')
def _npi_percentile(a, q, axis=None, interpolation='linear',
                    keepdims=False):
    return jnp.percentile(a, jnp.asarray(q), axis=axis,
                          method=interpolation, keepdims=keepdims)


@_reg('_npi_quantile')
def _npi_quantile(a, q, axis=None, interpolation='linear', keepdims=False):
    return jnp.quantile(a, jnp.asarray(q), axis=axis,
                        method=interpolation, keepdims=keepdims)


@_reg('_np_cumsum')
def _np_cumsum(a, axis=None, dtype=None):
    kw = {'dtype': jnp.dtype(dtype)} if dtype is not None else {}
    return jnp.cumsum(a, axis=axis, **kw)


@_reg('_npi_diff')
def _npi_diff(a, n=1, axis=-1):
    return jnp.diff(a, n=n, axis=axis)


@_reg('_npi_ediff1d')
def _npi_ediff1d(a, to_end=None, to_begin=None):
    return jnp.ediff1d(a, to_end=to_end, to_begin=to_begin)


@_reg('_npi_bincount', nograd=True)
def _npi_bincount(a, weights=None, minlength=0):
    length = max(int(minlength), int(onp.asarray(jax.device_get(a)).max())
                 + 1 if a.size else 1)
    return jnp.bincount(a, weights=weights, length=length)


# ---------------------------------------------------------------------------
# matrix / shape manipulation (ref: np_matrix_op.cc)
# ---------------------------------------------------------------------------

@_reg('_np_reshape')
def _np_reshape(a, newshape=None, order='C'):
    return jnp.reshape(a, newshape, order=order)


@_reg('_np_transpose')
def _np_transpose(a, axes=None):
    return jnp.transpose(a, axes)


@_reg('_np_squeeze')
def _np_squeeze(a, axis=None):
    return jnp.squeeze(a, axis)


@_reg('_np_moveaxis')
def _np_moveaxis(a, source, destination):
    return jnp.moveaxis(a, source, destination)


@_reg('_npi_swapaxes')
def _npi_swapaxes(a, dim1=0, dim2=1):
    return jnp.swapaxes(a, dim1, dim2)


@_reg('_np_roll')
def _np_roll(a, shift, axis=None):
    return jnp.roll(a, shift, axis)


@_reg('_npi_flip')
def _npi_flip(a, axis=None):
    return jnp.flip(a, axis)


@_reg('_npi_rot90')
def _npi_rot90(a, k=1, axes=(0, 1)):
    return jnp.rot90(a, k, axes)


@_reg('_npi_broadcast_to')
def _npi_broadcast_to(a, shape=()):
    return jnp.broadcast_to(a, _shape(shape))


@_reg('_npi_expand_dims')
def _npi_expand_dims(a, axis=0):
    return jnp.expand_dims(a, axis)


@_reg('_npi_concatenate')
def _npi_concatenate(*data, axis=0):
    if axis is None:
        return jnp.concatenate([jnp.ravel(d) for d in data])
    return jnp.concatenate(data, axis=axis)


@_reg('_npi_stack')
def _npi_stack(*data, axis=0):
    return jnp.stack(data, axis=axis)


@_reg('_npi_vstack')
def _npi_vstack(*data):
    return jnp.vstack(data)


@_reg('_npi_hstack')
def _npi_hstack(*data):
    return jnp.hstack(data)


@_reg('_npi_dstack')
def _npi_dstack(*data):
    return jnp.dstack(data)


@_reg('_npi_column_stack')
def _npi_column_stack(*data):
    return jnp.column_stack(data)


def _split_indices(ary, indices_or_sections, axis):
    if isinstance(indices_or_sections, int):
        return indices_or_sections
    return tuple(indices_or_sections)


@_reg('_npi_split', num_outputs=-1)
def _npi_split(ary, indices_or_sections=1, axis=0):
    return tuple(jnp.split(ary, _split_indices(ary, indices_or_sections,
                                               axis), axis=axis))


@_reg('_npi_hsplit', num_outputs=-1)
def _npi_hsplit(ary, indices_or_sections=1):
    return tuple(jnp.hsplit(ary, _split_indices(ary, indices_or_sections,
                                                1)))


@_reg('_npi_vsplit', num_outputs=-1)
def _npi_vsplit(ary, indices_or_sections=1):
    return tuple(jnp.vsplit(ary, _split_indices(ary, indices_or_sections,
                                                0)))


@_reg('_npi_dsplit', num_outputs=-1)
def _npi_dsplit(ary, indices_or_sections=1):
    return tuple(jnp.dsplit(ary, _split_indices(ary, indices_or_sections,
                                                2)))


@_reg('_npi_array_split', num_outputs=-1)
def _npi_array_split(ary, indices_or_sections=1, axis=0):
    return tuple(jnp.array_split(
        ary, _split_indices(ary, indices_or_sections, axis), axis=axis))


@_reg('_np_atleast_1d', num_outputs=-1)
def _np_atleast_1d(*arys):
    out = jnp.atleast_1d(*arys)
    return out if isinstance(out, (list, tuple)) else (out,)


@_reg('_np_atleast_2d', num_outputs=-1)
def _np_atleast_2d(*arys):
    out = jnp.atleast_2d(*arys)
    return out if isinstance(out, (list, tuple)) else (out,)


@_reg('_np_atleast_3d', num_outputs=-1)
def _np_atleast_3d(*arys):
    out = jnp.atleast_3d(*arys)
    return out if isinstance(out, (list, tuple)) else (out,)


@_reg('_np_diag')
def _np_diag(v, k=0):
    return jnp.diag(v, k)


@_reg('_np_diagflat')
def _np_diagflat(v, k=0):
    return jnp.diagflat(v, k)


@_reg('_np_diagonal')
def _np_diagonal(a, offset=0, axis1=0, axis2=1):
    return jnp.diagonal(a, offset, axis1, axis2)


@_reg('_np_trace')
def _np_trace(a, offset=0, axis1=0, axis2=1):
    return jnp.trace(a, offset, axis1, axis2)


@_reg('_npi_tril')
def _npi_tril(m, k=0):
    return jnp.tril(m, k)


@_reg('_npi_triu')
def _npi_triu(m, k=0):
    return jnp.triu(m, k)


@_reg('_npi_diag_indices_from', nograd=True)
def _npi_diag_indices_from(a):
    return tuple(jnp.diag_indices_from(a))


@_reg('_npi_pad')
def _npi_pad(a, pad_width, mode='constant', constant_values=0, **kwargs):
    pw = tuple(tuple(p) for p in pad_width)
    if mode == 'constant':
        return jnp.pad(a, pw, mode=mode, constant_values=constant_values)
    return jnp.pad(a, pw, mode=mode)


@_reg('_npi_squeeze')
def _npi_squeeze(a, axis=None):
    return jnp.squeeze(a, axis)


@_reg('_npi_tile')
def _npi_tile(a, reps=(1,)):
    return jnp.tile(a, _shape(reps))


@_reg('_npi_repeat')
def _npi_repeat(a, repeats=1, axis=None):
    return jnp.repeat(a, repeats, axis=axis)


@_reg('_npi_ravel')
def _npi_ravel(a, order='C'):
    return jnp.ravel(a, order=order)


@_reg('_npi_share_memory', nograd=True)
def _npi_share_memory(a, b):
    # functional arrays never alias from the user's perspective
    return jnp.zeros((), jnp.bool_)


@_reg('_npi_insert_scalar')
def _npi_insert_scalar(arr, obj=0, values=0.0, axis=None):
    return jnp.insert(arr, int(obj), values, axis=axis)


@_reg('_npi_insert_slice')
def _npi_insert_slice(arr, values, start=None, stop=None, step=None,
                      axis=None):
    idx = onp.arange(*slice(start, stop, step).indices(
        arr.shape[axis if axis is not None else 0]
        if axis is not None else arr.size))
    return jnp.insert(arr, idx, values, axis=axis)


@_reg('_npi_insert_tensor')
def _npi_insert_tensor(arr, obj, values, axis=None):
    return jnp.insert(arr, onp.asarray(jax.device_get(obj)), values,
                      axis=axis)


@_reg('_npi_delete', nograd=True)
def _npi_delete(arr, obj=None, start=None, stop=None, step=None,
                axis=None):
    if obj is None:
        obj = onp.arange(*slice(start, stop, step).indices(
            arr.shape[axis if axis is not None else 0]
            if axis is not None else arr.size))
    elif hasattr(obj, 'shape'):
        obj = onp.asarray(jax.device_get(obj))
    else:
        obj = int(obj)
    return jnp.delete(arr, obj, axis=axis)


@_reg('_npi_unique', nograd=True, num_outputs=-1)
def _npi_unique(a, return_index=False, return_inverse=False,
                return_counts=False, axis=None):
    out = jnp.unique(a, return_index=return_index,
                     return_inverse=return_inverse,
                     return_counts=return_counts, axis=axis)
    return out if isinstance(out, tuple) else (out,)


@_reg('_npi_nonzero', nograd=True)
def _npi_nonzero(a):
    # reference returns an (ndim, nnz) index tensor (np_nonzero_op.cc)
    return jnp.stack(jnp.nonzero(a), axis=0)


@_reg('_npi_flatnonzero', nograd=True)
def _npi_flatnonzero(a):
    return jnp.flatnonzero(a)


@_reg('_npi_searchsorted', nograd=True)
def _npi_searchsorted(a, v, side='left'):
    return jnp.searchsorted(a, v, side=side)


@_reg('_npi_where')
def _npi_where(condition, x, y):
    return jnp.where(condition.astype(bool), x, y)


@_reg('_npi_where_lscalar')
def _npi_where_lscalar(condition, y, scalar=0.0):
    return jnp.where(condition.astype(bool), scalar, y)


@_reg('_npi_where_rscalar')
def _npi_where_rscalar(condition, x, scalar=0.0):
    return jnp.where(condition.astype(bool), x, scalar)


@_reg('_npi_where_scalar2')
def _npi_where_scalar2(condition, x=0.0, y=0.0):
    return jnp.where(condition.astype(bool), x, y)


@_reg('_npi_boolean_mask_assign_scalar')
def _npi_boolean_mask_assign_scalar(data, mask, value=0.0):
    return jnp.where(mask.astype(bool), value, data)


@_reg('_npi_boolean_mask_assign_tensor')
def _npi_boolean_mask_assign_tensor(data, mask, value):
    m = mask.astype(bool)
    if value.ndim == data.ndim:
        return jnp.where(m, value, data)
    # reference packs values for the True positions (row-major)
    idx = jnp.cumsum(m.ravel()) - 1
    picked = jnp.take(value.ravel(), jnp.clip(idx, 0, value.size - 1))
    return jnp.where(m, picked.reshape(data.shape), data)


@_reg('_npi_polyval')
def _npi_polyval(p, x):
    return jnp.polyval(p, x)


@_reg('_npi_constraint_check', nograd=True)
def _npi_constraint_check(data, msg="constraint violated"):
    # ref: np_constraint_check.cc — raises on False at sync time
    ok = bool(jnp.all(data))
    if not ok:
        raise ValueError(msg)
    return jnp.asarray(True)


# ---------------------------------------------------------------------------
# tensordot / matmul / einsum / kron
# ref: np_tensordot_op.cc, np_matmul_op.cc, np_einsum_op.cc, np_kron.cc
# ---------------------------------------------------------------------------

@_reg('_npi_matmul')
def _npi_matmul(a, b):
    return jnp.matmul(a, b)


@_reg('_np_dot')
def _np_dot(a, b):
    return jnp.dot(a, b)


@_reg('_npi_tensordot')
def _npi_tensordot(a, b, a_axes_summed=(), b_axes_summed=()):
    return jnp.tensordot(a, b, axes=(tuple(a_axes_summed),
                                     tuple(b_axes_summed)))


@_reg('_npi_tensordot_int_axes')
def _npi_tensordot_int_axes(a, b, axes=2):
    return jnp.tensordot(a, b, axes=int(axes))


@_reg('_npi_kron')
def _npi_kron(a, b):
    return jnp.kron(a, b)


@_reg('_npi_einsum')
def _npi_einsum(*operands, subscripts='', optimize=False):
    return jnp.einsum(subscripts, *operands,
                      optimize='optimal' if optimize else 'auto')


@_reg('_npi_cross')
def _npi_cross(a, b, axisa=-1, axisb=-1, axisc=-1):
    return jnp.cross(a, b, axisa=axisa, axisb=axisb, axisc=axisc)


@_reg('_npi_vdot')
def _npi_vdot(a, b):
    return jnp.vdot(a, b)


@_reg('_npi_inner')
def _npi_inner(a, b):
    return jnp.inner(a, b)


@_reg('_npi_outer')
def _npi_outer(a, b):
    return jnp.outer(a, b)


# ---------------------------------------------------------------------------
# linalg (ref: src/operator/numpy/linalg/np_*.cc)
# ---------------------------------------------------------------------------

@_reg('_npi_cholesky')
def _npi_cholesky(a, lower=True):
    L = jnp.linalg.cholesky(a)
    return L if lower else jnp.swapaxes(L, -1, -2)


@_reg('_npi_svd', num_outputs=3)
def _npi_svd(a):
    u, s, vh = jnp.linalg.svd(a, full_matrices=False)
    return u, s, vh


@_reg('_npi_eig', num_outputs=2, nograd=True)
def _npi_eig(a):
    w, v = jnp.linalg.eig(a)
    return w, v


@_reg('_npi_eigh', num_outputs=2)
def _npi_eigh(a, upper=False):
    return jnp.linalg.eigh(a, UPLO='U' if upper else 'L')


@_reg('_npi_eigvals', nograd=True)
def _npi_eigvals(a):
    return jnp.linalg.eigvals(a)


@_reg('_npi_eigvalsh')
def _npi_eigvalsh(a, upper=False):
    return jnp.linalg.eigvalsh(a, UPLO='U' if upper else 'L')


@_reg('_npi_solve')
def _npi_solve(a, b):
    return jnp.linalg.solve(a, b)


@_reg('_npi_lstsq', num_outputs=4, nograd=True)
def _npi_lstsq(a, b, rcond=None):
    x, res, rank, s = jnp.linalg.lstsq(a, b, rcond=rcond)
    return x, res, rank, s


@_reg('_npi_inv')
def _npi_inv(a):
    return jnp.linalg.inv(a)


@_reg('_npi_pinv')
def _npi_pinv(a, rcond):
    return jnp.linalg.pinv(a, rtol=rcond)


@_reg('_npi_pinv_scalar_rcond')
def _npi_pinv_scalar_rcond(a, rcond=1e-15):
    return jnp.linalg.pinv(a, rtol=rcond)


@_reg('_npi_tensorinv')
def _npi_tensorinv(a, ind=2):
    return jnp.linalg.tensorinv(a, ind=ind)


@_reg('_npi_tensorsolve')
def _npi_tensorsolve(a, b, a_axes=None):
    return jnp.linalg.tensorsolve(a, b, axes=a_axes)


@_reg('_npi_matrix_rank', nograd=True)
def _npi_matrix_rank(M, tol=None, hermitian=False):
    return jnp.linalg.matrix_rank(M, rtol=tol)


@_reg('_npi_det')
def _npi_det(a):
    return jnp.linalg.det(a)


@_reg('_npi_slogdet', num_outputs=2)
def _npi_slogdet(a):
    sign, logdet = jnp.linalg.slogdet(a)
    return sign, logdet


@_reg('_npi_qr', num_outputs=2)
def _npi_qr(a):
    q, r = jnp.linalg.qr(a)
    return q, r


@_reg('_npi_multi_dot')
def _npi_multi_dot(*arrays):
    return jnp.linalg.multi_dot(arrays)


@_reg('_npi_matrix_power')
def _npi_matrix_power(a, n=1):
    return jnp.linalg.matrix_power(a, n)


# ---------------------------------------------------------------------------
# init ops (ref: np_init_op.cc) and windows (np_window_op.cc)
# ---------------------------------------------------------------------------

@_reg('_npi_zeros', nograd=True)
def _npi_zeros(shape=(), dtype='float32'):
    return jnp.zeros(_shape(shape), _dt(dtype))


@_reg('_npi_ones', nograd=True)
def _npi_ones(shape=(), dtype='float32'):
    return jnp.ones(_shape(shape), _dt(dtype))


@_reg('_npi_full', nograd=True)
def _npi_full(shape=(), fill_value=0.0, dtype=None):
    return jnp.full(_shape(shape), fill_value, _dt(dtype))


@_reg('_npi_full_like', nograd=True)
def _npi_full_like(a, fill_value=0.0, dtype=None):
    return jnp.full_like(a, fill_value,
                         dtype=None if dtype is None else jnp.dtype(dtype))


@_reg('_npi_arange', nograd=True)
def _npi_arange(start=0, stop=None, step=1, dtype='float32'):
    return jnp.arange(start, stop, step, _dt(dtype))


@_reg('_npi_linspace', nograd=True)
def _npi_linspace(start=0.0, stop=1.0, num=50, endpoint=True,
                  dtype='float32'):
    return jnp.linspace(start, stop, int(num), endpoint=endpoint,
                        dtype=_dt(dtype))


@_reg('_npi_logspace', nograd=True)
def _npi_logspace(start=0.0, stop=1.0, num=50, endpoint=True, base=10.0,
                  dtype='float32'):
    return jnp.logspace(start, stop, int(num), endpoint=endpoint,
                        base=base, dtype=_dt(dtype))


@_reg('_npi_eye', nograd=True)
def _npi_eye(N=1, M=None, k=0, dtype='float32'):
    return jnp.eye(int(N), None if M is None else int(M), int(k),
                   dtype=_dt(dtype))


@_reg('_npi_identity', nograd=True)
def _npi_identity(n=1, dtype='float32'):
    return jnp.identity(int(n), _dt(dtype))


@_reg('_npi_indices', nograd=True)
def _npi_indices(dimensions=(), dtype='int32'):
    return jnp.stack(jnp.indices(_shape(dimensions), _dt(dtype, 'int32')))


@_reg('_npi_tri', nograd=True)
def _npi_tri(N=1, M=None, k=0, dtype='float32'):
    return jnp.tri(int(N), None if M is None else int(M), int(k),
                   dtype=_dt(dtype))


@_reg('_npi_hanning', nograd=True)
def _npi_hanning(M=1, dtype='float32'):
    return jnp.hanning(int(M)).astype(_dt(dtype))


@_reg('_npi_hamming', nograd=True)
def _npi_hamming(M=1, dtype='float32'):
    return jnp.hamming(int(M)).astype(_dt(dtype))


@_reg('_npi_blackman', nograd=True)
def _npi_blackman(M=1, dtype='float32'):
    return jnp.blackman(int(M)).astype(_dt(dtype))


@_reg('_npi_meshgrid', num_outputs=-1, nograd=True)
def _npi_meshgrid(*xi, indexing='xy'):
    return tuple(jnp.meshgrid(*xi, indexing=indexing))


# ---------------------------------------------------------------------------
# random samplers (ref: src/operator/numpy/random/np_*_op.cc); keys come
# from the framework provider stack like ops/random_ops.py
# ---------------------------------------------------------------------------

def _sample_shape(shape, *params):
    if shape is not None:
        return _shape(shape)
    shp = ()
    for p in params:
        if hasattr(p, 'shape'):
            shp = jnp.broadcast_shapes(shp, p.shape)
    return shp


@_reg('_npi_uniform', nograd=True)
def _npi_uniform(low=0.0, high=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, low, high)
    u = jax.random.uniform(key, shp, _dt(dtype))
    return low + u * (jnp.asarray(high) - jnp.asarray(low))


@_reg('_npi_normal', nograd=True)
def _npi_normal(loc=0.0, scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, loc, scale)
    return loc + scale * jax.random.normal(key, shp, _dt(dtype))


@_reg('_npi_gamma', nograd=True)
def _npi_gamma(shape=1.0, scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, shape, scale)
    return scale * jax.random.gamma(key, shape, shp, _dt(dtype))


@_reg('_npi_bernoulli', nograd=True)
def _npi_bernoulli(prob=0.5, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, prob)
    return jax.random.bernoulli(key, prob, shp).astype(_dt(dtype))


@_reg('_npi_exponential', nograd=True)
def _npi_exponential(scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, scale)
    return scale * jax.random.exponential(key, shp, _dt(dtype))


@_reg('_npi_gumbel', nograd=True)
def _npi_gumbel(loc=0.0, scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, loc, scale)
    return loc + scale * jax.random.gumbel(key, shp, _dt(dtype))


@_reg('_npi_logistic', nograd=True)
def _npi_logistic(loc=0.0, scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, loc, scale)
    return loc + scale * jax.random.logistic(key, shp, _dt(dtype))


@_reg('_npi_laplace', nograd=True)
def _npi_laplace(loc=0.0, scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, loc, scale)
    return loc + scale * jax.random.laplace(key, shp, _dt(dtype))


@_reg('_npi_rayleigh', nograd=True)
def _npi_rayleigh(scale=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, scale)
    u = jax.random.uniform(key, shp, _dt(dtype), minval=1e-7)
    return scale * jnp.sqrt(-2.0 * jnp.log(u))


@_reg('_npi_weibull', nograd=True)
def _npi_weibull(a=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, a)
    u = jax.random.uniform(key, shp, _dt(dtype), minval=1e-7)
    return jnp.power(-jnp.log(u), 1.0 / jnp.asarray(a))


@_reg('_npi_pareto', nograd=True)
def _npi_pareto(a=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, a)
    u = jax.random.uniform(key, shp, _dt(dtype), minval=1e-7)
    return jnp.power(u, -1.0 / jnp.asarray(a)) - 1.0


@_reg('_npi_powerd', nograd=True)
def _npi_powerd(a=1.0, size=None, dtype='float32'):
    key = _random.next_key()
    shp = _sample_shape(size, a)
    u = jax.random.uniform(key, shp, _dt(dtype), minval=1e-7)
    return jnp.power(u, 1.0 / jnp.asarray(a))


@_reg('_npi_multinomial', nograd=True)
def _npi_multinomial(n=1, pvals=None, size=None):
    key = _random.next_key()
    pv = jnp.asarray(pvals)
    shp = () if size is None else tuple(size)
    pb = jnp.broadcast_to(pv, shp + pv.shape)
    counts = jax.random.multinomial(key, float(n), pb)
    return counts.astype(jnp.int64)


@_reg('_npi_choice', nograd=True)
def _npi_choice(a, size=None, replace=True, p=None):
    key = _random.next_key()
    shp = () if size is None else tuple(size)
    if not hasattr(a, 'shape') or getattr(a, 'ndim', 1) == 0:
        a = jnp.arange(int(a))
    return jax.random.choice(key, a, shp, replace=replace, p=p)


@_reg('_npi_shuffle', nograd=True)
def _npi_shuffle(a):
    key = _random.next_key()
    return jax.random.permutation(key, a)


@_reg('_npi_randint', nograd=True)
def _npi_randint(low=0, high=None, size=None, dtype='int32'):
    key = _random.next_key()
    if high is None:
        low, high = 0, low
    shp = () if size is None else tuple(size)
    return jax.random.randint(key, shp, low, high, _dt(dtype, 'int32'))
