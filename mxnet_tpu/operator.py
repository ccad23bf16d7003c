"""mx.operator — user-defined operators in Python (ref: python/mxnet/operator.py,
src/operator/custom/custom.cc).

The reference runs Python custom ops on a dedicated worker thread so they keep
dependency-engine semantics (src/operator/custom/custom-inl.h:76). Here the
eager path simply calls the user's ``forward`` inline — jax's async dispatch
means the surrounding ops are already futures, and the custom op acts as a
host-side sync point exactly like the reference's engine callback. When
autograd is recording, the user's ``backward`` is recorded on the tape as the
node's vjp, so custom ops compose with the rest of the graph.

Inside a hybridized/jitted trace a Python custom op cannot run natively on
the TPU; it is bridged with ``jax.pure_callback`` + ``jax.custom_vjp`` so the
traced program calls back into Python — the TPU analog of the reference's
custom-op worker thread crossing the engine boundary. Note: this requires a
runtime with host-callback support (CPU and standard TPU PjRt have it).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Type

import numpy as onp

__all__ = ['CustomOp', 'CustomOpProp', 'register', 'get_registered_op',
           'list_registered_ops']


class CustomOp:
    """Base class for user operator implementations
    (ref: python/mxnet/operator.py CustomOp)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Write `src` into `dst` honoring the OpReqType
        (ref: include/mxnet/op_attr_types.h:46 kNullOp/kWriteTo/kAddTo)."""
        if req == 'null':
            return
        from .ndarray.ndarray import NDArray
        src_data = src._data if isinstance(src, NDArray) else src
        if req == 'add':
            dst._data = dst._data + src_data
        else:  # 'write' / 'inplace'
            dst._data = src_data


class CustomOpProp:
    """Operator properties: shapes/types/arity + factory
    (ref: python/mxnet/operator.py CustomOpProp)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), \
            [in_type[0]] * len(self.list_auxiliary_states())

    def list_arguments(self):
        return ['data']

    def list_outputs(self):
        return ['output']

    def list_auxiliary_states(self):
        return []

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return CustomOp()


_registry: Dict[str, Type[CustomOpProp]] = {}


def register(reg_name):
    """Decorator registering a CustomOpProp subclass under `op_type`
    (ref: python/mxnet/operator.py register)."""
    def do_register(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise TypeError("can only register subclasses of CustomOpProp")
        _registry[reg_name] = prop_cls
        return prop_cls
    return do_register


def get_registered_op(op_type) -> Type[CustomOpProp]:
    if op_type not in _registry:
        raise ValueError(
            f"custom op type '{op_type}' is not registered "
            f"(known: {sorted(_registry)})")
    return _registry[op_type]


def list_registered_ops() -> List[str]:
    return sorted(_registry)


def _make_prop(op_type, kwargs) -> CustomOpProp:
    prop_cls = get_registered_op(op_type)
    # the reference marshals user kwargs through the C API as strings
    # (src/operator/custom/custom.cc ParamParser); keep that contract
    return prop_cls(**{k: str(v) for k, v in kwargs.items()})


def _invoke_traced(op_type, prop, op, in_data, aux, out_shapes, out_types):
    """Trace-time bridge: the jitted program calls back into the Python op
    via jax.pure_callback, with jax.custom_vjp routing cotangents through the
    user's ``backward`` — the TPU analog of the reference's custom-op worker
    thread crossing the engine boundary (src/operator/custom/custom-inl.h:76)."""
    import jax
    import jax.numpy as jnp
    from .base import state
    from .ndarray.ndarray import NDArray

    n_in = len(in_data)
    n_aux = len(aux)
    n_out = len(out_shapes)
    out_avals = tuple(jax.ShapeDtypeStruct(tuple(s), onp.dtype(t))
                      for s, t in zip(out_shapes, out_types))
    is_train = state.is_training
    need_top = prop.need_top_grad_

    def _host_arrays(arrs):
        return [NDArray(jnp.asarray(a)) for a in arrs]

    def host_forward(*arrs):
        rec, state.is_recording = state.is_recording, False
        try:
            nds = _host_arrays(arrs[:n_in])
            auxs = _host_arrays(arrs[n_in:])
            outs = [NDArray(jnp.zeros(a.shape, a.dtype)) for a in out_avals]
            op.forward(is_train=is_train, req=['write'] * n_out,
                       in_data=nds, out_data=outs, aux=auxs)
            return tuple(onp.asarray(o.asnumpy(), dtype=a.dtype)
                         for o, a in zip(outs, out_avals))
        finally:
            state.is_recording = rec

    def host_backward(*arrs):
        rec, state.is_recording = state.is_recording, False
        try:
            nds = _host_arrays(arrs[:n_in])
            auxs = _host_arrays(arrs[n_in:n_in + n_aux])
            outs = _host_arrays(arrs[n_in + n_aux:n_in + n_aux + n_out])
            cts = _host_arrays(arrs[n_in + n_aux + n_out:])
            in_grad = [NDArray(jnp.zeros_like(a._data)) for a in nds]
            op.backward(req=['write'] * n_in,
                        out_grad=cts if need_top else [],
                        in_data=nds, out_data=outs, in_grad=in_grad, aux=auxs)
            return tuple(onp.asarray(g.asnumpy(), dtype=n._data.dtype)
                         for g, n in zip(in_grad, nds))
        finally:
            state.is_recording = rec

    @jax.custom_vjp
    def f(*datas):
        return jax.pure_callback(host_forward, out_avals, *datas)

    def f_fwd(*datas):
        outs = jax.pure_callback(host_forward, out_avals, *datas)
        return outs, (datas, outs)

    def f_bwd(res, cts):
        datas, outs = res
        in_avals = tuple(jax.ShapeDtypeStruct(d.shape, d.dtype)
                         for d in datas[:n_in])
        grads = jax.pure_callback(host_backward, in_avals,
                                  *datas, *outs, *cts)
        return tuple(grads) + tuple(jnp.zeros_like(d) for d in datas[n_in:])

    f.defvjp(f_fwd, f_bwd)

    out = f(*[a._data for a in in_data + aux])
    out_nd = [NDArray(o) for o in out]
    return out_nd[0] if n_out == 1 else tuple(out_nd)


def invoke_custom(inputs, op_type: Optional[str] = None, **kwargs):
    """nd.Custom implementation: eager dispatch of a registered custom op,
    recording the user-defined backward on the autograd tape
    (ref: src/operator/custom/custom.cc Forward/Backward)."""
    import jax.numpy as jnp
    from . import _imperative
    from .base import state
    from .ndarray.ndarray import NDArray, _wrap

    import jax

    if op_type is None:
        raise ValueError("nd.Custom requires op_type=")
    prop = _make_prop(op_type, kwargs)

    n_args = len(prop.list_arguments())
    n_aux = len(prop.list_auxiliary_states())
    if len(inputs) != n_args + n_aux:
        raise ValueError(
            f"custom op '{op_type}' expects {n_args} args + {n_aux} aux "
            f"states, got {len(inputs)} inputs")
    in_data = list(inputs[:n_args])
    aux = list(inputs[n_args:])

    in_shapes = [tuple(a.shape) for a in in_data]
    in_shapes_out, out_shapes, _ = prop.infer_shape(in_shapes)
    in_types = [a.dtype for a in in_data]
    _, out_types, _ = prop.infer_type(in_types)
    n_out = len(prop.list_outputs())
    if len(out_shapes) != n_out or len(out_types) != n_out:
        raise ValueError(
            f"custom op '{op_type}': infer_shape/infer_type returned "
            f"{len(out_shapes)}/{len(out_types)} outputs but list_outputs() "
            f"declares {n_out}")

    op = prop.create_operator(None, in_shapes_out, in_types)

    if any(isinstance(a._data, jax.core.Tracer) for a in in_data + aux):
        return _invoke_traced(op_type, prop, op, in_data, aux,
                              out_shapes, out_types)

    out_data = [_wrap(jnp.zeros(s, dtype=onp.dtype(t)))
                for s, t in zip(out_shapes, out_types)]

    is_train = state.is_training
    rec = state.is_recording
    recording = rec and any(a._in_graph for a in in_data)
    # the op's own backward is the gradient; internal nd ops inside the
    # user's forward must not land on the tape
    state.is_recording = False
    try:
        op.forward(is_train=is_train, req=['write'] * len(out_data),
                   in_data=in_data, out_data=out_data, aux=aux)
    finally:
        state.is_recording = rec
    if recording:
        need_top = prop.need_top_grad_

        def vjp_fn(ct_struct):
            cts = ct_struct if isinstance(ct_struct, tuple) else (ct_struct,)
            out_grad = [_wrap(c) for c in cts] if need_top else []
            in_grad = [_wrap(jnp.zeros_like(a._data)) for a in in_data]
            brec, state.is_recording = state.is_recording, False
            try:
                op.backward(req=['write'] * len(in_grad), out_grad=out_grad,
                            in_data=in_data, out_data=out_data,
                            in_grad=in_grad, aux=aux)
            finally:
                state.is_recording = brec
            return tuple(g._data for g in in_grad)

        _imperative.record_node(in_data, out_data, vjp_fn, fn=None,
                                name=f"Custom[{op_type}]",
                                tuple_out=len(out_data) > 1)

    return out_data[0] if len(out_data) == 1 else tuple(out_data)
