"""Block / HybridBlock / CachedOp-equivalent compiled execution.

Ref: python/mxnet/gluon/block.py:229 (Block), :827 (HybridBlock),
src/imperative/cached_op.cc (CachedOp).

TPU-native hybridize: instead of building an NNVM graph, `hybridize()`
wraps the block's forward in a `jax.jit`-compiled function of
(param arrays, input arrays, rng key) → (outputs, updated aux states).
Static-alloc/static-shape modes of the reference map to XLA's AOT compile +
buffer donation; the compile cache is keyed on input shapes/dtypes and
train/predict mode, which reproduces CachedOp's shape-specialised graphs.
Mutable aux states (BatchNorm running stats) are detected during tracing as
rebound parameter proxies and threaded out as functional outputs.
"""
from __future__ import annotations

import re
import threading
import time as _time

import jax
import numpy as onp

from ..base import MXNetError, state, telem_flags as _telem
from ..context import Context, cpu, current_context
from ..ndarray.ndarray import NDArray, array, _wrap
from .. import ndarray as nd
from .. import _imperative
from .. import random as _random
from ..telemetry import compile as _compile
from .parameter import Parameter, ParameterDict, DeferredInitializationError


class _BlockScope:
    """Name scope manager (ref: block.py _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    _global_counter = {}

    @staticmethod
    def create(prefix, params, hint):
        """(full prefix, params, the prefix less the enclosing scope's)."""
        current = getattr(_BlockScope._current, 'value', None)
        if current is None:
            if prefix is None:
                count = _BlockScope._global_counter.get(hint, 0)
                _BlockScope._global_counter[hint] = count + 1
                prefix = f"{hint}{count}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params, prefix
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params, prefix

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, 'value', None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class Block:
    """Base building block (ref: gluon/block.py:229)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ''
        self._prefix, self._params, local = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith('_') else self._prefix
        # the name the parent knows this block by: its own less the
        # parent's prefix ('bertlayer3', 'qkv'). A device trace names the
        # ops of forward() under it (_trace_scope, mxnet_tpu/scopes.py).
        self._local_name = local.rstrip('_') or self._alias()
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    def _trace_scope(self):
        """What forward() runs under: ``jax.named_scope(<local name>)``,
        so that every op traced inside carries the nested block path in
        its HLO ``op_name`` and a profile reads in the model's own words.
        It acts while a program is traced, never while one runs, and is
        part of no cache key."""
        return jax.named_scope(self._local_name)

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = getattr(self, '_children', None)
            if existing is not None:
                self._children[name] = value
        elif isinstance(value, Parameter):
            if hasattr(self, '_reg_params'):
                self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        with self._trace_scope():
            out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary_lines = [f"{type(self).__name__} summary:"]
        params = self.collect_params()
        total = 0
        for name, p in params.items():
            n = int(onp.prod(p.shape)) if p.shape else 0
            total += n
            summary_lines.append(f"  {name}: {p.shape} ({n} params)")
        summary_lines.append(f"Total params: {total}")
        print('\n'.join(summary_lines))

    # --- serialization (ref: block.py:417,473) -----------------------------
    def save_parameters(self, filename, deduplicate=False):
        """Writes the reference's binary .params format (ref: gluon/block.py
        save_parameters → ndarray.cc NDArray::Save) — loadable by the
        reference and vice versa."""
        from ..serialization import atomic_write_file, save_ndarray_file
        params = self._collect_params_with_prefix()
        if deduplicate:
            # shared Parameter objects are stored once, under the first
            # structured name that reaches them (reference deduplicate
            # contract); load with allow_missing for the aliased names
            seen = set()
            uniq = {}
            for key, val in params.items():
                if id(val) in seen:
                    continue
                seen.add(id(val))
                uniq[key] = val
            params = uniq
        arg_dict = {key: val._reduce_np() if hasattr(val, '_reduce_np')
                    else val.data().asnumpy() for key, val in params.items()}
        atomic_write_file(filename, save_ndarray_file(arg_dict))

    def _collect_params_with_prefix(self, prefix=''):
        if prefix:
            prefix += '.'
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source='current'):
        from ..serialization import load_params_dict
        with open(filename, 'rb') as f:
            # allow_pickle: legacy round-1 .params files are still loadable
            # (restricted numpy-only unpickler; warns once when hit)
            loaded = load_params_dict(f.read(), allow_pickle=True)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        for name, param in params.items():
            if name not in loaded:
                if not allow_missing:
                    raise MXNetError(
                        f"Parameter '{name}' is missing in file '{filename}'")
                continue
            val = loaded[name]
            if param._data is None:
                if param._deferred_init:
                    param.shape = val.shape
                    param._finish_deferred_init()
                else:
                    param.initialize(ctx=ctx or [cpu(0)])
            param.set_data(array(val))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"extra parameters in file: {sorted(extra)}")

    save_params = save_parameters
    load_params = load_parameters

    def __repr__(self):
        s = f"{type(self).__name__}("
        for name, child in self._children.items():
            s += f"\n  ({name}): {repr(child)}"
        return s + (")" if not self._children else "\n)")


class HybridBlock(Block):
    """Block compilable into one XLA executable (ref: block.py:827)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_op = None
        self._flags = {}
        self._subgraph_backend = None

    def hybridize(self, active=True, backend=None, clear=True, **kwargs):
        """Ref: block.py:1043. `backend` names a registered subgraph
        partitioner (mxnet_tpu.subgraph) that pattern-matches the traced
        graph and swaps matched regions for fused kernels — the analog of
        the reference's SubgraphProperty backends
        (src/operator/subgraph/subgraph_property.h:252). None keeps the
        plain XLA compilation path."""
        self._active = active
        if backend is None:
            from .. import config as _config
            backend = _config.get('MXNET_SUBGRAPH_BACKEND') or None
        if backend is not None:
            from .. import subgraph as _subgraph
            self._subgraph_backend = _subgraph.get_backend(backend)
        elif clear:
            self._subgraph_backend = None
        self._flags.update(kwargs)
        if clear:
            self._cached_op = None
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._cached_op = None
        super().cast(dtype)

    def infer_shape(self, *args):
        self._deferred_infer(args)

    def _deferred_infer(self, args):
        """Run forward once with recording off to trigger deferred param
        init via the layers' own shape inference."""
        pass

    def __call__(self, *args):
        from .. import symbol as sym_mod
        if args and isinstance(args[0], sym_mod.Symbol):
            # symbolic trace (export path) bypasses the compiled cache
            return self.forward(*args)
        if self._active:
            try:
                out = self._call_cached_op(*args)
            except DeferredInitializationError:
                self._init_deferred(args)
                out = self._call_cached_op(*args)
            for hook in self._forward_hooks:
                hook(self, args, out)
            return out
        try:
            return super().__call__(*args)
        except DeferredInitializationError:
            self._init_deferred(args)
            return super().__call__(*args)

    def _init_deferred(self, args):
        # finish deferred init by running shape inference in eager mode
        for child in self._children.values():
            pass
        # layers resolve their own deferred params in forward; run once eagerly
        from ..base import state as _st
        rec = _st.is_recording
        _st.is_recording = False
        try:
            self.forward(*args)
        finally:
            _st.is_recording = rec

    def _call_cached_op(self, *args):
        if self._cached_op is None:
            self._cached_op = CachedOp(self, self._flags)
        return self._cached_op(*args)

    def __deepcopy__(self, memo):
        """Copies drop the compiled trace cache (it closes over the original
        block's parameter objects and jitted executables)."""
        import copy as _copy
        new = object.__new__(type(self))
        memo[id(self)] = new
        for k, v in self.__dict__.items():
            if k == '_cached_op':
                new._cached_op = None
            else:
                setattr(new, k, _copy.deepcopy(v, memo))
        return new

    def forward(self, x, *args):
        """Dispatch to hybrid_forward with params (ref: block.py:1156).
        Symbol inputs trace the block into a Symbol DAG (params become
        named variables) — the export / mx2onnx path."""
        from .. import symbol as sym_mod
        if isinstance(x, sym_mod.Symbol):
            params = {i: sym_mod.var(j.name)
                      for i, j in self._reg_params.items()}
            return self.hybrid_forward(sym_mod, x, *args, **params)
        ctx = x.context if isinstance(x, NDArray) else current_context()
        try:
            params = {i: j.data(ctx) for i, j in self._reg_params.items()}
        except DeferredInitializationError:
            self._infer_param_shapes(x, args)
            params = {i: j.data(ctx) for i, j in self._reg_params.items()}
        return self.hybrid_forward(nd, x, *args, **params)

    def _infer_param_shapes(self, x, args):
        raise DeferredInitializationError(
            f"{type(self).__name__} has uninitialized parameters and no "
            "shape inference; initialize with explicit in_units/in_channels")

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0, remove_amp_cast=True,
               input_names=('data',)):
        """Export to `path-symbol.json` + `path-####.params`
        (ref: block.py:1106): the block is traced into a Symbol DAG and
        parameters are saved in the arg:/aux: keyed NDArray format, so the
        pair reloads via SymbolBlock.imports — same deployment contract as
        the reference."""
        from .. import symbol as sym_mod
        from ..ndarray import save as nd_save
        inputs = [sym_mod.var(n) for n in input_names]
        out = self(*inputs)
        if isinstance(out, (list, tuple)):
            raise MXNetError(
                "export supports single-output blocks; group outputs first")
        sym_file = f"{path}-symbol.json"
        out.save(sym_file)
        arg_names = set(out.list_arguments()) - set(input_names)
        payload = {}
        for name, p in self.collect_params().items():
            if name not in arg_names:
                continue
            key = ('aux:' if p.grad_req == 'null' else 'arg:') + name
            payload[key] = p.data()
        fname = f"{path}-{epoch:04d}.params"
        nd_save(fname, payload)
        return sym_file, fname

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """Partition for `backend` and build the cached op in one step
        (ref: block.py optimize_for)."""
        self.hybridize(True, backend=backend, **kwargs)
        return self(x, *args)


class CachedOp:
    """Compiled executable for a HybridBlock (ref: src/imperative/cached_op.cc).

    Traces block.forward with tracer-backed parameter proxies, compiles with
    jax.jit, caches per (shapes, dtypes, mode). Parameter mutations during
    trace (BatchNorm running stats) are returned functionally and written
    back after each call.
    """

    def __init__(self, block, flags=None):
        self.block = block
        self.flags = flags or {}
        self._cache = {}

    def _params_for(self, ctx):
        params = []
        for name, p in sorted(self.block.collect_params().items()):
            params.append((name, p))
        return params

    def __call__(self, *inputs):
        ctx = None
        for x in inputs:
            if isinstance(x, NDArray):
                ctx = x.context
                break
        params = self._params_for(ctx)
        # force deferred-init resolution before tracing
        for _, p in params:
            if p._data is None:
                raise DeferredInitializationError(
                    f"Parameter '{p.name}' is deferred")
        from ..amp import amp as _amp
        key = (tuple((x.shape, str(x.dtype)) if isinstance(x, NDArray) else None
                     for x in inputs),
               state.is_training,
               # autocast state: a trace compiled before amp.init() must not
               # be reused after it (and vice versa)
               _amp.patch_epoch(),
               tuple(name for name, _ in params))
        entry = self._cache.get(key)
        compiled_now = False
        cctx = None
        site = f"cachedop:{self.block.name}"
        if entry is None:
            cctx = _compile.begin(site)
            t0 = _time.perf_counter()
            try:
                entry = self._build(params, inputs, state.is_training)
            except BaseException:
                _compile.abort(cctx)
                raise
            if cctx is not None:
                # the compile ledger takes over the counters: end(cctx)
                # below feeds record_compile with the structured
                # signature and the measured trace/lower/backend split
                _compile.set_signature(
                    cctx, self._compile_signature(params, inputs))
                compiled_now = True
            elif _telem['on']:
                from .. import telemetry as _telemetry
                _telemetry.record_compile(
                    site, repr(key[0]), _time.perf_counter() - t0)
                compiled_now = True
            self._cache[key] = entry
        elif _telem['on']:
            from .. import telemetry as _telemetry
            _telemetry.record_cache_hit(site)
        jitted, aux_names = entry

        rng = _random.next_key()

        # one taped node for the whole compiled call
        param_arrs = [p.data(ctx) for _, p in params]
        input_arrs = [x for x in inputs if isinstance(x, NDArray)]

        def run(*datas):
            n = len(params)
            outs, aux = jitted(list(datas[:n]), list(datas[n:]), rng)
            return tuple(outs) + tuple(aux)

        all_inputs = param_arrs + input_arrs
        t0 = _time.perf_counter()
        try:
            out_data, tensor_inputs, vjp_fn, gfn = _imperative.invoke(
                run, tuple(all_inputs), {})
        except BaseException:
            _compile.abort(cctx)
            raise
        if compiled_now:
            # _build only traced (jit is lazy): the first execution is
            # where XLA actually lowers and compiles — that is the cost
            # the recompile counters must show, not the trace time
            if cctx is not None:
                _compile.end(cctx)
            else:
                from .. import telemetry as _telemetry
                _telemetry.counter('mxnet_tpu_compile_seconds_total').inc(
                    _time.perf_counter() - t0, site=site)
        n_aux = len(aux_names)
        if n_aux:
            outs_flat, aux = out_data[:-n_aux], out_data[-n_aux:]
        else:
            outs_flat, aux = out_data, ()
        # write back mutated aux states (running stats). Inside an outer
        # trace, write to the outer proxy so the mutation is threaded out
        # functionally; otherwise update the real storage.
        name_to_param = dict(params)
        for name, new_val in zip(aux_names, aux):
            p = name_to_param[name]
            proxy = p._trace_proxy
            if proxy is not None:
                proxy._data = new_val
            else:
                for d in p._data:
                    d._data = jax.device_put(new_val, d._data.sharding)

        out_arrs = [_wrap(o) for o in outs_flat]
        if vjp_fn is not None:
            aux_arrs = [_wrap(a) for a in aux]
            _imperative.record_node(tensor_inputs, out_arrs + aux_arrs,
                                    vjp_fn, gfn,
                                    f"cachedop_{self.block.name}",
                                    tuple_out=True)
        if len(out_arrs) == 1:
            return out_arrs[0]
        return tuple(out_arrs)

    def _compile_signature(self, params, inputs):
        """Compile-ledger signature of one CachedOp variant: per-input
        shape/dtype rows plus the mode knobs baked into the cache key."""
        from ..amp import amp as _amp
        args = [_compile.array_sig(f'in{i}', x)
                for i, x in enumerate(inputs) if isinstance(x, NDArray)]
        return _compile.signature(args=args, flags={
            'training': bool(state.is_training),
            'amp_epoch': _amp.patch_epoch(),
            'params': len(params),
        })

    def _build(self, params, example_inputs, is_training):
        block = self.block
        aux_names_holder = []

        # param_datas is a positional LIST (sorted-name order), not a
        # name-keyed dict: dict keys land in the lowered module's arg
        # metadata, and gluon's auto-naming counter (dense0_, dense3_,
        # ...) would churn the persistent XLA cache key across processes
        # for structurally identical blocks. Names stay in this closure.
        def fn(param_datas, input_datas, rng):
            proxies = {}
            for (name, p), data in zip(params, param_datas):
                proxies[name] = NDArray(data)
                p._set_trace_proxy(proxies[name])
            orig_ids = {name: id(proxies[name]._data) for name, _ in params}
            wrapped = []
            it = iter(input_datas)
            for x in example_inputs:
                if isinstance(x, NDArray):
                    wrapped.append(NDArray(next(it)))
                else:
                    wrapped.append(x)
            prev_training = state.is_training
            state.is_training = is_training
            try:
                with _random.key_provider(_random.TraceKeyProvider(rng)), \
                        block._trace_scope():
                    out = block.forward(*wrapped)
            finally:
                state.is_training = prev_training
                for _, p in params:
                    p._clear_trace_proxy()
            outs = [out] if isinstance(out, NDArray) else list(out)
            out_datas = [o._data for o in outs]
            aux = []
            aux_names = []
            for name, _ in params:
                if id(proxies[name]._data) != orig_ids[name]:
                    aux_names.append(name)
                    aux.append(proxies[name]._data)
            aux_names_holder.clear()
            aux_names_holder.extend(aux_names)
            return out_datas, aux

        backend = getattr(self.block, '_subgraph_backend', None)
        if backend is not None:
            fn = backend.rewrite(fn)
        jitted = jax.jit(fn)
        # trace once now to discover aux names (jit caches the trace)
        ctx = None
        param_datas = [p.data(ctx)._data for _, p in params]
        input_datas = [x._data for x in example_inputs if isinstance(x, NDArray)]
        rng = jax.random.PRNGKey(0)
        _ = jax.eval_shape(jitted, param_datas, input_datas, rng)
        return jitted, list(aux_names_holder)


class SymbolBlock(HybridBlock):
    """Construct a block from a saved symbol+params (ref: block.py:1218)."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        s = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        ret = SymbolBlock(s, inputs)
        if param_file is not None:
            from ..ndarray import load as nd_load
            ret._load_arg_dict(nd_load(param_file), ctx=ctx)
        return ret

    def _load_arg_dict(self, loaded, ctx=None):
        """Load {\"arg:name\"/\"aux:name\"/name: NDArray} into this block's
        symbol parameters (shared by imports and the ONNX importer)."""
        input_names = {i.name for i in self._sym_inputs}
        arg_names = set(self._sym_outputs.list_arguments()) - input_names
        for key, arr in loaded.items():
            name = key.split(':', 1)[1] if ':' in key else key
            if name not in arg_names:
                continue
            p = self.params.get(name)
            p.shape = tuple(arr.shape)
            p.initialize(init='zeros', ctx=ctx)
            p.set_data(arr)

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix='', params=params)
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        self._sym_outputs = outputs
        self._sym_inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        input_names = {i.name for i in self._sym_inputs}
        for name in outputs.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)

    def forward(self, *args):
        from .. import symbol as sym_mod
        bindings = {i.name: x for i, x in zip(self._sym_inputs, args)}
        ctx = args[0].context if isinstance(args[0], NDArray) else None
        for name, p in self.params.items():
            if p._data is not None:
                bindings[name] = p.data(ctx)
        return self._sym_outputs.eval_dict(bindings)
