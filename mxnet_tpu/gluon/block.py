"""Gluon Block / HybridBlock.

Reference parity: python/mxnet/gluon/block.py — Block (dynamic graph,
name scopes, child registration, parameter collection, save/load) and
HybridBlock (``hybridize()``).

TPU-first redesign of the CachedOp (reference: src/imperative/cached_op.cc):
``hybridize()`` makes the whole block compile to ONE XLA program via
``jax.jit`` of a pure function ``(prng_key, params, *inputs) → (outputs,
aux_updates)``:

- parameters become explicit jit arguments (differentiable, never
  constant-folded) delivered to layers through a trace-time substitution
  scope;
- train-mode statefulness (BatchNorm moving stats) is functionalized: layers
  record new aux values into a collector during the trace; the compiled
  program returns them and the wrapper writes them back — replacing the
  reference's in-kernel aux mutation;
- randomness (Dropout) folds a per-call key argument (random.key_scope), so
  replays draw fresh masks without retracing;
- the autograd tape records ONE node holding the jit-vjp of the whole
  program: forward and backward each execute as a single compiled XLA
  program — the reference's CachedOp::Forward/Backward bulked segments,
  with XLA doing the memory planning the reference's nnvm passes did.
"""

from __future__ import annotations

import re
import threading
from collections import OrderedDict

from .. import autograd as _ag
from .. import name as _name
from ..base import MXNetError, np_dtype
from ..ndarray.ndarray import NDArray, _from_jax
from ..profiler import scope
from .parameter import (DeferredInitializationError, Parameter, ParameterDict)


class _TraceState(threading.local):
    def __init__(self):
        self.param_map = None    # id(Parameter) -> traced array
        self.aux_collector = None  # name -> raw new value
        self.force_eager = False


_TRACE = _TraceState()


class _BlockScope:
    """Name/parameter scoping for child blocks (reference: _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        self._name_scope = _name.Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


_CASTING = threading.local()    # .outer: inside a `Block.cast` already


class Block:
    """Base of all neural network layers and models (reference:
    gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            [f"  ({key}): " + repr(block).replace("\n", "\n  ")
             for key, block in self._children.items()])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed. " \
                "If you want to share parameters between blocks, please " \
                "set 'params' at Block construction instead."
            self._reg_params[name] = value
        super().__setattr__(name, value)

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

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All Parameters of this block and children, optionally filtered by
        regex `select` (reference: Block.collect_params)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _check_container_with_block(self):
        children = set(self._children.values())
        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and k != "_children":
                flat = v.values() if isinstance(v, dict) else v
                for item in flat:
                    if isinstance(item, Block) and item not in children:
                        import warnings

                        warnings.warn(
                            f'"{item}" is an unregistered container with '
                            "Blocks. Note that Blocks inside the list, tuple "
                            "or dict will not be registered automatically. "
                            "Make sure to register them using "
                            "register_child() or switching to "
                            "nn.Sequential/nn.HybridSequential instead.")

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Save parameters with structural names (reference:
        Block.save_parameters → .params file format)."""
        from ..ndarray import save as nd_save

        params = self._collect_params_with_prefix()
        if deduplicate:
            reverse_params = {}
            for k, v in params.items():
                if v not in reverse_params.values():
                    reverse_params[k] = v
            params = reverse_params
        arg_dict = {key: val._reduce() if hasattr(val, "_reduce")
                    else val.data() for key, val in params.items()}
        nd_save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        """Load from save_parameters format; also accepts full-name
        (save_params legacy / ParameterDict.save) files."""
        from ..ndarray import load as nd_load

        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in i for i in loaded.keys()):
            # legacy full-prefix format
            del loaded
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix,
                cast_dtype=cast_dtype, dtype_source=dtype_source)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    f"Parameter '{name}' is missing in file '{filename}', " \
                    f"which contains parameters: {_brief_print_list(loaded.keys())}. " \
                    "Set allow_missing=True to ignore missing parameters."
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    f"Parameter '{name}' loaded from file '{filename}' is "
                    "not present in ParameterDict, which contains parameters "
                    f"{_brief_print_list(params.keys())}. Set "
                    "ignore_extra=True to ignore.")
            if name in params:
                params[name]._load_init(loaded[name], ctx,
                                        cast_dtype=cast_dtype,
                                        dtype_source=dtype_source)

    save_params = save_parameters
    load_params = load_parameters

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def apply(self, fn):
        """Apply fn recursively to self and children (reference:
        Block.apply)."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as _init

        self.collect_params().initialize(
            init if init is not None else _init.Uniform(), ctx, verbose,
            force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        """Cast the parameters of this block and of those below it.  The
        outermost call is one ``startup.params`` span; its children's
        calls run inside it."""
        if getattr(_CASTING, "outer", False):
            return self._cast_tree(dtype)
        _CASTING.outer = True
        try:
            with scope("startup.params", what="cast", dtype=str(dtype)):
                self._cast_tree(dtype)
        finally:
            _CASTING.outer = False

    def _cast_tree(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (reference: Block.summary)."""
        from ..visualization import block_summary

        block_summary(self, *inputs)


def _brief_print_list(lst, limit=7):
    lst = list(lst)
    if len(lst) > limit:
        return _brief_print_list(lst[:limit // 2], limit) + ", ..., " + \
            _brief_print_list(lst[-limit // 2:], limit)
    return ", ".join([f"'{str(i)}'" for i in lst])


class HybridBlock(Block):
    """A Block compilable into one XLA program (reference: gluon.HybridBlock
    + src/imperative/cached_op.cc; see module docstring for the design)."""

    # activation sharding annotation (parallel/sharding.py): a
    # (spec_tuple, mesh) pair applied to this block's forward output via
    # with_sharding_constraint — class attr so pre-existing instances
    # and __setattr__-before-__init__ paths read None cheaply
    _act_spec = None

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = []
        self._jit_fns = {}
        self._param_order = None
        if not hasattr(self, "_cache_version"):
            self._cache_version = 0

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, (HybridBlock, Parameter)):
            self._clear_cached_op()

    def _clear_cached_op(self):
        self._jit_fns = {}
        self._param_order = None
        # Monotonic structure-version: every event that invalidates the
        # CachedOp (parameter set, register_child, hybridize, cast, LoRA
        # attach/detach) lands here, so external caches keyed on this
        # block (Trainer's captured train_step) invalidate on the same
        # events.  getattr: __setattr__ fires before __init__ finishes.
        self._cache_version = getattr(self, "_cache_version", 0) + 1

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                f"Children of HybridBlock must also be HybridBlock, but "
                f"{str(block)} has type {str(type(block))}. If you are using "
                "Sequential, please try HybridSequential instead.")
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = list(kwargs.items())
        self._clear_cached_op()
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Complete deferred parameter shapes from input shapes.  Built-in
        layers override; composite blocks resolve child-by-child during the
        eager pass, so they don't need to."""
        raise ValueError(
            f"Deferred initialization failed because shape cannot be "
            f"inferred for block {self.name}. Override infer_shape, or "
            "construct the layer with explicit input dims.")

    def infer_type(self, *args):
        pass

    def optimize_for(self, x, backend="XLA", *extra, **kwargs):
        """Partition this block's traced graph for a subgraph backend
        and return a SymbolBlock running the partitioned graph with the
        current parameters bound (reference: HybridBlock.optimize_for,
        ≥1.6 — the MKLDNN/TensorRT offload entry).  ``x`` warms the
        trace exactly like the reference's sample input."""
        from .. import symbol as _sym

        if not self._active:
            self.hybridize()
        self(x, *extra)  # materialize deferred shapes / build the cache
        # trace with explicit, ordered input names so multi-input blocks
        # bind positionally in SymbolBlock (a hard-coded single 'data'
        # var mis-binds them)
        n_in = 1 + len(extra)
        in_names = ["data"] if n_in == 1 else \
            [f"data{i}" for i in range(n_in)]
        sym = _sym.trace_block(self, inputs=in_names)
        psym = sym.optimize_for(backend, **kwargs)
        sb = SymbolBlock(psym, [_sym.var(n) for n in in_names])
        params = self.collect_params()
        for name, p in sb.params.items():
            if name in params:
                p._load_init(params[name].data(), None, cast_dtype=True)
        return sb

    def export(self, path, epoch=0):
        """Serialize to symbol.json + params (reference: HybridBlock.export
        → the deploy format)."""
        from .. import symbol as _sym

        if not self._active:
            raise RuntimeError(
                "Please first call block.hybridize() and then run forward "
                "with this block at least once before calling export.")
        sym = _sym.trace_block(self)
        sym.save(f"{path}-symbol.json")
        from ..ndarray import save as nd_save

        # arg:/aux: keyed by the SAME global names the traced Variables
        # carry (Parameter.name), the reference's deploy convention —
        # SymbolBlock.imports matches sym.list_inputs() against these
        arg_dict = {}
        for name, param in self.collect_params().items():
            tag = "aux" if param.grad_req == "null" else "arg"
            arg_dict[f"{tag}:{name}"] = param.data()
        nd_save(f"{path}-{epoch:04d}.params", arg_dict)
        return sym

    # -- activation sharding ---------------------------------------------------

    def shard_activations(self, spec, mesh=None):
        """Pin this block's forward output to a PartitionSpec (Megatron
        activation annotation, e.g. ``('dp', None, 'tp')`` after a
        column-parallel projection).  ``mesh=None`` resolves the process
        default mesh at call time.  Takes effect inside every jit that
        traces this block — CachedOp forward and the captured train
        step — and is a no-op when no mesh (or a trivial one) is
        active, so annotated models still run unsharded."""
        self._act_spec = (tuple(spec), mesh)
        self._clear_cached_op()
        return self

    def _constrain_out(self, out):
        if self._act_spec is None:
            return out
        from ..parallel.mesh import default_mesh
        from ..parallel.sharding import constrain

        spec, mesh = self._act_spec
        if mesh is None:
            mesh = default_mesh()
        if mesh is None:
            return out

        def one(v):
            if isinstance(v, NDArray):
                v._set_data(constrain(v._data, mesh, spec))
                return v
            if hasattr(v, "ndim"):
                return constrain(v, mesh, spec)
            return v

        if isinstance(out, (tuple, list)):
            return type(out)(one(v) for v in out)
        return one(out)

    # -- forward dispatch ------------------------------------------------------

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            # remember the seen input shapes: the export trace attaches
            # them to its Variables so hybrid_forward code reading
            # x.shape keeps working symbolically
            self._last_input_shapes = [
                tuple(a.shape) if hasattr(a, "shape") else None
                for a in (x,) + args]
            if self._active and not _TRACE.force_eager:
                return self._call_cached_op(x, *args)
            return self._eager_forward(x, *args)
        from ..symbol import Symbol as _Symbol

        if isinstance(x, _Symbol):
            # symbolic dual dispatch (reference: F=mx.sym in
            # hybrid_forward): parameters become named Variables so the
            # traced graph round-trips through symbol.json + .params
            from .. import symbol as _sym_mod

            params = {}
            for k, p in self._reg_params.items():
                v = p.var()
                if p.grad_req == "null":
                    v._set_attr(__aux__=True)
                    v.attrs["__aux__"] = True
                params[k] = v
            return self.hybrid_forward(_sym_mod, x, *args, **params)
        # raw array / tracer: pure path inside an enclosing trace
        params = {}
        for k, p in self._reg_params.items():
            pm = _TRACE.param_map
            if pm is not None and id(p) in pm:
                params[k] = pm[id(p)]
            else:
                params[k] = p.data()._data
        from .. import ndarray as F

        return self._constrain_out(
            self.hybrid_forward(F, x, *args, **params))

    def _eager_forward(self, x, *args):
        from .. import ndarray as F

        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer_shape(x, *args)
            for p in self._reg_params.values():
                p._finish_deferred_init()
            params = {k: p.data() for k, p in self._reg_params.items()}
        return self._constrain_out(
            self.hybrid_forward(F, x, *args, **params))

    def _deferred_infer_shape(self, x, *args):
        self.infer_shape(x, *args)

    def _ensure_initialized(self, *args):
        try:
            for p in self.collect_params().values():
                if p._deferred_init:
                    raise DeferredInitializationError(p.name)
        except DeferredInitializationError:
            # one throwaway eager pass materializes every deferred shape
            # child-by-child (the reference runs the nnvm InferShape pass)
            prev = _TRACE.force_eager
            _TRACE.force_eager = True
            try:
                with _ag.pause():
                    self.forward(*args)
            finally:
                _TRACE.force_eager = prev

    def _get_jit_fn(self, training, args_tree, static_sig):
        cache_key = (training, args_tree, static_sig)
        fn = self._jit_fns.get(cache_key)
        if fn is not None:
            return fn
        import jax
        import jax.tree_util as jtu

        from .. import random as _random

        static_vals = dict(static_sig)

        def pure_step(key, param_vals, dyn_flat):
            flat = list(dyn_flat)
            for i, v in static_vals.items():
                flat.insert(i, v)
            call_args = jtu.tree_unflatten(args_tree, flat)
            pm = {pid: val for pid, val in
                  zip(self._param_order_ids, param_vals)}
            aux = {}
            with param_override_scope(pm, aux), \
                    _random.key_scope(key), \
                    (_ag.train_mode() if training
                     else _ag.predict_mode()):
                out = self.forward(*call_args)
            return out, aux

        # hybridize(remat=...) / MXNET_BACKWARD_DO_MIRROR: backward
        # recomputes activations (reference mirror pass; remat.py)
        from .. import remat as _remat

        pure_step = _remat.wrap(pure_step,
                                dict(self._flags).get("remat"))
        fn = jax.jit(pure_step)
        self._jit_fns[cache_key] = fn
        return fn

    def _call_cached_op(self, *args):
        """The CachedOp replay path: one compiled XLA program per
        (args-structure, shape-signature, train-mode).  Arguments may be
        arbitrary pytrees of NDArrays (e.g. the RNN `(x, [h, c])` call
        pattern); non-array leaves are compile-time constants."""
        import jax
        import jax.numpy as jnp
        import jax.tree_util as jtu

        from .. import random as _random

        self._ensure_initialized(*args)
        if self._param_order is None:
            allp = self.collect_params()
            self._param_order = list(allp.items())
            self._param_order_ids = [id(p) for _, p in self._param_order]

        flat_args, args_tree = jtu.tree_flatten(tuple(args))
        dyn_idx = [i for i, a in enumerate(flat_args)
                   if isinstance(a, NDArray) or hasattr(a, "shape")]
        dyn_set = set(dyn_idx)
        try:
            static_sig = tuple((i, a) for i, a in enumerate(flat_args)
                               if i not in dyn_set)
            hash(static_sig)
        except TypeError:
            static_sig = tuple((i, repr(a)) for i, a in enumerate(flat_args)
                               if i not in dyn_set)
        nd_pos_in_dyn = [j for j, i in enumerate(dyn_idx)
                         if isinstance(flat_args[i], NDArray)]
        nd_inputs = [flat_args[i] for i in dyn_idx
                     if isinstance(flat_args[i], NDArray)]
        dyn_raw = [flat_args[i]._data if isinstance(flat_args[i], NDArray)
                   else flat_args[i] for i in dyn_idx]

        param_nds = [p.data() for _, p in self._param_order]
        param_vals = [p._data for p in param_nds]
        training = _ag.is_training()
        jfn = self._get_jit_fn(training, args_tree, static_sig)
        key = _random.next_key()

        recording = _ag.is_recording() and (
            any(a._on_tape() for a in nd_inputs)
            or any(p._on_tape() for p in param_nds))

        if not recording:
            out, aux = jfn(key, param_vals, dyn_raw)
            self._write_aux(aux)
            out_leaves, out_tree = jtu.tree_flatten(out)
            return jtu.tree_unflatten(out_tree,
                                      [_from_jax(o) for o in out_leaves])

        out_aux, vjp_fn = jax.vjp(
            lambda pv, dr: jfn(key, pv, dr), param_vals, dyn_raw)
        out, aux = out_aux
        self._write_aux(aux)
        out_leaves, out_tree = jtu.tree_flatten(out)
        outs = [_from_jax(o) for o in out_leaves]
        aux_zero = jtu.tree_map(jnp.zeros_like, aux)
        n_out = len(outs)

        def tape_vjp(out_ct):
            cts = [out_ct] if n_out == 1 else list(out_ct)
            full_ct = (jtu.tree_unflatten(out_tree, cts), aux_zero)
            pv_ct, dyn_ct = vjp_fn(full_ct)
            return list(pv_ct) + [dyn_ct[j] for j in nd_pos_in_dyn]

        n_params = len(param_vals)

        def tape_pure(*raw):
            pv = list(raw[:n_params])
            dr = list(dyn_raw)
            for j, v in zip(nd_pos_in_dyn, raw[n_params:]):
                dr[j] = v
            out_p, _aux = jfn(key, pv, dr)
            leaves, _ = jtu.tree_flatten(out_p)
            return tuple(leaves) if len(leaves) > 1 else leaves[0]

        node = _ag.TapeNode(tape_vjp, param_nds + nd_inputs, outs,
                            name=f"CachedOp:{self.name}",
                            pure_fn=tape_pure)
        for o in outs:
            o._tape_node = node
        return jtu.tree_unflatten(out_tree, outs)

    def _write_aux(self, aux):
        if not aux:
            return
        with _ag.pause():
            byname = dict(self._param_order)
            for name, val in aux.items():
                p = byname.get(name)
                if p is not None:
                    p.data()._set_data(val)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


import contextlib


@contextlib.contextmanager
def param_override_scope(param_map, collected):
    """Run a block functionally: Parameters whose id() is in
    ``param_map`` read the mapped value instead of their stored data,
    and aux updates recorded via :func:`record_aux_update` land in the
    ``collected`` dict (keyed by param name).  The ONE home of the
    save/set/restore protocol — the whole-block jit path, the sharded
    trainer, and the pipeline trainer all enter through here.
    """
    prev_map, prev_aux = _TRACE.param_map, _TRACE.aux_collector
    _TRACE.param_map = param_map
    _TRACE.aux_collector = collected
    try:
        yield
    finally:
        _TRACE.param_map, _TRACE.aux_collector = prev_map, prev_aux


def record_aux_update(param_name, raw_value):
    """Layers call this to update an aux (non-differentiable) parameter from
    inside hybrid_forward — functionalized under a trace, immediate eagerly.

    Replaces the reference's in-kernel aux-state mutation
    (e.g. BatchNorm moving_mean, src/operator/nn/batch_norm.cc).
    """
    col = _TRACE.aux_collector
    if col is not None:
        col[param_name] = raw_value
        return True
    return False


class SymbolBlock(HybridBlock):
    """Run a loaded symbolic graph as a block (reference:
    gluon.SymbolBlock.imports for deploy-format models)."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as _sym

        sym = _sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym.var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.collect_params().load(param_file, ctx=ctx, cast_dtype=True,
                                      dtype_source="saved",
                                      allow_missing=False, ignore_extra=True)
        return ret

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        from .. import symbol as _sym

        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(inputs, _sym.Symbol):
            inputs = [inputs]
        self._outputs_sym = outputs
        self._input_names = [i.name for i in inputs]
        input_set = set(self._input_names)
        # every non-input free variable becomes a parameter of this block,
        # under its EXACT traced name (no symbolblock prefix — the deploy
        # .params file is keyed by the original global names)
        from .parameter import Parameter as _Param

        aux = set(outputs.list_auxiliary_states())
        for name in outputs.list_inputs():
            if name not in input_set and name not in self.params._params:
                self.params._params[name] = _Param(
                    name, shape=None, dtype=None,
                    allow_deferred_init=True,
                    grad_req="null" if name in aux else "write")

    def forward(self, *args):
        from .. import symbol as _sym

        feed = dict(zip(self._input_names, args))
        for name, p in self.params.items():
            feed[name] = p.data()
        return self._outputs_sym.eval(**feed)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError
