"""Gluon Parameter / ParameterDict.

Reference parity: python/mxnet/gluon/parameter.py — Parameter with deferred
shape init (shape entries of 0 solved at first forward), grad_req/lr_mult/
wd_mult, Constant, ParameterDict with prefix namespacing and shared params.

TPU-first notes: the reference keeps one copy of each parameter per GPU
(``list_data``); here a parameter is ONE logical array — multi-chip placement
is a *sharding* of that array over the mesh (jax.sharding), applied by the
Trainer/parallel layer, not by replicating handles.  ``list_data`` therefore
returns a single-element list.
"""

from __future__ import annotations

import re

import numpy as _np

from ..base import MXNetError, np_dtype
from ..context import Context, current_context, cpu
from ..ndarray.ndarray import NDArray, _from_jax
from ..profiler import scope
from .. import initializer


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its deferred shape was known."""


class Parameter:
    """A Block parameter (reference: gluon.Parameter)."""

    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self._var = None
        self._data = None
        self._grad = None
        self._ctx_list = None
        self._deferred_init = ()
        self._differentiable = differentiable
        self._allow_deferred_init = allow_deferred_init
        self._grad_req = None
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.name = name
        self._dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.grad_req = grad_req
        self.init = init
        self._stype = stype
        self._grad_stype = grad_stype
        # sharding annotation (TPU-native extension): a
        # jax.sharding.PartitionSpec set by the parallel layer; applied when
        # the parameter is materialized inside a Mesh scope.
        self.partition_spec = None

    def __repr__(self):
        s = "Parameter {name} (shape={shape}, dtype={dtype})"
        return s.format(name=self.name, shape=self.shape, dtype=self.dtype)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null"), \
            f"grad_req must be one of 'write', 'add', or 'null', but got {req}"
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                self._data._grad = None
                self._data._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    @property
    def dtype(self):
        return self._dtype

    @dtype.setter
    def dtype(self, dtype):
        self.cast(dtype)

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        assert len(self._shape) == len(new_shape) and \
            all(j in (0, i) for i, j in zip(new_shape, self._shape)), \
            f"Expected shape {new_shape} is incompatible with given shape " \
            f"{self._shape}."
        self._shape = tuple(new_shape)

    @property
    def stype(self):
        return self._stype

    def _check_and_get(self, arr, ctx):
        if arr is not None:
            return arr
        if self._deferred_init:
            raise DeferredInitializationError(
                f"Parameter '{self.name}' has not been initialized yet "
                "because initialization was deferred. Actual initialization "
                "happens during the first forward pass. Please pass one "
                "batch of data through the network before accessing "
                "Parameters.")
        raise RuntimeError(
            f"Parameter '{self.name}' has not been initialized. Note that "
            "you should initialize parameters and create Trainer with "
            "Block.collect_params() instead of Block.params because the "
            "later does not include Parameters of nested child Blocks")

    def _load_init(self, data, ctx=None, cast_dtype=False,
                   dtype_source="current"):
        """Load from a saved NDArray (reference: Parameter._load_init)."""
        if self.shape:
            unknown_dim_ok = any(s == 0 for s in self.shape)
            for self_dim, data_dim in zip(self.shape, data.shape):
                assert self_dim in (0, data_dim), \
                    f"Failed loading Parameter '{self.name}' from saved " \
                    f"params: shape incompatibility, expected {self.shape} " \
                    f"vs saved {data.shape}"
            self._shape = data.shape
        if self.dtype is not None and not cast_dtype:
            if _np.dtype(self.dtype).type != _np.dtype(data.dtype).type:
                raise AssertionError(
                    f"Failed loading Parameter '{self.name}' from saved "
                    f"params: dtype incompatibility, expected "
                    f"{self.dtype} vs saved {data.dtype}. Set cast_dtype=True "
                    "to cast the dtype of saved params.")
        elif cast_dtype:
            if dtype_source == "current":
                data = data.astype(self.dtype)
            elif dtype_source == "saved":
                self._dtype = data.dtype
        self._init_impl(data)
        self._deferred_init = ()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init_fn, default_init, ctx = self._deferred_init
        self._deferred_init = ()
        assert self.shape is not None and all(s > 0 for s in self.shape), \
            f"Cannot initialize Parameter '{self.name}' because it has " \
            f"invalid shape: {self.shape}."
        self._init_impl_from_init(init_fn, default_init, ctx)

    def _init_impl_from_init(self, init_fn, default_init, ctx):
        """Materialize + run initializers.  A specific init (the `init`
        argument or self.init) rides in InitDesc attrs and takes precedence
        over the global initializer's name-suffix dispatch (reference:
        Parameter._init_impl + attrs['__init__'])."""
        import jax.numpy as jnp

        data = _from_jax(jnp.zeros(self.shape, dtype=np_dtype(self.dtype)))
        specific = init_fn if init_fn is not None else self.init
        dispatcher = initializer.create(
            default_init if default_init is not None else "uniform")
        attrs = {"__init__": specific} if specific is not None else {}
        dispatcher(initializer.InitDesc(self.name, attrs), data)
        self._init_impl(data, ctx)

    def _init_impl(self, data, ctx=None):
        if not isinstance(data, NDArray):
            import jax.numpy as jnp

            data = _from_jax(jnp.asarray(data, dtype=np_dtype(self.dtype)))
        # a parameter lives where its ctx says; what the initializer
        # produced sits on JAX's default device.  An accelerator
        # placement is always committed, so that mixing it with host
        # arrays raises instead of quietly computing on the host.  On
        # the host CPU an array already there stays uncommitted:
        # replicated-by-default flows (a mesh-sharded table next to
        # plain parameters) lean on that.
        import jax

        ctx = ctx or current_context()
        dev = ctx.jax_device
        if dev.platform != "cpu" or data._data.devices() != {dev}:
            data._data = jax.device_put(data._data, dev)
        data._ctx = ctx
        self._data = data
        self._ctx_list = [ctx]
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._data.attach_grad(self._grad_req, stype=self._grad_stype)
        self._grad = self._data._grad

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """Materialize the parameter (reference: Parameter.initialize).

        Deferred when the shape still contains unknown (0) dims and
        allow_deferred_init is set.
        """
        if self._data is not None and not force_reinit:
            return
        if default_init is None:
            default_init = initializer.Uniform()
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self.shape is None or any(s <= 0 for s in self.shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, default_init,
                                       ctx[0] if ctx else None)
                return
            raise ValueError(
                f"Cannot initialize Parameter '{self.name}' because it has "
                f"invalid shape: {self.shape}.")
        self._deferred_init = ()
        self._init_impl_from_init(init, default_init,
                                  ctx[0] if ctx else None)

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_context(
                ctx[0] if isinstance(ctx, (list, tuple)) else ctx)
            if self._grad_req != "null":
                self._init_grad()

    def set_data(self, data):
        """Set the value on every context (reference: Parameter.set_data).
        A ``startup.params`` span: loading a model is a run of these."""
        raw = data._data if isinstance(data, NDArray) else data
        with scope("startup.params", what="set_data",
                   bytes=int(getattr(raw, "nbytes", 0))):
            self.shape = data.shape
            if self._data is None:
                assert self._deferred_init, \
                    f"Parameter '{self.name}' has not been initialized"
                self._init_impl(data if isinstance(data, NDArray)
                                else _from_jax(data))
                self._deferred_init = ()
                return
            self._data._set_data(raw.astype(self._data._data.dtype)
                                 if hasattr(raw, "astype") else raw)

    def row_sparse_data(self, row_id):
        return self.data()

    def list_row_sparse_data(self, row_id):
        return [self.data()]

    def data(self, ctx=None):
        return self._check_and_get(self._data, ctx)

    def list_data(self):
        return [self._check_and_get(self._data, None)]

    def grad(self, ctx=None):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                f"Cannot get gradient array for Parameter '{self.name}' "
                "because grad_req='null'")
        self._check_and_get(self._data, ctx)
        return self._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return [self._deferred_init[2] or current_context()]
            raise RuntimeError(f"Parameter '{self.name}' has not been "
                               "initialized")
        return list(self._ctx_list)

    def zero_grad(self):
        if self._grad is None:
            return
        import jax.numpy as jnp

        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(self._grad, RowSparseNDArray):
            # drop to the empty compact form — densifying a big
            # embedding's grad just to zero it would be O(table)
            g = self._grad
            g._set_sparse(jnp.zeros((0,), jnp.int32),
                          jnp.zeros((0,) + g.shape[1:],
                                    g._rs_values.dtype))
            return
        self._grad._set_data(jnp.zeros_like(self._grad._data))

    def cast(self, dtype):
        self._dtype = np_dtype(dtype)
        if self._data is None:
            return
        self._data._set_data(self._data._data.astype(np_dtype(dtype)))
        if self._grad_req != "null":
            self._init_grad()

    def var(self):
        """Symbol placeholder for this parameter (reference: Parameter.var)."""
        from .. import symbol

        if self._var is None:
            self._var = symbol.var(self.name, shape=self.shape,
                                   dtype=self.dtype, lr_mult=self.lr_mult,
                                   wd_mult=self.wd_mult, init=self.init)
        return self._var

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_var"] = None
        return state


class Constant(Parameter):
    """Non-trainable constant parameter (reference: gluon.Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            import jax.numpy as jnp

            value = _from_jax(jnp.asarray(_np.asarray(value)))
        self.value = value

        class Init(initializer.Initializer):
            def _init_weight(self, _, arr):
                arr._set_data(value._data.astype(arr._data.dtype))

        init_name = f"Constant_{name}_{id(self)}"
        initializer._INIT_REGISTRY[init_name.lower()] = Init
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=init_name.lower())


class ParameterDict:
    """Ordered dict of Parameters with prefix (reference:
    gluon.ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
        self._shared = shared

    def __repr__(self):
        s = "{name}(\n{content}\n)"
        name = self._prefix + " " if self._prefix else ""
        return s.format(name=name, content="\n".join(
            [repr(v).replace("\n", "\n  ") for v in self.values()]))

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._shared._params[name]
        return None

    def get(self, name, **kwargs):
        """Get-or-create ``self.prefix + name`` (reference semantics: found
        params must be attribute-compatible with kwargs)."""
        name = self.prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and len(v) == len(existing):
                        inferred_shape = []
                        matched = True
                        for dim1, dim2 in zip(v, existing):
                            if dim1 != dim2 and dim1 * dim2 != 0:
                                matched = False
                                break
                            inferred_shape.append(max(dim1, dim2))
                        if matched:
                            param._shape = tuple(inferred_shape)
                            continue
                    elif k == "dtype" and _np.dtype(v) == _np.dtype(existing):
                        continue
                    assert v is None or v == existing, \
                        f"Cannot retrieve Parameter '{name}' because " \
                        f"desired attribute does not match with stored for " \
                        f"attribute '{k}': desired '{v}' vs stored " \
                        f"'{getattr(param, k)}'"
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self.prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError(
                    f"No constant named '{name}'. Please specify value if "
                    "you want to create a new constant.")
            param = Constant(name, value)
            self._params[name] = param
        elif value is not None:
            assert isinstance(param, Constant), \
                f"Parameter '{name}' already exists but it is not a constant."
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    f"Cannot update self with other because they have " \
                    f"different Parameters with the same name '{k}'"
            else:
                self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if verbose and init is not None:
            init.set_verbosity(verbose=verbose)
        with scope("startup.params", what="initialize", leaves=len(self)):
            for _, v in self.items():
                v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def list_ctx(self):
        s = set()
        for v in self.values():
            s.update(v.list_ctx())
        return list(s)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        from ..ndarray import save as nd_save

        arg_dict = {}
        for param in self.values():
            weight = param.data()
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    f"Prefix '{strip_prefix}' is to be striped before "
                    f"saving, but Parameter's name '{param.name}' does not "
                    "start with it")
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd_save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix="", cast_dtype=False,
             dtype_source="current"):
        from ..ndarray import load as nd_load

        if restore_prefix:
            for name in self.keys():
                assert name.startswith(restore_prefix), \
                    f"restore_prefix is '{restore_prefix}' but Parameter " \
                    f"name '{name}' does not start with it"
        lprefix = len(restore_prefix)
        loaded = nd_load(filename)
        arg_dict = {(restore_prefix + k[4:] if k.startswith(("arg:", "aux:"))
                     else restore_prefix + k): v for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    f"Parameter '{name[lprefix:]}' is missing in file " \
                    f"'{filename}'"
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    f"Parameter '{name[lprefix:]}' loaded from file " \
                    f"'{filename}' is not present in ParameterDict"
                continue
            self[name]._load_init(arg_dict[name], ctx,
                                  cast_dtype=cast_dtype,
                                  dtype_source=dtype_source)
