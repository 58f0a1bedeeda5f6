"""Weight initializers.

Reference parity: python/mxnet/initializer.py — Initializer base with
registry + name-pattern dispatch (``_weight``/``_bias``/``_gamma``/...),
InitDesc, and the built-ins: Zero, One, Constant, Uniform, Normal,
Orthogonal, Xavier, MSRAPrelu, Bilinear, LSTMBias, Mixed, Load.

Randomness draws from numpy's global RNG (seeded by ``mx.random.seed``,
matching the reference's CPU-side initializer behavior) — initialization is
a one-time host-side event, so there is no reason to burn a TPU PRNG key.
"""

from __future__ import annotations

import json
import logging
import re

import numpy as _np

from .base import MXNetError, np_dtype
from .ndarray.ndarray import NDArray, _from_jax

_INIT_REGISTRY = {}


def register(klass):
    name = klass.__name__.lower()
    _INIT_REGISTRY[name] = klass
    return klass


def create(name, **kwargs):
    """mx.init.create — build an initializer from its registered name."""
    if isinstance(name, Initializer):
        return name
    if name.lower() not in _INIT_REGISTRY:
        raise ValueError(f"Cannot find initializer {name}")
    return _INIT_REGISTRY[name.lower()](**kwargs)


class InitDesc(str):
    """Name + attrs descriptor passed to initializers (reference:
    mx.init.InitDesc)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    """Base initializer; callable on (InitDesc, NDArray)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        if print_func is None:
            def asum_stat(x):
                return str((_np.abs(x.asnumpy())).mean())
            print_func = asum_stat
        self._print_func = print_func
        return self

    def _verbose_print(self, desc, init, arr):
        if self._verbose and self._print_func:
            logging.info("Initialized %s as %s: %s", desc, init,
                         self._print_func(arr))

    def dumps(self):
        """JSON [name, kwargs] — reference serialization for sending the
        initializer to KVStore servers."""
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, str):
            raise TypeError("desc must be an InitDesc or string")
        if isinstance(desc, InitDesc):
            if desc.global_init is None:
                desc.global_init = self
            # a per-parameter init (Parameter(init=...)) overrides suffix
            # dispatch (reference: attrs['__init__'] handling)
            init = desc.attrs.get("__init__", "")
            if init:
                create(init)._init_weight(desc, arr)
                self._verbose_print(desc, str(init), arr)
                return
        if desc.endswith("weight"):
            self._init_weight(desc, arr)
        elif desc.endswith("embed_table"):
            # ShardedEmbedding's table (mxnet_tpu/embedding): a weight
            # in every sense — named differently only so the row-shard
            # overlay can claim it without colliding with the
            # column-parallel ``embedding\d*_weight`` TP rule
            self._init_weight(desc, arr)
        elif desc.endswith("bias"):
            self._init_bias(desc, arr)
        elif desc.endswith("gamma"):
            self._init_gamma(desc, arr)
        elif desc.endswith("beta"):
            self._init_beta(desc, arr)
        elif desc.endswith("min"):
            self._init_zero(desc, arr)
        elif desc.endswith("max"):
            self._init_one(desc, arr)
        elif desc.endswith("weight_quantize"):
            self._init_quantized_weight(desc, arr)
        elif desc.endswith("bias_quantize"):
            self._init_quantized_bias(desc, arr)
        else:
            self._init_default(desc, arr)
        self._verbose_print(desc, "init", arr)

    # legacy call signature: init(name, arr)
    def _legacy_init(self, name, arr):
        self.__call__(InitDesc(name), arr)

    def _set(self, arr, value):
        import jax.numpy as jnp

        arr._set_data(jnp.asarray(_np.asarray(value),
                                  dtype=arr._data.dtype))

    def _init_bilinear(self, _, arr):
        shape = arr.shape
        weight = _np.zeros(int(_np.prod(shape)), dtype="float32")
        f = _np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(_np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._set(arr, weight.reshape(shape))

    def _init_loc_bias(self, _, arr):
        assert arr.shape[0] == 6
        self._set(arr, _np.array([1.0, 0, 0, 0, 1.0, 0]))

    def _init_zero(self, _, arr):
        self._set(arr, _np.zeros(arr.shape))

    def _init_one(self, _, arr):
        self._set(arr, _np.ones(arr.shape))

    def _init_bias(self, _, arr):
        self._set(arr, _np.zeros(arr.shape))

    def _init_quantized_bias(self, _, arr):
        self._set(arr, _np.zeros(arr.shape))

    def _init_gamma(self, _, arr):
        self._set(arr, _np.ones(arr.shape))

    def _init_beta(self, _, arr):
        self._set(arr, _np.zeros(arr.shape))

    def _init_weight(self, name, arr):
        raise NotImplementedError("Must override it")

    def _init_quantized_weight(self, _, arr):
        self._set(arr, _np.random.randint(-127, 127, arr.shape))

    def _init_default(self, name, _):
        raise ValueError(
            f"Unknown initialization pattern for {name}. Default "
            "initialization is now limited to \"weight\", \"bias\", "
            "\"gamma\" (1.0), and \"beta\" (0.0). Please use "
            "mx.sym.Variable(init=mx.init.*) to set initialization "
            "pattern")

    def __eq__(self, other):
        if not isinstance(other, Initializer):
            return NotImplemented
        return (self.__class__ is other.__class__
                and self._kwargs == other._kwargs)

    __hash__ = None


@register
class Zero(Initializer):
    def __init__(self):
        super().__init__()

    def _init_weight(self, _, arr):
        # filled where it lies: a host array of a large model's zeros
        # (float64, 8 bytes a parameter) is drawn and shipped for nothing
        import jax.numpy as jnp

        arr._set_data(jnp.zeros(arr.shape, arr._data.dtype))


@register
class One(Initializer):
    def __init__(self):
        super().__init__()

    def _init_weight(self, _, arr):
        import jax.numpy as jnp

        arr._set_data(jnp.ones(arr.shape, arr._data.dtype))


# reference alias names (mx.init registry: @register(alias=...))
_INIT_REGISTRY["zeros"] = Zero
_INIT_REGISTRY["ones"] = One


@register
class Constant(Initializer):
    def __init__(self, value):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        v = self.value
        if isinstance(v, NDArray):
            v = v.asnumpy()
        self._set(arr, _np.broadcast_to(v, arr.shape))


@register
class Uniform(Initializer):
    """U(-scale, scale) (reference default scale 0.07)."""

    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        self._set(arr, _np.random.uniform(-self.scale, self.scale,
                                          arr.shape))


@register
class Normal(Initializer):
    """N(0, sigma) (reference default sigma 0.01)."""

    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        self._set(arr, _np.random.normal(0, self.sigma, arr.shape))


@register
class Orthogonal(Initializer):
    """Orthogonal matrix init (Saxe et al.; reference: mx.init.Orthogonal)."""

    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(_np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = _np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = _np.random.normal(0.0, 1.0, (nout, nin))
        u, _v, q = _np.linalg.svd(tmp, full_matrices=False)
        res = u if u.shape == tmp.shape else q
        self._set(arr, self.scale * res.reshape(arr.shape))


@register
class Xavier(Initializer):
    """Xavier/Glorot init (reference: mx.init.Xavier).

    factor_type in {'avg','in','out'}; rnd_type in {'uniform','gaussian'}.
    """

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise ValueError(
                f"Xavier initializer cannot be applied to vector {name}. "
                "It requires at least 2D.")
        if len(shape) > 2:
            hw_scale = _np.prod(shape[2:])
        fan_in = shape[1] * hw_scale
        fan_out = shape[0] * hw_scale
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise ValueError("Incorrect factor type")
        scale = _np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            self._set(arr, _np.random.uniform(-scale, scale, shape))
        elif self.rnd_type == "gaussian":
            self._set(arr, _np.random.normal(0, scale, shape))
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    """He/MSRA init for PReLU nets (reference: mx.init.MSRAPrelu)."""

    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    def __init__(self):
        super().__init__()

    def _init_weight(self, _, arr):
        self._init_bilinear(_, arr)


@register
class LSTMBias(Initializer):
    """Initializes LSTM biases to 0 except the forget gate (reference:
    mx.init.LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        bias = _np.zeros(arr.shape)
        num_hidden = int(arr.shape[0] / 4)
        bias[num_hidden:2 * num_hidden] = self.forget_bias
        self._set(arr, bias)

    _init_bias = _init_weight


@register
class FusedRNN(Initializer):
    """Initializer for fused RNN packed parameters (reference:
    mx.init.FusedRNN) — delegates per-slice to the wrapped initializer."""

    def __init__(self, init, num_hidden, num_layers, mode,
                 bidirectional=False, forget_bias=1.0):
        if isinstance(init, str):
            klass, kwargs = json.loads(init)
            init = _INIT_REGISTRY[klass.lower()](**kwargs)
        super().__init__(init=init.dumps() if init is not None else None,
                         num_hidden=num_hidden, num_layers=num_layers,
                         mode=mode, bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        # packed single-vector parameter: init as a whole via the wrapped
        # initializer, then overwrite LSTM forget-gate biases.  Packing
        # (ops/rnn.py): all (Wx, Wh) pairs layer/direction-major, then all
        # (bx, bh) pairs; LSTM gate order i f g o → forget slice [H, 2H).
        if self._init is not None:
            self._init._init_weight(desc, arr)
        if self._mode != "lstm":
            return
        a = arr.asnumpy().copy()
        h = self._num_hidden
        dirs = 2 if self._bidirectional else 1
        gates = 4
        bias_start = a.size - self._num_layers * dirs * 2 * gates * h
        off = bias_start
        for _layer in range(self._num_layers):
            for _d in range(dirs):
                a[off + h:off + 2 * h] = self._forget_bias  # bx forget
                off += gates * h
                a[off + h:off + 2 * h] = 0.0                # bh forget
                off += gates * h
        self._set(arr, a)


class Mixed:
    """Patterns → initializers dispatch (reference: mx.init.Mixed)."""

    def __init__(self, patterns, initializers):
        assert len(patterns) == len(initializers)
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise ValueError(
            f"Parameter name {name} did not match any pattern. Consider "
            "adding a \".*\" pattern at the and with default Initializer.")


@register
class Load:
    """Init from a dict of arrays, falling back to default_init
    (reference: mx.init.Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        if isinstance(param, str):
            from .ndarray import load as nd_load

            param = nd_load(param)
        self.param = {}
        for name, arr in param.items():
            if name.startswith("arg:") or name.startswith("aux:"):
                self.param[name[4:]] = arr
            else:
                self.param[name] = arr
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            if arr.shape != self.param[name].shape:
                raise ValueError(
                    f"Parameter {name} cannot be initialized from loading. "
                    f"Shape mismatch, target {arr.shape} vs loaded "
                    f"{self.param[name].shape}")
            arr._set_data(self.param[name]._data)
            if self.verbose:
                logging.info("Initialized %s by loading", name)
        else:
            if self.default_init is None:
                raise ValueError(
                    f"Cannot Initialize parameter: {name}. Not found in "
                    "loaded param and no default Initializer is provided.")
            self.default_init(name, arr)
            if self.verbose:
                logging.info("Initialized %s by default", name)
