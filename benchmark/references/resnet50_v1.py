"""ResNet-50 v1 (He et al. 2015, arXiv:1512.03385), the plain reference.

Forward pass, softmax cross-entropy and gradients in straightforward
``jax.numpy``/``lax``: float32, every convolution and product at
``Precision.HIGHEST``, BatchNorm on the batch's own statistics (biased
variance, as in training), no kernels.  It imports nothing of the
program and makes its own weights from the seed.

It follows table 1 of the paper (the 50-layer column, stride on the
first 1x1 of a stage's first bottleneck, projection shortcuts where the
shape changes) as ``gluon.model_zoo.vision.resnet50_v1`` builds it.
Departures from the paper, each because the model zoo has them as
parameters: the 1x1 convolutions of a bottleneck carry a bias (BatchNorm
removes its effect, so its gradient is zero up to rounding), and the
weights are drawn normal(sqrt(2 / fan_in)) (He et al. 2015b), the
classifier normal(0.01).  BatchNorm's running statistics are leaves (the
program holds them) that no number compared depends on.

``product`` is what the operands of every convolution and of the
classifier pass through: nothing here, float8 e4m3 in the control
(``low_precision``).

The backward pass is taken one bottleneck at a time from the kept block
inputs, so that a whole batch of 256 fits: BatchNorm needs the whole
batch at once, so there are no blocks of rows (``rows`` is ignored).
"""

import functools
import math

STAGES = ((3, 256), (4, 512), (6, 1024), (3, 2048))
EPS = 1e-5


def _blocks():
    """(stage, first conv index, in, mid, out, stride, projection)."""
    c_in = 64
    for s, (n, out) in enumerate(STAGES, start=1):
        idx = 0
        for b in range(n):
            first = b == 0
            yield (s, idx, c_in, out // 4, out,
                   (1 if s == 1 else 2) if first else 1, first)
            idx += 4 if first else 3
            c_in = out


def _bn(name, c):
    return [(f"{name}_gamma", (c,), "ones"), (f"{name}_beta", (c,), "zeros"),
            (f"{name}_running_mean", (c,), "zeros"),
            (f"{name}_running_var", (c,), "ones")]


def _he(c_in, k):
    return f"normal:{math.sqrt(2.0 / (c_in * k * k))}"


def param_spec(config):
    """(name, shape, init) of every leaf, named as the model zoo names
    its parameters below the net's own prefix."""
    spec = [("conv0_weight", (64, 3, 7, 7), _he(3, 7))] \
        + _bn("batchnorm0", 64)
    for s, i, c_in, mid, out, stride, proj in _blocks():
        p = f"stage{s}_"
        spec += [(f"{p}conv{i}_weight", (mid, c_in, 1, 1), _he(c_in, 1)),
                 (f"{p}conv{i}_bias", (mid,), "zeros")]
        spec += _bn(f"{p}batchnorm{i}", mid)
        spec += [(f"{p}conv{i + 1}_weight", (mid, mid, 3, 3), _he(mid, 3))]
        spec += _bn(f"{p}batchnorm{i + 1}", mid)
        spec += [(f"{p}conv{i + 2}_weight", (out, mid, 1, 1), _he(mid, 1)),
                 (f"{p}conv{i + 2}_bias", (out,), "zeros")]
        spec += _bn(f"{p}batchnorm{i + 2}", out)
        if proj:
            spec += [(f"{p}conv{i + 3}_weight", (out, c_in, 1, 1),
                      _he(c_in, 1))]
            spec += _bn(f"{p}batchnorm{i + 3}", out)
    n = config["num_classes"]
    return spec + [("dense0_weight", (n, 2048), "normal:0.01"),
                   ("dense0_bias", (n,), "zeros")]


def trainable(name):
    return "running_" not in name


# -- operand rounding ----------------------------------------------------------

def product(x):
    return x


def low_precision(x):
    """Round to float8 e4m3 under one scale per tensor, and widen;
    straight-through for gradients."""
    import jax
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


# -- the model -----------------------------------------------------------------

def _conv(x, w, stride, pad, prod, bias=None):
    import jax

    y = jax.lax.conv_general_dilated(
        prod(x), prod(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)
    return y if bias is None else y + bias[None, :, None, None]


def _batch_norm(x, p, name):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) \
        * p[name + "_gamma"][None, :, None, None] \
        + p[name + "_beta"][None, :, None, None]


def stem(x, p, prod):
    import jax
    import jax.numpy as jnp

    x = _conv(x, p["conv0_weight"], 2, 3, prod)
    x = jnp.maximum(_batch_norm(x, p, "batchnorm0"), 0.0)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])


def bottleneck(x, p, s, i, stride, proj, prod):
    import jax.numpy as jnp

    q = f"stage{s}_"
    y = _conv(x, p[f"{q}conv{i}_weight"], stride, 0, prod,
              p[f"{q}conv{i}_bias"])
    y = jnp.maximum(_batch_norm(y, p, f"{q}batchnorm{i}"), 0.0)
    y = _conv(y, p[f"{q}conv{i + 1}_weight"], 1, 1, prod)
    y = jnp.maximum(_batch_norm(y, p, f"{q}batchnorm{i + 1}"), 0.0)
    y = _conv(y, p[f"{q}conv{i + 2}_weight"], 1, 0, prod,
              p[f"{q}conv{i + 2}_bias"])
    y = _batch_norm(y, p, f"{q}batchnorm{i + 2}")
    if proj:
        x = _conv(x, p[f"{q}conv{i + 3}_weight"], stride, 0, prod)
        x = _batch_norm(x, p, f"{q}batchnorm{i + 3}")
    return jnp.maximum(y + x, 0.0)


def head_loss(x, p, labels, prod):
    """Mean softmax cross-entropy of the pooled features' classifier."""
    import jax
    import jax.numpy as jnp

    pooled = jnp.mean(x, axis=(2, 3))
    logits = jnp.einsum("nc,kc->nk", prod(pooled), prod(p["dense0_weight"]),
                        precision=jax.lax.Precision.HIGHEST) \
        + p["dense0_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _part(params, prefix):
    return {k: v for k, v in params.items()
            if k.startswith(prefix) and trainable(k)}


@functools.lru_cache(maxsize=None)
def _jitted(prod):
    import jax

    def vjp_of(fn):
        def run(x, p, ct):
            _, vjp = jax.vjp(fn, x, p)
            return vjp(ct)
        return jax.jit(run)

    blocks = {}
    for s, i, c_in, mid, out, stride, proj in _blocks():
        fn = functools.partial(bottleneck, s=s, i=i, stride=stride,
                               proj=proj, prod=prod)
        blocks[(s, i)] = (jax.jit(fn), vjp_of(fn))
    st = functools.partial(stem, prod=prod)

    def head_vg(x, p, labels):
        return jax.value_and_grad(
            lambda x, p: head_loss(x, p, labels, prod), argnums=(0, 1))(x, p)

    return blocks, jax.jit(st), vjp_of(st), jax.jit(head_vg)


def _block_params(params, s, i, proj):
    names = [f"stage{s}_conv{j}_" for j in range(i, i + 3 + proj)] \
        + [f"stage{s}_batchnorm{j}_" for j in range(i, i + 3 + proj)]
    return {k: v for k, v in params.items()
            if trainable(k) and any(k.startswith(n) for n in names)}


def loss_and_grads(params, x, labels, config, rows=None, prod=product):
    """Mean loss of (N, 3, side, side) images with (N,) int labels, and
    its gradients by trainable leaf, in float32."""
    import jax.numpy as jnp

    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    blocks, stem_f, stem_vjp, head_vg = _jitted(prod)
    p_stem = {k: params[k] for k in ("conv0_weight", "batchnorm0_gamma",
                                     "batchnorm0_beta")}
    p_head = {k: params[k] for k in ("dense0_weight", "dense0_bias")}
    xs = [stem_f(x.astype(jnp.float32), p_stem)]
    layout = list(_blocks())
    for s, i, _, _, _, _, proj in layout:
        xs.append(blocks[(s, i)][0](xs[-1],
                                    _block_params(params, s, i, proj)))
    loss, (ct, g_head) = head_vg(xs[-1], p_head, labels)
    grads = dict(g_head)
    for n in reversed(range(len(layout))):
        s, i, _, _, _, _, proj = layout[n]
        ct, g = blocks[(s, i)][1](xs[n], _block_params(params, s, i, proj),
                                  ct)
        xs[n + 1] = None
        grads.update(g)
    _, g = stem_vjp(x.astype(jnp.float32), p_stem, ct)
    grads.update(g)
    return loss, grads
