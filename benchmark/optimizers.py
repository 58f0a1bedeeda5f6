"""Plain optimizers for the references: float32, one leaf at a time.

Each follows the update the configuration's traffic names, as the
program's documentation gives it (the MXNet contrib ``adamw`` and the
``sgd`` momentum rule), so that the reference's parameter change after a
few steps is the change a correct program makes.

``store`` is the type the configuration keeps its parameters in: after
each update the new value is rounded to it and widened again, because a
parameter the configuration stores in bfloat16 cannot hold more (a
LayerNorm gain of 1.0 does not move by 3e-4 there).  The arithmetic of
the update itself stays float32.
"""


def _stored(x, store):
    import jax.numpy as jnp

    return x.astype(store).astype(jnp.float32)


def adamw_init(params):
    import jax.numpy as jnp

    return {k: (jnp.zeros_like(v), jnp.zeros_like(v))
            for k, v in params.items()}


def adamw_step(params, grads, state, t, hp, store):
    """MXNet's contrib AdamW: bias correction folded into the rate,
    epsilon added to the uncorrected sqrt(v), weight decay not scaled by
    the rate.  ``t`` counts from 1."""
    import jax.numpy as jnp

    lr, wd = hp["learning_rate"], hp.get("wd", 0.0)
    b1, b2 = hp.get("beta1", 0.9), hp.get("beta2", 0.999)
    eps = hp.get("epsilon", 1e-8)
    lr_t = lr * (1.0 - b2 ** t) ** 0.5 / (1.0 - b1 ** t)
    new_p, new_s = {}, {}
    for k, p in params.items():
        m, v = state[k]
        g = grads[k]
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        new_p[k] = _stored(p - (lr_t * m / (jnp.sqrt(v) + eps) + wd * p),
                           store)
        new_s[k] = (m, v)
    return new_p, new_s


def sgd_init(params):
    import jax.numpy as jnp

    return {k: jnp.zeros_like(v) for k, v in params.items()}


def sgd_step(params, grads, state, t, hp, store):
    """SGD with momentum: mom = momentum*mom - lr*(g + wd*w); w += mom."""
    lr, wd = hp["learning_rate"], hp.get("wd", 0.0)
    mu = hp.get("momentum", 0.0)
    new_p, new_s = {}, {}
    for k, p in params.items():
        mom = mu * state[k] - lr * (grads[k] + wd * p)
        new_p[k] = _stored(p + mom, store)
        new_s[k] = mom
    return new_p, new_s


OPTIMIZERS = {"adamw": (adamw_init, adamw_step),
              "sgd": (sgd_init, sgd_step)}


def first_gradient(name, state_leaf, hp, weight=None):
    """The gradient the program's optimizer got at its first step, from
    the state it left: Adam's first moment is (1 - beta1) g; SGD's
    momentum is -lr (g + wd w)."""
    if name == "adamw":
        return state_leaf[0] / (1.0 - hp.get("beta1", 0.9))
    if name == "sgd":
        g = -state_leaf / hp["learning_rate"]
        if hp.get("wd", 0.0):
            g = g - hp["wd"] * weight
        return g
    raise KeyError(f"optimizers: no optimizer {name!r}")
