"""The optimizers a configuration may state, written out plainly for the
references, and how to read the first gradient back out of the program's
optimizer state after one step."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_state(opt, params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    if opt["name"] == "sgd":
        return {"trace": zeros}
    if opt["name"] == "adam":
        return {"mu": zeros, "nu": zeros, "count": jnp.zeros((), jnp.int32)}
    raise ValueError(f"no plain optimizer named {opt['name']!r}")


def apply(opt, params, grads, state):
    """One update: returns (new_params, new_state)."""
    tm = jax.tree_util.tree_map
    lr = opt["lr"]
    if opt["name"] == "sgd":
        trace = tm(lambda g, t: g + opt["momentum"] * t, grads,
                   state["trace"])
        return tm(lambda p, t: p - lr * t, params, trace), {"trace": trace}
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    count = state["count"] + 1
    mu = tm(lambda g, m: b1 * m + (1 - b1) * g, grads, state["mu"])
    nu = tm(lambda g, v: b2 * v + (1 - b2) * g * g, grads, state["nu"])
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = tm(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
             params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


def _find_field(tree, field):
    """First namedtuple in an optax state that has ``field``."""
    if hasattr(tree, "_fields"):
        if field in tree._fields:
            return getattr(tree, field)
        children = [getattr(tree, f) for f in tree._fields]
    elif isinstance(tree, (tuple, list)):
        children = list(tree)
    else:
        return None
    for c in children:
        found = _find_field(c, field)
        if found is not None:
            return found
    return None


def first_gradient(opt, opt_state_after_one_step):
    """The gradient the optimizer was given at step 1, from its state:
    momentum's trace is the gradient itself, Adam's first moment is
    ``(1 - beta_1)`` of it."""
    if opt["name"] == "sgd":
        g = _find_field(opt_state_after_one_step, "trace")
        scale = 1.0
    else:
        g = _find_field(opt_state_after_one_step, "mu")
        scale = 1.0 / (1.0 - opt["beta_1"])
    if g is None:
        raise ValueError("the optimizer state holds no first moment to read "
                         "the gradient from")
    return jax.tree_util.tree_map(lambda a: a * scale, g)
