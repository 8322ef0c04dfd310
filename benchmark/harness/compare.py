"""The comparison that decides ``correct`` for a training cell.

The program's first three steps, one shuffled epoch through ``fit`` (its
mean loss, the first gradient as the optimizer got it, the parameters'
change after the three), are held against the plain reference run on the
same weights and on the batches the data tier asked for, in that order.  Norms are compared
leaf by leaf and the worst leaf counts: the gap between the program's norm
and the reference's, measured against the reference's norm of that leaf or
of the median leaf, whichever is larger.  Leaves whose reference gradient is
under a thousandth of the median leaf's are left out of the change: under
Adam they move by round-off alone.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

STEPS = 3


def fp8_round(a):
    """Round to fp8 e4m3 (one implicit and three stored mantissa bits,
    subnormals below 2**-6) after scaling the tensor's largest magnitude to
    the format's 448, written out in float32 so it runs anywhere; the
    gradient passes straight through.  The lower precision the control
    computes in."""
    import jax
    import jax.numpy as jnp

    if not jnp.issubdtype(a.dtype, jnp.floating):
        return a
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    x = a * scale
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 1e-30)))
    quantum = jnp.exp2(jnp.maximum(e - 3.0, -9.0))
    q = jnp.round(x / quantum) * quantum / scale
    return a + jax.lax.stop_gradient(q - a)


def leaf_norms(tree):
    """L2 norm of every leaf, float32, in ``tree_flatten`` order."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree_util.tree_leaves(tree)])


def leaf_names(tree) -> List[str]:
    import jax

    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _cast_floats(tree, dtype):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def reference_step(reference, config: Dict[str, Any],
                   quant: Optional[Callable] = None,
                   dtype: Optional[str] = None,
                   act: Optional[Callable] = None) -> Callable:
    """``step(params, opt_state, xs, y) -> (params, opt_state, loss, norms
    of the gradient's leaves)`` in the plain reference; ``quant`` rounds
    every matmul and convolution operand and ``act`` every activation a
    layer hands on (the control rounds both); ``dtype`` computes
    the loss in that type against float32 master weights (a second witness
    of what the configuration's own precision costs)."""
    import jax
    import jax.numpy as jnp

    from . import optim

    opt = config["deployment"]["optimizer"]
    kw = {} if quant is None else {"quant": quant}
    if act is not None:
        kw["act"] = act

    def lossf(p, xs, y):
        if dtype is not None:
            p, xs = _cast_floats(p, dtype), _cast_floats(xs, dtype)
        return reference.loss_fn(p, xs, y, config, **kw).astype(jnp.float32)

    def step(p, state, xs, y):
        loss, grads = jax.value_and_grad(lossf)(p, xs, y)
        new_p, new_state = optim.apply(opt, p, grads, state)
        return new_p, new_state, loss, leaf_norms(grads)

    return step


def reference_steps(reference, config: Dict[str, Any], params, batches,
                    quant: Optional[Callable] = None,
                    dtype: Optional[str] = None,
                    act: Optional[Callable] = None,
                    placed: Optional[Tuple[Any, Any]] = None
                    ) -> Dict[str, Any]:
    """Follow ``STEPS`` steps in the plain reference.  ``batches`` is a list
    of ``(xs, y)``; ``placed`` is (sharding of weights and state, sharding of
    rows), given to the step going in and coming out so that its second
    call finds the program its first call compiled."""
    import jax

    from . import optim

    step = reference_step(reference, config, quant, dtype, act)
    if placed is None:
        step = jax.jit(step)
    else:
        whole, split = placed
        step = jax.jit(step, in_shardings=(whole, whole, split, split),
                       out_shardings=whole)
    with jax.default_matmul_precision("highest"):
        p = params
        state = optim.init_state(config["deployment"]["optimizer"], params)
        losses, grad_norms = [], None
        for xs, y in batches[:STEPS]:
            p, state, loss, gn = step(p, state, tuple(xs), y)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = np.asarray(gn)
        delta = np.asarray(jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(lambda u, v: u - v, a, b)))(p, params))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def batches_asked(asked: List[np.ndarray], batch: int) -> Dict[str, Any]:
    """The index arrays a data tier asked its rows for, as ``STEPS`` batches
    of ``batch`` rows in the order asked (a dispatch of several steps asks
    for their rows at once).  ``twice`` counts what an epoch's shuffle may
    not do: a row asked for twice, or rows missing from the last batch."""
    rows = np.concatenate(asked) if asked else np.zeros(0, np.int64)
    want = STEPS * batch
    twice = int(len(rows) - len(np.unique(rows))) + abs(len(rows) - want)
    rows = np.resize(rows, want) if len(rows) else np.zeros(want, np.int64)
    return {"batches": [rows[k * batch:(k + 1) * batch]
                        for k in range(STEPS)], "twice": twice}


def _gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per leaf: the gap between the two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    return np.abs(got - want) / np.maximum(want, float(np.median(want)))


def numbers(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, Any]:
    """Every number compared.  Of the gradient's and the change's norms
    both the worst leaf's gap and the median leaf's are given: the worst
    swings with a few leaves whose gradient is what a cancellation leaves
    over, the median is steady."""
    out: Dict[str, Any] = {}
    # the epoch's mean loss, as ``fit`` reports it, against the mean of the
    # reference's three
    mean = float(np.mean(want["losses"]))
    loss = got["loss"] if "loss" in got else float(np.mean(got["losses"]))
    out["loss"] = abs(loss - mean) / max(abs(mean), 1e-30)
    want_g = np.asarray(want["grad_norms"])
    moved = want_g >= 1e-3 * float(np.median(want_g))
    g = _gaps(np.asarray(got["grad_norms"]), want_g)
    d = _gaps(np.asarray(got["delta_norms"]),
              np.asarray(want["delta_norms"]))[moved]
    out["grad_norm"], out["grad_norm_median"] = g.max(), np.median(g)
    out["delta_norm"], out["delta_norm_median"] = d.max(), np.median(d)
    out = {k: float(v) if np.isfinite(v) else float("inf")
           for k, v in out.items()}
    out["_worst_leaf"] = {"grad_norm": int(np.argmax(g)),
                          "delta_norm": int(np.flatnonzero(moved)[
                              np.argmax(d)])}
    out["_left_out"] = int((~moved).sum())
    return out


def judge(nums: Dict[str, Any], limits: Dict[str, Any]
          ) -> Tuple[bool, Dict[str, List[float]]]:
    """(correct, {name: [value, limit]}) over the numbers that the cell's
    limits file gives a limit."""
    checks = {name: [nums[name], limit]
              for name, limit in limits["limits"].items()}
    ok = bool(checks) and all(v <= lim for v, lim in checks.values())
    return ok, checks
