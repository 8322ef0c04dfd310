"""Plain reference for ``resnet50-imagenet``: ResNet-50 (He et al. 2015,
arXiv:1512.03385, table 1, 50-layer column) forward pass and loss in
straightforward ``jax.numpy``, float32.

Imports nothing of the program.  Parameters are a flat dict keyed by the
unit's name (``stem_conv``, ``s0b0_a_conv``, ``s0b0_a_bn`` ... ``fc``); the
stride of a down-sampling block sits on its first 1x1 convolution and its
projection, as in the paper.  Batch normalisation is in training mode: the
statistics are those of the batch (biased variance), over all of its rows.

Departures from a textbook listing, none of which changes the mathematics:
each bottleneck block and the stem are wrapped in ``jax.checkpoint`` so that
the float32 backward pass at 256 x 224 x 224 fits one chip's memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")


def _units(cfg):
    """(name, kind, shape) of every parameterised unit, in forward order."""
    out = [("stem_conv", "conv", (7, 7, cfg["in_channels"], cfg["stem_filters"])),
           ("stem_bn", "bn", (cfg["stem_filters"],))]
    c_in = cfg["stem_filters"]
    for s, (blocks, f) in enumerate(zip(cfg["stage_blocks"],
                                        cfg["stage_filters"])):
        for b in range(blocks):
            n = f"s{s}b{b}"
            if b == 0:
                out += [(f"{n}_proj", "conv", (1, 1, c_in, 4 * f)),
                        (f"{n}_proj_bn", "bn", (4 * f,))]
            out += [(f"{n}_a_conv", "conv", (1, 1, c_in, f)),
                    (f"{n}_a_bn", "bn", (f,)),
                    (f"{n}_b_conv", "conv", (3, 3, f, f)),
                    (f"{n}_b_bn", "bn", (f,)),
                    (f"{n}_c_conv", "conv", (1, 1, f, 4 * f)),
                    (f"{n}_c_bn", "bn", (4 * f,))]
            c_in = 4 * f
    out.append(("fc", "dense", (c_in, cfg["class_num"])))
    return out


def init_params(key, cfg):
    """Weights from one key: He-normal convolutions, unit BN scale, a
    0.01-normal classifier.  Traced under one ``jit`` by the caller."""
    units = _units(cfg)
    keys = jax.random.split(key, len(units))
    params = {}
    for k, (name, kind, shape) in zip(keys, units):
        if kind == "conv":
            fan_in = shape[0] * shape[1] * shape[2]
            params[name] = {"kernel": jax.random.normal(k, shape, jnp.float32)
                            * (2.0 / fan_in) ** 0.5}
        elif kind == "bn":
            params[name] = {"gamma": jnp.ones(shape, jnp.float32),
                            "beta": jnp.zeros(shape, jnp.float32)}
        else:
            params[name] = {"kernel": jax.random.normal(k, shape, jnp.float32)
                            * 0.01,
                            "bias": jnp.zeros(shape[1:], jnp.float32)}
    return params


class _Rounding:
    """How a lower-precision control rounds: ``operand`` every convolution
    and matmul operand, ``stored`` every activation a layer hands on.  Both
    are the identity in the reference itself."""

    def __init__(self, quant, act):
        self.operand, self.stored = quant, act


def _conv(p, x, stride, r):
    return r.stored(lax.conv_general_dilated(
        r.operand(x), r.operand(p["kernel"]), (stride, stride), "SAME",
        dimension_numbers=_DN, precision=lax.Precision.HIGHEST))


def _bn_relu(p, x, eps, r, relu=True):
    """Batch normalisation over the batch's own statistics (biased
    variance), then ReLU.  Every value a lower precision would store passes
    through ``r.stored``: the statistics too."""
    s = r.stored
    mean = s(jnp.mean(x, axis=(0, 1, 2)))
    centred = s(x - mean)
    var = s(jnp.mean(jnp.square(centred), axis=(0, 1, 2)))
    y = s(s(centred * s(lax.rsqrt(var + eps))) * p["gamma"] + p["beta"])
    return s(jax.nn.relu(y)) if relu else y


def _block(params, x, name, stride, project, eps, r):
    short = x
    if project:
        short = _bn_relu(params[f"{name}_proj_bn"],
                         _conv(params[f"{name}_proj"], x, stride, r), eps, r,
                         relu=False)
    y = _bn_relu(params[f"{name}_a_bn"],
                 _conv(params[f"{name}_a_conv"], x, stride, r), eps, r)
    y = _bn_relu(params[f"{name}_b_bn"],
                 _conv(params[f"{name}_b_conv"], y, 1, r), eps, r)
    y = _bn_relu(params[f"{name}_c_bn"],
                 _conv(params[f"{name}_c_conv"], y, 1, r), eps, r, relu=False)
    return r.stored(jax.nn.relu(r.stored(y + short)))


def _stem(params, x, eps, r):
    x = _bn_relu(params["stem_bn"], _conv(params["stem_conv"], x, 2, r),
                 eps, r)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "SAME")


def logits_fn(params, xs, cfg, quant=lambda a: a, act=lambda a: a):
    (x,) = xs
    eps = cfg["bn_epsilon"]
    quant = _Rounding(quant, act)
    x = jax.checkpoint(lambda p, a: _stem(p, a, eps, quant))(
        {k: params[k] for k in ("stem_conv", "stem_bn")}, x)
    for s, blocks in enumerate(cfg["stage_blocks"]):
        for b in range(blocks):
            name = f"s{s}b{b}"
            sub = {k: v for k, v in params.items()
                   if k.startswith(name + "_")}
            stride = 2 if (b == 0 and s > 0) else 1
            x = jax.checkpoint(
                lambda p, a, name=name, stride=stride, project=(b == 0):
                _block(p, a, name, stride, project, eps, quant))(sub, x)
    x = quant.stored(jnp.mean(x, axis=(1, 2)))
    return quant.stored(
        jnp.dot(quant.operand(x), quant.operand(params["fc"]["kernel"]),
                precision=lax.Precision.HIGHEST) + params["fc"]["bias"])


def loss_fn(params, xs, y, cfg, quant=lambda a: a, act=lambda a: a):
    """Mean cross-entropy of integer labels against the logits."""
    logp = jax.nn.log_softmax(logits_fn(params, xs, cfg, quant, act),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=-1))
