"""``resnet50-imagenet`` through the program's normal path, the rows it is
fed, the work one step needs, and how the program's parameter tree maps onto
the reference's."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def build(cfg: Dict[str, Any]):
    """The compiled net: ``resnet50`` at its defaults (full BatchNorm, the
    space-to-depth stem) under SGD with momentum."""
    from analytics_zoo_tpu.models.image.imageclassification import resnet50
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import SGD

    if (list(cfg["stage_blocks"]) != [3, 4, 6, 3]
            or list(cfg["stage_filters"]) != [64, 128, 256, 512]
            or cfg["stem_filters"] != 64):
        raise ValueError("the program's resnet50 has the published stages "
                         "and widths only")
    reset_name_scope()
    net = resnet50(class_num=cfg["class_num"],
                   input_shape=(cfg["image"], cfg["image"],
                                cfg["in_channels"]))
    dep = cfg["deployment"]
    opt = dep["optimizer"]
    net.compile(optimizer=SGD(lr=opt["lr"], momentum=opt["momentum"]),
                loss=dep["loss"])
    return net


def make_data(cfg: Dict[str, Any], seed: int, n: int
              ) -> Tuple[List[np.ndarray], np.ndarray]:
    """``n`` images and labels from the seed: uniform bytes scaled to
    float32 about zero, labels uniform over the classes.  The labels have a
    stream of their own, so the first rows are the same however many are
    made."""
    hw, c = cfg["image"], cfg["in_channels"]
    raw = np.random.default_rng(seed).integers(0, 256, (n, hw, hw, c),
                                               dtype=np.uint8)
    x = np.empty(raw.shape, np.float32)
    np.subtract(raw, np.float32(127.5), out=x)
    x *= np.float32(1 / 64.0)
    y = np.random.default_rng([seed, 1]).integers(
        0, cfg["class_num"], n).astype(np.int32)
    return [x], y


def _convs(cfg) -> List[Tuple[int, int, int, int, int]]:
    """(output height, kernel height, kernel width, channels in, channels
    out) of every convolution, forward order, square maps."""
    hw = cfg["image"] // 2
    out = [(hw, 7, 7, cfg["in_channels"], cfg["stem_filters"])]
    hw //= 2                                    # the 3x3/2 max pool
    c_in = cfg["stem_filters"]
    for s, (blocks, f) in enumerate(zip(cfg["stage_blocks"],
                                        cfg["stage_filters"])):
        for b in range(blocks):
            if b == 0 and s > 0:
                hw //= 2                        # stride on a_conv and proj
            if b == 0:
                out.append((hw, 1, 1, c_in, 4 * f))
            out += [(hw, 1, 1, c_in, f), (hw, 3, 3, f, f),
                    (hw, 1, 1, f, 4 * f)]
            c_in = 4 * f
    return out


def forward_macs(cfg) -> int:
    """Multiply-adds of one image's forward pass: convolutions and the
    classifier."""
    macs = sum(h * h * kh * kw * ci * co for h, kh, kw, ci, co in _convs(cfg))
    return macs + 4 * cfg["stage_filters"][-1] * cfg["class_num"]


def param_count(cfg) -> int:
    n = sum(kh * kw * ci * co + 2 * co for _, kh, kw, ci, co in _convs(cfg))
    last = 4 * cfg["stage_filters"][-1]
    return n + last * cfg["class_num"] + cfg["class_num"]


def work(cfg: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and unavoidable bytes of one training step of ``batch`` images.
    2 per multiply-add; backward is twice forward, except that the stem
    needs no gradient for its input.  Bytes: float32 parameters, gradients
    and momentum each read and written once, the float32 batch read once."""
    h, kh, kw, ci, co = _convs(cfg)[0]
    stem = h * h * kh * kw * ci * co
    flops = 2.0 * batch * (3 * forward_macs(cfg) - stem)
    p = param_count(cfg)
    sample = cfg["image"] ** 2 * cfg["in_channels"] * 4 + 4
    return {"flops": flops, "bytes": 3 * 8.0 * p + batch * sample,
            "samples": batch}


def to_program(ref_params, net, input_shapes):
    """The reference's flat dict is keyed by the program's layer names; the
    layers that hold no parameter get their empty entry."""
    import jax

    shapes, _ = jax.eval_shape(lambda r: net.init(r, *input_shapes),
                               jax.random.PRNGKey(0))
    missing = set(k for k, v in shapes.items() if v) - set(ref_params)
    if missing:
        raise KeyError(f"the program has layers the reference lacks: "
                       f"{sorted(missing)}")
    return {k: ref_params.get(k, {}) for k in shapes}


def from_program(tree, net):
    """A program-shaped tree (parameters, a gradient) as the reference's."""
    return {k: v for k, v in tree.items() if v}
