"""``ouro-2.6b`` through the program's normal path (``models.LoopedLM``:
compile, then ``fit(ids, next_ids)``), the rows it is fed, the work one step
needs, what its attention kernels are asked for, and how the program's
parameter tree maps onto the reference's."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

def build(cfg: Dict[str, Any]):
    from analytics_zoo_tpu.models import LoopedLM
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    dep = cfg["deployment"]
    opt = dep["optimizer"]
    model = LoopedLM.from_config(cfg)
    model.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                                 beta_2=opt["beta_2"],
                                 epsilon=opt["epsilon"]),
                  loss=dep["loss"])
    return model.model


def make_data(cfg: Dict[str, Any], seed: int, n: int
              ) -> Tuple[List[np.ndarray], np.ndarray]:
    """``n`` packed rows from the seed: ``seq_len + 1`` ids uniform over the
    vocabulary; the first ``seq_len`` are the ids and the last ``seq_len``
    the labels (the next token at every position)."""
    l = cfg["seq_len"]
    tokens = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, l + 1), dtype=np.int32)
    return ([np.ascontiguousarray(tokens[:, :-1])],
            np.ascontiguousarray(tokens[:, 1:]))


def layer_params(cfg) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 3 * d * ff + 4 * d


def param_count(cfg) -> int:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg) + 2 * v * d
            + d + d + 1)


def forward_macs_per_token(cfg) -> int:
    """Multiply-adds of one token's forward pass: every layer's matrices
    and the causal half of attention's two products in each of the passes,
    and one head a pass.  Embedding look-up, norms, gates: no matrices."""
    d, ff, l = cfg["hidden_size"], cfg["intermediate_size"], cfg["seq_len"]
    applications = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    per_layer = 4 * d * d + 3 * d * ff + 2 * (l // 2) * d
    return (applications * per_layer
            + cfg["total_ut_steps"] * d * cfg["vocab_size"])


def work(cfg: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and unavoidable bytes of one training step of ``batch``
    sequences.  2 per multiply-add, backward twice forward, NO
    recomputation counted.  Bytes: float32 parameters and gradients and
    Adam's two moments each read and written once, the rows read once."""
    tokens = batch * cfg["seq_len"]
    flops = 2.0 * 3 * tokens * forward_macs_per_token(cfg)
    return {"flops": flops,
            "bytes": 4 * 8.0 * param_count(cfg) + 2 * 4.0 * tokens,
            "samples": batch}


def attention_kernel_work(cfg: Dict[str, Any], batch: int
                          ) -> Dict[str, float]:
    """What the flash-attention kernels are asked for in one step, as the
    algorithm needs it, whatever the kernels' blocks: a causal head has
    L (L + 1) / 2 scores, and a product over them is 2 * D FLOPs a score.
    Each layer application asks for the forward twice (once more in the
    recomputation; q k^T and p v: 2 products each) and for the backward
    once (q k^T again, dO v^T, p^T dO, dS k, dS^T q: 5 products, however
    the dq and the dkv kernel share them out).  Bytes, the least that has
    to move, bfloat16 (L, D) tensors a head: forward reads q, k, v and
    writes o (4, twice); backward reads q, k, v, o, dO and writes dq, dk,
    dv (8)."""
    l, h = cfg["seq_len"], cfg["num_attention_heads"]
    d = cfg["hidden_size"] // h
    heads = batch * h * cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    product = 2.0 * d * l * (l + 1) / 2
    return {"flops": heads * product * (2 * 2 + 5),
            "bytes": heads * 2.0 * l * d * (2 * 4 + 8)}


def to_program(ref_params, net, input_shapes):
    """The reference's tree under the program's names; no array is copied
    (both store the layers stacked)."""
    lay = ref_params["layers"]
    blocks = {
        "attn": {n: {"kernel": lay["w" + n]} for n in "qkvo"},
        "ffn": {"gate": {"kernel": lay["w_gate"]},
                "up": {"kernel": lay["w_up"]},
                "down": {"kernel": lay["w_down"]}},
        **{f"norm{i}": {"gamma": lay[f"norm{i}"]} for i in range(1, 5)},
    }
    return {
        "embed": ref_params["embed"],
        net.stack.name: {"blocks": blocks,
                         "final_norm": {"gamma": ref_params["final_norm"]}},
        "head": {"kernel": ref_params["head"]},
        "exit_gate": {"kernel": ref_params["gate_w"][:, None],
                      "bias": ref_params["gate_b"]},
    }


def from_program(tree, net):
    stack = tree[net.stack.name]
    b = stack["blocks"]
    layers = {"w" + n: b["attn"][n]["kernel"] for n in "qkvo"}
    layers.update(w_gate=b["ffn"]["gate"]["kernel"],
                  w_up=b["ffn"]["up"]["kernel"],
                  w_down=b["ffn"]["down"]["kernel"],
                  **{f"norm{i}": b[f"norm{i}"]["gamma"]
                     for i in range(1, 5)})
    return {"embed": tree["embed"], "layers": layers,
            "final_norm": stack["final_norm"]["gamma"],
            "head": tree["head"]["kernel"],
            "gate_w": tree["exit_gate"]["kernel"][:, 0],
            "gate_b": tree["exit_gate"]["bias"]}
