"""``granite-4.0-h-micro`` through the program's normal path
(``models.HybridLM``: compile, then ``fit(ids, next_ids)``), the rows it is
fed, the work one step needs (the chunked scan's own among it), what its one
attention layer asks of its kernels, and how the program's parameter tree
maps onto the reference's."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def build(cfg: Dict[str, Any]):
    from analytics_zoo_tpu.models import HybridLM
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    dep = cfg["deployment"]
    opt = dep["optimizer"]
    model = HybridLM.from_config(cfg)
    model.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                                 beta_2=opt["beta_2"],
                                 epsilon=opt["epsilon"]),
                  loss=dep["loss"])
    return model.model


def make_data(cfg: Dict[str, Any], seed: int, n: int
              ) -> Tuple[List[np.ndarray], np.ndarray]:
    """``n`` packed rows from the seed: ``seq_len + 1`` ids uniform over the
    vocabulary's slice; the first ``seq_len`` are the ids and the last
    ``seq_len`` the labels (the next token at every position)."""
    l = cfg["seq_len"]
    tokens = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, l + 1), dtype=np.int32)
    return ([np.ascontiguousarray(tokens[:, :-1])],
            np.ascontiguousarray(tokens[:, 1:]))


def _widths(cfg) -> Dict[str, int]:
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], ff=cfg["shared_intermediate_size"],
                h=h, d_in=h * p, gn=gn, conv=h * p + 2 * gn,
                proj=2 * h * p + 2 * gn + h, taps=cfg["mamba_d_conv"],
                kv=head * cfg["num_key_value_heads"])


def mixer_params(cfg) -> int:
    """A Mamba-2 mixer's: the two projections, the convolution's taps and
    bias, ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm."""
    w = _widths(cfg)
    return (w["d"] * w["proj"] + w["d_in"] * w["d"] + w["conv"] * w["taps"]
            + w["conv"] + 3 * w["h"] + w["d_in"])


def layer_params(cfg, kind: str) -> int:
    w = _widths(cfg)
    mixer = (mixer_params(cfg) if kind == "mamba"
             else 2 * w["d"] * w["d"] + 2 * w["d"] * w["kv"])
    return mixer + 3 * w["d"] * w["ff"] + 2 * w["d"]


def param_count(cfg) -> int:
    """The layers, the final norm and the embedding, which is the head."""
    return (sum(layer_params(cfg, kind) for kind in cfg["layer_types"])
            + cfg["hidden_size"] + cfg["vocab_size"] * cfg["hidden_size"])


def scan_macs_per_token(cfg) -> int:
    """Multiply-adds a token of one layer's scan in its chunked form, at the
    causal half of each chunk of Q: the scores ``C B^T`` (Q / 2 of N a
    group), their product with ``delta x`` (Q / 2 of P a head), the chunk's
    state and the carried state's term (P x N a head each)."""
    half = cfg["mamba_chunk_size"] // 2
    h, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
               cfg["mamba_d_state"])
    return (cfg["mamba_n_groups"] * half * n + h * half * p
            + 2 * h * p * n)


def forward_macs_per_token(cfg) -> int:
    """Multiply-adds of one token's forward pass: every layer's matrices,
    the convolution's taps and the chunked scan in a Mamba layer, the causal
    half of attention's two products in an attention layer, and the tied
    head.  Embedding look-up, norms, gates: no matrices."""
    w, l = _widths(cfg), cfg["seq_len"]
    mlp = 3 * w["d"] * w["ff"]
    per_kind = {
        "mamba": (w["d"] * w["proj"] + w["d_in"] * w["d"]
                  + w["taps"] * w["conv"] + scan_macs_per_token(cfg) + mlp),
        "attention": (2 * w["d"] * w["d"] + 2 * w["d"] * w["kv"]
                      + 2 * (l // 2) * w["d"] + mlp),
    }
    return (sum(per_kind[kind] for kind in cfg["layer_types"])
            + w["d"] * cfg["vocab_size"])


def scan_work(cfg: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and least bytes of the chunked scans of one step, forward and
    backward, as the algorithm at ``mamba_chunk_size`` needs them whatever
    implements it; no recomputation counted.  Bytes, in the compute dtype's
    2: the forward reads x, B, C and delta and writes y; the backward reads
    them and dy and writes dx, dB, dC and d delta: 5 x-wide, 6 of G x N and
    3 of H values a token."""
    w = _widths(cfg)
    tokens = batch * cfg["seq_len"] * cfg["layer_types"].count("mamba")
    return {"flops": 2.0 * 3 * tokens * scan_macs_per_token(cfg),
            "bytes": 2.0 * tokens * (5 * w["d_in"] + 6 * w["gn"]
                                     + 3 * w["h"])}


def work(cfg: Dict[str, Any], batch: int) -> Dict[str, Any]:
    """FLOPs and unavoidable bytes of one training step of ``batch``
    sequences.  2 per multiply-add, backward twice forward, NO
    recomputation counted.  Bytes: float32 parameters and gradients and
    Adam's two moments each read and written once, the rows read once.
    ``ssm_scan``: the chunked scans' own share (``scan_work``)."""
    tokens = batch * cfg["seq_len"]
    return {"flops": 2.0 * 3 * tokens * forward_macs_per_token(cfg),
            "bytes": 4 * 8.0 * param_count(cfg) + 2 * 4.0 * tokens,
            "samples": batch, "ssm_scan": scan_work(cfg, batch)}


def attention_kernel_work(cfg: Dict[str, Any], batch: int
                          ) -> Dict[str, float]:
    """What the attention layers ask of their kernels in one step, as the
    algorithm needs it at the published head size, whatever the kernels'
    blocks or padding: a causal head has L (L + 1) / 2 scores, and a product
    over them is 2 * D FLOPs a score.  Each layer asks for the forward twice
    (once more in the recomputation; 2 products each) and for the backward
    once (5 products).  Bytes, the least that has to move, bfloat16 (L, D)
    tensors: a pass of the forward reads q and writes o a query head and
    reads k and v a key/value head; the backward reads q, o, dO and writes
    dq a query head, reads k, v and writes dk, dv a key/value head."""
    l, h, kv = (cfg["seq_len"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    d = cfg["hidden_size"] // h
    layers = batch * cfg["layer_types"].count("attention")
    product = 2.0 * d * l * (l + 1) / 2
    return {"flops": layers * h * product * (2 * 2 + 5),
            "bytes": layers * 2.0 * l * d * (2 * (2 * h + 2 * kv)
                                             + 4 * h + 4 * kv)}


# the reference's names of a layer's leaves -> the program's path to them
_SHARED = {"norm1": ("norm1", "gamma"), "norm2": ("norm2", "gamma"),
           "w_gate": ("ffn", "gate", "kernel"),
           "w_up": ("ffn", "up", "kernel"),
           "w_down": ("ffn", "down", "kernel")}
_BY_KIND = {
    "attention": {**_SHARED, **{"w" + n: ("mixer", n, "kernel")
                                for n in "qkvo"}},
    "mamba": {**_SHARED,
              "w_in": ("mixer", "in_proj", "kernel"),
              "w_out": ("mixer", "out_proj", "kernel"),
              "conv_w": ("mixer", "conv", "kernel"),
              "conv_b": ("mixer", "conv", "bias"),
              "dt_bias": ("mixer", "dt_bias"), "A_log": ("mixer", "A_log"),
              "D": ("mixer", "D"), "norm_g": ("mixer", "norm", "gamma")},
}


def to_program(ref_params, net, input_shapes):
    """The reference's tree under the program's names; no array is copied
    (both store each run of like layers stacked)."""
    stack = {"final_norm": {"gamma": ref_params["final_norm"]}}
    for i, ((kind, _), run) in enumerate(zip(net.stack.runs,
                                             ref_params["runs"])):
        block: Dict[str, Any] = {}
        for name, path in _BY_KIND[kind].items():
            at = block
            for key in path[:-1]:
                at = at.setdefault(key, {})
            at[path[-1]] = run[name]
        stack[f"run{i}"] = block
    return {"embed": ref_params["embed"], net.stack.name: stack}


def from_program(tree, net):
    stack = tree[net.stack.name]
    runs = []
    for i, (kind, _) in enumerate(net.stack.runs):
        run = {}
        for name, path in _BY_KIND[kind].items():
            at = stack[f"run{i}"]
            for key in path:
                at = at[key]
            run[name] = at
        runs.append(run)
    return {"embed": tree["embed"],
            "final_norm": stack["final_norm"]["gamma"], "runs": runs}
