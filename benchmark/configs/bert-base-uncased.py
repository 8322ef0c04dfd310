"""``bert-base-uncased`` through the program's normal path
(``BERTClassifier`` over the ``BERT`` layer), the rows it is fed, the work
one step needs, and how the program's parameter tree maps onto the
reference's."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def build(cfg: Dict[str, Any]):
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.tfpark.text_estimators import BERTClassifier
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    net = BERTClassifier(
        num_classes=cfg["num_labels"],
        bert_config=dict(
            vocab=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            n_block=cfg["num_hidden_layers"],
            nhead=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            max_position_len=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            hidden_drop=cfg["hidden_dropout_prob"],
            attn_drop=cfg["attention_probs_dropout_prob"]))
    dep = cfg["deployment"]
    opt = dep["optimizer"]
    net.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                               beta_2=opt["beta_2"],
                               epsilon=opt["epsilon"]),
                loss=dep["loss"])
    return net


def make_data(cfg: Dict[str, Any], seed: int, n: int
              ) -> Tuple[List[np.ndarray], np.ndarray]:
    """``n`` sequences from the seed: ids uniform over the vocabulary, the
    second half of each in segment 1, a mask of ones; labels uniform over
    the classes, each row's its own.  The labels have a stream of their
    own, so the first rows are the same however many are made."""
    l = cfg["seq_len"]
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, l)).astype(np.int32)
    segments = np.zeros((n, l), np.int32)
    segments[:, l // 2:] = 1
    mask = np.ones((n, l), np.int32)
    y = np.random.default_rng([seed, 1]).integers(
        0, cfg["num_labels"], n).astype(np.int32)
    return [ids, segments, mask], y


def block_params(cfg) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * (d * d + d) + 2 * d * ff + ff + d + 4 * d


def param_count(cfg) -> int:
    d = cfg["hidden_size"]
    embed = (cfg["vocab_size"] + cfg["max_position_embeddings"]
             + cfg["type_vocab_size"]) * d + 2 * d
    return (embed + cfg["num_hidden_layers"] * block_params(cfg)
            + d * d + d + d * cfg["num_labels"] + cfg["num_labels"])


def forward_macs(cfg) -> int:
    """Multiply-adds of one sequence's forward pass: the blocks' matrices
    at every position, attention's two products, pooler and head once."""
    d, ff, l = cfg["hidden_size"], cfg["intermediate_size"], cfg["seq_len"]
    per_block = l * (4 * d * d + 2 * d * ff) + 2 * l * l * d
    return (cfg["num_hidden_layers"] * per_block
            + d * d + d * cfg["num_labels"])


def work(cfg: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and unavoidable bytes of one training step of ``batch``
    sequences.  2 per multiply-add, backward twice forward; the embedding
    look-ups are not multiplications.  Bytes: float32 parameters and
    gradients and Adam's two moments each read and written once, the rows
    read once."""
    flops = 2.0 * 3 * batch * forward_macs(cfg)
    p = param_count(cfg)
    sample = 3 * cfg["seq_len"] * 4 + 4
    return {"flops": flops, "bytes": 4 * 8.0 * p + batch * sample,
            "samples": batch}


def to_program(ref_params, net, input_shapes):
    bert = {k: v for k, v in ref_params.items() if k != "head"}
    return {net.bert.name: bert, "head": ref_params["head"]}


def from_program(tree, net):
    return {**tree[net.bert.name], "head": tree["head"]}
