"""Plain reference for ``bert-base-uncased``: the BERT encoder (Devlin et
al. 2018, arXiv:1810.04805; google-research/bert ``modeling.py``) with a
sequence-classification head on the pooled output, in straightforward
``jax.numpy``, float32.  Imports nothing of the program.

Post-layer-norm blocks, learned position and segment embeddings, and the
tanh form of GELU that ``modeling.py`` writes out (``_gelu``).  Dropout is
not applied: the configuration states both rates as
0 (its ``reduced`` says why).  Attention scores are the full (L, L) matrix.
The twelve blocks run as one ``lax.scan`` over their stacked weights, each
under ``jax.checkpoint``, so that the float32 backward pass compiles
quickly and fits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def _glorot(key, shape):
    lim = math.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def _dense_init(key, d_in, d_out):
    return {"kernel": _glorot(key, (d_in, d_out)),
            "bias": jnp.zeros((d_out,), jnp.float32)}


def _ln_init(d):
    return {"gamma": jnp.ones((d,), jnp.float32),
            "beta": jnp.zeros((d,), jnp.float32)}


def init_params(key, cfg):
    """Weights from one key: Glorot-uniform matrices, embeddings a tenth of
    that, zero biases, unit layer-norm scales."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    ks = iter(jax.random.split(key, 5 + 6 * cfg["num_hidden_layers"]))
    params = {
        "word_embed": _glorot(next(ks), (cfg["vocab_size"], d)) * 0.1,
        "pos_embed": _glorot(next(ks),
                             (cfg["max_position_embeddings"], d)) * 0.1,
        "type_embed": _glorot(next(ks), (cfg["type_vocab_size"], d)) * 0.1,
        "embed_ln": _ln_init(d),
        "pooler": _dense_init(next(ks), d, d),
        "head": _dense_init(next(ks), d, cfg["num_labels"]),
    }
    for i in range(cfg["num_hidden_layers"]):
        params[f"enc{i}"] = {
            "attn": {n: _dense_init(next(ks), d, d) for n in "qkvo"},
            "ln1": _ln_init(d), "ln2": _ln_init(d),
            "ffn1": _dense_init(next(ks), d, ff),
            "ffn2": _dense_init(next(ks), ff, d),
        }
    return params


class _Rounding:
    """How a lower-precision control rounds: ``operand`` every matmul
    operand, ``stored`` every activation a layer hands on.  Both are the
    identity in the reference itself."""

    def __init__(self, quant, act):
        self.operand, self.stored = quant, act


def _dense(p, x, r):
    return r.stored(jnp.dot(r.operand(x), r.operand(p["kernel"]),
                            precision=_HI) + p["bias"])


def _ln(p, x, eps, r):
    """Layer normalisation; every value a lower precision would store
    passes through ``r.stored``: the statistics too."""
    s = r.stored
    mean = s(jnp.mean(x, axis=-1, keepdims=True))
    centred = s(x - mean)
    var = s(jnp.mean(jnp.square(centred), axis=-1, keepdims=True))
    return s(s(centred * s(lax.rsqrt(var + eps))) * p["gamma"] + p["beta"])


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, mask, cfg, r):
    b, l, d = x.shape
    h = cfg["num_attention_heads"]

    def heads(t):
        return t.reshape(b, l, h, d // h).transpose(0, 2, 1, 3)

    q, k, v = (heads(_dense(p["attn"][n], x, r)) for n in "qkv")
    scores = r.stored(jnp.einsum("bhqd,bhkd->bhqk", r.operand(q),
                                 r.operand(k), precision=_HI)
                      / math.sqrt(d // h))
    scores = jnp.where(mask[:, None, None, :] > 0, scores, -1e30)
    probs = r.stored(jax.nn.softmax(scores, axis=-1))
    ctx = r.stored(jnp.einsum("bhqk,bhkd->bhqd", r.operand(probs),
                              r.operand(v), precision=_HI))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, d)
    x = _ln(p["ln1"], r.stored(x + _dense(p["attn"]["o"], ctx, r)),
            cfg["layer_norm_eps"], r)
    f = _dense(p["ffn2"], r.stored(_gelu(_dense(p["ffn1"], x, r))), r)
    return _ln(p["ln2"], r.stored(x + f), cfg["layer_norm_eps"], r)


def logits_fn(params, xs, cfg, quant=lambda a: a, act=lambda a: a):
    ids, segments, mask = xs
    l = ids.shape[1]
    r = _Rounding(quant, act)
    x = r.stored(params["word_embed"][ids] + params["type_embed"][segments]
                 + params["pos_embed"][None, :l])
    x = _ln(params["embed_ln"], x, cfg["layer_norm_eps"], r)
    # the blocks are alike, so they run as one scanned block over their
    # stacked weights: the same sums in the same order, a twelfth of the
    # program to compile
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"enc{i}"] for i in range(cfg["num_hidden_layers"])])
    block = jax.checkpoint(lambda p, a: _block(p, a, mask, cfg, r))
    x, _ = lax.scan(lambda a, p: (block(p, a), None), x, stacked)
    pooled = r.stored(jnp.tanh(_dense(params["pooler"], x[:, 0], r)))
    return _dense(params["head"], pooled, r)


def loss_fn(params, xs, y, cfg, quant=lambda a: a, act=lambda a: a):
    """Mean cross-entropy of integer labels against the logits."""
    logp = jax.nn.log_softmax(logits_fn(params, xs, cfg, quant, act),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=-1))
