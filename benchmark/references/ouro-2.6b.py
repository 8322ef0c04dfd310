"""Plain reference for ``ouro-2.6b``: the looped decoder of the Ouro family
(ByteDance, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; https://huggingface.co/ByteDance/Ouro-2.6B) with the
paper's pre-training loss, in straightforward ``jax.numpy``, float32.
Imports nothing of the program.

With d the hidden size, H heads of D = d / H, no biases, N(.) RMSNorm with a
learned scale:

    layer   a = x + N2(Attn(N1 x)),  y = a + N4(MLP(N3 a))
    Attn    q, k, v = u Wq, u Wk, u Wv in H heads; rotary (rotate-half,
            theta, all D dims) on q and k; softmax(q k^T / sqrt(D) + causal) v;
            Wo
    MLP     (silu(u W_gate) * (u W_up)) W_down
    loop    h_0 = E[ids];  h_t = N_f(layers(h_{t-1})),  t = 1..T, the same
            layers every pass;  logits_t = h_t W_out;
            lambda_t = sigmoid(h_t . w_g + b_g)
    exit    p_1 = lambda_1,  p_t = lambda_t prod_{j<t}(1 - lambda_j),
            p_T = prod_{j<T}(1 - lambda_j)
    loss    mean over tokens of  sum_t p_t CE(logits_t, y) - beta H(p),
            H(p) = -sum_t p_t log p_t

What ``config.json`` does not state is listed under ``assumed`` in the
configuration's file.  Attention scores are the full (L, L) matrix of one
head of one sequence; a sequence is computed at a time and each pass, each
layer and each head's attention are under ``jax.checkpoint`` so that the
float32 backward pass fits beside float32 Adam: the same sums, computed
again.  The layers' weights are stored stacked (a leading layer dimension)
and run as a ``lax.scan``, and so do the passes.

``cfg["planted_fault"] == "last_pass_gradient_only"`` cuts the gradient
after every pass but the last (``benchmark/limits`` readings: the limits have
to tell it from the model); ``total_ut_steps`` one lower is the other such
reading.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _glorot(key, shape):
    """Glorot-uniform over the last two dimensions; a leading one stacks
    independent matrices."""
    lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_params(key, cfg):
    """Weights from one key: Glorot-uniform matrices (the embedding too),
    unit norm scales, a zero gate bias."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    v, n = cfg["vocab_size"], cfg["num_hidden_layers"]
    ks = iter(jax.random.split(key, 10))
    shapes = {"wq": (n, d, d), "wk": (n, d, d), "wv": (n, d, d),
              "wo": (n, d, d), "w_gate": (n, d, ff), "w_up": (n, d, ff),
              "w_down": (n, ff, d)}
    layers = {name: _glorot(next(ks), shapes[name]) for name in _MATRICES}
    for i in range(1, 5):
        layers[f"norm{i}"] = jnp.ones((n, d), jnp.float32)
    return {
        "embed": _glorot(next(ks), (v, d)),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "head": _glorot(next(ks), (d, v)),
        "gate_w": _glorot(next(ks), (d, 1))[:, 0],
        "gate_b": jnp.zeros((1,), jnp.float32),
    }


class _Rounding:
    """How a lower-precision control rounds: ``operand`` every matmul
    operand, ``stored`` every activation a layer hands on.  Both are the
    identity in the reference itself."""

    def __init__(self, quant, act):
        self.operand, self.stored = quant, act


def _mm(x, w, r):
    return r.stored(jnp.dot(r.operand(x), r.operand(w), precision=_HI))


def _rms(g, x, eps, r):
    s = r.stored
    ms = s(jnp.mean(jnp.square(x), axis=-1, keepdims=True))
    return s(s(x * s(lax.rsqrt(ms + eps))) * g)


def _rotary(x, theta):
    """x (H, L, D): the pair (i, i + D/2) at position m turned by
    m * theta ** (-2i / D)."""
    l, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)
    half = d // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _head_attention(q, k, v, r):
    """One head of one sequence: q, k, v (L, D)."""
    l, d = q.shape
    scores = r.stored(jnp.dot(r.operand(q), r.operand(k).T, precision=_HI)
                      / math.sqrt(d))
    scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -1e30)
    probs = r.stored(jax.nn.softmax(scores, axis=-1))
    return r.stored(jnp.dot(r.operand(probs), r.operand(v), precision=_HI))


def _layer(p, x, cfg, r):
    """One decoder layer on one sequence: x (L, d)."""
    l, d = x.shape
    h = cfg["num_attention_heads"]
    eps, s = cfg["rms_norm_eps"], r.stored

    def heads(t):
        return t.reshape(l, h, d // h).transpose(1, 0, 2)      # (H, L, D)

    u = _rms(p["norm1"], x, eps, r)
    q, k, v = (heads(_mm(u, p[n], r)) for n in ("wq", "wk", "wv"))
    q = s(_rotary(q, cfg["rope_theta"]))
    k = s(_rotary(k, cfg["rope_theta"]))
    ctx = lax.map(jax.checkpoint(lambda qkv: _head_attention(*qkv, r)),
                  (q, k, v))
    ctx = ctx.transpose(1, 0, 2).reshape(l, d)
    a = s(x + _rms(p["norm2"], _mm(ctx, p["wo"], r), eps, r))
    u = _rms(p["norm3"], a, eps, r)
    f = _mm(s(jax.nn.silu(_mm(u, p["w_gate"], r)) * _mm(u, p["w_up"], r)),
            p["w_down"], r)
    return s(a + _rms(p["norm4"], f, eps, r))


def _pass_ce(h, head, y, r):
    """Cross-entropy of one pass's logits at every position: (L,)."""
    logp = jax.nn.log_softmax(_mm(h, head, r), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]


def one_pass(params, h, cfg, r):
    """h_{t-1} (L, d) -> h_t: the layers in order, then the final norm."""
    layer = jax.checkpoint(lambda p, x: _layer(p, x, cfg, r))
    h, _ = lax.scan(lambda x, p: (layer(p, x), None), h, params["layers"])
    return _rms(params["final_norm"], h, cfg["rms_norm_eps"], r)


def exit_gate(params, h):
    """lambda_t (L,) from h_t (L, d)."""
    return jax.nn.sigmoid(jnp.dot(h, params["gate_w"], precision=_HI)
                          + params["gate_b"][0])


def exit_distribution(gates):
    """lambda (T, L) -> p (T, L), summing to 1 over T: the last pass takes
    what is left, whatever its own gate says."""
    stay = jnp.ones_like(gates[0])
    p = []
    for lam in gates[:-1]:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def _sequence_loss(params, ids, y, cfg, quant, act):
    """Sum over one sequence's tokens of sum_t p_t CE_t - beta H(p).  The
    passes run as a ``lax.scan`` (the same weights every pass), each pass
    under ``jax.checkpoint``."""
    r = _Rounding(quant, act)
    total = cfg["total_ut_steps"]
    cut = cfg.get("planted_fault") == "last_pass_gradient_only"

    @jax.checkpoint
    def body(h, t):
        h = one_pass(params, h, cfg, r)
        if cut:     # planted: no gradient flows back out of an early pass
            h = jnp.where(t < total - 1, lax.stop_gradient(h), h)
        return h, (_pass_ce(h, params["head"], y, r), exit_gate(params, h))

    _, (ce, gates) = lax.scan(body, r.stored(params["embed"][ids]),
                              jnp.arange(total))                # (T, L) each
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    return jnp.sum(jnp.sum(p * ce, axis=0) - cfg["exit_entropy_beta"]
                   * entropy)


def loss_fn(params, xs, y, cfg, quant=lambda a: a, act=lambda a: a):
    """The mean over all tokens of the batch, a sequence at a time."""
    (ids,) = xs
    one = jax.checkpoint(lambda i, t: _sequence_loss(
        params, i, t, cfg, quant, act))
    sums = lax.map(lambda a: one(*a), (ids.astype(jnp.int32),
                                       y.astype(jnp.int32)))
    return jnp.sum(sums) / y.size
