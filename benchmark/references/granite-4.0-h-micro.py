"""Plain reference for ``granite-4.0-h-micro``: the hybrid decoder of IBM's
Granite 4.0-H family (``model_type`` ``granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro; the state-space
layer is Mamba-2: Dao & Gu 2024, arXiv:2405.21060) with the token-level
cross-entropy, in straightforward ``jax.numpy``, float32.  Imports nothing of
the program.

With d the hidden size, N(.) RMSNorm (``rms_norm_eps``, a learned scale),
r = ``residual_multiplier``, no biases but the convolution's:

    model   h_0 = embedding_multiplier * E[ids]
            layer:  a = x + r * Mixer(N1 x);   y = a + r * MLP(N2 a)
            MLP     (silu(u W_gate) * (u W_up)) W_down     (the shared MLP)
            logits = N_f(h_n) E^T / logits_scaling          (tied head)
            loss    mean over tokens of CE(logits, next id)
    Mamba-2 [z | xBC | dt] = u W_in       widths d_in | d_in + 2 G N | H
            xBC = silu(causal depthwise conv, mamba_d_conv taps, with bias)
            x -> H heads of P;  B, C: G groups of N, a group shared by H / G
            heads
            delta_t = softplus(dt_t + dt_bias);   A = -exp(A_log)
            S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T    (S_0 = 0)
            y_t = S_t C_t + D x_t
            out = N_g(y * silu(z)) W_out  (RMSNorm over a group's heads,
            after the gate)
    Attn    q: ``num_attention_heads`` heads; k, v: ``num_key_value_heads``
            heads, each shared by consecutive query heads; no positional
            encoding; softmax(q k^T * attention_multiplier + causal) v;  W_o

``layer_types`` gives each layer's kind; consecutive layers of one kind are
stored stacked (a leading layer dimension) as one entry of ``runs``.  What ``config.json`` does not state is listed under
``assumed`` in the configuration's file.

**The state-space layer is the recurrence itself, a step at a time**: a
``lax.scan`` over the positions that carries S (H, P, N), in segments of 64
steps under ``jax.checkpoint`` so that the float32 backward pass keeps a
state a segment and one segment's steps.  Attention scores are the full
(L, L) matrix of one head of one sequence.  A sequence is computed at a
time, and each sequence, each layer, each head's attention, each scan
segment and each block of 1,024 positions of the MLP and of the logits are
under ``jax.checkpoint``: the same sums, computed again.  The layers of a
run are read from its stacked leaves one at a time, in a Python loop.

``cfg["planted_fault"]`` (``benchmark/limits`` readings: the limits have to
tell each from the model): ``"no_carried_state"`` starts every stretch of
``mamba_chunk_size`` positions from S = 0; ``"attention_scale_rsqrt"`` scales
the scores by 1 / sqrt(head size) in place of ``attention_multiplier``.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_SEGMENT = 64           # scan steps under one jax.checkpoint
_ROWS = 1024            # positions of a position-wise block
PLANTED_FAULTS = ("no_carried_state", "attention_scale_rsqrt")


def _glorot(key, shape):
    """Glorot-uniform over the last two dimensions; a leading one stacks
    independent matrices."""
    lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def dims(cfg):
    """The widths the configuration implies."""
    d, h, p = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    head = d // cfg["num_attention_heads"]
    return dict(d=d, ff=cfg["shared_intermediate_size"], h=h, p=p, g=g, n=n,
                d_in=h * p, conv=h * p + 2 * g * n,
                proj=2 * h * p + 2 * g * n + h, taps=cfg["mamba_d_conv"],
                head=head, kv=head * cfg["num_key_value_heads"])


def runs_of(cfg):
    """[(kind, layers)]: consecutive layers of one kind."""
    return [(kind, len(list(same)))
            for kind, same in itertools.groupby(cfg["layer_types"])]


def _init_run(key, kind, n, cfg):
    """One run's layers, stacked.  Glorot-uniform matrices and unit norm
    scales; the Mamba-2 defaults for the rest: the convolution's taps and
    bias uniform within 1 / sqrt(taps), A uniform in 1..16, delta
    log-uniform in 1e-3..1e-1 (``dt_bias`` its inverse softplus), D ones."""
    m = dims(cfg)
    d, ff = m["d"], m["ff"]
    ks = iter(jax.random.split(key, 10))
    p = {"norm1": jnp.ones((n, d), jnp.float32),
         "norm2": jnp.ones((n, d), jnp.float32),
         "w_gate": _glorot(next(ks), (n, d, ff)),
         "w_up": _glorot(next(ks), (n, d, ff)),
         "w_down": _glorot(next(ks), (n, ff, d))}
    if kind == "attention":
        p.update(wq=_glorot(next(ks), (n, d, d)),
                 wk=_glorot(next(ks), (n, d, m["kv"])),
                 wv=_glorot(next(ks), (n, d, m["kv"])),
                 wo=_glorot(next(ks), (n, d, d)))
        return p
    lim = 1.0 / math.sqrt(m["taps"])
    delta = jnp.exp(jax.random.uniform(
        next(ks), (n, m["h"]), jnp.float32, math.log(1e-3), math.log(1e-1)))
    p.update(
        w_in=_glorot(next(ks), (n, d, m["proj"])),
        w_out=_glorot(next(ks), (n, m["d_in"], d)),
        conv_w=jax.random.uniform(next(ks), (n, m["taps"], m["conv"]),
                                  jnp.float32, -lim, lim),
        conv_b=jax.random.uniform(next(ks), (n, m["conv"]), jnp.float32,
                                  -lim, lim),
        dt_bias=delta + jnp.log(-jnp.expm1(-delta)),
        A_log=jnp.log(jax.random.uniform(next(ks), (n, m["h"]), jnp.float32,
                                         1.0, 16.0)),
        D=jnp.ones((n, m["h"]), jnp.float32),
        norm_g=jnp.ones((n, m["d_in"]), jnp.float32))
    return p


def init_params(key, cfg):
    runs = runs_of(cfg)
    ke, *ks = jax.random.split(key, 1 + len(runs))
    return {"embed": _glorot(ke, (cfg["vocab_size"], cfg["hidden_size"])),
            "final_norm": jnp.ones((cfg["hidden_size"],), jnp.float32),
            "runs": [_init_run(k, kind, n, cfg)
                     for k, (kind, n) in zip(ks, runs)]}


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


def _by_rows(fn, *rows):
    """``fn`` of every block of ``_ROWS`` positions, a block at a time under
    ``jax.checkpoint``: what is position-wise and wide (the MLP's products,
    the logits) is alive a block at a time, in both passes."""
    l = rows[0].shape[0]
    block = math.gcd(l, _ROWS)
    out = lax.map(jax.checkpoint(lambda xs: fn(*xs)), tuple(
        t.reshape((l // block, block) + t.shape[1:]) for t in rows))
    return out.reshape((l,) + out.shape[2:])


def _mlp(p, u, r):
    return _by_rows(lambda v: _mm(
        r.stored(jax.nn.silu(_mm(v, p["w_gate"], r)) * _mm(v, p["w_up"], r)),
        p["w_down"], r), u)


def _head_attention(q, k, v, scale, r):
    """One head of one sequence: q, k, v (L, D)."""
    l = q.shape[0]
    scores = r.stored(jnp.dot(r.operand(q), r.operand(k).T, precision=_HI)
                      * scale)
    scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -1e30)
    probs = r.stored(jax.nn.softmax(scores, axis=-1))
    return r.stored(jnp.dot(r.operand(probs), r.operand(v), precision=_HI))


def _attention(p, u, cfg, r):
    """Grouped-query attention on one sequence: u (L, d)."""
    m = dims(cfg)
    l, heads, kv_heads = (u.shape[0], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"])
    scale = cfg["attention_multiplier"]
    if cfg.get("planted_fault") == "attention_scale_rsqrt":
        scale = 1.0 / math.sqrt(m["head"])

    def split(t, n):
        return t.reshape(l, n, m["head"]).transpose(1, 0, 2)    # (n, L, D)

    q = split(_mm(u, p["wq"], r), heads)
    # key/value head j serves query heads j * group .. j * group + group - 1
    k, v = (jnp.repeat(split(_mm(u, p[w], r), kv_heads),
                       heads // kv_heads, axis=0) for w in ("wk", "wv"))
    ctx = lax.map(jax.checkpoint(
        lambda qkv: _head_attention(*qkv, scale, r)), (q, k, v))
    return _mm(ctx.transpose(1, 0, 2).reshape(l, m["d"]), p["wo"], r)


def _recurrence(x, dt, a, b, c, reset_every, r):
    """The state-space recurrence, a step at a time.  x (L, H, P), dt (L, H)
    after the softplus, a (H,), b and c (L, G, N), a group for H / G heads
    -> y (L, H, P) with ``y_t = S_t C_t``.  ``reset_every`` > 0 plants the
    fault: the state starts from 0 again at every multiple of it.  A control
    rounds what is put into the state and what is read from it, not the
    carried state."""
    l, h, p = x.shape
    g, n = b.shape[-2:]

    def step(state, xs):
        x_t, dt_t, b_t, c_t, t = xs
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        b_t, c_t = (jnp.repeat(r.operand(v), h // g, axis=0)
                    for v in (b_t, c_t))                        # (H, N)
        put = jnp.einsum("hp,hn->hpn", r.operand(dt_t[:, None] * x_t), b_t,
                         precision=_HI)
        state = jnp.exp(dt_t * a)[:, None, None] * state + put
        return state, r.stored(jnp.einsum("hpn,hn->hp", r.operand(state),
                                          c_t, precision=_HI))

    @jax.checkpoint
    def segment(state, xs):
        return lax.scan(step, state, xs)

    seg = math.gcd(l, _SEGMENT)
    xs = (x, dt, b, c, jnp.arange(l))
    xs = tuple(t.reshape((l // seg, seg) + t.shape[1:]) for t in xs)
    _, y = lax.scan(segment, jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape(l, h, p)


def _mamba(p, u, cfg, r):
    """The Mamba-2 mixer on one sequence: u (L, d)."""
    m = dims(cfg)
    l, s = u.shape[0], r.stored
    h, g = m["h"], m["g"]
    z, xbc, dt = jnp.split(_mm(u, p["w_in"], r),
                           [m["d_in"], m["d_in"] + m["conv"]], axis=-1)
    # tap j weighs the input taps - 1 - j positions back
    padded = jnp.pad(xbc, ((m["taps"] - 1, 0), (0, 0)))
    xbc = s(jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + l]
                            for j in range(m["taps"])) + p["conv_b"]))
    x, b, c = jnp.split(xbc, [m["d_in"], m["d_in"] + g * m["n"]], axis=-1)
    x = x.reshape(l, h, m["p"])
    b, c = b.reshape(l, g, m["n"]), c.reshape(l, g, m["n"])
    delta = jax.nn.softplus(dt + p["dt_bias"])
    reset = (cfg["mamba_chunk_size"]
             if cfg.get("planted_fault") == "no_carried_state" else 0)
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), b, c, reset, r)
    y = s(y + p["D"][:, None] * x).reshape(l, m["d_in"])
    y = s(y * jax.nn.silu(z)).reshape(l, g, m["d_in"] // g)
    y = _rms(1.0, y, cfg["rms_norm_eps"], r).reshape(l, m["d_in"])
    return _mm(s(y * p["norm_g"]), p["w_out"], r)


def _layer(kind, p, x, cfg, r):
    """One decoder layer on one sequence: x (L, d)."""
    s, res = r.stored, cfg["residual_multiplier"]
    mixer = _attention if kind == "attention" else _mamba
    a = s(x + res * mixer(p, _rms(p["norm1"], x, cfg["rms_norm_eps"], r),
                          cfg, r))
    return s(a + res * _mlp(p, _rms(p["norm2"], a, cfg["rms_norm_eps"], r),
                            r))


def hidden_states(params, ids, cfg, r):
    """ids (L,) -> N_f(h_n) (L, d)."""
    h = r.stored(cfg["embedding_multiplier"] * params["embed"][ids])
    for (kind, n), run in zip(runs_of(cfg), params["runs"]):
        layer = jax.checkpoint(
            lambda p, x, kind=kind: _layer(kind, p, x, cfg, r))
        for j in range(n):
            h = layer(jax.tree_util.tree_map(lambda a: a[j], run), h)
    return _rms(params["final_norm"], h, cfg["rms_norm_eps"], r)


def logits(params, ids, cfg, quant=lambda a: a, act=lambda a: a):
    """One sequence's logits (L, V)."""
    r = _Rounding(quant, act)
    return _mm(hidden_states(params, ids, cfg, r), params["embed"].T,
               r) / cfg["logits_scaling"]


def _sequence_loss(params, ids, y, cfg, quant, act):
    """Sum over one sequence's tokens of CE(logits, y)."""
    r = _Rounding(quant, act)

    def ce(h, t):
        logp = jax.nn.log_softmax(
            _mm(h, params["embed"].T, r) / cfg["logits_scaling"], axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    return jnp.sum(_by_rows(ce, hidden_states(params, ids, cfg, r), y))


def loss_fn(params, xs, y, cfg, quant=lambda a: a, act=lambda a: a):
    """The mean over all tokens of the batch, a sequence at a time."""
    (ids,) = xs
    one = jax.checkpoint(lambda i, t: _sequence_loss(
        params, i, t, cfg, quant, act))
    sums = lax.map(lambda a: one(*a), (ids.astype(jnp.int32),
                                       y.astype(jnp.int32)))
    return jnp.sum(sums) / y.size
