"""Kimi Delta Attention (KDA): linear attention by a gated delta rule with a
decay a channel (Kimi Linear, Moonshot AI 2025; the delta rule: Yang et al.
2024, "Gated Delta Networks", arXiv:2412.06464), its state computed a chunk
of positions at a time.

A head keeps a state ``S`` of (key size x value size) that decays channel
by channel and is corrected toward the new value by a rank-one term:

    q, k = l2norm_head(silu(conv(u W_q))), l2norm_head(silu(conv(u W_k)))
    v = silu(conv(u W_v))                      (causal depthwise conv, no bias)
    g_t = -exp(A_log_h) * softplus(u W_fa W_fb + dt_bias)  (a channel's log)
    beta_t = sigmoid(u W_b)                    (one a head)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
                                               (S_0 = 0)
    o_t = S_t^T q_t / sqrt(key size)
    out = [RMSNorm_head(o) * sigmoid(u W_ga W_gb + b_gb)] W_o

Step by step that is L dependent updates of a (K, V) state a head.
``kda_chunked`` computes the same ``o`` a chunk of ``C`` positions at a time
(the WY form of the delta rule).  With ``G_t`` the log-decay summed from the
chunk's start and ``A_t = exp(G_t)``, a chunk's updates ``U`` solve a unit
lower-triangular system, ``(I + diag(beta) M) U = diag(beta) (V - (K o A)
S_0)``, where ``M_ts = sum_c k_tc k_sc exp(G_tc - G_sc)`` for ``s < t``; the
outputs are ``O = (Q o A) S_0 + P U`` with ``P`` the same form over q and k
for ``s <= t``; and the state carried to the next chunk is ``Diag(A_C) S_0
+ (K o A_C / A)^T U``.  So the chunks' systems are solved all at once, and
only the (K, V) states are carried from chunk to chunk (L / C steps).

A decay a channel makes ``exp(G_t - G_s)`` a (C, C, K) quantity, and its
factors ``exp(G_t)``, ``exp(-G_s)`` overflow float32 within a chunk (a step's
log-decay reaches -1.6 at initialisation: -100 over 64 positions).  So the
pairs are formed a sub-chunk at a time: between sub-chunks the factors are
taken relative to the later sub-chunk's start, so that none exceeds 1 and
the products go to the MXU; inside a sub-chunk (``_decay_pairs``) the
exponent of every pair is formed exactly, on the vector unit, with a
backward pass written by hand that forms it again (on a v5e one layer's scan
at 2 x 8,192 tokens takes 109.1 ms forward and backward so, 120.3 ms with
autodiff's backward, which keeps the exponentials).  Decays, cumulative
sums, the solve and the states are float32; the products between sub-chunks
and the outputs' take operands of the compute dtype and accumulate in
float32.  ``kda_recurrent`` is the recurrence itself, a position at a time:
the oracle of the tests and of ``chip_smoke.py``.

``kda_chunked`` is the path off the TPU and the oracle of the kernels; on
the TPU the mixer takes the same mathematics from the Pallas kernels of
``ops/kda_scan.py`` (forward, and a backward written by hand), which keep a
chunk's pairs, its solve and every head's carried state in VMEM.
"""

from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.nn import initializers
from analytics_zoo_tpu.nn.layers.attention import (
    _dense, _dense_params, _named_dense)
from analytics_zoo_tpu.nn.layers.normalization import RMSNorm
from analytics_zoo_tpu.nn.layers.ssm import causal_conv
from analytics_zoo_tpu.nn.module import StatelessLayer
from analytics_zoo_tpu.ops.kda_scan import RESIDUAL

_HI = lax.Precision.HIGHEST
# the XLA form of the scan takes at most this many heads of one sequence at
# a time (the kernels take every head at once)
_SCAN_HEADS = 16
# the pairs of a chunk are formed this many positions at a time (or the
# whole chunk where it is shorter): the family's kernels' sub-chunk
_SUB_CHUNK = 16


def kda_recurrent(q, k, v, g, beta):
    """The recurrence a position at a time, float32: q, k (B, L, H, K),
    v (B, L, H, V), g (B, L, H, K) log-decays, beta (B, L, H) -> o (B, L,
    H, V) with ``o_t = S_t^T q_t`` and ``S_0 = 0``."""
    f32 = jnp.float32
    bsz, _, h, dk = k.shape

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        pred = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HI)
        s = s + (b_t[..., None, None] * k_t[..., :, None]
                 * (v_t - pred)[..., None, :])
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HI)

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((bsz, h, dk, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 1)


def _pair_decay(gam, strict: bool, transposed: bool = False):
    """``exp(gam_t - gam_s)`` of every pair of a sub-chunk, (..., S, S, K)
    indexed [t, s] (``transposed``: [s, t]), 0 above the diagonal (and on
    it, ``strict``).  Masked BEFORE the exponential, where the difference is
    positive and may overflow.  ``transposed`` gives the backward pass's
    second sum an expression of its own, so that XLA fuses each sum with its
    own exponential and keeps none of them in memory."""
    s = gam.shape[-2]
    keep = jnp.tril(jnp.ones((s, s), bool), -1 if strict else 0)
    if transposed:
        diff = gam[..., None, :, :] - gam[..., :, None, :]
        keep = keep.T
    else:
        diff = gam[..., :, None, :] - gam[..., None, :, :]
    return jnp.exp(jnp.where(keep[:, :, None], diff, -jnp.inf))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _decay_pairs(a, b, gam, strict):
    """``sum_c a_tc b_sc exp(gam_tc - gam_sc)`` for ``s < t`` (``strict``)
    or ``s <= t``, inside each sub-chunk: a, b, gam (..., S, K) float32 ->
    (..., S, S)."""
    return jnp.sum(a[..., :, None, :] * b[..., None, :, :]
                   * _pair_decay(gam, strict), axis=-1)


def _decay_pairs_fwd(a, b, gam, strict):
    return _decay_pairs(a, b, gam, strict), (a, b, gam)


def _decay_pairs_bwd(strict, res, d):
    a, b, gam = res
    da = jnp.sum(d[..., None] * b[..., None, :, :] * _pair_decay(gam, strict),
                 axis=-2)
    db = jnp.sum(jnp.swapaxes(d, -1, -2)[..., None] * a[..., None, :, :]
                 * _pair_decay(gam, strict, transposed=True), axis=-2)
    # gam_t enters with a plus, gam_s with a minus
    return da, db, a * da - b * db


_decay_pairs.defvjp(_decay_pairs_fwd, _decay_pairs_bwd)


def _within_chunk(q, k, gam, sub: int, dtype):
    """M (strictly lower) and P (lower) of every chunk, (..., C, C) float32,
    from q, k and the log-decays summed from the chunk's start, (..., C, K)
    float32.  Between sub-chunks: row t of sub-chunk i decayed from its
    start ``R_i`` (``exp(G_t - R_i)``), column s before it decayed to ``R_i``
    (``exp(R_i - G_s)``), both at most 1, one product in ``dtype``; inside a
    sub-chunk: ``_decay_pairs``."""
    *lead, c, _ = k.shape
    ns = c // sub

    def blocks(t):
        return t.reshape(tuple(lead) + (ns, sub, t.shape[-1]))

    gb = blocks(gam)
    # a sub-chunk's start: the log-decay after the last step before it
    start = jnp.concatenate([jnp.zeros_like(gb[..., :1, -1, :]),
                             gb[..., :-1, -1, :]], axis=-2)    # (..., ns, K)
    from_start = jnp.exp(gb - start[..., None, :])
    rows = {"m": (blocks(k) * from_start).astype(dtype),
            "p": (blocks(q) * from_start).astype(dtype)}
    diag = {"m": _decay_pairs(blocks(k), blocks(k), gb, True),
            "p": _decay_pairs(blocks(q), blocks(k), gb, False)}
    band = {"m": [], "p": []}
    for i in range(ns):
        parts = {name: [] for name in band}
        if i:
            before = (k[..., :i * sub, :] * jnp.exp(
                start[..., i:i + 1, :] - gam[..., :i * sub, :])).astype(dtype)
            for name in band:
                parts[name].append(jnp.einsum(
                    "...tk,...sk->...ts", rows[name][..., i, :, :], before,
                    preferred_element_type=jnp.float32))
        for name in band:
            parts[name].append(diag[name][..., i, :, :])
            if i < ns - 1:
                parts[name].append(jnp.zeros(
                    tuple(lead) + (sub, c - (i + 1) * sub), jnp.float32))
            band[name].append(jnp.concatenate(parts[name], axis=-1))
    out = {name: jnp.concatenate(rows_, axis=-2)
           for name, rows_ in band.items()}
    return out["m"], out["p"]


def kda_chunked(q, k, v, g, beta, chunk: int, sub: int = _SUB_CHUNK):
    """``o_t = S_t^T q_t`` of the recurrence above, a chunk at a time.

    q, k (B, L, H, K) and v (B, L, H, V) in the compute dtype; g (B, L, H,
    K) float32 log-decays (at most 0); beta (B, L, H) float32.  L is a
    multiple of ``chunk`` and ``chunk`` of ``sub``.  Returns (B, L, H, V)
    float32."""
    f32, dt = jnp.float32, q.dtype
    bsz, l, h, dk = k.shape
    n = l // chunk

    def chunks(t):                  # (B, L, H, X) -> (N, B, H, C, X)
        return jnp.transpose(t.reshape(bsz, n, chunk, h, t.shape[-1]),
                             (1, 0, 3, 2, 4))

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gam = jnp.cumsum(chunks(g.astype(f32)), axis=-2)
    bet = chunks(beta.astype(f32)[..., None])
    qf, kf = qc.astype(f32), kc.astype(f32)
    m, p = _within_chunk(qf, kf, gam, sub, dt)
    # the chunk's updates for a state of 0 (u0) and per unit of it (w):
    # (I + diag(beta) M) [w | u0] = diag(beta) [K o A | V], float32
    a = jnp.exp(gam)
    wu = lax.linalg.triangular_solve(
        bet * m, bet * jnp.concatenate([kf * a, vc.astype(f32)], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w, u0 = wu[..., :dk], wu[..., dk:]
    # what the carried state gives the outputs: Q o A - P w
    q_state = qf * a - jnp.einsum("...ts,...sk->...tk", p.astype(dt),
                                  w.astype(dt), preferred_element_type=f32)
    k_end = kf * jnp.exp(gam[..., -1:, :] - gam)       # decayed to the end

    # the state each chunk starts from, carried in float32: N steps
    def carry(s, xs):
        w_n, u_n, k_n, a_n = xs
        u = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s, precision=_HI)
        return (a_n[..., None] * s
                + jnp.einsum("bhck,bhcv->bhkv", k_n, u, precision=_HI)), s

    _, s_in = lax.scan(carry, jnp.zeros((bsz, h, dk, v.shape[-1]), f32),
                       (w, u0, k_end, a[..., -1, :]))
    o = (jnp.einsum("...ts,...sv->...tv", p.astype(dt), u0.astype(dt),
                    preferred_element_type=f32)
         + jnp.einsum("...tk,...kv->...tv", q_state.astype(dt),
                      s_in.astype(dt), preferred_element_type=f32))
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(bsz, l, h, -1)


def _l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum of squares + eps) over the last axis, the sum in
    float32; the result has x's dtype (and the backward pass keeps x in
    it)."""
    return x * lax.rsqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1,
                                 keepdims=True) + eps).astype(x.dtype)


class KDAMixer(StatelessLayer):
    """The KDA mixer over (B, L, hidden) -> (B, L, hidden): the module
    docstring's equations, ``n_heads`` heads of ``head_dim`` (keys and
    values alike), short convolutions of ``d_conv`` taps, the decay's and
    the output gate's low-rank pairs through ``head_dim``, the scan in
    chunks of ``chunk_size`` formed ``_SUB_CHUNK`` positions at a time (a
    length that is no multiple of the chunk is padded with positions that
    change no state, and a warning says so).  No projection has a bias but
    the output gate's second.

    Two ``jax.named_scope``s mark it for a device trace: ``zoo:kda/mixer``
    the whole mixer and, nested in it, ``zoo:kda/scan`` from q, k, v, the
    decays and beta to the outputs before the gated norm.  Every trace
    counts ``ops_kernel_selected_total{kernel="kda_scan",path=...}``:
    ``pallas`` on a TPU at shapes Mosaic compiles (``ops/kda_scan.shapes_ok``:
    the kernels ``kda_scan_fwd`` and ``kda_scan_bwd``, inside the scan's
    scope, every head at once), ``reference`` (``kda_chunked``, plain XLA, a
    sequence's ``_SCAN_HEADS`` heads at a time) anywhere else.  The choice
    is made from the backend and the shapes; there is no switch."""

    def __init__(self, hidden_size: int, n_heads: int, head_dim: int,
                 d_conv: int = 4, chunk_size: int = 64, epsilon: float = 1e-5,
                 init="glorot_uniform", **kw):
        super().__init__(**kw)
        self.sub_chunk = min(_SUB_CHUNK, chunk_size)
        if chunk_size % self.sub_chunk:
            raise ValueError(f"a chunk of {chunk_size} is no multiple of "
                             f"the sub-chunk of {self.sub_chunk}")
        self.hidden_size, self.n_heads, self.head_dim = (hidden_size, n_heads,
                                                         head_dim)
        self.d_conv, self.epsilon = d_conv, epsilon
        self.chunk_size = chunk_size
        self.d_inner = n_heads * head_dim
        self.initializer = initializers.get(init)
        self.norm = RMSNorm(epsilon, name=f"{self.name}_norm")

    def projections(self):
        """(name, fan-in, fan-out) of the results worth keeping for the
        backward pass (``attention._keep_within_budget``)."""
        d, inner = self.hidden_size, self.d_inner
        return (("o_proj", inner, d), ("q_proj", d, inner),
                ("k_proj", d, inner), ("v_proj", d, inner))

    def values_a_token(self) -> int:
        """About what a token keeps where nothing is computed again: q, k,
        v before and after their convolutions, the decays (float32) and
        their cumulative sums, the gate, the outputs before and after the
        norm, and the chunked scan's (C, C) products and float32 solutions
        (4 x inner)."""
        return 16 * self.d_inner + 2 * self.n_heads * self.chunk_size

    def build_params(self, rng, x_shape, *rest):
        ks = iter(jax.random.split(rng, 14))
        d, inner, h, f32 = (self.hidden_size, self.d_inner, self.n_heads,
                            jnp.float32)

        def dense(d_in, d_out, use_bias=False):
            return _dense_params(next(ks), d_in, d_out, self.initializer,
                                 use_bias=use_bias)

        lim = 1.0 / math.sqrt(self.d_conv)
        params = {name: dense(d, inner)
                  for name in ("q_proj", "k_proj", "v_proj")}
        for name in ("q_conv", "k_conv", "v_conv"):
            params[name] = {"kernel": jax.random.uniform(
                next(ks), (self.d_conv, inner), f32, -lim, lim)}
        # the decay's time scale log-uniform in 1e-3..1e-1 (dt_bias its
        # inverse softplus), A in 1..16, as the family's initialisation
        delta = jnp.exp(jax.random.uniform(next(ks), (inner,), f32,
                                           math.log(1e-3), math.log(1e-1)))
        params.update(
            f_a=dense(d, self.head_dim), f_b=dense(self.head_dim, inner),
            g_a=dense(d, self.head_dim),
            g_b=dense(self.head_dim, inner, use_bias=True),
            b_proj=dense(d, h),
            dt_bias=delta + jnp.log(-jnp.expm1(-delta)),
            A_log=jnp.log(jax.random.uniform(next(ks), (h,), f32, 1.0, 16.0)),
            o_norm={"gamma": jnp.ones((self.head_dim,), f32)},
            o_proj=dense(inner, d))
        return params

    def _chunking(self, l: int):
        """The scan's chunk over a sequence of ``l`` and the positions it
        is padded by."""
        sub = self.sub_chunk
        chunk = min(self.chunk_size, -(-l // sub) * sub)
        return chunk, -l % chunk

    def kernel_residuals(self, x_shape, dtype):
        """What the scan's kernels write for their own backward pass, as
        (name, bytes of one application to an input of ``x_shape``), for
        the stack's recomputation rule (``attention.HybridDecoderStack``):
        the chunk-start states, float32, and the scaled output the gated
        norm takes, in ``dtype``, both named ``kda_scan.RESIDUAL``.  Kept
        together, the backward pass runs no forward kernel again; either
        alone spares none.  0 bytes where the kernels are not selected
        (asked as ``_scan`` asks, without counting a selection)."""
        from analytics_zoo_tpu.ops import dispatch
        from analytics_zoo_tpu.ops.kda_scan import shapes_ok

        bsz, l, _ = x_shape
        chunk, pad = self._chunking(l)
        heads = (bsz, l + pad, self.n_heads, self.head_dim)
        if not (dispatch.on_tpu() and shapes_ok(heads, self.head_dim, chunk,
                                                self.sub_chunk, dtype)):
            return ((RESIDUAL, 0),)
        states = bsz * (l + pad) // chunk * self.d_inner * self.head_dim * 4
        return ((RESIDUAL, states + bsz * l * self.d_inner
                 * jnp.dtype(dtype).itemsize),)

    def _scan(self, q, k, v, g, beta):
        from analytics_zoo_tpu.ops import dispatch
        from analytics_zoo_tpu.ops.kda_scan import kda_scan, shapes_ok

        l = q.shape[1]
        sub = self.sub_chunk
        chunk, pad = self._chunking(l)
        if pad:
            warnings.warn(
                f"{self.name}: a sequence of {l} is no multiple of the "
                f"scan's chunk of {chunk}; padded by {pad} positions that "
                "change no state", stacklevel=2)
            # no decay, nothing put in, nothing read
            q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                        * (t.ndim - 2))
                                for t in (q, k, v, g, beta))
        # the kernels where Mosaic takes the shapes, on the TPU: every head
        # at once, a chunk's products never in HBM
        path = dispatch.select_path("kda_scan", shapes_ok=shapes_ok(
            q.shape, v.shape[-1], chunk, sub, q.dtype))
        if path == dispatch.PATH_PALLAS:
            return kda_scan(q, k, v, g, beta, chunk, sub)[:, :l]
        # the XLA form: a sequence's _SCAN_HEADS heads at a time and
        # computed again in the backward pass, so that only their chunk
        # products are alive
        bsz, lp, h = q.shape[:3]
        part = math.gcd(h, _SCAN_HEADS)

        def parts(t):           # (B, L, H, ...) -> (B H / part, L, part, ...)
            t = t.reshape((bsz, lp, h // part, part) + t.shape[3:])
            return jnp.moveaxis(t, 2, 1).reshape((-1, lp, part) + t.shape[4:])

        o = lax.map(jax.checkpoint(lambda xs: kda_chunked(
            *(t[None] for t in xs), chunk, sub)[0]),
            tuple(parts(t) for t in (q, k, v, g, beta)))
        o = jnp.moveaxis(o.reshape((bsz, h // part, lp, part, -1)), 1, 2)
        return o.reshape(bsz, lp, h, -1)[:, :l]

    def forward(self, params, x, training=False, rng=None):
        f32 = jnp.float32
        bsz, l, _ = x.shape
        heads = (bsz, l, self.n_heads, self.head_dim)
        with jax.named_scope("zoo:kda/mixer"):
            q, k, v = (causal_conv(params[name + "_conv"],
                                   _named_dense(params, name + "_proj", x)
                                   ).reshape(heads)
                       for name in "qkv")
            q, k = _l2norm(q), _l2norm(k)
            a = -jnp.exp(params["A_log"].astype(f32))
            g = (jnp.repeat(a, self.head_dim) * jax.nn.softplus(
                _dense(params["f_b"], _dense(params["f_a"], x)).astype(f32)
                + params["dt_bias"].astype(f32))).reshape(heads)
            beta = jax.nn.sigmoid(_dense(params["b_proj"], x).astype(f32))
            with jax.named_scope("zoo:kda/scan"):
                o = self._scan(q, k, v, g, beta)
            # the score scale, on the outputs (o is linear in q); the gated
            # norm's statistics in float32, its values in the compute dtype.
            # Named as the kernels' states are (``kernel_residuals``)
            o = self.norm.forward(params["o_norm"], checkpoint_name(
                (o * self.head_dim ** -0.5).astype(x.dtype), RESIDUAL))
            gate = jax.nn.sigmoid(_dense(params["g_b"], _dense(
                params["g_a"], x))).reshape(heads)
            return _named_dense(params, "o_proj",
                                (o * gate).reshape(bsz, l, self.d_inner))
