"""State-space layers: the Mamba-2 mixer (Dao & Gu 2024, "Transformers are
SSMs", arXiv:2405.21060) with its scan computed a chunk at a time.

A head keeps a state ``S`` of (head size x state size) that decays by a
scalar a step and takes a rank-one update:

    [z | xBC | dt] = u W_in
    xBC = silu(causal depthwise conv over the sequence, with bias)
    x -> H heads of P;  B, C -> G groups of N, a group shared by H / G heads
    delta_t = softplus(dt_t + dt_bias);   A = -exp(A_log)      (a scalar a head)
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T           (S_0 = 0)
    y_t = S_t C_t + D x_t
    out = N_g(y * silu(z)) W_out

Step by step that is L dependent updates of a (P, N) state a head, which a
TPU runs one tiny operation at a time.  ``ssd_chunked`` computes the same
``y`` from four products a chunk of ``Q`` positions (the paper's section 6):
inside a chunk the masked product ``(C B^T o decay) (delta x)``; one state a
chunk, ``sum_s decay_to_end_s (delta x)_s B_s^T``; the states carried from
chunk to chunk, ``S_in[c+1] = decay_c S_in[c] + state_c`` (L / Q steps); and
the carried state's term ``decay_from_start_t C_t S_in``.  Decays, cumulative
sums and states are float32; the products take operands of the compute dtype
and accumulate in float32.  ``ssd_chunked`` is plain ``jax.numpy`` under XLA,
its backward pass autodiff through this form: one layer's (B, L / Q, H, Q, Q)
decay tensors cross HBM in every pass.  It is the path off the TPU and the
oracle; on the TPU the mixer takes the same four products from the Pallas
kernels of ``ops/ssm_scan.py`` (forward, and a backward written by hand),
which keep a chunk's decay tile in VMEM.
"""

from __future__ import annotations

import math
import warnings

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.nn import initializers
from analytics_zoo_tpu.nn.layers.attention import _dense_params, _named_dense
from analytics_zoo_tpu.nn.layers.normalization import RMSNorm
from analytics_zoo_tpu.nn.module import StatelessLayer


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """``y_t = S_t C_t`` of the recurrence above, a chunk at a time.

    x (B, L, H, P) and b, c (B, L, G, N) in the compute dtype; dt (B, L, H)
    float32, after the softplus; a (H,) float32, negative.  L is a multiple
    of ``chunk``.  Returns (B, L, H, P) float32."""
    bsz, l, h, p = x.shape
    g, n = b.shape[-2:]
    nc, r, f32 = l // chunk, h // g, jnp.float32
    dx = (x.astype(f32) * dt[..., None]).astype(x.dtype)
    dx = dx.reshape(bsz, nc, chunk, g, r, p)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    # log of the decay, summed from a chunk's start: (B, nc, G, R, Q)
    cum = jnp.cumsum(jnp.moveaxis(
        (dt * a).reshape(bsz, nc, chunk, g, r), 2, -1), axis=-1)
    # inside a chunk: position t takes from s <= t what s put in, decayed
    # over s+1..t.  Masked BEFORE the exponential: above the diagonal the
    # difference is positive and may overflow, and 0 * inf is no gradient
    seg = cum[..., :, None] - cum[..., None, :]
    seg = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), seg, -jnp.inf)
    cb = jnp.einsum("zctgn,zcsgn->zcgts", c, b, preferred_element_type=f32)
    m = (cb[:, :, :, None] * jnp.exp(seg)).astype(x.dtype)
    y = jnp.einsum("zcgrts,zcsgrp->zctgrp", m, dx,
                   preferred_element_type=f32)
    # one state a chunk: what its positions put in, decayed to its end
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), -1, 2)
    states = jnp.einsum(
        "zcsgn,zcsgrp->zcgrpn", b,
        (dx.astype(f32) * to_end[..., None]).astype(x.dtype),
        preferred_element_type=f32)
    # the state a chunk starts from, carried in float32: nc steps

    def carry(s, xs):
        decay, state = xs
        return decay[..., None, None] * s + state, s

    _, s_in = jax.lax.scan(
        carry, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0),
         jnp.moveaxis(states, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1).astype(x.dtype)     # (B, nc, G, R, P, N)
    from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)      # (B, nc, Q, G, R)
    y = y + from_start[..., None] * jnp.einsum(
        "zctgn,zcgrpn->zctgrp", c, s_in, preferred_element_type=f32)
    return y.reshape(bsz, l, h, p)


class Mamba2Mixer(StatelessLayer):
    """The Mamba-2 mixer over (B, L, hidden) -> (B, L, hidden): the module
    docstring's equations, ``n_heads`` heads of ``head_dim`` with a state of
    ``d_state`` a head, ``n_groups`` groups of B and C, a causal depthwise
    convolution of ``d_conv`` taps, the scan in chunks of ``chunk_size``
    (a shorter sequence is one chunk; a length that is no multiple of the
    chunk is padded with steps that change no state, and a warning says
    so).  The gated norm is taken over each group's share of the heads.
    No projection has a bias; the convolution has one unless
    ``conv_bias=False``.

    Two ``jax.named_scope``s mark it for a device trace: ``zoo:ssm/mixer``
    the whole mixer and, nested in it, ``zoo:ssm/scan`` from the split of
    the convolved ``xBC`` to ``y`` before the gate.  Every trace counts
    ``ops_kernel_selected_total{kernel="ssm_scan",path=...}``: ``pallas``
    on a TPU at shapes Mosaic compiles (``ops/ssm_scan.shapes_ok``: the
    kernels ``ssm_scan_fwd`` and ``ssm_scan_bwd``, inside the scan's
    scope), ``reference`` (``ssd_chunked``) anywhere else.  The choice is
    made from the backend and the shapes; there is no switch."""

    def __init__(self, hidden_size: int, n_heads: int, head_dim: int,
                 d_state: int, n_groups: int = 1, d_conv: int = 4,
                 chunk_size: int = 256, conv_bias: bool = True,
                 epsilon: float = 1e-5, init="glorot_uniform", **kw):
        super().__init__(**kw)
        if n_heads % n_groups:
            raise ValueError(f"{n_heads} heads do not divide into "
                             f"{n_groups} groups")
        self.hidden_size, self.n_heads, self.head_dim = (hidden_size, n_heads,
                                                         head_dim)
        self.d_state, self.n_groups, self.d_conv = d_state, n_groups, d_conv
        self.chunk_size, self.conv_bias, self.epsilon = (chunk_size,
                                                         conv_bias, epsilon)
        self.d_inner = n_heads * head_dim
        self.conv_dim = self.d_inner + 2 * n_groups * d_state
        self.proj_dim = self.d_inner + self.conv_dim + n_heads
        self.initializer = initializers.get(init)
        self.norm = RMSNorm(epsilon, name=f"{self.name}_norm")

    def projections(self):
        """(name, fan-in, fan-out) of the results worth keeping for the
        backward pass (``attention._keep_within_budget``)."""
        return (("out_proj", self.d_inner, self.hidden_size),
                ("in_proj", self.hidden_size, self.proj_dim))

    def values_a_token(self) -> int:
        """About what a token keeps where nothing is computed again: the
        projection, the convolved xBC, delta x, y, the gated and the normed
        value, and the scan's two (heads x chunk) decay tensors in float32."""
        return (self.proj_dim + self.conv_dim + 4 * self.d_inner
                + 4 * self.n_heads * self.chunk_size)

    def build_params(self, rng, x_shape, *rest):
        ki, ko, kc, kb, ka, kd = jax.random.split(rng, 6)
        h, f32 = self.n_heads, jnp.float32
        # the Mamba-2 defaults: the taps as a convolution's, A in 1..16,
        # delta log-uniform in 1e-3..1e-1 (dt_bias its inverse softplus)
        lim = 1.0 / math.sqrt(self.d_conv)
        conv = {"kernel": jax.random.uniform(
            kc, (self.d_conv, self.conv_dim), f32, -lim, lim)}
        if self.conv_bias:
            conv["bias"] = jax.random.uniform(kb, (self.conv_dim,), f32,
                                              -lim, lim)
        delta = jnp.exp(jax.random.uniform(kd, (h,), f32, math.log(1e-3),
                                           math.log(1e-1)))
        return {
            "in_proj": _dense_params(ki, self.hidden_size, self.proj_dim,
                                     self.initializer, use_bias=False),
            "conv": conv,
            "dt_bias": delta + jnp.log(-jnp.expm1(-delta)),
            "A_log": jnp.log(jax.random.uniform(ka, (h,), f32, 1.0, 16.0)),
            "D": jnp.ones((h,), f32),
            "norm": {"gamma": jnp.ones((self.d_inner,), f32)},
            "out_proj": _dense_params(ko, self.d_inner, self.hidden_size,
                                      self.initializer, use_bias=False),
        }

    def _conv(self, p, xbc):
        """silu of the causal depthwise convolution: tap ``j`` weighs the
        input ``d_conv - 1 - j`` positions back."""
        l, f32 = xbc.shape[1], jnp.float32
        padded = jnp.pad(xbc.astype(f32),
                         ((0, 0), (self.d_conv - 1, 0), (0, 0)))
        taps = p["kernel"].astype(f32)
        out = sum(taps[j] * padded[:, j:j + l] for j in range(self.d_conv))
        if "bias" in p:
            out = out + p["bias"].astype(f32)
        return jax.nn.silu(out).astype(xbc.dtype)

    def _scan(self, params, xbc, dt):
        from analytics_zoo_tpu.ops import dispatch
        from analytics_zoo_tpu.ops.ssm_scan import shapes_ok, ssm_scan

        bsz, l, _ = xbc.shape
        h, g, n, f32 = self.n_heads, self.n_groups, self.d_state, jnp.float32
        x, b, c = jnp.split(xbc, [self.d_inner, self.d_inner + g * n],
                            axis=-1)
        x = x.reshape(bsz, l, h, self.head_dim)
        b, c = b.reshape(bsz, l, g, n), c.reshape(bsz, l, g, n)
        dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
        a = -jnp.exp(params["A_log"].astype(f32))
        chunk = min(self.chunk_size, l)
        pad = -l % chunk
        if pad:
            warnings.warn(
                f"{self.name}: a sequence of {l} is no multiple of the "
                f"scan's chunk of {chunk}; padded by {pad} steps that "
                "change no state", stacklevel=2)
            # delta = 0: no decay, nothing put in
            x, b, c, dt = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                   * (t.ndim - 2)) for t in (x, b, c, dt))
        # the kernels where Mosaic takes the shapes, on the TPU; a chunk
        # is all the work a grid step has, so none is too small
        path = dispatch.select_path("ssm_scan", shapes_ok=shapes_ok(
            x.shape, g, n, chunk, x.dtype))
        if path == dispatch.PATH_PALLAS:
            y = ssm_scan(x, dt, a, b, c, chunk)
        else:
            y = ssd_chunked(x, dt, a, b, c, chunk)
        y = y + params["D"].astype(f32)[:, None] * x.astype(f32)
        return y[:, :l].reshape(bsz, l, self.d_inner)

    def forward(self, params, x, training=False, rng=None):
        f32 = jnp.float32
        with jax.named_scope("zoo:ssm/mixer"):
            z, xbc, dt = jnp.split(
                _named_dense(params, "in_proj", x),
                [self.d_inner, self.d_inner + self.conv_dim], axis=-1)
            xbc = self._conv(params["conv"], xbc)
            with jax.named_scope("zoo:ssm/scan"):
                y = self._scan(params, xbc, dt)
            y = y * jax.nn.silu(z.astype(f32))
            by_group = (self.n_groups, self.d_inner // self.n_groups)
            y = self.norm.forward(
                {"gamma": params["norm"]["gamma"].reshape(by_group)},
                y.reshape(y.shape[:-1] + by_group)).reshape(y.shape)
            return _named_dense(params, "out_proj", y.astype(x.dtype))
