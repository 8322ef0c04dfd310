"""Normalization layers: BatchNormalization, LayerNorm, RMSNorm, LRN2D.

Reference capability: api/keras/layers/{BatchNormalization,LRN2D,
WithinChannelLRN2D}.scala.

TPU-first: BatchNorm keeps moving statistics in the layer *state* pytree —
updated functionally (no mutation) so the whole train step stays one pure
jitted program; with data parallelism the batch statistics are computed
per-shard (matching the reference, which normalizes per worker-replica —
InternalDistriOptimizer clones per core).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from analytics_zoo_tpu.nn.module import Layer, StatelessLayer


class BatchNormalization(Layer):
    """Batch normalization over the channel axis.

    Reference: api/keras/layers/BatchNormalization.scala.  ``axis`` follows
    channels-last by default (-1); pass ``dim_ordering='th'``/``axis=1`` for
    channels-first inputs.
    """

    def __init__(self, epsilon: float = 1e-3, momentum: float = 0.99,
                 beta_init="zero", gamma_init="one", axis: int = -1,
                 dim_ordering: str = "tf", scale: bool = True,
                 center: bool = True, stats_fraction: float = 1.0, **kw):
        """``stats_fraction < 1`` enables ghost-BN: training statistics
        are computed over the leading ``ceil(fraction * B)`` rows of the
        batch (normalization still covers every row).  On TPU the BN
        stats pass is HBM traffic, and BatchNorm's and the elementwise
        fusions are what bounds ResNet-50's step (``PERF.md`` §5), so
        reading a quarter of the rows for stats removes most of one of
        BN's three activation passes; what that is worth on the chip
        is not measured (the benchmark's cell runs full BN).  Estimator
        numerics: subset stats are the ghost-BN regularizer (Hoffer et
        al. 2017); ``tests/test_ghost_bn.py`` holds validation accuracy
        to full BN's on its texture task."""
        super().__init__(**kw)
        self.epsilon = epsilon
        self.momentum = momentum
        self.axis = 1 if dim_ordering == "th" else axis
        self.scale = scale
        self.center = center
        if not 0.0 < stats_fraction <= 1.0:
            raise ValueError(
                f"stats_fraction must be in (0, 1], got {stats_fraction}")
        self.stats_fraction = float(stats_fraction)

    def _dim(self, input_shape) -> int:
        return input_shape[self.axis]

    def build(self, rng, input_shape):
        d = self._dim(input_shape)
        params = {}
        if self.scale:
            params["gamma"] = jnp.ones((d,), jnp.float32)
        if self.center:
            params["beta"] = jnp.zeros((d,), jnp.float32)
        state = {"moving_mean": jnp.zeros((d,), jnp.float32),
                 "moving_var": jnp.ones((d,), jnp.float32)}
        return params, state

    def call(self, params, state, x, training: bool = False, rng=None):
        axis = self.axis % x.ndim
        reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]

        if training:
            xs = x
            if self.stats_fraction < 1.0 and x.shape[0] > 1:
                n = max(1, int(math.ceil(x.shape[0]
                                         * self.stats_fraction)))
                xs = x[:n]              # ghost-BN: stats from a slice
            mean = jnp.mean(xs, axis=reduce_axes)
            var = jnp.var(xs, axis=reduce_axes)
            m = self.momentum
            new_state = {
                "moving_mean": m * state["moving_mean"] + (1 - m) * mean,
                "moving_var": m * state["moving_var"] + (1 - m) * var,
            }
        else:
            mean, var = state["moving_mean"], state["moving_var"]
            new_state = state

        inv = lax.rsqrt(var + self.epsilon)
        if self.scale:
            inv = inv * params["gamma"]
        y = (x - mean.reshape(shape)) * inv.reshape(shape)
        if self.center:
            y = y + params["beta"].reshape(shape)
        return y, new_state


class LayerNorm(StatelessLayer):
    """Layer normalization over the last axis (used by Transformer/BERT —
    reference api/keras/layers/internal InternalLayerNorm)."""

    def __init__(self, epsilon: float = 1e-5, **kw):
        super().__init__(**kw)
        self.epsilon = epsilon

    def build_params(self, rng, input_shape):
        d = input_shape[-1]
        return {"gamma": jnp.ones((d,), jnp.float32),
                "beta": jnp.zeros((d,), jnp.float32)}

    def forward(self, params, x, training=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.epsilon)
        return y * params["gamma"] + params["beta"]


class RMSNorm(StatelessLayer):
    """Root-mean-square normalisation over the last axis with a learned
    scale and no centring (Zhang & Sennrich 2019, arXiv:1910.07467):
    ``x / sqrt(mean(x**2) + eps) * gamma``.  The statistics are taken in
    float32 whatever the input's dtype; the output has the input's."""

    def __init__(self, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        self.epsilon = epsilon

    def build_params(self, rng, input_shape):
        return {"gamma": jnp.ones((input_shape[-1],), jnp.float32)}

    def forward(self, params, x, training=False, rng=None):
        x32 = x.astype(jnp.float32)
        inv = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + self.epsilon)
        return (x32 * inv * params["gamma"].astype(jnp.float32)
                ).astype(x.dtype)


class LRN2D(StatelessLayer):
    """Local response normalization across channels.

    Reference: api/keras/layers/LRN2D.scala (AlexNet-style).
    ``y = x / (k + alpha/n * sum(x^2 over n neighbouring channels))^beta``.
    """

    def __init__(self, alpha: float = 1e-4, k: float = 1.0, beta: float = 0.75,
                 n: int = 5, dim_ordering: str = "tf", **kw):
        super().__init__(**kw)
        self.alpha, self.k, self.beta, self.n = alpha, k, beta, n
        self.dim_ordering = dim_ordering

    def forward(self, params, x, training=False, rng=None):
        ch_axis = 1 if self.dim_ordering == "th" else -1
        sq = jnp.square(x)
        # Sliding window over channels via pad + reduce_window on that axis.
        half = self.n // 2
        window = [1] * x.ndim
        window[ch_axis] = self.n
        pads = [(0, 0, 0)] * x.ndim
        pads[ch_axis] = (half, self.n - 1 - half, 0)
        summed = lax.reduce_window(
            lax.pad(sq, 0.0, pads), 0.0, lax.add, tuple(window),
            (1,) * x.ndim, "VALID")
        denom = jnp.power(self.k + self.alpha / self.n * summed, self.beta)
        return x / denom


class WithinChannelLRN2D(StatelessLayer):
    """LRN within each channel over a spatial window
    (reference api/keras/layers/WithinChannelLRN2D.scala)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 **kw):
        super().__init__(**kw)
        self.size, self.alpha, self.beta = size, alpha, beta

    def forward(self, params, x, training=False, rng=None):
        # NHWC: window over H, W
        sq = jnp.square(x)
        window = (1, self.size, self.size, 1)
        summed = lax.reduce_window(sq, 0.0, lax.add, window, (1, 1, 1, 1),
                                   "SAME")
        denom = jnp.power(1.0 + self.alpha / (self.size ** 2) * summed,
                          self.beta)
        return x / denom
