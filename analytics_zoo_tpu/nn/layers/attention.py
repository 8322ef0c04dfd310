"""Attention layers: MultiHeadAttention, LatentAttention, TransformerLayer,
BERT, the pieces of a looped decoder (RotaryEmbedding, GatedFFN,
SandwichDecoderBlock, LoopedDecoderStack) and of a decoder of several kinds
of layer (PreNormDecoderBlock, HybridDecoderStack).

Reference capability: api/keras/layers/TransformerLayer.scala:56 (GPT-style
decoder stack: token+position embedding, n blocks of attention+FFN with
residuals and LayerNorm) and api/keras/layers/BERT.scala:66 (encoder stack
with word/position/segment embeddings, attention mask, pooler).

TPU-first: attention lowers to ``ops.attention.dot_product_attention`` —
blockwise online-softmax (flash) rather than the reference's materialized
O(L²) score matrix; projections are fused batched matmuls (MXU); dropout
uses threaded PRNG keys.  Long-context via ring attention plugs in here
through the same op interface (parallel/sequence.py).
"""

from __future__ import annotations

import contextlib
import itertools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.nn import activations, initializers
from analytics_zoo_tpu.nn.module import Layer, StatelessLayer, split_rng
from analytics_zoo_tpu.ops.attention import dot_product_attention
from analytics_zoo_tpu.parallel.mode import (
    current_pipeline as _current_pipeline,
    current_seq_parallel as _current_seq_parallel)


def _dense_params(rng, d_in, d_out, init, dtype=jnp.float32,
                  use_bias: bool = True):
    params = {"kernel": init(rng, (d_in, d_out), dtype)}
    if use_bias:
        params["bias"] = jnp.zeros((d_out,), dtype)
    return params


def _dense(p, x):
    y = jnp.dot(x, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def _named_dense(params, name, x):
    """``_dense`` of ``params[name]`` with its result named ``name`` for a
    ``jax.checkpoint`` policy around the caller (``_kept_for_backward``);
    under no ``jax.checkpoint`` the name is the identity and lowers to
    nothing."""
    return checkpoint_name(_dense(params[name], x), name)


# Single source of LayerNorm math: the canonical layer from normalization.py
from analytics_zoo_tpu.nn.layers.normalization import (
    LayerNorm as _LayerNorm, RMSNorm)

_LN = _LayerNorm(name="attention_shared_ln")


def _layernorm_params(d):
    return _LN.build_params(None, (1, d))


def _layernorm(p, x):
    return _LN.forward(p, x)


def _dropout(rng, x, rate, training):
    if not training or rate <= 0 or rng is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


class RotaryEmbedding(StatelessLayer):
    """Rotary position embedding (Su et al. 2021, arXiv:2104.09864) in the
    rotate-half layout: dimension ``i`` of a head is paired with
    ``i + D/2`` and the pair at position ``m`` is turned by
    ``m * theta ** (-2i / D)``.  Input (..., L, D), positions ``0..L-1``.
    No parameters; the turn is computed in float32 and the result has the
    input's dtype."""

    def __init__(self, theta: float = 10000.0, **kw):
        super().__init__(**kw)
        self.theta = float(theta)

    def forward(self, params, x, training=False, rng=None):
        l, d = x.shape[-2], x.shape[-1]
        if d % 2:
            raise ValueError(f"rotary embedding needs an even head size, "
                             f"got {d}")
        pos = jnp.arange(l, dtype=jnp.float32)
        inv_freq = self.theta ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angles = pos[:, None] * inv_freq[None, :]           # (L, D/2)
        cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
        sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
        x32 = x.astype(jnp.float32)
        x1, x2 = jnp.split(x32, 2, axis=-1)
        turned = jnp.concatenate([-x2, x1], axis=-1)
        return (x32 * cos + turned * sin).astype(x.dtype)


class MultiHeadAttention(StatelessLayer):
    """Multi-head (self or cross) attention with fused QKV projection.

    Single input → self-attention; two inputs (q, kv) → cross-attention.
    An optional third input is the attention mask (1 = attend),
    broadcastable to (B, 1, Lq, Lk).  ``rotary_theta`` turns q and k by
    their positions (``RotaryEmbedding``) before the scores are taken, and
    without it the layer knows no positions; ``use_bias=False`` leaves the
    four projections without biases.  ``n_kv_head`` gives keys and values
    fewer heads than the queries (grouped-query attention: each is shared
    by ``nhead / n_kv_head`` consecutive query heads); ``sm_scale`` takes
    the place of ``1 / sqrt(head size)`` on the scores.
    """

    def __init__(self, nhead: int, hidden_size: Optional[int] = None,
                 attn_drop: float = 0.0, output_drop: float = 0.0,
                 causal: bool = False, init="glorot_uniform",
                 seq_shards: Optional[int] = None, use_bias: bool = True,
                 rotary_theta: Optional[float] = None,
                 n_kv_head: Optional[int] = None,
                 sm_scale: Optional[float] = None, **kw):
        super().__init__(**kw)
        self.use_bias = use_bias
        self.n_kv_head = nhead if n_kv_head is None else n_kv_head
        if nhead % self.n_kv_head:
            raise ValueError(f"{nhead} query heads do not divide over "
                             f"{self.n_kv_head} key/value heads")
        self.sm_scale = sm_scale
        self.rotary = (None if rotary_theta is None else RotaryEmbedding(
            rotary_theta, name=f"{self.name}_rotary"))
        self.nhead = nhead
        self.hidden_size = hidden_size
        self.attn_drop = attn_drop
        self.output_drop = output_drop
        self.causal = causal
        # sequence shards for ring attention outside an explicit sp
        # regime: None defers to the ZooConfig.seq_shards knob at
        # forward time; 0/1 disables (docs/PARALLELISM.md)
        self.seq_shards = seq_shards
        self.initializer = initializers.get(init)

    def build_params(self, rng, q_shape, *rest):
        d = self.hidden_size or q_shape[-1]
        if d % self.nhead:
            raise ValueError(f"hidden {d} not divisible by nhead {self.nhead}")
        kv_d = rest[0][-1] if rest else q_shape[-1]
        kv_out = d // self.nhead * self.n_kv_head
        ks = jax.random.split(rng, 4)
        dims = {"q": (q_shape[-1], d), "k": (kv_d, kv_out),
                "v": (kv_d, kv_out), "o": (d, d)}
        return {n: _dense_params(k, d_in, d_out, self.initializer,
                                 use_bias=self.use_bias)
                for k, (n, (d_in, d_out)) in zip(ks, dims.items())}

    def projections(self):
        """(name, fan-in, fan-out) of the results worth keeping for the
        backward pass (``_keep_within_budget``)."""
        d = self.hidden_size
        kv = d // self.nhead * self.n_kv_head
        return (("o", d, d), ("q", d, d), ("k", d, kv), ("v", d, kv))

    def values_a_token(self) -> int:
        """About what a token keeps where nothing is computed again: q,
        the heads' result and the output, k and v."""
        d = self.hidden_size
        return 3 * d + 2 * (d // self.nhead * self.n_kv_head)

    def _split_heads(self, x, nhead):
        b, l, d = x.shape
        return x.reshape(b, l, nhead, d // nhead).transpose(0, 2, 1, 3)

    def forward(self, params, *inputs, training=False, rng=None):
        # Input forms: (x) self-attn; (q, kv) cross-attn with kv 3D;
        # (x, mask) self-attn with a 2D key-padding or 4D full mask;
        # (q, kv, mask).  A 3D (B, Lq, Lk) mask needs the 3-arg form.
        mask = None
        if len(inputs) == 1:
            q_in = kv_in = inputs[0]
        elif len(inputs) == 2:
            if inputs[1].ndim == 3:
                q_in, kv_in = inputs
            else:
                q_in = kv_in = inputs[0]
                mask = inputs[1]
        else:
            q_in, kv_in, mask = inputs
        q = self._split_heads(_named_dense(params, "q", q_in), self.nhead)
        k = self._split_heads(_named_dense(params, "k", kv_in),
                              self.n_kv_head)
        v = self._split_heads(_named_dense(params, "v", kv_in),
                              self.n_kv_head)
        if self.rotary is not None:
            q = self.rotary.forward({}, q)
            k = self.rotary.forward({}, k)
        if self.n_kv_head != self.nhead:
            # query heads g*j .. g*j + g - 1 read key/value head j; the
            # repeat's transpose sums their gradients
            k, v = (jnp.repeat(t, self.nhead // self.n_kv_head, axis=1)
                    for t in (k, v))
        if mask is not None:
            if mask.ndim == 2:      # (B, Lk) key padding mask
                mask = mask[:, None, None, :]
            elif mask.ndim == 3:    # (B, Lq, Lk) full mask
                mask = mask[:, None, :, :]
        r1, r2 = split_rng(rng, 2)
        sp = _current_seq_parallel()
        if sp is not None:
            # sequence-parallel regime (compile(sharding="sp")): K/V
            # rotate around the mesh's sequence ring instead of
            # materialising blockwise attention on one device.  The ring
            # kernel supports causal/no mask and skips attention-prob
            # dropout (parallel/sequence.py).
            if mask is not None:
                raise ValueError(
                    "sequence-parallel attention does not support "
                    "padding/attention masks (causal=True is supported); "
                    "drop the mask input or use sharding='dp'")
            if kv_in is not q_in:
                raise ValueError(
                    "sequence-parallel attention supports self-attention "
                    "only (q and kv shards must rotate together)")
            if self.sm_scale is not None:
                raise ValueError("sequence-parallel attention takes no "
                                 "sm_scale")
            from analytics_zoo_tpu.parallel.sequence import (
                ring_self_attention)
            out = ring_self_attention(q, k, v, sp.mesh, sp.axis,
                                      causal=self.causal,
                                      batch_axis=sp.batch_axis)
        else:
            # attn_drop acts on the softmax probabilities (reference
            # TransformerLayer/BERT semantics) via the blockwise path,
            # which keeps the flash memory bound; inference uses the
            # fused kernels
            drop = self.attn_drop if (training and r1 is not None) else 0.0
            ring_mesh = None
            if (mask is None and kv_in is q_in and drop == 0.0
                    and self.sm_scale is None):
                # seq_shards knob: long-context self-attention shards L
                # over a ring of devices even without an explicit sp
                # regime (serving's long-document bucket rides this).
                # The op's counted dispatch still applies its min-length
                # and knob routing, so short sequences stay local.
                from analytics_zoo_tpu.ops.dispatch import config_knob
                ways = (self.seq_shards if self.seq_shards is not None
                        else config_knob("seq_shards", 0) or 0)
                if ways and ways > 1:
                    from analytics_zoo_tpu.parallel.sharding import seq_mesh
                    ring_mesh = seq_mesh(int(ways))
            if ring_mesh is not None:
                from analytics_zoo_tpu.ops.ring_attention import (
                    ring_attention)
                out = ring_attention(q, k, v, mesh=ring_mesh, axis="seq",
                                     causal=self.causal)
            else:
                out = dot_product_attention(q, k, v, mask=mask,
                                            causal=self.causal,
                                            sm_scale=self.sm_scale,
                                            dropout_rate=drop,
                                            dropout_rng=r1)
        b, h, l, hd = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(b, l, h * hd)
        out = _named_dense(params, "o", out)
        return _dropout(r2, out, self.output_drop, training)


class LatentAttention(StatelessLayer):
    """Multi-head latent attention (MLA: DeepSeek-V2, arXiv:2405.04434)
    without positions, causal self-attention, as Kimi Linear's global
    layers have it (``mla_use_nope``, ``q_lora_rank`` null):

        q = u W_q -> per head [q_n (qk_nope) | q_r (qk_rope)]
        [c | k_r] = u W_kv_a                 (kv_lora_rank | qk_rope)
        [k_n | v] = N_c(c) W_kv_b -> per head (qk_nope | v_head)
        k = [k_n | k_r, one for all heads]
        o = softmax(q k^T * scale + causal) v;   out = o W_o

    ``q_r`` and ``k_r`` are not turned (no rotary).  ``scale`` is
    ``1 / sqrt(qk_nope + qk_rope)``; N_c is an RMSNorm with a learned
    scale; no projection has a bias.  Attention goes
    through ``ops.attention.dot_product_attention``: on a TPU the flash
    kernels, at a head of qk_nope + qk_rope padded to the next multiple of
    128 and v padded to the same width.  ``jax.named_scope("zoo:mla/attn")``
    marks the whole mixer."""

    def __init__(self, nhead: int, hidden_size: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, epsilon: float = 1e-6,
                 init="glorot_uniform", **kw):
        super().__init__(**kw)
        self.nhead, self.hidden_size = nhead, hidden_size
        self.rank, self.nope, self.rope = (kv_lora_rank, qk_nope_head_dim,
                                           qk_rope_head_dim)
        self.v_head = v_head_dim
        self.qk_head = qk_nope_head_dim + qk_rope_head_dim
        self.sm_scale = self.qk_head ** -0.5
        self.initializer = initializers.get(init)
        self.norm = RMSNorm(epsilon, name=f"{self.name}_kv_norm")

    def _dims(self):
        """name -> (fan-in, fan-out) of the four projections."""
        d, h = self.hidden_size, self.nhead
        return {"o": (h * self.v_head, d), "q": (d, h * self.qk_head),
                "kv_a": (d, self.rank + self.rope),
                "kv_b": (self.rank, h * (self.nope + self.v_head))}

    def projections(self):
        """(name, fan-in, fan-out) of the results worth keeping for the
        backward pass (``_keep_within_budget``)."""
        return tuple((name,) + dims for name, dims in self._dims().items())

    def values_a_token(self) -> int:
        """About what a token keeps where nothing is computed again: every
        projection's result, the normed latent, the keys with the shared
        part repeated, and the output."""
        return (sum(out for _, out in self._dims().values()) + self.rank
                + self.nhead * self.qk_head + self.hidden_size)

    def build_params(self, rng, x_shape, *rest):
        dims = self._dims()
        ks = jax.random.split(rng, len(dims))
        params = {name: _dense_params(k, d_in, d_out, self.initializer,
                                      use_bias=False)
                  for k, (name, (d_in, d_out)) in zip(ks, dims.items())}
        params["kv_norm"] = self.norm.build_params(None, (self.rank,))
        return params

    def forward(self, params, x, training=False, rng=None):
        b, l, _ = x.shape
        h = self.nhead
        with jax.named_scope("zoo:mla/attn"):
            q = _named_dense(params, "q", x).reshape(b, l, h, self.qk_head)
            c, k_r = jnp.split(_named_dense(params, "kv_a", x), [self.rank],
                               axis=-1)
            kv = _named_dense(params, "kv_b", self.norm.forward(
                params["kv_norm"], c)).reshape(b, l, h,
                                               self.nope + self.v_head)
            k_n, v = jnp.split(kv, [self.nope], axis=-1)
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                k_r[:, :, None, :], (b, l, h, self.rope))], axis=-1)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            o = dot_product_attention(q, k, v, causal=True,
                                      sm_scale=self.sm_scale)
            o = o.transpose(0, 2, 1, 3).reshape(b, l, h * self.v_head)
            return _named_dense(params, "o", o)


class TransformerBlock(StatelessLayer):
    """One attention + FFN block with residuals.

    ``after_norm=False`` → post-LN (original Transformer / BERT / the
    reference's TransformerLayer); ``True`` → pre-LN (more stable deep).
    """

    def __init__(self, nhead: int, hidden_size: int,
                 intermediate_size: Optional[int] = None,
                 hidden_drop: float = 0.1, attn_drop: float = 0.1,
                 causal: bool = False, activation="gelu",
                 after_norm: bool = False, init="glorot_uniform",
                 seq_shards: Optional[int] = None, **kw):
        super().__init__(**kw)
        self.attn = MultiHeadAttention(nhead, hidden_size,
                                       attn_drop=attn_drop, causal=causal,
                                       init=init, seq_shards=seq_shards,
                                       name=f"{self.name}_attn")
        self.hidden_size = hidden_size
        self.intermediate = intermediate_size or 4 * hidden_size
        self.hidden_drop = hidden_drop
        self.act = activations.get(activation)
        self.pre_ln = after_norm
        self.initializer = initializers.get(init)

    def build_params(self, rng, x_shape, *rest):
        d = self.hidden_size
        ks = jax.random.split(rng, 3)
        return {
            "attn": self.attn.build_params(ks[0], x_shape),
            "ln1": _layernorm_params(d),
            "ln2": _layernorm_params(d),
            "ffn1": _dense_params(ks[1], d, self.intermediate,
                                  self.initializer),
            "ffn2": _dense_params(ks[2], self.intermediate, d,
                                  self.initializer),
        }

    def forward(self, params, x, *rest, training=False, rng=None):
        mask = rest[0] if rest else None
        r1, r2, r3 = split_rng(rng, 3)
        attn_in = _layernorm(params["ln1"], x) if self.pre_ln else x
        a_args = (attn_in,) if mask is None else (attn_in, mask)
        a = self.attn.forward(params["attn"], *a_args, training=training,
                              rng=r1)
        x = x + _dropout(r2, a, self.hidden_drop, training)
        if not self.pre_ln:
            x = _layernorm(params["ln1"], x)
        ffn_in = _layernorm(params["ln2"], x) if self.pre_ln else x
        h = self.act(_dense(params["ffn1"], ffn_in))
        h = _dense(params["ffn2"], h)
        x = x + _dropout(r3, h, self.hidden_drop, training)
        if not self.pre_ln:
            x = _layernorm(params["ln2"], x)
        return x


def _stack_block_params(block, keys, hshape):
    """Build one params pytree per key and stack on a leading dim — the
    layout `lax.scan` consumes and the PipelineStrategy shards."""
    per_block = [block.build_params(k, hshape) for k in keys]
    return jax.tree_util.tree_map(lambda *ps: jnp.stack(ps, axis=0),
                                  *per_block)


def _run_block_stack(block, n_block, blocks_params, x, training, rng,
                     mask=None, remat: Optional[Sequence[str]] = None):
    """Run a stacked homogeneous block pytree: the GPipe schedule under
    an active pipeline regime, otherwise one `lax.scan` (per-block rng
    threading for dropout).  Shared by TransformerLayer, BERT and
    LoopedDecoderStack so the stacked paths cannot diverge.  ``remat``
    names what the backward pass keeps of a block besides its input (the
    ``checkpoint_name``s of the projections' results) and computes the
    rest of the block again there: none named, the whole block; ``None``,
    nothing again (the pipeline regime has its own ``pipe.remat``)."""
    pipe = _current_pipeline()
    if pipe is not None:
        from analytics_zoo_tpu.parallel.pipeline import pipeline_apply

        if mask is None:  # zoolint: disable=JG-TRACED-BRANCH(None-ness is static pytree structure; the looped stack calls this from inside its scan over passes)
            def stage(p, h):
                return block.forward(p, h, training=False, rng=None)

            return pipeline_apply(stage, blocks_params, x, pipe.mesh,
                                  pipe.axis, pipe.n_microbatches,
                                  pipe.remat, batch_axis=pipe.batch_axis)

        # masked pp: the mask is an aux side input — it never rides the
        # ppermute ring; every stage indexes the microbatch matching the
        # activation it holds (parallel/pipeline.py pipeline_spmd)
        def stage_m(p, h, m):
            return block.forward(p, h, m, training=False, rng=None)

        return pipeline_apply(stage_m, blocks_params, x, pipe.mesh,
                              pipe.axis, pipe.n_microbatches,
                              pipe.remat, batch_axis=pipe.batch_axis,
                              aux=mask)

    def apply(p, h, r):
        args = (h,) if mask is None else (h, mask)
        return block.forward(p, *args, training=training, rng=r)

    if remat is not None:  # zoolint: disable=JG-TRACED-BRANCH(names decided from static shapes before tracing)
        apply = jax.checkpoint(apply, policy=(
            jax.checkpoint_policies.save_only_these_names(*remat)
            if remat else None))

    if rng is not None:  # zoolint: disable=JG-TRACED-BRANCH(None-ness is static pytree structure)
        rngs = jax.random.split(rng, n_block)

        def body(h, pr):
            p, r = pr
            return apply(p, h, r), None

        x, _ = jax.lax.scan(body, x, (blocks_params, rngs))
    else:
        def body(h, p):
            return apply(p, h, None), None

        x, _ = jax.lax.scan(body, x, blocks_params)
    return x


class GatedFFN(StatelessLayer):
    """Gated feed-forward (Shazeer 2020, arXiv:2002.05202):
    ``(act(x W_gate) * (x W_up)) W_down``, no biases; ``silu`` makes it
    SwiGLU."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 activation="silu", init="glorot_uniform", **kw):
        super().__init__(**kw)
        self.hidden_size = hidden_size
        self.intermediate = intermediate_size
        self.act = activations.get(activation)
        self.initializer = initializers.get(init)

    def build_params(self, rng, x_shape, *rest):
        d, ff = self.hidden_size, self.intermediate
        ks = jax.random.split(rng, 3)
        mk = lambda k, i, o: _dense_params(k, i, o, self.initializer,
                                           use_bias=False)
        return {"gate": mk(ks[0], d, ff), "up": mk(ks[1], d, ff),
                "down": mk(ks[2], ff, d)}

    def projections(self):
        """(name, fan-in, fan-out) of the results worth keeping for the
        backward pass (``_keep_within_budget``), ``down`` first."""
        d, ff = self.hidden_size, self.intermediate
        return (("down", ff, d), ("gate", d, ff), ("up", d, ff))

    def values_a_token(self) -> int:
        """About what a token keeps where nothing is computed again: the
        gate's two factors and their product."""
        return 3 * self.intermediate

    def forward(self, params, x, training=False, rng=None):
        return _named_dense(
            params, "down", self.act(_named_dense(params, "gate", x))
            * _named_dense(params, "up", x))


class SandwichDecoderBlock(StatelessLayer):
    """One causal decoder block with a norm before AND after each
    sub-layer: ``a = x + N2(Attn(N1 x))``, ``y = a + N4(FFN(N3 a))``;
    RMSNorm, rotary positions on q and k, a gated FFN, no biases."""

    def __init__(self, nhead: int, hidden_size: int, intermediate_size: int,
                 rotary_theta: float = 10000.0, epsilon: float = 1e-6,
                 activation="silu", init="glorot_uniform", **kw):
        super().__init__(**kw)
        self.attn = MultiHeadAttention(
            nhead, hidden_size, causal=True, init=init, use_bias=False,
            rotary_theta=rotary_theta, name=f"{self.name}_attn")
        self.ffn = GatedFFN(hidden_size, intermediate_size, activation,
                            init=init, name=f"{self.name}_ffn")
        self.norm = RMSNorm(epsilon, name=f"{self.name}_norm")

    def build_params(self, rng, x_shape, *rest):
        ka, kf = jax.random.split(rng)
        params = {"attn": self.attn.build_params(ka, x_shape),
                  "ffn": self.ffn.build_params(kf, x_shape)}
        for i in range(1, 5):
            params[f"norm{i}"] = self.norm.build_params(None, x_shape)
        return params

    def forward(self, params, x, training=False, rng=None):
        n = self.norm.forward
        a = x + n(params["norm2"], self.attn.forward(
            params["attn"], n(params["norm1"], x), training=training))
        return a + n(params["norm4"], self.ffn.forward(
            params["ffn"], n(params["norm3"], a)))


# What the looped stack may keep for the backward pass beyond a chip that
# already trains the model with every block computed again there (the layer
# cannot see the optimizer's state, and a program that read the free memory
# at trace time could be neither cached nor reasoned about).  Set on a TPU
# v5e (16 GB) from ``ouro-2.6b-fit-packed4k``, whose named values are
# 0.75 GiB each over its 24 applications of 8,192 tokens: between the one
# value whose keeping paid there (``down``: the step 2.5 % shorter, 2.2 GiB
# of the chip left free at its fullest) and the two that memory would have
# allowed (1.5 GiB: 1.1 GiB free) but that made the step slower again,
# because XLA spends more on a second kept value's layout and float32 copy
# than its product costs (PERF.md section 6, PR 34: the sweep's table).
_KEEP_BYTES = 1 << 30

# What a hybrid stack may keep besides, of what a mixer's kernel writes for
# its own backward pass (``kernel_residuals``): kept, the backward pass runs
# none of that kernel's forward again.  Set on a TPU v5e (16 GB) from
# ``kimi-linear-48b-a3b-fit-packed8k``, at 9.81 GB in use at its fullest
# with every scan computed again (PERF.md section 4): its KDA scans' entry
# is 2.5 GiB over four layers of 16,384 tokens, so 3 GiB takes it and leaves
# the chip about 3.5 GB; a step of twice the tokens (5 GiB) keeps none.
_KERNEL_KEEP_BYTES = 3 << 30

# The projections of a sandwich block by the name of their result
# (``_named_dense``): fan-in, fan-out.  Keeping a result costs fan-out
# values a token and spares fan-in x fan-out multiply-adds in the backward
# pass, so a kept byte spares fan-in / itemsize of them.
_PROJECTIONS = (("down", "ffn", "hidden"), ("o", "hidden", "hidden"),
                ("q", "hidden", "hidden"), ("k", "hidden", "hidden"),
                ("v", "hidden", "hidden"), ("gate", "hidden", "ffn"),
                ("up", "hidden", "ffn"))


def _keep_within_budget(block_input: int, everything: int,
                        candidates: Sequence[Tuple[str, int, int]]
                        ) -> Dict[str, int]:
    """The rule by which a stack keeps values for the backward pass, over
    ``candidates`` (name, fan-in, bytes over all the stack's applications)
    in their table's order.  ``everything`` (what the stack keeps where
    nothing is computed again) fits ``_KEEP_BYTES``: all is kept, ``rest``
    among it.  Otherwise the candidates are taken by multiply-adds spared a
    kept byte (a kept byte spares fan-in / itemsize of them) while their sum
    stays within ``_KEEP_BYTES``; one that does not fit is passed over and a
    smaller one after it may still be taken.  None taken: each block is
    computed again whole."""
    kept = {"block_input": block_input,
            **{name: 0 for name, _, _ in candidates}, "rest": 0}
    if everything <= _KEEP_BYTES:
        kept.update((name, size) for name, _, size in candidates)
        kept["rest"] = everything - sum(kept.values())
        return kept
    room = _KEEP_BYTES
    # sorted() is stable: equals stay in the table's order
    for name, _, size in sorted(candidates, key=lambda c: -c[1]):
        if size <= room:
            kept[name] = size
            room -= size
    return kept


def _kept_for_backward(tokens: int, itemsize: int, hidden: int,
                       intermediate: int, applications: int
                       ) -> Dict[str, int]:
    """What ``applications`` sandwich blocks over ``tokens`` tokens keep for
    the backward pass, in bytes by name: ``block_input`` always; each
    projection's result (0: computed again); ``rest``, what else a block
    keeps when nothing is computed again (0 otherwise), by
    ``_keep_within_budget``.  A block keeps about ten hidden-wide and three
    FFN-wide values a token (the norms' and projections' inputs, the gate's
    two factors)."""
    width = {"hidden": hidden, "ffn": intermediate}
    a_value = tokens * itemsize * applications
    return _keep_within_budget(
        a_value * hidden, a_value * (10 * hidden + 3 * intermediate),
        [(name, width[fan_in], a_value * width[fan_out])
         for name, fan_in, fan_out in _PROJECTIONS])


def _keep_kernel_residuals(entries: Dict[str, int]) -> Dict[str, int]:
    """Of what kernels write for their own backward pass (name: bytes over
    all the stack's layers), each entry whole while the sum of those kept
    stays within ``_KERNEL_KEEP_BYTES``, in order; 0: computed again.  An
    entry is kept whole or not at all: part of it spares no kernel call."""
    kept, room = {}, _KERNEL_KEEP_BYTES
    for name, size in entries.items():
        kept[name] = size if size <= room else 0
        room -= kept[name]
    return kept


def _kept_names(kept: Dict[str, int]) -> Optional[Sequence[str]]:
    """Of the rule's answer, what ``_run_block_stack`` is told: the
    projections and kernel residuals kept, in the tables' order; ``None``
    where nothing is computed again."""
    if kept["rest"]:
        return None
    return [name for name, size in kept.items()
            if size and name not in ("block_input", "rest")]


def _tell_kept(kept: Dict[str, int]) -> Dict[str, int]:
    """``stack_kept_bytes{name}`` of what a stack keeps (at trace time: once
    a compilation)."""
    from analytics_zoo_tpu.observe.metrics import set_gauge

    for name, size in kept.items():
        set_gauge("stack_kept_bytes", size, name=name)
    return kept


class LoopedDecoderStack(StatelessLayer):
    """``n_block`` sandwich decoder blocks applied ``passes`` times with
    ONE set of weights (a looped language model: Ouro, arXiv:2510.25741):
    ``h_t = N_f(blocks(h_{t-1}))``, the final norm closing every pass and
    its output feeding the next.

    Input: hidden states (B, L, d).  Output: every pass's ``h_t`` stacked,
    (passes, B, L, d).  The blocks live as one pytree with a leading
    ``n_block`` dim (the ``stacked=True`` layout of TransformerLayer and
    BERT) and run as a ``lax.scan`` over blocks inside a ``lax.scan`` over
    passes, so one block is traced and compiled, not ``n_block * passes``.
    Every block's gradient is the sum over the passes.

    What the ``n_block * passes`` applications keep for the backward pass
    follows from the block's widths and the input's shape
    (``_kept_for_backward``): everything where that fits a fixed budget of
    bytes; otherwise each block's input and, of its projections' results,
    those dearest to compute again a kept byte (the FFN's ``down`` first)
    that fit, the rest of the block being computed again there.  The
    registry's ``stack_kept_bytes{name}`` says which at every trace.
    """

    def __init__(self, n_block: int, nhead: int, hidden_size: int,
                 intermediate_size: int, passes: int = 4,
                 rotary_theta: float = 10000.0, epsilon: float = 1e-6,
                 activation="silu", init="glorot_uniform", **kw):
        super().__init__(**kw)
        self.n_block, self.passes = n_block, passes
        self.hidden_size, self.intermediate = hidden_size, intermediate_size
        self.block = SandwichDecoderBlock(
            nhead, hidden_size, intermediate_size, rotary_theta, epsilon,
            activation, init=init, name=f"{self.name}_block")
        self.final_norm = RMSNorm(epsilon, name=f"{self.name}_final_norm")

    def build_params(self, rng, x_shape, *rest):
        return {"blocks": _stack_block_params(
                    self.block, jax.random.split(rng, self.n_block),
                    tuple(x_shape)),
                "final_norm": self.final_norm.build_params(None, x_shape)}

    def _kept(self, x) -> Dict[str, int]:
        """The rule at this stack's widths and ``x``'s shape, told to the
        registry (at trace time: once a compilation)."""
        return _tell_kept(_kept_for_backward(
            x.size // x.shape[-1], x.dtype.itemsize, self.hidden_size,
            self.intermediate, self.n_block * self.passes))

    def forward(self, params, x, training=False, rng=None):
        remat = _kept_names(self._kept(x))

        def one_pass(h, _):
            h = _run_block_stack(self.block, self.n_block, params["blocks"],
                                 h, training, None, remat=remat)
            h = self.final_norm.forward(params["final_norm"], h)
            return h, h

        _, hs = jax.lax.scan(one_pass, x, None, length=self.passes)
        return hs


class PreNormDecoderBlock(StatelessLayer):
    """One decoder block with a norm before each sub-layer and the
    sub-layers' results scaled into the residual stream:
    ``a = x + r * Mixer(N1 x)``, ``y = a + r * FFN(N2 a)``; RMSNorm, no
    biases.  ``mixer`` and ``ffn`` are any layers over (B, L, hidden) that
    have ``projections()`` and ``values_a_token()`` (mixers:
    ``MultiHeadAttention``, ``LatentAttention``, ``ssm.Mamba2Mixer``,
    ``kda.KDAMixer``; FFNs: ``GatedFFN``, ``moe.DroplessMoE``); without an
    ``ffn`` the block builds a ``GatedFFN`` of ``intermediate_size``.
    ``scope`` is a ``jax.named_scope`` around the mixer for one that opens
    none itself.  A mixer may also have ``kernel_residuals()``
    (``kda.KDAMixer``)."""

    def __init__(self, mixer, hidden_size: int,
                 intermediate_size: Optional[int] = None,
                 residual_multiplier: float = 1.0, epsilon: float = 1e-6,
                 activation="silu", init="glorot_uniform",
                 scope: Optional[str] = None, ffn: Optional[Layer] = None,
                 **kw):
        super().__init__(**kw)
        self.mixer, self.scope = mixer, scope
        self.hidden_size = hidden_size
        self.residual_multiplier = residual_multiplier
        self.ffn = ffn or GatedFFN(hidden_size, intermediate_size, activation,
                                   init=init, name=f"{self.name}_ffn")
        self.norm = RMSNorm(epsilon, name=f"{self.name}_norm")

    def build_params(self, rng, x_shape, *rest):
        km, kf = jax.random.split(rng)
        return {"mixer": self.mixer.build_params(km, x_shape),
                "ffn": self.ffn.build_params(kf, x_shape),
                "norm1": self.norm.build_params(None, x_shape),
                "norm2": self.norm.build_params(None, x_shape)}

    def projections(self):
        """The block's table for ``_keep_within_budget``: (name, fan-in,
        fan-out) of every named projection, the FFN's first (its ``down``)
        ahead of the mixer's and the FFN's others after them."""
        ffn = tuple(self.ffn.projections())
        return ffn[:1] + tuple(self.mixer.projections()) + ffn[1:]

    def values_a_token(self) -> int:
        """About what a token keeps where nothing is computed again: the
        mixer's, the norms' inputs and results, the FFN's."""
        return (self.mixer.values_a_token() + 5 * self.hidden_size
                + self.ffn.values_a_token())

    def kernel_residuals(self, x_shape, dtype):
        """The mixer's (name, bytes) of what its kernel writes for its own
        backward pass, for the stack's second table; none declared: ()."""
        declared = getattr(self.mixer, "kernel_residuals", None)
        return declared(x_shape, dtype) if declared else ()

    def forward(self, params, x, training=False, rng=None):
        n, r = self.norm.forward, self.residual_multiplier
        with (jax.named_scope(self.scope) if self.scope
              else contextlib.nullcontext()):
            mixed = self.mixer.forward(
                params["mixer"], n(params["norm1"], x), training=training)
        a = x + r * mixed
        return a + r * self.ffn.forward(params["ffn"], n(params["norm2"], a))


class HybridDecoderStack(StatelessLayer):
    """A decoder stack of several kinds of block in a stated order:
    ``layer_types`` names each layer's kind and ``blocks`` gives one
    template block a kind (``PreNormDecoderBlock``s over different mixers).
    Consecutive layers of one kind are a run: a run's parameters are stacked
    on a leading dim under ``run<i>`` and scanned (``_run_block_stack``), so
    each kind is traced once a run and not once a layer; the runs follow one
    another in order, and a final RMSNorm closes the stack.

    What the layers keep for the backward pass is ``LoopedDecoderStack``'s
    rule under the same budget (``_keep_within_budget``), over the tables
    the kinds bring: a name stands for the same projection in every kind
    that has it (the FFN's three) and counts over all of them.  A second
    table, under a budget of its own (``_keep_kernel_residuals``), holds
    what the kinds' kernels write for their own backward pass
    (``kernel_residuals``), summed over the layers by name.
    ``stack_kept_bytes{name}`` says what was kept at every trace."""

    def __init__(self, layer_types: Sequence[str], blocks: Dict[str, Layer],
                 hidden_size: int, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        missing = sorted(set(layer_types) - set(blocks))
        if missing or not layer_types:
            raise ValueError(f"layer_types {list(layer_types)} need a block "
                             f"for each kind; none for {missing}")
        self.layer_types, self.blocks = tuple(layer_types), dict(blocks)
        self.hidden_size = hidden_size
        self.runs = [(kind, len(list(same)))    # (kind, layers), in order
                     for kind, same in itertools.groupby(self.layer_types)]
        self.final_norm = RMSNorm(epsilon, name=f"{self.name}_final_norm")

    def build_params(self, rng, x_shape, *rest):
        params = {"final_norm": self.final_norm.build_params(None, x_shape)}
        keys = jax.random.split(rng, len(self.runs))
        for i, ((kind, n), key) in enumerate(zip(self.runs, keys)):
            params[f"run{i}"] = _stack_block_params(
                self.blocks[kind], jax.random.split(key, n), tuple(x_shape))
        return params

    def _kept(self, x) -> Dict[str, int]:
        """The rule over this stack's kinds and ``x``'s shape, told to the
        registry."""
        a_token = x.size // x.shape[-1] * x.dtype.itemsize
        table: Dict[str, list] = {}         # name -> [fan-in, bytes]
        residuals: Dict[str, int] = {}      # name -> bytes
        everything = 0
        for kind in dict.fromkeys(self.layer_types):
            block, n = self.blocks[kind], self.layer_types.count(kind)
            everything += a_token * n * block.values_a_token()
            for name, fan_in, fan_out in block.projections():
                table.setdefault(name, [fan_in, 0])[1] += (a_token * n
                                                           * fan_out)
            for name, size in block.kernel_residuals(x.shape, x.dtype):
                residuals[name] = residuals.get(name, 0) + n * size
        kept = _keep_within_budget(
            a_token * len(self.layer_types) * self.hidden_size, everything,
            [(name, fan_in, size)
             for name, (fan_in, size) in table.items()])
        return _tell_kept({**kept, **_keep_kernel_residuals(residuals)})

    def forward(self, params, x, training=False, rng=None):
        remat = _kept_names(self._kept(x))
        for i, (kind, n) in enumerate(self.runs):
            x = _run_block_stack(self.blocks[kind], n, params[f"run{i}"], x,
                                 training, None, remat=remat)
        return self.final_norm.forward(params["final_norm"], x)


class TransformerLayer(StatelessLayer):
    """GPT-style decoder stack over token ids
    (reference api/keras/layers/TransformerLayer.scala:56).

    Input: int32 token ids (B, L) [+ optional position ids (B, L)].
    Output: hidden states (B, L, hidden_size).

    ``stacked=True`` stores the homogeneous blocks as ONE pytree with a
    leading ``n_block`` dim under ``params["blocks"]`` and runs them via
    ``lax.scan`` — faster compiles for deep stacks, and the layout the
    pipeline-parallel regime shards: under ``compile(sharding="pp")``
    the stack lowers to the GPipe microbatch schedule
    (parallel/pipeline.py) with stage weights 1/S per device.  Inside
    pipeline stages dropout is disabled (the ppermute ring carries no
    rng); embedding dropout still applies.
    """

    def __init__(self, vocab: int = 40990, seq_len: int = 77,
                 n_block: int = 12, nhead: int = 12, hidden_size: int = 768,
                 intermediate_size: Optional[int] = None,
                 hidden_drop: float = 0.1, attn_drop: float = 0.1,
                 embedding_drop: float = 0.1, causal: bool = True,
                 after_norm: bool = False, init="glorot_uniform",
                 stacked: bool = False,
                 seq_shards: Optional[int] = None, **kw):
        super().__init__(**kw)
        self.vocab, self.seq_len = vocab, seq_len
        self.hidden_size = hidden_size
        self.embedding_drop = embedding_drop
        self.n_block = n_block
        self.stacked = stacked
        if stacked:
            # one template block; per-block weights differ via the rng
            self.block = TransformerBlock(nhead, hidden_size,
                                          intermediate_size, hidden_drop,
                                          attn_drop, causal=causal,
                                          after_norm=after_norm, init=init,
                                          seq_shards=seq_shards,
                                          name=f"{self.name}_block")
            self.blocks = []
        else:
            self.blocks = [
                TransformerBlock(nhead, hidden_size, intermediate_size,
                                 hidden_drop, attn_drop, causal=causal,
                                 after_norm=after_norm, init=init,
                                 seq_shards=seq_shards,
                                 name=f"{self.name}_block{i}")
                for i in range(n_block)]
        self.initializer = initializers.get(init)

    def build_params(self, rng, ids_shape, *rest):
        ks = jax.random.split(rng, 2 + self.n_block)
        d = self.hidden_size
        params = {
            "tok_embed": self.initializer(ks[0], (self.vocab, d),
                                          jnp.float32) * 0.1,
            "pos_embed": self.initializer(ks[1], (self.seq_len, d),
                                          jnp.float32) * 0.1,
        }
        hshape = tuple(ids_shape) + (d,)
        if self.stacked:
            params["blocks"] = _stack_block_params(
                self.block, ks[2:2 + self.n_block], hshape)
        else:
            for i, blk in enumerate(self.blocks):
                params[f"block{i}"] = blk.build_params(ks[2 + i], hshape)
        return params

    def forward(self, params, ids, *rest, training=False, rng=None):
        pos_ids = rest[0] if rest else None
        ids = ids.astype(jnp.int32)  # container abstract-eval passes f32
        l = ids.shape[1]
        x = params["tok_embed"][ids]
        if pos_ids is None:
            x = x + params["pos_embed"][None, :l]
        else:
            x = x + params["pos_embed"][pos_ids.astype(jnp.int32)]
        if self.stacked:
            r0, rblocks = split_rng(rng, 2)
            x = _dropout(r0, x, self.embedding_drop, training)
            return _run_block_stack(self.block, self.n_block,
                                    params["blocks"], x, training, rblocks)
        rngs = split_rng(rng, 1 + len(self.blocks))
        x = _dropout(rngs[0], x, self.embedding_drop, training)
        for i, blk in enumerate(self.blocks):
            x = blk.forward(params[f"block{i}"], x, training=training,
                            rng=rngs[1 + i])
        return x


class BERT(StatelessLayer):
    """BERT encoder (reference api/keras/layers/BERT.scala:66).

    Inputs: token ids (B, L), segment ids (B, L), [position ids (B, L)],
    [attention mask (B, L), 1 = real token].
    Output: (sequence_output (B, L, H), pooled_output (B, H)).
    """

    def __init__(self, vocab: int = 40990, hidden_size: int = 768,
                 n_block: int = 12, nhead: int = 12,
                 intermediate_size: int = 3072, max_position_len: int = 512,
                 type_vocab_size: int = 2, hidden_drop: float = 0.1,
                 attn_drop: float = 0.1, init="glorot_uniform",
                 stacked: bool = False,
                 seq_shards: Optional[int] = None, **kw):
        super().__init__(**kw)
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.max_position_len = max_position_len
        self.type_vocab_size = type_vocab_size
        self.hidden_drop = hidden_drop
        self.n_block = n_block
        # stacked=True: blocks live as ONE pytree (leading n_block dim)
        # run via lax.scan — compile time stays flat as the stack
        # deepens (trace one block, not twelve); the attention mask
        # threads through the scan as a broadcast operand
        self.stacked = stacked
        mk = lambda name: TransformerBlock(
            nhead, hidden_size, intermediate_size, hidden_drop, attn_drop,
            causal=False, activation="gelu", after_norm=False, init=init,
            seq_shards=seq_shards, name=name)
        if stacked:
            self.block = mk(f"{self.name}_enc")
            self.blocks = []
        else:
            self.blocks = [mk(f"{self.name}_enc{i}")
                           for i in range(n_block)]
        self.initializer = initializers.get(init)

    def build_params(self, rng, ids_shape, *rest):
        d = self.hidden_size
        ks = jax.random.split(rng, 4 + self.n_block)
        params = {
            "word_embed": self.initializer(ks[0], (self.vocab, d),
                                           jnp.float32) * 0.1,
            "pos_embed": self.initializer(ks[1], (self.max_position_len, d),
                                          jnp.float32) * 0.1,
            "type_embed": self.initializer(ks[2], (self.type_vocab_size, d),
                                           jnp.float32) * 0.1,
            "embed_ln": _layernorm_params(d),
            "pooler": _dense_params(ks[3], d, d, self.initializer),
        }
        hshape = tuple(ids_shape) + (d,)
        if self.stacked:
            params["blocks"] = _stack_block_params(
                self.block, ks[4:4 + self.n_block], hshape)
        else:
            for i, blk in enumerate(self.blocks):
                params[f"enc{i}"] = blk.build_params(ks[4 + i], hshape)
        return params

    def forward(self, params, ids, *rest, training=False, rng=None):
        ids = ids.astype(jnp.int32)  # container abstract-eval passes f32
        seg_ids = (rest[0].astype(jnp.int32) if len(rest) > 0
                   else jnp.zeros_like(ids))
        pos_ids = rest[1] if len(rest) > 1 else None
        mask = rest[2] if len(rest) > 2 else None
        l = ids.shape[1]
        x = params["word_embed"][ids] + params["type_embed"][seg_ids]
        if pos_ids is None:
            x = x + params["pos_embed"][None, :l]
        else:
            x = x + params["pos_embed"][pos_ids.astype(jnp.int32)]
        x = _layernorm(params["embed_ln"], x)
        if self.stacked:
            r0, rblocks = split_rng(rng, 2)
            x = _dropout(r0, x, self.hidden_drop, training)
            x = _run_block_stack(self.block, self.n_block,
                                 params["blocks"], x, training, rblocks,
                                 mask=mask)
        else:
            rngs = split_rng(rng, 1 + len(self.blocks))
            x = _dropout(rngs[0], x, self.hidden_drop, training)
            for i, blk in enumerate(self.blocks):
                args = (x,) if mask is None else (x, mask)
                x = blk.forward(params[f"enc{i}"], *args,
                                training=training, rng=rngs[1 + i])
        pooled = jnp.tanh(_dense(params["pooler"], x[:, 0]))
        return [x, pooled]
