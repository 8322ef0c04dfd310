"""Attention ops: blockwise (flash-style) attention with online softmax.

Reference capability: the O(L²) full attention inside
api/keras/layers/TransformerLayer.scala:56 and BERT.scala:66 (SURVEY §5.7:
the reference has NO long-context support — sequence length is bounded by
single-node memory).  This module is the TPU-native upgrade: attention is
computed **blockwise over KV chunks with an online softmax** (Rabe &
Staats 2021 / FlashAttention), so peak memory is O(L·block) instead of
O(L²), and the same code is the building block for ring attention
(parallel/sequence.py) where the KV scan runs over devices instead of
chunks.

Two paths, same math:
- ``blockwise_attention``: pure JAX ``lax.scan`` over KV blocks —
  differentiable (XLA derives the backward), runs on any backend.
- ``flash_attention`` (ops/flash_attention.py): hand-written Pallas TPU
  kernel for the forward hot loop; falls back to blockwise elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def reference_attention(q, k, v, mask=None, causal: bool = False,
                        sm_scale: Optional[float] = None):
    """Naive O(L²) attention — the numerics oracle for tests.

    Shapes: q (B, H, Lq, D), k/v (B, H, Lk, D); mask broadcastable to
    (B, H, Lq, Lk) with 1 = attend.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        logits = jnp.where(cm, logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    # zero fully-masked query rows so every dispatch path (blockwise, ring,
    # flash) agrees: they return 0 there, not the softmax of a constant row
    row_valid = jnp.any(logits > NEG_INF / 2, axis=-1, keepdims=True)
    w = jnp.where(row_valid, w, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def online_softmax_fold(m_prev, l_prev, acc, logits, values,
                        drop_mask=None, keep_prob: float = 1.0):
    """One fold of the online-softmax accumulation — the single source of
    this numerics, shared by blockwise attention (KV-chunk loop) and ring
    attention (device loop, parallel/sequence.py).

    ``logits`` (B,H,Lq,Kblk) must already carry all masking as NEG_INF.
    Returns the updated running (max, normalizer, weighted-value acc);
    fully-masked rows are kept finite-safe and contribute zero.

    ``drop_mask`` (same shape as logits) implements dropout on the softmax
    *probabilities*: the normalizer keeps the undropped sum, only the
    value accumulation is masked/rescaled — since w = p/l this is exactly
    dropout on the normalized weights, without materializing them.
    """
    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe,
                              NEG_INF))
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    p_acc = p if drop_mask is None else (
        jnp.where(drop_mask, p, 0.0) / keep_prob)
    acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p_acc,
                                              values)
    m_out = m_safe + jnp.where(jnp.isfinite(m_new), 0.0, NEG_INF)
    return m_out, l_new, acc


def blockwise_attention(q, k, v, mask=None, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        block_size: int = 512,
                        dropout_rate: float = 0.0, dropout_rng=None):
    """Flash-style attention: scan over KV blocks with a running
    (max, sum, acc) online softmax.  O(Lq · block) memory.

    Differentiable end-to-end (the scan is unrolled by XLA's autodiff);
    wrap the call in ``jax.checkpoint`` to trade recompute for memory in
    very long sequences.

    ``dropout_rate`` > 0 (with ``dropout_rng``) applies dropout to the
    softmax probabilities — reference TransformerLayer/BERT attn_drop
    semantics — per KV block via ``fold_in``, keeping the memory bound.
    """
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(d)
    bs = min(block_size, lk)
    nblocks = -(-lk // bs)  # ceil
    pad = nblocks * bs - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        padmask = jnp.arange(nblocks * bs) < lk        # (Lk',)
    else:
        padmask = None
    if mask is not None:
        mask = jnp.broadcast_to(mask.astype(bool), (b, h, lq, lk))
        if pad:
            mask = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, pad)))
        # (nblocks, B, H, Lq, bs) scan order
        mask_blocks = jnp.moveaxis(
            mask.reshape(b, h, lq, nblocks, bs), 3, 0)
    k_blocks = jnp.moveaxis(k.reshape(b, h, nblocks, bs, d), 2, 0)
    v_blocks = jnp.moveaxis(v.reshape(b, h, nblocks, bs, d), 2, 0)

    q_scaled = q * scale
    q_pos = jnp.arange(lq) + (lk - lq)  # causal offset for cross lengths

    def step(carry, inputs):
        m_prev, l_prev, acc = carry
        if mask is not None:
            kb, vb, mb, blk = inputs
        else:
            kb, vb, blk = inputs
        logits = jnp.einsum("bhqd,bhkd->bhqk", q_scaled, kb)  # (B,H,Lq,bs)
        if padmask is not None:
            kpos_valid = lax.dynamic_slice_in_dim(padmask, blk * bs, bs)
            logits = jnp.where(kpos_valid[None, None, None, :], logits,
                               NEG_INF)
        if causal:
            kpos = blk * bs + jnp.arange(bs)
            cm = q_pos[:, None] >= kpos[None, :]
            logits = jnp.where(cm[None, None], logits, NEG_INF)
        if mask is not None:
            logits = jnp.where(mb, logits, NEG_INF)
        if dropout_rate > 0.0 and dropout_rng is not None:
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_rng, blk),
                1.0 - dropout_rate, logits.shape)
            return online_softmax_fold(m_prev, l_prev, acc, logits, vb,
                                       drop_mask=keep,
                                       keep_prob=1.0 - dropout_rate), None
        return online_softmax_fold(m_prev, l_prev, acc, logits, vb), None

    # f32 carry: with bf16 inputs the running normalizer/accumulator must
    # not round across KV blocks (matches the Pallas kernel's f32 scratch)
    init = (jnp.full((b, h, lq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, lq), jnp.float32),
            jnp.zeros((b, h, lq, d), jnp.float32))
    blks = jnp.arange(nblocks)
    xs = ((k_blocks, v_blocks, mask_blocks, blks) if mask is not None
          else (k_blocks, v_blocks, blks))
    (m, l, acc), _ = lax.scan(step, init, xs)
    l = jnp.maximum(l, 1e-20)
    return (acc / l[..., None]).astype(q.dtype)


def dot_product_attention(q, k, v, mask=None, causal: bool = False,
                          sm_scale: Optional[float] = None,
                          block_size: int = 512,
                          use_flash: Optional[bool] = None,
                          dropout_rate: float = 0.0, dropout_rng=None):
    """Entry point used by the attention layers.

    Chooses the Pallas flash kernel on TPU when shapes allow, else the
    blockwise scan.  ``use_flash`` forces the choice (tests).  The kernel's
    tiles are 128 lanes wide: a head size that is a multiple of 128 goes to
    it as it is; one under 128 that is at least 64 is padded with zeros to
    128 (the scores and the result's first D columns are what they were, at
    up to twice the kernel's work; ``sm_scale`` stays the unpadded
    ``1 / sqrt(D)`` unless given); a narrower head, or a wider one that is
    no multiple of 128, takes the XLA paths below.
    ``dropout_rate`` > 0 with a ``dropout_rng`` applies probability
    dropout (reference attn_drop semantics) via the blockwise path, which
    keeps the O(Lq · block) memory bound during training.
    """
    from analytics_zoo_tpu.ops import dispatch

    dropping = dropout_rate > 0.0 and dropout_rng is not None
    # the hand-written kernel is sent what it wins from L = 2,048 up.  On a
    # TPU v5e (chip runs, PR 30; bf16, causal, D 128): B 4, H 8, L 2,048:
    # forward 0.48 ms, dq 0.57, dkv 0.71 in a queue of calls; a call at a
    # time (chip_smoke.py) forward 1.03 ms against 5.56 for
    # reference_attention, forward and backward 2.53 against 10.56.  B 2,
    # H 16, L 4,096, the looped decoder's: 1.51 / 1.78 / 2.16 ms, 48 / 24 /
    # 24 calls a step (PERF.md section 5).  At B 2, H 16, L 1,024 the three
    # take 0.41 / 0.24 / 0.41 ms and at L 512 0.39 / 0.20 / 0.37: below
    # 2,048 a call is mostly its fixed cost; the XLA paths below were not
    # timed against it there, and the floor stays where it was.
    d = q.shape[-1]
    lanes = -d % 128 if 64 <= d < 128 else 0    # zero columns to add
    path = dispatch.select_path(
        "flash_attention",
        shapes_ok=(mask is None and not dropping
                   and (d + lanes) % 128 == 0 and q.shape[2] % 128 == 0
                   and k.shape[2] % 128 == 0),
        min_work_met=max(q.shape[2], k.shape[2]) >= 2048,
        force=(None if use_flash is None else
               (dispatch.PATH_PALLAS if use_flash
                else dispatch.PATH_REFERENCE)),
    )
    if path == dispatch.PATH_PALLAS:
        if mask is not None:
            raise ValueError("flash kernel does not take a mask; pass "
                             "use_flash=False (or None for auto dispatch)")
        if dropping:
            raise ValueError("flash kernel does not support attention "
                             "dropout; pass use_flash=False/None")
        from analytics_zoo_tpu.ops.flash_attention import flash_attention
        if lanes:
            q, k, v = (jnp.pad(t, ((0, 0),) * 3 + ((0, lanes),))
                       for t in (q, k, v))
            sm_scale = sm_scale if sm_scale is not None else d ** -0.5
        return flash_attention(q, k, v, causal=causal,
                               sm_scale=sm_scale)[..., :d]
    if not dropping and q.shape[2] * k.shape[2] <= 256 * 256:
        # tiny sequences: one fused softmax beats the scan
        return reference_attention(q, k, v, mask=mask, causal=causal,
                                   sm_scale=sm_scale)
    return blockwise_attention(q, k, v, mask=mask, causal=causal,
                               sm_scale=sm_scale, block_size=block_size,
                               dropout_rate=dropout_rate if dropping else 0.0,
                               dropout_rng=dropout_rng if dropping else None)
