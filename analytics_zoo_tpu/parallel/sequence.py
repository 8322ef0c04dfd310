"""Sequence/context parallelism: ring attention over the mesh.

Reference capability: **absent** (SURVEY.md §5.7 — the reference's
TransformerLayer/BERT materialize full O(L²) attention on one host, and
sequence length is bounded by single-node memory).  This module is the
TPU-native upgrade that makes long context first-class: the sequence axis
is sharded over devices, K/V shards rotate around the ring via
``lax.ppermute`` (ICI neighbour exchanges), and each device folds incoming
blocks into the same online-softmax accumulator used by blockwise
attention (ops/attention.py) — i.e. ring attention (Liu et al.) is
literally blockwise attention whose KV loop runs over devices.

Use ``ring_attention`` inside ``shard_map`` with q/k/v sharded on the
sequence axis; ``ring_self_attention`` wraps the shard_map for you.
Differentiable end-to-end (ppermute has a transpose rule).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from analytics_zoo_tpu.ops.attention import online_softmax_fold

NEG_INF = -1e30


def mark_varying(x, axis_name):
    """Mark a freshly-created (replicated) array as device-varying along
    ``axis_name`` (a name or tuple of names) so shard_map scan carry
    types match axis-dependent loop outputs.  Shared by ring attention
    and the pipeline schedule."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    # only mark axes the value is not already varying over (pcast rejects
    # mixed varying/invarying inputs)
    cur = jax.typeof(x).vma
    axes = tuple(a for a in axes if a not in cur)
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Attention where K/V are sharded over ``axis_name`` (per-device
    shapes: q (B, H, Lq_local, D), k/v (B, H, Lk_local, D)).

    Must run inside shard_map/pjit with ``axis_name`` bound.  Each of the
    ``n`` ring steps computes local blockwise attention against the
    currently-held KV shard, then rotates KV to the next device.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(d)
    q_scaled = q * scale
    # global positions of my queries (sequence sharded evenly)
    q_pos = my * lq + jnp.arange(lq)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        m_prev, l_prev, acc, kc, vc = carry
        # device holding shard s sends to s+1, so after i rotations we hold
        # the shard originally on device (my - i) mod n
        src = (my - i) % n
        logits = jnp.einsum("bhqd,bhkd->bhqk", q_scaled, kc)
        if causal:
            k_pos = src * lk + jnp.arange(lk)
            cm = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(cm[None, None], logits, NEG_INF)
        m_out, l_new, acc = online_softmax_fold(m_prev, l_prev, acc, logits,
                                                vc)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m_out, l_new, acc, kc, vc), None

    def _vary(x):
        # fresh accumulators must carry the same varying-axes type as the
        # q-derived scan outputs — including a batch axis when the caller
        # composes sp with dp (q is then varying over ('data', seq))
        try:
            axes = tuple(jax.typeof(q).vma | {axis_name})
        except (AttributeError, TypeError):
            axes = axis_name
        return mark_varying(x, axes)

    # f32 carry across ring steps, matching blockwise_attention/the Pallas
    # kernel's f32 scratch, so bf16 inputs don't round the accumulator
    init = (_vary(jnp.full((b, h, lq), NEG_INF, jnp.float32)),
            _vary(jnp.zeros((b, h, lq), jnp.float32)),
            _vary(jnp.zeros((b, h, lq, d), jnp.float32)), k, v)
    (m, l, acc, _, _), _ = lax.scan(step, init, jnp.arange(n))
    l = jnp.maximum(l, 1e-20)
    return (acc / l[..., None]).astype(q.dtype)


def ring_self_attention(q, k, v, mesh: Mesh, seq_axis: str,
                        causal: bool = False,
                        sm_scale: Optional[float] = None,
                        batch_axis: Optional[str] = None):
    """Convenience wrapper: shard q/k/v (B, H, L, D) on dim 2 over
    ``seq_axis`` of ``mesh`` and run ring attention.

    ``batch_axis``: additionally shard dim 0 over this mesh axis — the
    sp×dp composition (each data group runs its own ring; leaving it
    unset on a multi-axis mesh makes GSPMD allgather the batch).

    Since the ops/ring_attention.py tentpole this is a thin delegator
    into the counted dispatch contract: the sp regime asked for the ring
    explicitly, so the knob pins "on" (no min-length bail-out) and the
    per-hop compute routes pallas/interpret/pure-JAX via
    ``ops.dispatch.select_path`` — with a double-buffered ppermute
    schedule, causal hop skipping, and a custom_vjp backward that
    re-streams K/V instead of checkpointing every hop."""
    from analytics_zoo_tpu.ops.ring_attention import (
        ring_attention as _ring_op)

    return _ring_op(q, k, v, mesh=mesh, axis=seq_axis,
                    batch_axis=batch_axis, causal=causal,
                    sm_scale=sm_scale, knob="on")
