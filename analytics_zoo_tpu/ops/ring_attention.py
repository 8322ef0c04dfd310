"""Sequence-parallel ring attention: the L axis sharded over ICI.

Reference capability: **absent** (SURVEY §5.7 — the reference's
TransformerLayer/BERT were hard-bounded by single-node O(L²) attention).
``ops/flash_attention.py`` (PR 12) removed the O(L²) *memory* but still
needs the full K/V sequence resident on one chip, so per-chip HBM — not
the mesh — caps context length.  This module removes that bound: the
sequence axis is sharded over a mesh axis (``shard_map``), K/V shards
rotate neighbour-to-neighbour via ``jax.lax.ppermute`` (ICI ring), and
every hop streams the resident K/V block through the *existing* flash
kernel, folding each hop's (out, lse) into the running online-softmax
(m, l, acc) carry — ring attention (Liu et al.) is literally blockwise
attention whose KV loop runs over devices.  Max context becomes a
function of mesh size: per-chip peak attention memory is O(L/ways).

Schedule (forward, ``ways`` hops, double-buffered):

    hop i:   ppermute(K/V) for hop i+1 issued FIRST  ──┐ overlaps
             flash(q_local, K/V from shard (my-i)%n) ──┘ on ICI/MXU
             (m, l, acc) ← online-softmax merge of the hop's (out, lse)

Causal skip: with tail-padding the global order is shard-major, so the
block from source shard ``src=(my-i)%n`` lies wholly *below* the
diagonal when ``src < my`` (full compute, no mask), *on* it when
``src == my`` (hop 0 — intra-block causal mask), and wholly *above* it
when ``src > my`` — those hops are skipped entirely (``lax.cond``
pass-through; the ppermute still runs, keeping the ring in lock-step).

Backward (``jax.custom_vjp``, FlashAttention-2 recipe): the forward
saves per-shard (q, k, v, out, lse) only; the backward re-streams K/V
around the *reverse* ring (ppermute by −1) with (dk, dv) partial sums
riding along with their K/V block — after ``ways`` hops each grad shard
is home.  Per hop the existing Pallas backward kernels recompute the
probability tile from (q, k, global lse) — no (Lq, Lk) matrix and no
gathered KV ever materialize, in forward or backward.

Dispatch (``ops/dispatch.select_path``, counted in
``ops_kernel_selected_total{kernel=ring_attention,path}``):

- mesh routing — no mesh / no seq axis / 1-way mesh → single-device
  blockwise fallback (path "reference");
- min-length routing — below ``RING_MIN_LEN`` total tokens the ring's
  per-hop latency loses to single-chip flash, so "auto" stays local;
- ``ZooConfig.ring_attention`` knob — "auto"/"on"/"off" like
  ``fused_embedding``; "on" rings wherever a mesh allows, "off" pins
  the single-device path;
- ``force`` — explicit test override; "interpret" runs the flash
  kernels under ``pallas_call(interpret=True)`` per hop, which is how
  the CPU tier proves kernel-path parity.

On CPU the auto path is the pure-JAX ring (same shard_map/ppermute
schedule, ``online_softmax_fold`` per hop) — tier-1 stays green with no
TPU in the loop.  Ragged L (not divisible by ``ways``) is tail-padded;
causal masking hides the pad keys from every real query, and the
non-causal ragged case routes to the pure-JAX hops, which mask global
key positions ``>= L`` explicitly (the kernel path rejects that combo).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops.attention import (blockwise_attention,
                                             online_softmax_fold)

NEG_INF = -1e30

# Below this many total tokens the ring's ways-1 ppermute latencies cost
# more than they save: single-chip flash at L=2048/D=64 is ~11ms on v5e
# while one ICI round-trip alone is ~1μs/hop + per-hop kernel launch —
# the win only appears once per-chip K/V no longer fits VMEM-friendly
# tiles, i.e. multi-k contexts.  Same role as attention's 2048 floor.
RING_MIN_LEN = 4096


# ---------------------------------------------------------------------------
# per-shard helpers (run inside shard_map; all shapes are per-device)
# ---------------------------------------------------------------------------

def _vary_like(x, axis_name, ref):
    """Fresh accumulators must carry the same varying-axes type as the
    q-derived values (including a batch axis under sp x dp)."""
    # lazy: parallel.sequence imports ops.attention, so a top-level import
    # here would close a cycle through ops/__init__ during package init
    from analytics_zoo_tpu.parallel.sequence import mark_varying
    return mark_varying(x, tuple(jax.typeof(ref).vma | {axis_name}))


def _hop_masks(i, src, lq, lk, causal, valid_len, total_len):
    """(lq, lk) bool mask for hop ``i`` of the pure-JAX path, or None.

    ``src`` may be traced (it depends on ``axis_index``); the mask is
    built lazily so fully-live hops pay nothing.
    """
    need_valid = valid_len < total_len
    need_causal = causal and i == 0
    if not (need_valid or need_causal):  # zoolint: disable=JG-TRACED-BRANCH(valid_len/total_len/causal/i are static python ints and bools — only src is ever traced)
        return None
    mask = jnp.ones((lq, lk), bool)
    if need_causal:  # zoolint: disable=JG-TRACED-BRANCH(static python bool — hop index and causal flag are trace-time constants)
        # hop 0 holds the diagonal block: local positions line up
        mask = mask & (jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :])
    if need_valid:  # zoolint: disable=JG-TRACED-BRANCH(static python bool — pad geometry is fixed at trace time)
        k_pos = src * lk + jnp.arange(lk)
        mask = mask & (k_pos < valid_len)[None, :]
    return mask


def _ref_hop_fwd(q, kc, vc, m, l, acc, scale, mask):
    """One pure-JAX hop: fold the resident K/V block into (m, l, acc)
    via the shared online-softmax fold."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                        kc.astype(jnp.float32))
    if mask is not None:  # zoolint: disable=JG-TRACED-BRANCH(None-ness is static pytree structure decided per hop at trace time)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    return online_softmax_fold(m, l, acc, logits, vc)


def _kernel_hop_fwd(q, kc, vc, m, l, acc, scale, diag_causal, block_q,
                    block_k, interpret):
    """One flash-kernel hop: the Pallas forward emits this block's
    (out, lse); merging into the carry is the standard flash combine —
    the block contributes (m=lse, l=1, acc=out) in carry coordinates."""
    from analytics_zoo_tpu.ops.flash_attention import _flash_fwd

    o_blk, lse_blk = _flash_fwd(q, kc, vc, scale, diag_causal, block_q,
                                block_k, interpret, with_lse=True)
    m_new = jnp.maximum(m, lse_blk)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(lse_blk - m_new)
    l_new = l * alpha + beta
    acc_new = (acc * alpha[..., None]
               + o_blk.astype(jnp.float32) * beta[..., None])
    return m_new, l_new, acc_new


def _ring_fwd_impl(q, k, v, axis_name, ways, causal, scale, block_q,
                   block_k, kernel, valid_len):
    """Forward ring over the shard's ``ways`` hops.  Returns (out, lse)
    — lse is the backward's residual (FlashAttention-2)."""
    my = lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lk = k.shape[2]
    total = ways * lk
    interpret = kernel == dispatch.PATH_INTERPRET
    use_kernel = kernel in (dispatch.PATH_PALLAS, dispatch.PATH_INTERPRET)

    vary = functools.partial(_vary_like, axis_name=axis_name, ref=q)
    m = vary(jnp.full((b, h, lq), NEG_INF, jnp.float32))
    l = vary(jnp.zeros((b, h, lq), jnp.float32))
    acc = vary(jnp.zeros((b, h, lq, d), jnp.float32))

    perm = [(j, (j + 1) % ways) for j in range(ways)]
    kc, vc = k, v
    for i in range(ways):
        # double buffer: issue hop i+1's ppermute BEFORE hop i's compute
        # so the neighbour exchange overlaps the flash kernel on ICI
        if i + 1 < ways:
            kn = lax.ppermute(kc, axis_name, perm)
            vn = lax.ppermute(vc, axis_name, perm)
        src = (my - i) % ways  # origin shard of the resident block

        if use_kernel:
            def fold(args, _diag=(causal and i == 0)):
                qa, ka, va, ma, la, aa = args
                return _kernel_hop_fwd(qa, ka, va, ma, la, aa, scale,
                                       _diag, block_q, block_k, interpret)
        else:
            def fold(args, _i=i, _src=src):
                qa, ka, va, ma, la, aa = args
                mask = _hop_masks(_i, _src, lq, lk, causal, valid_len,
                                  total)
                return _ref_hop_fwd(qa, ka, va, ma, la, aa, scale, mask)

        if causal and i > 0:
            # src > my ⟺ the whole block sits above the diagonal —
            # skip the compute entirely; carry passes through unchanged
            m, l, acc = lax.cond(my >= i, fold,
                                 lambda args: (args[3], args[4], args[5]),
                                 (q, kc, vc, m, l, acc))
        else:
            m, l, acc = fold((q, kc, vc, m, l, acc))
        if i + 1 < ways:
            kc, vc = kn, vn

    l_safe = jnp.maximum(l, 1e-20)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


def _ref_hop_bwd(q, kc, vc, do, out_lse_delta, scale, mask):
    """Pure-JAX hop of the FlashAttention-2 backward: probabilities
    recomputed from (q, k, global lse); returns the hop's partial
    (dq, dk, dv) contributions."""
    lse, delta = out_lse_delta
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                   kc.astype(jnp.float32))
    if mask is not None:  # zoolint: disable=JG-TRACED-BRANCH(None-ness is static pytree structure decided per hop at trace time)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vc.astype(jnp.float32))
    ds = p * (dp - delta[..., None])
    dq = scale * jnp.einsum("bhqk,bhkd->bhqd", ds, kc.astype(jnp.float32))
    dk = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq, dk, dv


def _ring_bwd_impl(axis_name, ways, causal, scale, block_q, block_k,
                   kernel, valid_len, res, g):
    """Backward ring: K/V re-stream around the REVERSE ring with their
    (dk, dv) partial sums riding along; after ``ways`` rotations every
    grad shard is back on its home device."""
    q, k, v, out, lse = res
    my = lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lk = k.shape[2]
    total = ways * lk
    interpret = kernel == dispatch.PATH_INTERPRET
    use_kernel = kernel in (dispatch.PATH_PALLAS, dispatch.PATH_INTERPRET)

    # delta_i = rowsum(dO_i * O_i) — global because out/lse are the
    # full-softmax forward results (same role as in _flash_bwd)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    vary = functools.partial(_vary_like, axis_name=axis_name, ref=q)
    dq = vary(jnp.zeros((b, h, lq, d), jnp.float32))
    dk_c = vary(jnp.zeros((b, h, lk, d), jnp.float32))
    dv_c = vary(jnp.zeros((b, h, lk, d), jnp.float32))

    perm = [(j, (j - 1) % ways) for j in range(ways)]
    kc, vc = k, v
    for i in range(ways):
        src = (my + i) % ways  # reverse ring: +i after i rotations
        if i + 1 < ways:
            kn = lax.ppermute(kc, axis_name, perm)
            vn = lax.ppermute(vc, axis_name, perm)

        if use_kernel:
            def hop(args, _diag=(causal and i == 0)):
                qa, ka, va, dqa, dka, dva = args
                from analytics_zoo_tpu.ops.flash_attention import _flash_bwd

                dq_h, dk_h, dv_h = _flash_bwd(qa, ka, va, out, lse, g,
                                              scale, _diag, block_q,
                                              block_k, interpret)
                return (dqa + dq_h, dka + dk_h.astype(jnp.float32),
                        dva + dv_h.astype(jnp.float32))
        else:
            def hop(args, _i=i, _src=src):
                qa, ka, va, dqa, dka, dva = args
                mask = _hop_masks(_i, _src, lq, lk, causal, valid_len,
                                  total)
                dq_h, dk_h, dv_h = _ref_hop_bwd(qa, ka, va, g,
                                                (lse, delta), scale, mask)
                return dqa + dq_h, dka + dk_h, dva + dv_h

        if causal and i > 0:
            # reverse ring: the resident block wrapped (src < my) iff
            # my + i >= ways — only those hops are below the diagonal
            dq, dk_c, dv_c = lax.cond(
                my + i >= ways, hop,
                lambda args: (args[3], args[4], args[5]),
                (q, kc, vc, dq, dk_c, dv_c))
        else:
            dq, dk_c, dv_c = hop((q, kc, vc, dq, dk_c, dv_c))

        # the grads travel WITH their block: ways rotations total bring
        # each (dk, dv) shard home (k/v themselves are done after the
        # last fold and need no final hop)
        dk_c = lax.ppermute(dk_c, axis_name, perm)
        dv_c = lax.ppermute(dv_c, axis_name, perm)
        if i + 1 < ways:
            kc, vc = kn, vn

    return (dq.astype(q.dtype), dk_c.astype(k.dtype),
            dv_c.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9,
                                                    10))
def _ring_shard(q, k, v, axis_name, ways, causal, scale, block_q, block_k,
                kernel, valid_len):
    """Per-shard ring attention (runs inside shard_map).  The custom_vjp
    sits at the shard level so the backward can re-stream K/V instead of
    saving ``ways`` activations per hop."""
    out, _ = _ring_fwd_impl(q, k, v, axis_name, ways, causal, scale,
                            block_q, block_k, kernel, valid_len)
    return out


def _ring_shard_fwd(q, k, v, axis_name, ways, causal, scale, block_q,
                    block_k, kernel, valid_len):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, ways, causal, scale,
                              block_q, block_k, kernel, valid_len)
    return out, (q, k, v, out, lse)


def _ring_shard_bwd(axis_name, ways, causal, scale, block_q, block_k,
                    kernel, valid_len, res, g):
    return _ring_bwd_impl(axis_name, ways, causal, scale, block_q,
                          block_k, kernel, valid_len, res, g)


_ring_shard.defvjp(_ring_shard_fwd, _ring_shard_bwd)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def ring_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                   axis: str = "seq", batch_axis: Optional[str] = None,
                   causal: bool = False, sm_scale: Optional[float] = None,
                   block_q: int = 256, block_k: int = 256,
                   knob: Optional[str] = None,
                   force: Optional[str] = None):
    """Self-attention with the sequence axis sharded over ``mesh[axis]``.

    Shapes: q/k/v (B, H, L, D) — *global* arrays; the op shard_maps them
    over ``axis`` (and optionally ``batch_axis`` on dim 0 for the sp x dp
    composition).  Routing is the counted dispatch contract: without a
    usable mesh (or below ``RING_MIN_LEN``, or knob "off") the call is a
    single-device blockwise fallback; with one, K/V stream around the
    ring and the per-hop compute runs the flash kernel (TPU), its
    interpreter (``force="interpret"``, CPU tier) or the pure-JAX fold.
    """
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    b, h, l, d = q.shape
    if k.shape[2] != l:
        raise ValueError(
            "ring attention is self-attention only: q and kv shards must "
            f"rotate together (Lq={l}, Lk={k.shape[2]})")
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)

    ways = 0
    if mesh is not None and axis in getattr(mesh, "shape", {}):
        ways = int(mesh.shape[axis])
    ring_ok = ways > 1 and l >= ways
    pad = (-l) % ways if ring_ok else 0
    kernel_ok = ring_ok and (pad == 0 or causal)
    # Mosaic refuses the hop kernel's (1, 8, block_q) logsumexp block
    # unless block_q is a lane multiple ("last two dimensions of your
    # block shape are divisible by 8 and 128"): a 1032-token shard picks
    # block_q=8.  Auto-dispatch keeps such shards on the pure-JAX hops.
    compiles = kernel_ok and ((l + pad) // ways) % 128 == 0

    if force in (dispatch.PATH_PALLAS, dispatch.PATH_INTERPRET) \
            and not kernel_ok:
        raise ValueError(
            "ring_attention kernel path needs a mesh with a >1-way "
            f"'{axis}' axis and L%ways==0 (or causal=True); got "
            f"L={l}, ways={ways}, causal={causal}")
    if knob is None:
        knob = dispatch.config_knob("ring_attention", "auto")

    path = dispatch.select_path("ring_attention", shapes_ok=compiles,
                                min_work_met=l >= RING_MIN_LEN,
                                knob=knob, force=force)

    use_ring = (ring_ok and knob != "off"
                and (force is not None or knob == "on"
                     or l >= RING_MIN_LEN))
    if not use_ring:
        return blockwise_attention(q, k, v, causal=causal,
                                   sm_scale=scale)

    if pad:
        padding = [(0, 0)] * 2 + [(0, pad)] + [(0, 0)]
        q = jnp.pad(q, padding)
        k = jnp.pad(k, padding)
        v = jnp.pad(v, padding)

    spec = P(batch_axis, None, axis, None)
    shard_fn = lambda qs, ks, vs: _ring_shard(
        qs, ks, vs, axis, ways, causal, scale, block_q, block_k, path, l)
    # pallas_call has no replication rule; the kernel hops are verified
    # element-exact against the pure-JAX ring by tests
    kernel_hops = path in (dispatch.PATH_PALLAS, dispatch.PATH_INTERPRET)
    out = shard_map(shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=not kernel_hops)(q, k, v)
    return out[:, :, :l] if pad else out
