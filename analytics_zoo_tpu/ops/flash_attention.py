"""Pallas TPU flash-attention forward kernel.

Replaces the O(L²) attention inside the reference's TransformerLayer/BERT
(api/keras/layers/TransformerLayer.scala:56, BERT.scala:66) with a fused
blockwise kernel: Q/K/V tiles stream HBM→VMEM, the (block_q, block_k)
logits tile lives only in VMEM, and the online-softmax running (m, l, acc)
state sits in VMEM scratch across the KV grid dimension.  The MXU sees two
matmuls per tile (Q·Kᵀ and P·V), their operands in the input's dtype
(bfloat16 inputs multiply in bfloat16, float32 inputs in float32; P is cast
to it) and their sums in float32; everything else (scores, softmax, the
running statistics) is float32 VPU work fused in between.

Tiles come from the shape (``_tiles``), not from a constant; under
``causal`` a tile above the diagonal is neither computed nor fetched, and
only a tile the diagonal crosses pays for the mask.

Autodiff: ``flash_attention`` carries a ``jax.custom_vjp`` with
HAND-WRITTEN Pallas backward kernels (the FlashAttention-2 recipe): the
forward additionally emits the per-row logsumexp, the backward recomputes
the probability tiles from (q, k, lse) in VMEM — no (Lq, Lk) matrix ever
materialises — and two kernels accumulate dQ (grid over KV blocks) and
dK/dV (grid over Q blocks) in f32 scratch.  Off-TPU
``dot_product_attention``'s dispatch takes the pure-JAX blockwise path;
the kernels run there only in interpreter mode under tests.

The three kernels are named (``pallas_call(name=...)``): on a device trace
their operations read ``flash_attention_fwd``, ``flash_attention_dq`` and
``flash_attention_dkv`` (with XLA's ``.N`` suffix).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b


def _dot(a, b, dims):
    """One MXU product: operands as they are, float32 accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# tiles: which (block_q, block_k) a call takes, and what they cost in VMEM
# ---------------------------------------------------------------------------

_MAX_SIDE = 1024                  # chip runs, PR 30: see flash_attention
_VMEM_BUDGET = 32 * 2 ** 20       # what a tile's buffers may add up to
_VMEM_DEFAULT = 16 * 2 ** 20      # Mosaic's scoped limit when none is set
# float32 temporaries of the score tile's size that a kernel keeps alive
_SCORE_TEMPS = {"fwd": 2, "dq": 3, "dkv": 4}


def _tile_bytes(kernel: str, bq: int, bk: int, d: int, itemsize: int) -> int:
    """VMEM that one grid step of ``kernel`` asks for: its streamed blocks
    twice (the pipeline double-buffers them), its float32 scratch, and
    the score-sized temporaries with the one cast to the operand dtype.
    Generous: Mosaic's own count came out at 0.5-0.7 of this (PR 30)."""
    rows = 2 * 8 * bq * 4                                # lse, or delta
    if kernel == "fwd":
        streams = (2 * bq + 2 * bk) * d * itemsize + rows    # q o / k v
        scratch = (2 * 128 + d) * bq * 4                     # m l acc
    elif kernel == "dq":
        streams = (3 * bq + 2 * bk) * d * itemsize + 2 * rows
        scratch = bq * d * 4
    else:
        streams = (2 * bq + 4 * bk) * d * itemsize + 2 * rows
        scratch = 2 * bk * d * 4
    temps = bq * bk * (4 * _SCORE_TEMPS[kernel] + itemsize)
    return 2 * streams + scratch + temps


def _sides(length: int, causal: bool):
    """Block lengths a sequence of ``length`` may be cut in: the
    multiples of 128 that divide it, up to ``_MAX_SIDE``; causal, up to
    half of it, so that a dead quarter is there to skip.  A longer length
    with no such divisor from 512 up (2,176 = 17 x 128), or one with none
    at all (under 128), may also go whole: a block of the full length is
    always a legal one."""
    sides = [s for s in range(128, min(length, _MAX_SIDE) + 1, 128)
             if length % s == 0]
    if causal:
        sides = [s for s in sides if 2 * s <= length] or sides
    if not sides or (max(sides) < 512 and length > _MAX_SIDE):
        sides.append(length)
    return sides


def _tiles(kernel: str, lq: int, lk: int, d: int, dtype, causal: bool):
    """(block_q, block_k) for a caller that names none: the largest score
    tile whose buffers fit ``_VMEM_BUDGET``, the longer key block among
    equals (the forward's row statistics and accumulator are touched once
    a tile, so they cost less the more keys a tile has)."""
    itemsize = jnp.dtype(dtype).itemsize
    fits = [(bq * bk, bk, bq) for bq in _sides(lq, causal)
            for bk in _sides(lk, causal)
            if _tile_bytes(kernel, bq, bk, d, itemsize) <= _VMEM_BUDGET]
    if not fits:
        return min(_sides(lq, causal)), min(_sides(lk, causal))
    _, bk, bq = max(fits)
    return bq, bk


def _pick_block(block: int, length: int) -> int:
    """Largest block <= ``block`` that divides ``length`` (halving keeps
    it a multiple of 128 down to the tile floor)."""
    b = min(block, length)
    while length % b:
        b //= 2
    return b


def _blocks(q, k, block_q, block_k, kernel: str, causal: bool):
    """A caller's blocks are obeyed (cut to divide the lengths); a side
    it leaves ``None`` comes from ``_tiles``."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    if block_q is None or block_k is None:
        tq, tk = _tiles(kernel, lq, lk, d, q.dtype, causal)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    bq = _pick_block(block_q, lq)
    bk = _pick_block(block_k, lk)
    assert lq % bq == 0 and lk % bk == 0, (
        f"sequence lengths ({lq},{lk}) must divide blocks ({bq},{bk})")
    return b, h, lq, lk, d, bq, bk


def _compiler_params(kernel: str, bq: int, bk: int, d: int, dtype):
    """Raise Mosaic's scoped VMEM limit where the tile needs more than
    the default leaves; small tiles (a ring hop's, a test's) keep it."""
    need = _tile_bytes(kernel, bq, bk, d, jnp.dtype(dtype).itemsize)
    if need <= _VMEM_DEFAULT * 3 // 4:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need + need // 4)


# ---------------------------------------------------------------------------
# causal: a score tile is dead (above the diagonal), full (below it) or
# crossed by it.  ``off`` = lk - lq shifts the diagonal (a ring hop, a
# decoder reading a longer memory).
# ---------------------------------------------------------------------------

def _tile_kinds(qi, ki, bq: int, bk: int, off: int, causal: bool):
    """(crossed, full) of tile (qi, ki); one that is neither is dead.
    Not causal: every tile is full."""
    if not causal:
        return False, True
    q_start = qi * bq + off
    live = ki * bk <= q_start + bq - 1
    full = ki * bk + bk - 1 <= q_start
    return jnp.logical_and(live, jnp.logical_not(full)), full


def _run_tile(tile, crossed, full):
    """Run ``tile(masked)`` by the tile's kind: only a crossed tile pays
    for the mask (iota, compare, two selects); a dead one does nothing."""
    if crossed is False:
        tile(False)
        return
    pl.when(crossed)(functools.partial(tile, True))
    pl.when(full)(functools.partial(tile, False))


def _mask_above_diagonal(s, q0, k0, q_axis: int):
    """NEG_INF where the key is after the query; ``q_axis`` says which
    axis of ``s`` the queries lie on, ``q0``/``k0`` where the tile starts."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float,
                causal: bool, lq: int, lk: int):
    """``rest`` is (lse_ref, m, l, acc) or, without the residual,
    (m, l, acc)."""
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(masked: bool):
        v = v_ref[0]
        s = _dot(q_ref[0], k_ref[0], _NT) * sm_scale     # (bq, bk) f32
        if masked:
            s = _mask_above_diagonal(s, qi * bq + (lk - lq), ki * bk, 0)
        m_prev = m_scr[:, :1]                            # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # a row that has seen no key yet has m_new == NEG_INF: p = 1
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _run_tile(tile, *_tile_kinds(qi, ki, bq, bk, lk - lq, causal))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            row = m_scr[:, 0] + jnp.log(l[:, 0])
            # lse block is (1, 8, bq): the row dim is padded to the TPU's
            # 8-sublane tile floor (a (1, bq) block is an illegal
            # sub-tile); all 8 sublanes carry the same row, the caller
            # reads sublane 0
            lse_ref[0] = jnp.broadcast_to(row[None, :], lse_ref.shape[1:])


def _kv_index_map(causal: bool, bq: int, bk: int, off: int):
    """Block index of K and V on a (bh, qi, ki) grid.  Causal: a dead
    step names the block the last live one held, so the pipeline sees no
    change and copies nothing."""
    if not causal:
        return lambda bh, qi, ki: (bh, ki, 0)

    def index_map(bh, qi, ki):
        last_live = jnp.maximum(qi * bq + bq - 1 + off, 0) // bk
        return bh, jnp.minimum(ki, last_live), 0

    return index_map


def _flash_fwd(q, k, v, sm_scale: float, causal: bool,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, with_lse: bool = False):
    b, h, lq, lk, d, bq, bk = _blocks(q, k, block_q, block_k, "fwd", causal)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * h, lk, d)
    vf = v.reshape(b * h, lk, d)

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, bk, d), _kv_index_map(causal, bq, bk, lk - lq))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((b * h, lq, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 8, bq),
                                      lambda bh, qi, ki: (bh, 0, qi)))
        out_shape.append(jax.ShapeDtypeStruct((b * h, 8, lq), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          lq=lq, lk=lk),
        grid=(b * h, lq // bq, lk // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd", bq, bk, d, q.dtype),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)
    out = res[0].reshape(b, h, lq, d)
    if with_lse:
        return out, res[1][:, 0, :].reshape(b, h, lq)
    return out


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2): probabilities recomputed from
# (q, k, lse); dQ accumulates over KV blocks, dK/dV over Q blocks.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, causal, lq, lk):
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(masked: bool):
        k = k_ref[0]
        s = _dot(q_ref[0], k, _NT) * sm_scale            # (bq, bk)
        if masked:
            s = _mask_above_diagonal(s, qi * bq + (lk - lq), ki * bk, 0)
        # lse/delta blocks are (1, 8, bq) — sublane-padded rows; take
        # sublane 0 (see _finalize of the forward)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        if masked:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dp = _dot(do_ref[0], v_ref[0], _NT)              # (bq, bk)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(k.dtype), k, _NN)

    _run_tile(tile, *_tile_kinds(qi, ki, bq, bk, lk - lq, causal))

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    lq, lk):
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(masked: bool):
        # the tile transposed, keys down the sublanes and queries along
        # the lanes: the lse and delta rows broadcast as they are stored,
        # and no product needs an operand turned (p.T @ dO, dS.T @ q)
        q, do = q_ref[0], do_ref[0]
        st = _dot(k_ref[0], q, _NT) * sm_scale           # (bk, bq)
        if masked:
            st = _mask_above_diagonal(st, qi * bq + (lk - lq), ki * bk, 1)
        pt = jnp.exp(st - lse_ref[0, :1, :])
        if masked:
            pt = jnp.where(st <= NEG_INF / 2, 0.0, pt)
        dv_scr[:] = dv_scr[:] + _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[0], do, _NT)                    # (bk, bq)
        dst = pt * (dpt - delta_ref[0, :1, :])
        dk_scr[:] = dk_scr[:] + _dot(dst.astype(q.dtype), q, _NN)

    _run_tile(tile, *_tile_kinds(qi, ki, bq, bk, lk - lq, causal))

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, sm_scale, causal, block_q, block_k,
               interpret):
    b, h, lq, lk, d, bq, bk = _blocks(q, k, block_q, block_k, "dq", causal)
    qf = q.reshape(b * h, lq, d)
    kf = k.reshape(b * h, lk, d)
    vf = v.reshape(b * h, lk, d)
    dof = g.reshape(b * h, lq, d)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, fused by XLA.
    # Rows are sublane-padded to (BH, 8, L): a (1, bq) block is an
    # illegal TPU sub-tile, (1, 8, bq) satisfies the (8, 128) tile floor
    # and the kernels read sublane 0.
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(b * h, lq, d).astype(jnp.float32),
                    axis=-1)
    lse8 = jnp.broadcast_to(lse.reshape(b * h, 1, lq), (b * h, 8, lq))
    delta8 = jnp.broadcast_to(delta[:, None, :], (b * h, 8, lq))
    common = dict(sm_scale=sm_scale, causal=causal, lq=lq, lk=lk)
    off = lk - lq

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, bk, d), _kv_index_map(causal, bq, bk, off))
    row_spec = pl.BlockSpec((1, 8, bq), lambda bh, qi, ki: (bh, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b * h, lq // bq, lk // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params("dq", bq, bk, d, q.dtype),
        interpret=interpret,
        name="flash_attention_dq",
    )(qf, kf, vf, dof, lse8, delta8)

    # the dkv kernel walks the query blocks of one key block, and may cut
    # the sequences otherwise; causal, its dead steps come first and name
    # the first live query block
    *_, bq, bk = _blocks(q, k, block_q, block_k, "dkv", causal)
    nq = lq // bq

    def live_q(ki, qi):
        if not causal:
            return qi
        first_live = jnp.clip((ki * bk - off) // bq, 0, nq - 1)
        return jnp.maximum(qi, first_live)

    q_spec = pl.BlockSpec((1, bq, d),
                          lambda bh, ki, qi: (bh, live_q(ki, qi), 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0))
    row_spec = pl.BlockSpec((1, 8, bq),
                            lambda bh, ki, qi: (bh, 0, live_q(ki, qi)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(b * h, lk // bk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, lk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, lk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_compiler_params("dkv", bq, bk, d, q.dtype),
        interpret=interpret,
        name="flash_attention_dkv",
    )(qf, kf, vf, dof, lse8, delta8)
    return (dq.reshape(b, h, lq, d), dk.reshape(b, h, lk, d),
            dv.reshape(b, h, lk, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None, block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = False):
    """Fused attention forward. Shapes q (B,H,Lq,D), k/v (B,H,Lk,D).

    D and the sequence blocks should be multiples of 128 for MXU tiling
    (dispatch in ops/attention.py enforces this).  A caller's blocks are
    obeyed; left ``None`` they come from ``_tiles``: the largest score
    tile, up to 1,024 a side, that divides the lengths and fits the VMEM
    budget, each backward kernel choosing for itself.  On a TPU v5e (chip
    runs, PR 30; one call, bf16, causal, D 128; forward / dq / dkv, ms):
    B 2, H 16, L 4,096: 1.51 / 1.78 / 2.16 at 1,024 x 1,024, against
    2.90 / 2.15 / 2.27 at 512 x 512, 6.18 / 3.71 / 3.99 at 256 x 256 and
    1.78 / 2.18 / 2.48 at 1,024 x 2,048 (a longer side only adds work
    above the diagonal); B 4, H 8, L 2,048: 0.48 / 0.57 / 0.71, against
    1.67 / 1.02 / 1.10 at 256 x 256.  The forward gains most from long
    key blocks: its row statistics and accumulator are touched once a
    tile.
    """
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)


def _fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q,
                      block_k, interpret)


flash_attention.defvjp(_fwd_rule, _bwd_rule)
