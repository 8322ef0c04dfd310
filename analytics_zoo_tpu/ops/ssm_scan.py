"""Pallas TPU kernels for the Mamba-2 chunked scan.

The same mathematics as ``nn/layers/ssm.py`` ``ssd_chunked`` (the paper's
section 6; that function stays the ``reference`` path and the oracle), with
the one thing XLA cannot do for it: a chunk's per-head decay tile
``exp(where(s <= t, cum_t - cum_s, -inf))`` of (Q, Q), its product with
``C B^T``, the cast and the product with ``delta x`` never leave VMEM.  In
XLA that tile is a (B, L / Q, H, Q, Q) float32 tensor that crosses HBM in
the forward pass, in its recomputation and several times in the backward.

Grid (batch, chunk, head block), chunk and head block in order: ``C B^T``
is formed once a group and chunk and kept in VMEM scratch; the (P, N)
float32 state of every head lives in VMEM scratch from chunk to chunk, so
the forward writes only ``y`` and, for the backward, the state each chunk
starts from.  Heads narrower than the 128 lanes are taken together (two
heads of 64): their ``delta x`` is one (Q, 128) operand, a head's product
with its own tile fills all 128 output lanes at the cost of 64, and a
lane select keeps the head's half, so no operand is ever shifted across
lanes.

Precision is ``ssd_chunked``'s: log-decays, their cumulative sums, the
mask BEFORE the exponential, the carried states and every accumulation in
float32; the products' operands in the compute dtype; ``y`` float32.

Autodiff: ``ssm_scan`` carries a ``jax.custom_vjp`` with a HAND-WRITTEN
backward kernel (the FlashAttention-2 recipe of ``ops/flash_attention.py``):
residuals are the inputs and the states the chunks started from; the tile is
recomputed in VMEM, transposed (``s`` down the sublanes), so that no (Q, Q)
tile is ever turned for a product; the chunks run in reverse with the states'
gradient in float32 scratch.  ``d seg = dM o M`` and its row and column
sums are float32, as autodiff has them.  The small (B, L, H) work stays in
XLA on both sides: ``dt * a``, its cumulative sum inside a chunk, and in the
backward the reverse cumulative sum and the sums for ``a``.

The kernels are named: on a device trace they read ``ssm_scan_fwd`` and
``ssm_scan_bwd`` (with XLA's ``.N`` suffix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NN = (((1,), (0,)), ((), ()))    # a @ b
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_TN = (((0,), (0,)), ((), ()))    # a.T @ b

_LANES = 128
# heads a grid step takes, unrolled.  On a TPU v5e (chip runs, PR 37; B 2,
# L 4,096, 64 heads of 64, state 128, chunk 256, bf16; forward / forward
# with the states / backward, ms, the XLA work around the calls included):
# 0.74 / 0.76 / 2.21 at 8, 0.67-0.71 / 0.68-0.72 / 2.03-2.09 at 16, 0.64 /
# 0.65 / 2.01 at 32, where Mosaic takes 23 s to compile the backward (7 s
# at 16, 3 s at 8): a step's fixed costs (the mask, the group's tiles, the
# narrow blocks of per-head scalars) are shared by more heads.  Reading only
# the live three quarters of a tile, in blocks of 128 rows, was slower (0.91
# / 0.92 / 2.70 at 8): more, smaller products.
_MAX_HEAD_BLOCK = 16
_VMEM_LIMIT = 48 * 2 ** 20        # the backward's blocks, scratch and tiles


def _dot(a, b, dims):
    """One MXU product: operands as they are, float32 accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _head_block(heads_a_group: int) -> int:
    """Heads of one group a grid step takes: the largest divisor of the
    group's heads up to ``_MAX_HEAD_BLOCK``."""
    return max(d for d in range(1, _MAX_HEAD_BLOCK + 1)
               if heads_a_group % d == 0)


def _heads_a_lane_group(hb: int, p: int) -> int:
    """Heads whose ``delta x`` is taken as one operand: as many as fill
    the 128 lanes, where the head block divides into such groups."""
    hp = _LANES // p if p < _LANES and _LANES % p == 0 else 1
    hp = min(hp, hb)
    return hp if hb % hp == 0 else 1


def shapes_ok(x_shape, n_groups: int, d_state: int, chunk: int,
              dtype) -> bool:
    """What Mosaic compiles (``tests/test_ssm_scan_kernel.py`` pins both
    sides): float32 or bfloat16, the length whole chunks, and every block
    whole tiles or its whole array: a chunk of a multiple of 8 positions
    (or the whole length), a head block's ``x`` whole lanes (or all the
    heads), a group's state a multiple of 128 (or one group)."""
    _, l, h, p = x_shape
    if h % n_groups or l % chunk or jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    hb = _head_block(h // n_groups)
    return ((chunk % 8 == 0 or chunk == l)
            and ((hb * p) % _LANES == 0 or hb == h)
            and (d_state % _LANES == 0 or n_groups == 1))


# ---------------------------------------------------------------------------
# a head's scalars on the lanes or sublanes of its lane group
# ---------------------------------------------------------------------------

def _spread(cols, j0: int, hp: int, p: int, lane):
    """(Q, hp * p): lanes ``k*p .. (k+1)*p`` hold column ``j0 + k`` of
    ``cols`` (Q, heads)."""
    out = cols[:, j0 + hp - 1:j0 + hp]
    for k in reversed(range(hp - 1)):
        out = jnp.where(lane < (k + 1) * p, cols[:, j0 + k:j0 + k + 1], out)
    return out


def _stack(row, j0: int, hp: int, p: int, sublane):
    """(hp * p, 1): sublanes ``k*p .. (k+1)*p`` hold entry ``j0 + k`` of
    ``row`` (1, heads).  Always through a select: Mosaic has no broadcast
    of one element over sublanes and lanes at once, which a bare slice
    times a (hp * p, N) state would fold into."""
    out = jnp.where(sublane >= 0, row[:, j0 + hp - 1:j0 + hp], 0.0)
    for k in reversed(range(hp - 1)):
        out = jnp.where(sublane < (k + 1) * p, row[:, j0 + k:j0 + k + 1],
                        out)
    return out


def _in_group(index, k: int, hp: int, p: int):
    """Whether a lane (or sublane) index lies in head ``k`` of its lane
    group; ``None`` where the group is one head."""
    if hp == 1:
        return None
    return jnp.logical_and(index >= k * p, index < (k + 1) * p)


def _keep(mask, value):
    return value if mask is None else jnp.where(mask, value, 0)


def _total(value):
    """The sum of a 2-D value as (1, 1)."""
    return jnp.sum(jnp.sum(value, axis=1, keepdims=True), axis=0,
                   keepdims=True)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, y_ref,
                *rest, hb: int, hp: int, p: int, blocks_a_group: int):
    """``rest`` is (sin_ref, state, cb) or, without the residual,
    (state, cb)."""
    sin_ref = rest[0] if len(rest) == 3 else None
    state_scr, cb_scr = rest[-2:]
    ci, hi = pl.program_id(1), pl.program_id(2)
    q, w, f32, dtype = x_ref.shape[1], hp * p, jnp.float32, x_ref.dtype
    b, c = b_ref[0], c_ref[0]

    @pl.when(ci == 0)
    def _first_chunk():
        state_scr[hi] = jnp.zeros(state_scr.shape[1:], f32)

    @pl.when(hi % blocks_a_group == 0)
    def _first_of_group():
        cb_scr[:] = _dot(c, b, _NT)                       # [t, s]

    dtc, cumc, cumr = dtc_ref[0, 0, 0], cumc_ref[0, 0, 0], cumr_ref[0, 0, 0]
    last = cumc[q - 1:q, :]                               # (1, hb)
    to_end, from_start = jnp.exp(last - cumc), jnp.exp(cumc)
    chunk_decay = jnp.exp(last)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    cb = cb_scr[:]
    for j0 in range(0, hb, hp):
        at = slice(j0 * p, j0 * p + w)
        dx = (x_ref[0, :, at].astype(f32)
              * _spread(dtc, j0, hp, p, lane)).astype(dtype)
        y = None
        for k in range(hp):
            j = j0 + k
            # masked BEFORE the exponential, as the reference has it
            seg = jnp.where(lower, cumc[:, j:j + 1] - cumr[j:j + 1, :],
                            -jnp.inf)
            m = (cb * jnp.exp(seg)).astype(dtype)
            mine = _dot(m, dx, _NN)                       # (Q, w), head k's
            y = mine if y is None else jnp.where(lane >= k * p, mine, y)
        s_prev = state_scr[hi, at, :]                     # (w, N) float32
        y_ref[0, :, at] = y + _spread(from_start, j0, hp, p, lane) * _dot(
            c, s_prev.astype(dtype), _NT)
        if sin_ref is not None:
            sin_ref[0, 0, at, :] = s_prev
        dxw = (dx.astype(f32)
               * _spread(to_end, j0, hp, p, lane)).astype(dtype)
        state_scr[hi, at, :] = (_stack(chunk_decay, j0, hp, p, sublane)
                                * s_prev + _dot(dxw, b, _TN))


def _layouts(x, dt, a, b, c, chunk: int):
    """The arrays as the kernels read them (x, delta and the summed
    log-decay by column, the latter by row, B, C), and the sizes."""
    bsz, l, h, p = x.shape
    g, n = b.shape[-2:]
    nc, hb = l // chunk, _head_block(h // g)
    nhb = h // hb

    def by_block(t):                     # (B, L, H) -> (B, nc, nhb, Q, hb)
        return jnp.moveaxis(t.reshape(bsz, nc, chunk, nhb, hb), 3, 2)

    # log of the decay, summed from a chunk's start
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, h), axis=2)
    cumc = by_block(cum)
    return (x.reshape(bsz, l, h * p), by_block(dt), cumc,
            jnp.swapaxes(cumc, 3, 4), b.reshape(bsz, l, g * n),
            c.reshape(bsz, l, g * n)), (bsz, l, h, p, g, n, nc, hb, nhb)


def _specs(sizes, chunk: int, chunk_of):
    """Block specs on a (batch, chunk, head block) grid; ``chunk_of`` maps
    the grid's chunk index to the chunk (the backward runs them in
    reverse)."""
    bsz, l, h, p, g, n, nc, hb, nhb = sizes
    per_group = nhb // g
    wide = pl.BlockSpec((1, chunk, hb * p),
                        lambda bi, ci, hi: (bi, chunk_of(ci), hi))
    cols = pl.BlockSpec((1, 1, 1, chunk, hb),
                        lambda bi, ci, hi: (bi, chunk_of(ci), hi, 0, 0))
    rows = pl.BlockSpec((1, 1, 1, hb, chunk),
                        lambda bi, ci, hi: (bi, chunk_of(ci), hi, 0, 0))
    group = pl.BlockSpec((1, chunk, n), lambda bi, ci, hi: (
        bi, chunk_of(ci), hi // per_group))
    states = pl.BlockSpec((1, 1, hb * p, n),
                          lambda bi, ci, hi: (bi, chunk_of(ci), hi, 0))
    return wide, cols, rows, group, states


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _scan_fwd(x, dt, a, b, c, chunk: int, interpret: bool,
              with_states: bool = False):
    arrays, sizes = _layouts(x, dt, a, b, c, chunk)
    bsz, l, h, p, g, n, nc, hb, nhb = sizes
    hp = _heads_a_lane_group(hb, p)
    wide, cols, rows, group, states = _specs(sizes, chunk, lambda ci: ci)
    out_specs = [wide]
    out_shape = [jax.ShapeDtypeStruct((bsz, l, h * p), jnp.float32)]
    if with_states:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, h * p, n),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, hp=hp, p=p,
                          blocks_a_group=nhb // g),
        grid=(bsz, nc, nhb),
        in_specs=[wide, cols, cols, rows, group, group],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nhb, hb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(*arrays)
    y = res[0].reshape(bsz, l, h, p)
    return (y, res[1]) if with_states else y


# ---------------------------------------------------------------------------
# backward: the tile transposed, the chunks in reverse
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, dtc_ref, cumc_ref, cumr_ref, b_ref, c_ref, sin_ref,
                dy_ref, dx_ref, ddtc_ref, dcumc_ref, dcumr_ref, db_ref,
                dc_ref, ds_scr, cbt_scr, dcbt_scr, db_scr, dc_scr, *,
                hb: int, hp: int, p: int, blocks_a_group: int):
    ri, hi = pl.program_id(1), pl.program_id(2)
    q, w, f32, dtype = x_ref.shape[1], hp * p, jnp.float32, x_ref.dtype
    b, c = b_ref[0], c_ref[0]

    @pl.when(ri == 0)
    def _last_chunk():
        ds_scr[hi] = jnp.zeros(ds_scr.shape[1:], f32)

    @pl.when(hi % blocks_a_group == 0)
    def _first_of_group():
        cbt_scr[:] = _dot(b, c, _NT)                      # [s, t]
        dcbt_scr[:] = jnp.zeros(dcbt_scr.shape, f32)
        db_scr[:] = jnp.zeros(db_scr.shape, f32)
        dc_scr[:] = jnp.zeros(dc_scr.shape, f32)

    dtc, cumc, cumr = dtc_ref[0, 0, 0], cumc_ref[0, 0, 0], cumr_ref[0, 0, 0]
    last = cumc[q - 1:q, :]
    to_end, from_start = jnp.exp(last - cumc), jnp.exp(cumc)
    chunk_decay = jnp.exp(last)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (q, hb), 1)
    head_sublane = jax.lax.broadcasted_iota(jnp.int32, (hb, q), 0)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    upper = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    cbt = cbt_scr[:]
    dcbt = jnp.zeros((q, q), f32)
    db, dc = jnp.zeros(db_scr.shape, f32), jnp.zeros(dc_scr.shape, f32)
    ddt_cols = jnp.zeros((q, hb), f32)
    dcum_cols = jnp.zeros((q, hb), f32)
    dcum_rows = jnp.zeros((hb, q), f32)
    for j0 in range(0, hb, hp):
        at = slice(j0 * p, j0 * p + w)
        xf = x_ref[0, :, at].astype(f32)
        dtw = _spread(dtc, j0, hp, p, lane)
        dx = (xf * dtw).astype(dtype)
        dxf = dx.astype(f32)
        dy = dy_ref[0, :, at]
        dyb = dy.astype(dtype)
        tew = _spread(to_end, j0, hp, p, lane)
        fsw = _spread(from_start, j0, hp, p, lane)
        decay = _stack(chunk_decay, j0, hp, p, sublane)
        s_prev = sin_ref[0, 0, at, :]
        sb = s_prev.astype(dtype)
        dso = ds_scr[hi, at, :]          # gradient of the state sent on
        dsob = dso.astype(dtype)
        # the chunk's own state: B^T (delta x o decay_to_end)
        d_dxw = _dot(b, dsob, _NT)                        # (Q, w)
        db = db + _dot((dxf * tew).astype(dtype), dsob, _NN)
        d_dx = d_dxw * tew
        e_end = d_dxw * dxf * tew        # summed a head: d to_end * to_end
        # the carried term: decay_from_start o (C S_in^T)
        e_start = dy * _dot(c, sb, _NT) * fsw
        dzb = (dy * fsw).astype(dtype)
        dc = dc + _dot(dzb, sb, _NN)
        ds_scr[hi, at, :] = decay * dso + _dot(dzb, c, _TN)
        e_state = dso * s_prev           # summed a head: d chunk_decay
        for k in range(hp):
            j = j0 + k
            mine = _in_group(lane, k, hp, p)
            # the tile transposed: [s, t], masked BEFORE the exponential
            seg = jnp.where(upper, cumr[j:j + 1, :] - cumc[:, j:j + 1],
                            -jnp.inf)
            decays = jnp.exp(seg)
            mt = cbt * decays
            dmt = _dot(_keep(mine, dx), dyb, _NT)         # [s, t]
            dcbt = dcbt + dmt * decays
            dseg = dmt * mt              # float32, as autodiff has it
            d_dx = d_dx + _keep(mine, _dot(mt.astype(dtype), dyb, _NN))
            end = jnp.sum(_keep(mine, e_end), axis=1, keepdims=True)
            at_last = jnp.sum(end, axis=0, keepdims=True) + (
                chunk_decay[:, j:j + 1] * _total(_keep(
                    _in_group(sublane, k, hp, p), e_state)))
            col = (jnp.sum(_keep(mine, e_start), axis=1, keepdims=True)
                   - end - jnp.sum(dseg, axis=1, keepdims=True)
                   + jnp.where(at_end, at_last, 0.0))
            dcum_cols = jnp.where(head_lane == j, col, dcum_cols)
            dcum_rows = jnp.where(head_sublane == j,
                                  jnp.sum(dseg, axis=0, keepdims=True),
                                  dcum_rows)
        dx_ref[0, :, at] = (d_dx * dtw).astype(dx_ref.dtype)
        e_dt = d_dx * xf
        for k in range(hp):
            ddt_cols = jnp.where(
                head_lane == j0 + k,
                jnp.sum(_keep(_in_group(lane, k, hp, p), e_dt), axis=1,
                        keepdims=True), ddt_cols)
    ddtc_ref[0, 0, 0] = ddt_cols
    dcumc_ref[0, 0, 0] = dcum_cols
    dcumr_ref[0, 0, 0] = dcum_rows
    dcbt_scr[:] = dcbt_scr[:] + dcbt
    db_scr[:] = db_scr[:] + db
    dc_scr[:] = dc_scr[:] + dc

    @pl.when(hi % blocks_a_group == blocks_a_group - 1)
    def _last_of_group():
        tile = dcbt_scr[:].astype(dtype)                  # d(C B^T)[t, s]
        db_ref[0] = (db_scr[:] + _dot(tile, c, _NN)).astype(db_ref.dtype)
        dc_ref[0] = (dc_scr[:] + _dot(tile, b, _TN)).astype(dc_ref.dtype)


def _scan_bwd(x, dt, a, b, c, s_in, dy, chunk: int, interpret: bool):
    arrays, sizes = _layouts(x, dt, a, b, c, chunk)
    bsz, l, h, p, g, n, nc, hb, nhb = sizes
    hp = _heads_a_lane_group(hb, p)
    f32 = jnp.float32
    wide, cols, rows, group, states = _specs(sizes, chunk,
                                             lambda ri: nc - 1 - ri)
    by_block = (bsz, nc, nhb, chunk, hb)
    dx, ddtc, dcumc, dcumr, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, hp=hp, p=p,
                          blocks_a_group=nhb // g),
        grid=(bsz, nc, nhb),
        in_specs=[wide, cols, cols, rows, group, group, states, wide],
        out_specs=[wide, cols, cols, rows, group, group],
        out_shape=[jax.ShapeDtypeStruct((bsz, l, h * p), x.dtype),
                   jax.ShapeDtypeStruct(by_block, f32),
                   jax.ShapeDtypeStruct(by_block, f32),
                   jax.ShapeDtypeStruct((bsz, nc, nhb, hb, chunk), f32),
                   jax.ShapeDtypeStruct((bsz, l, g * n), b.dtype),
                   jax.ShapeDtypeStruct((bsz, l, g * n), c.dtype)],
        scratch_shapes=[pltpu.VMEM((nhb, hb * p, n), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, n), f32),
                        pltpu.VMEM((chunk, n), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(*arrays, s_in, dy.reshape(bsz, l, h * p))

    def by_head(t):                      # (B, nc, nhb, Q, hb) -> (B, L, H)
        return jnp.moveaxis(t, 2, 3).reshape(bsz, l, h)

    # cum is a running sum inside a chunk: its gradient runs back
    dcum = (dcumc + jnp.swapaxes(dcumr, 3, 4))
    dcum = jnp.moveaxis(dcum, 2, 3).reshape(bsz, nc, chunk, h)
    dda = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2)
    dda = dda.reshape(bsz, l, h)
    return (dx.reshape(x.shape), by_head(ddtc) + dda * a,
            jnp.sum(dda * dt, axis=(0, 1)), db.reshape(b.shape),
            dc.reshape(c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssm_scan(x, dt, a, b, c, chunk: int, interpret: bool = False):
    """``ssd_chunked(x, dt, a, b, c, chunk)`` as kernels: x (B, L, H, P)
    and b, c (B, L, G, N) in the compute dtype, dt (B, L, H) float32 after
    the softplus, a (H,) float32, L whole chunks.  Returns (B, L, H, P)
    float32.  ``shapes_ok`` says which shapes Mosaic takes; under
    ``interpret`` any shape runs."""
    return _scan_fwd(x, dt, a, b, c, chunk, interpret)


def _fwd_rule(x, dt, a, b, c, chunk, interpret):
    y, s_in = _scan_fwd(x, dt, a, b, c, chunk, interpret, with_states=True)
    return y, (x, dt, a, b, c, s_in)


def _bwd_rule(chunk, interpret, res, dy):
    x, dt, a, b, c, s_in = res
    return _scan_bwd(x, dt, a, b, c, s_in, dy, chunk, interpret)


ssm_scan.defvjp(_fwd_rule, _bwd_rule)
