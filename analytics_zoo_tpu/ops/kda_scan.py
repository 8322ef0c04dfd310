"""Pallas TPU kernels for the chunked KDA scan (Kimi Delta Attention).

The same mathematics as ``nn/layers/kda.py`` ``kda_chunked`` (that function
stays the ``reference`` path and the oracle), with what XLA cannot do for
it: a chunk's pairs, the solve of its unit lower-triangular system and the
carried (K, V) state of a head never leave VMEM.  In XLA the state is a
loop of L / C dependent steps over small products, and every chunk's M, P,
``[w | u0]``, ``q_state`` and ``k_end`` cross HBM.

Grid (batch, head block, chunk), the chunks in order: q, k, v and the
log-decays are read straight from the (B, L, heads x 128) layout (a head is
128 whole lanes), beta as a narrow (C, heads of the block) block; each
head's state, kept transposed as (V, K) so that the chunk's decay scales its
lanes, lives in VMEM scratch from chunk to chunk.  A grid step, for each
head of its block:

- the log-decays summed from the chunk's start (a product with a lower
  triangle of ones, float32 at full precision);
- M (strictly lower) and P (lower): between sub-chunks one product of
  compute-dtype operands whose factors are taken relative to the later
  sub-chunk's start (none exceeds 1); inside a sub-chunk every pair's
  exponent formed exactly, masked BEFORE the exponential, a column at a
  time on the vector unit;
- ``(I + diag(beta) M)^-1`` by forward substitution in float32 on the
  vector unit, 16 rows at a time, each block of rows first reduced by the
  rows already finished in one product at full precision; then ``w`` and
  ``u0`` as one product each with ``diag(beta) [K o A | V]``, float32 at
  full precision;
- the outputs ``P u0 + q_state S`` (compute-dtype operands, float32
  accumulation), and the state carried on, ``Diag(A_C) S + k_end^T (u0 -
  w S)``, float32 at full precision.

The forward writes only ``o`` or, for the backward, also the state each
chunk started from, (B, L / C, heads x V, K) float32: the backward's one
residual beside the inputs, named ``RESIDUAL`` for a ``jax.checkpoint``
policy around the caller (``KDAMixer`` names the scan's output the same: a
policy that keeps both runs no forward kernel again in the backward pass).

Autodiff: ``kda_scan`` carries a ``jax.custom_vjp`` with a HAND-WRITTEN
backward kernel.  The chunks run in reverse with the state's gradient in
float32 scratch; a chunk's M, P and inverse are formed again from the inputs
and the saved state; the cotangents of the pairs inside a sub-chunk follow
``kda._decay_pairs_bwd`` (the exponentials formed again); the reverse sum of
the log-decays' gradient within the chunk is a product with an upper
triangle of ones.  It writes dq, dk, dv, dg and d beta.

Precision is ``kda_chunked``'s: log-decays, their sums, the pairs inside a
sub-chunk, beta, the solve, ``w``, ``u0`` and the carried state float32 (the
state's and the solve's products at full precision); the products between
sub-chunks and the outputs' take compute-dtype operands and accumulate in
float32; ``o`` is float32.  Their cotangents stay float32 where autodiff of
the reference rounds them to the compute dtype.

The kernels are named: on a device trace they read ``kda_scan_fwd`` and
``kda_scan_bwd`` (with XLA's ``.N`` suffix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NN = (((1,), (0,)), ((), ()))    # a @ b
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_TN = (((0,), (0,)), ((), ()))    # a.T @ b

_LANES = 128
# heads a grid step takes, unrolled.  On a TPU v5e (chip runs, PR 39; B 2,
# L 8,192, 32 heads of 128, chunk 64, bf16; forward with the states /
# forward and backward, ms, the XLA work around the calls included): 20.04
# / 47.30 at 2, 19.34 / 46.70 at 4, where Mosaic takes twice as long to
# compile the backward (7 s against 3.5); 1 was slower by 5 % (before the
# solve's blocks)
_MAX_HEAD_BLOCK = 2
# rows of the chunk's solve substituted together, the rest by one product
# a block: 23.07 / 54.03 at 8, 20.04 / 47.30 at 16, 21.80 / 50.96 at 64
# (the whole chunk by substitution); the inverse as ten products of its
# nilpotent part at full precision took 26.68 / 60.17
_SOLVE_ROWS = 16
# the ``checkpoint_name`` of the chunk-start states
RESIDUAL = "kda_scan"


def _dot(a, b, dims):
    """One MXU product with float32 accumulation; float32 operands at full
    precision."""
    exact = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return lax.dot_general(a, b, dims,
                           precision=lax.Precision.HIGHEST if exact else None,
                           preferred_element_type=jnp.float32)


def _head_block(heads: int) -> int:
    """Heads a grid step takes: the largest divisor of the heads up to
    ``_MAX_HEAD_BLOCK``."""
    return max(d for d in range(1, _MAX_HEAD_BLOCK + 1) if heads % d == 0)


def shapes_ok(q_shape, v_dim: int, chunk: int, sub: int, dtype) -> bool:
    """Where the kernels run (``tests/test_kda_scan_kernel.py`` pins both
    sides against the TPU compiler): float32 or bfloat16 (Mosaic refuses
    float16 and integers), the length whole chunks, a chunk whole
    sub-chunks of a multiple of 8 positions (Mosaic refuses blocks of
    part of a sublane tile), a head's keys and values whole lanes (what the
    kernels' slices are written for)."""
    _, l, _, dk = q_shape
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.bfloat16))
            and l % chunk == 0 and chunk % sub == 0 and sub % 8 == 0
            and dk % _LANES == 0 and v_dim % _LANES == 0)


# ---------------------------------------------------------------------------
# a chunk of one head, as both kernels form it
# ---------------------------------------------------------------------------

def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _ones_triangle(c: int, lower: bool):
    """(c, c) float32: 1 where the column is at most (``lower``) or at
    least the row."""
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    return jnp.where(col <= row if lower else col >= row, 1.0, 0.0)


def _within_chunk(qf, kf, gam, sub: int, dtype):
    """M (strictly lower) and P (lower), (C, C) float32: ``kda._within_chunk``
    a sub-chunk of rows at a time.  Between sub-chunks one product of
    ``dtype`` operands, decayed from and to the later sub-chunk's start;
    inside a sub-chunk a column at a time, the exponent of every pair exact
    and masked before the exponential."""
    c = kf.shape[0]
    row = _iota((c, 1), 0)
    sub_row, lane = _iota((sub, 1), 0), _iota((sub, c), 1)
    bands_m, bands_p = [], []
    for lo in range(0, c, sub):
        gi, ki, qi = gam[lo:lo + sub], kf[lo:lo + sub], qf[lo:lo + sub]
        if lo:
            start = gam[lo - 1:lo]
            from_start = jnp.exp(gi - start)
            before = (kf * jnp.exp(jnp.where(row < lo, start - gam, -jnp.inf))
                      ).astype(dtype)
            bm = _dot((ki * from_start).astype(dtype), before, _NT)
            bp = _dot((qi * from_start).astype(dtype), before, _NT)
        else:
            bm = bp = jnp.zeros((sub, c), jnp.float32)
        for s in range(sub):
            e = jnp.exp(jnp.where(sub_row >= s, gi - gi[s:s + 1], -jnp.inf))
            ek = e * ki[s:s + 1]
            at = lane == lo + s
            bm = jnp.where(jnp.logical_and(at, sub_row > s),
                           jnp.sum(ki * ek, axis=1, keepdims=True), bm)
            bp = jnp.where(at, jnp.sum(qi * ek, axis=1, keepdims=True), bp)
        bands_m.append(bm)
        bands_p.append(bp)
    return (jnp.concatenate(bands_m, axis=0),
            jnp.concatenate(bands_p, axis=0))


def _unit_lower_inverse(n):
    """``(I + n)^-1`` of a strictly lower (C, C) float32 ``n``, float32
    throughout, ``_SOLVE_ROWS`` rows at a time: a block's rows first lose
    what the rows already finished give them (one product at full
    precision), then forward substitution inside the block on the vector
    unit (column s takes ``n[r, s]`` times row s, final by then, off every
    row r below it, a tile of 8 rows at a time)."""
    c = n.shape[0]
    block = min(_SOLVE_ROWS, c)
    eye = jnp.where(_iota((c, c), 0) == _iota((c, c), 1), 1.0, 0.0)
    done = []
    for lo in range(0, c, block):
        x = eye[lo:lo + block]
        if lo:
            # the rows not yet finished are 0, so n's columns past them
            # take nothing
            x = x - _dot(n[lo:lo + block], jnp.concatenate(
                done + [jnp.zeros((c - lo, c), jnp.float32)], axis=0), _NN)
        tiles = [x[t:t + 8] for t in range(0, block, 8)]
        n_tiles = [n[lo + t:lo + t + 8] for t in range(0, block, 8)]
        for s in range(block - 1):
            pivot = tiles[s // 8][s % 8:s % 8 + 1]
            for t in range(s // 8, len(tiles)):
                tiles[t] = tiles[t] - n_tiles[t][:, lo + s:lo + s + 1] * pivot
        done.append(jnp.concatenate(tiles, axis=0))
    return jnp.concatenate(done, axis=0)


def _chunk(q, k, v, g, beta, sub: int):
    """Everything of one head's chunk that does not depend on the state:
    q, k (C, K) and v (C, V) in the compute dtype, g (C, K) float32 log-
    decays, beta (C, 1) float32."""
    f32, dtype = jnp.float32, q.dtype
    c = q.shape[0]
    gam = _dot(_ones_triangle(c, True), g, _NN)       # summed from the start
    qf, kf = q.astype(f32), k.astype(f32)
    m, p = _within_chunk(qf, kf, gam, sub, dtype)
    a = jnp.exp(gam)
    tinv = _unit_lower_inverse(beta * m)
    ka, vf = kf * a, v.astype(f32)
    w = _dot(tinv, beta * ka, _NN)
    u0 = _dot(tinv, beta * vf, _NN)
    q_state = qf * a - _dot(p.astype(dtype), w.astype(dtype), _NN)
    last = gam[c - 1:c]
    return dict(gam=gam, qf=qf, kf=kf, vf=vf, m=m, p=p, a=a, ka=ka, tinv=tinv,
                w=w, u0=u0, q_state=q_state, a_last=jnp.exp(last),
                to_end=jnp.exp(last - gam))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, hb: int,
                dk: int, dv: int, sub: int):
    """``rest`` is (sin_ref, state) or, without the residual, (state,)."""
    sin_ref = rest[0] if len(rest) == 2 else None
    state_scr = rest[-1]                 # (hb, V, K): each head's S^T
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state_scr[...] = jnp.zeros(state_scr.shape, jnp.float32)

    betas = b_ref[0, 0, 0]                                # (C, hb)
    for j in range(hb):
        at_k, at_v = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        f = _chunk(q_ref[0, :, at_k], k_ref[0, :, at_k], v_ref[0, :, at_v],
                   g_ref[0, :, at_k], betas[:, j:j + 1], sub)
        s = state_scr[j]
        if sin_ref is not None:
            sin_ref[0, 0, at_v, :] = s
        o_ref[0, :, at_v] = (
            _dot(f["p"].astype(dtype), f["u0"].astype(dtype), _NN)
            + _dot(f["q_state"].astype(dtype), s.astype(dtype), _NT))
        u = f["u0"] - _dot(f["w"], s, _NT)
        state_scr[j] = s * f["a_last"] + _dot(u, f["kf"] * f["to_end"], _TN)


def _layouts(q, k, v, g, beta, chunk: int):
    """The arrays as the kernels read them (heads on the lanes; beta by
    chunk and head block, a head a column), and the sizes."""
    bsz, l, h, dk = q.shape
    dv = v.shape[-1]
    nc, hb = l // chunk, _head_block(h)
    nhb = h // hb
    betas = jnp.moveaxis(beta.reshape(bsz, nc, chunk, nhb, hb), 3, 2)
    return ((q.reshape(bsz, l, h * dk), k.reshape(bsz, l, h * dk),
             v.reshape(bsz, l, h * dv), g.reshape(bsz, l, h * dk), betas),
            (bsz, l, h, dk, dv, nc, hb, nhb))


def _specs(sizes, chunk: int, chunk_of):
    """Block specs on a (batch, head block, chunk) grid; ``chunk_of`` maps
    the grid's chunk index to the chunk (the backward runs them in
    reverse)."""
    bsz, l, h, dk, dv, nc, hb, nhb = sizes
    keys = pl.BlockSpec((1, chunk, hb * dk),
                        lambda bi, hi, ci: (bi, chunk_of(ci), hi))
    values = pl.BlockSpec((1, chunk, hb * dv),
                          lambda bi, hi, ci: (bi, chunk_of(ci), hi))
    cols = pl.BlockSpec((1, 1, 1, chunk, hb),
                        lambda bi, hi, ci: (bi, chunk_of(ci), hi, 0, 0))
    states = pl.BlockSpec((1, 1, hb * dv, dk),
                          lambda bi, hi, ci: (bi, chunk_of(ci), hi, 0))
    return keys, values, cols, states


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _scan_fwd(q, k, v, g, beta, chunk: int, sub: int, interpret: bool,
              with_states: bool = False):
    arrays, sizes = _layouts(q, k, v, g, beta, chunk)
    bsz, l, h, dk, dv, nc, hb, nhb = sizes
    keys, values, cols, states = _specs(sizes, chunk, lambda ci: ci)
    out_specs = [values]
    out_shape = [jax.ShapeDtypeStruct((bsz, l, h * dv), jnp.float32)]
    if with_states:
        out_specs.append(states)
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, h * dv, dk),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, dk=dk, dv=dv, sub=sub),
        grid=(bsz, nhb, nc),
        in_specs=[keys, keys, values, keys, cols],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="kda_scan_fwd",
    )(*arrays)
    o = res[0].reshape(bsz, l, h, dv)
    return (o, res[1]) if with_states else o


# ---------------------------------------------------------------------------
# backward: the chunks in reverse
# ---------------------------------------------------------------------------

def _within_chunk_bwd(f, dm, dp, sub: int, dtype):
    """The cotangents of q, k and the summed log-decays (C, K) float32 from
    those of M (strictly lower) and P (lower), ``_within_chunk`` read
    backwards: between sub-chunks the two factors' products and exponents;
    inside a sub-chunk ``kda._decay_pairs_bwd``'s sums a column at a time,
    the exponentials formed again."""
    qf, kf, gam = f["qf"], f["kf"], f["gam"]
    c = kf.shape[0]
    f32 = jnp.float32
    row = _iota((c, 1), 0)
    sub_row, lane = _iota((sub, 1), 0), _iota((sub, c), 1)
    dq_rows, dk_rows, dgam_rows = [], [], []
    dk_all = jnp.zeros(kf.shape, f32)
    dgam_all = jnp.zeros(kf.shape, f32)
    for lo in range(0, c, sub):
        gi, ki, qi = gam[lo:lo + sub], kf[lo:lo + sub], qf[lo:lo + sub]
        dmi, dpi = dm[lo:lo + sub], dp[lo:lo + sub]
        dqi = jnp.zeros(ki.shape, f32)
        dki = jnp.zeros(ki.shape, f32)
        dgi = jnp.zeros(ki.shape, f32)
        if lo:
            start = gam[lo - 1:lo]
            from_start = jnp.exp(gi - start)
            to_start = jnp.exp(jnp.where(row < lo, start - gam, -jnp.inf))
            rk, rq, before = ki * from_start, qi * from_start, kf * to_start
            dmb, dpb = dmi.astype(dtype), dpi.astype(dtype)
            beforeb = before.astype(dtype)
            drk = _dot(dmb, beforeb, _NN)
            drq = _dot(dpb, beforeb, _NN)
            dbefore = (_dot(dmb, rk.astype(dtype), _TN)
                       + _dot(dpb, rq.astype(dtype), _TN))
            dqi = drq * from_start
            dki = drk * from_start
            e_rows = drk * rk + drq * rq        # d (gam_t - start), row t
            e_before = dbefore * before         # d (start - gam_s), row s
            dgi = e_rows
            dk_all = dk_all + dbefore * to_start
            d_start = (jnp.sum(e_before, axis=0, keepdims=True)
                       - jnp.sum(e_rows, axis=0, keepdims=True))
            dgam_all = dgam_all - e_before + jnp.where(row == lo - 1,
                                                       d_start, 0.0)
        da_m = jnp.zeros(ki.shape, f32)
        da_p = jnp.zeros(ki.shape, f32)
        db = jnp.zeros(ki.shape, f32)
        for s in range(sub):
            e = jnp.exp(jnp.where(sub_row >= s, gi - gi[s:s + 1], -jnp.inf))
            at = lane == lo + s
            dmc = jnp.sum(jnp.where(at, dmi, 0.0), axis=1, keepdims=True)
            dpc = jnp.sum(jnp.where(at, dpi, 0.0), axis=1, keepdims=True)
            ek = e * ki[s:s + 1]
            da_m = da_m + dmc * ek
            da_p = da_p + dpc * ek
            db = jnp.where(sub_row == s, jnp.sum((dmc * ki + dpc * qi) * e,
                                                 axis=0, keepdims=True), db)
        dq_rows.append(dqi + da_p)
        dk_rows.append(dki + da_m + db)
        # gam_t enters with a plus, gam_s with a minus
        dgam_rows.append(dgi + ki * da_m + qi * da_p - ki * db)
    return (jnp.concatenate(dq_rows, axis=0),
            jnp.concatenate(dk_rows, axis=0) + dk_all,
            jnp.concatenate(dgam_rows, axis=0) + dgam_all)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sin_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, hb: int, dk: int,
                dv: int, sub: int):
    f32, dtype = jnp.float32, q_ref.dtype
    c = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = jnp.zeros(ds_scr.shape, f32)

    row, col = _iota((c, c), 0), _iota((c, c), 1)
    strict, lower = col < row, col <= row
    head_lane = _iota((c, hb), 1)
    betas = b_ref[0, 0, 0]
    dbetas = jnp.zeros((c, hb), f32)
    for j in range(hb):
        at_k, at_v = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        beta = betas[:, j:j + 1]
        f = _chunk(q_ref[0, :, at_k], k_ref[0, :, at_k], v_ref[0, :, at_v],
                   g_ref[0, :, at_k], beta, sub)
        qf, kf, a, w, u0 = f["qf"], f["kf"], f["a"], f["w"], f["u0"]
        k_end = kf * f["to_end"]
        s = sin_ref[0, 0, at_v, :]       # S^T the chunk started from
        ds1 = ds_scr[j]                  # gradient of the S^T sent on
        do = do_ref[0, :, at_v]
        dob = do.astype(dtype)
        pb, wb = f["p"].astype(dtype), w.astype(dtype)
        u = u0 - _dot(w, s, _NT)
        # the state sent on: Diag(A_C) S + k_end^T u
        du = _dot(k_end, ds1, _NT)
        dk_end = _dot(u, ds1, _NN)
        da_last = jnp.sum(ds1 * s, axis=0, keepdims=True)
        # the outputs: P u0 + q_state S
        dq_state = _dot(dob, s.astype(dtype), _NN)
        dq_stateb = dq_state.astype(dtype)
        dp = jnp.where(lower, _dot(dob, f["u0"].astype(dtype), _NT)
                       - _dot(dq_stateb, wb, _NT), 0.0)
        du0 = _dot(pb, dob, _TN) + du
        dw = -_dot(pb, dq_stateb, _TN) - _dot(du, s, _NN)
        ds_scr[j] = (ds1 * f["a_last"] - _dot(du, w, _TN)
                     + _dot(dob, f["q_state"].astype(dtype), _TN))
        # the solve: (I + diag(beta) M) [w | u0] = diag(beta) [K o A | V]
        drw = _dot(f["tinv"], dw, _TN)
        dru = _dot(f["tinv"], du0, _TN)
        dt_ = jnp.where(strict, -(_dot(drw, w, _NT) + _dot(dru, u0, _NT)),
                        0.0)
        dbeta = (jnp.sum(dt_ * f["m"], axis=1, keepdims=True)
                 + jnp.sum(drw * f["ka"], axis=1, keepdims=True)
                 + jnp.sum(dru * f["vf"], axis=1, keepdims=True))
        dbetas = jnp.where(head_lane == j, dbeta, dbetas)
        dq_pairs, dk_pairs, dgam = _within_chunk_bwd(f, beta * dt_, dp, sub,
                                                     dtype)
        e_end = dk_end * k_end           # d (gam_C - gam_t)
        dgam = (dgam + (dq_state * qf + beta * drw * kf) * a - e_end
                + jnp.where(row[:, :1] == c - 1,
                            jnp.sum(e_end, axis=0, keepdims=True)
                            + da_last * f["a_last"], 0.0))
        dq_ref[0, :, at_k] = (dq_pairs + dq_state * a).astype(dq_ref.dtype)
        dk_ref[0, :, at_k] = (dk_pairs + beta * drw * a + dk_end * f["to_end"]
                              ).astype(dk_ref.dtype)
        dv_ref[0, :, at_v] = (beta * dru).astype(dv_ref.dtype)
        # gam is a running sum inside the chunk: its gradient runs back
        dg_ref[0, :, at_k] = _dot(_ones_triangle(c, False), dgam, _NN)
    db_ref[0, 0, 0] = dbetas


def _scan_bwd(q, k, v, g, beta, s_in, do, chunk: int, sub: int,
              interpret: bool):
    arrays, sizes = _layouts(q, k, v, g, beta, chunk)
    bsz, l, h, dk, dv, nc, hb, nhb = sizes
    f32 = jnp.float32
    keys, values, cols, states = _specs(sizes, chunk,
                                        lambda ri: nc - 1 - ri)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, dk=dk, dv=dv, sub=sub),
        grid=(bsz, nhb, nc),
        in_specs=[keys, keys, values, keys, cols, states, values],
        out_specs=[keys, keys, values, keys, cols],
        out_shape=[jax.ShapeDtypeStruct((bsz, l, h * dk), q.dtype),
                   jax.ShapeDtypeStruct((bsz, l, h * dk), k.dtype),
                   jax.ShapeDtypeStruct((bsz, l, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, l, h * dk), f32),
                   jax.ShapeDtypeStruct((bsz, nc, nhb, chunk, hb), f32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="kda_scan_bwd",
    )(*arrays, s_in, do.reshape(bsz, l, h * dv))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape),
            jnp.moveaxis(dbeta, 2, 3).reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def kda_scan(q, k, v, g, beta, chunk: int, sub: int = 16,
             interpret: bool = False):
    """``kda_chunked(q, k, v, g, beta, chunk, sub)`` as kernels: q, k (B,
    L, H, K) and v (B, L, H, V) in the compute dtype, g (B, L, H, K)
    float32 log-decays (at most 0), beta (B, L, H) float32, L whole chunks.
    Returns (B, L, H, V) float32.  ``shapes_ok`` says which shapes Mosaic
    takes; under ``interpret`` any shape runs."""
    return _scan_fwd(q, k, v, g, beta, chunk, sub, interpret)


def _fwd_rule(q, k, v, g, beta, chunk, sub, interpret):
    o, s_in = _scan_fwd(q, k, v, g, beta, chunk, sub, interpret,
                        with_states=True)
    return o, (q, k, v, g, beta, checkpoint_name(s_in, RESIDUAL))


def _bwd_rule(chunk, sub, interpret, res, do):
    q, k, v, g, beta, s_in = res
    return _scan_bwd(q, k, v, g, beta, s_in, do, chunk, sub, interpret)


kda_scan.defvjp(_fwd_rule, _bwd_rule)
