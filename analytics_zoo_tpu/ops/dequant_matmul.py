"""Pallas TPU fused dequantize-matmul kernel (int8 / packed-int4 weights).

The serving tier's quantized forward (reference: OpenVINO int8 calibration,
InferenceModel.scala:443) stores replica weights compressed; the XLA path
(``dequantize_pytree`` → matmul) decodes each weight back to a full f32
array in HBM before the MXU sees it, so the HBM win evaporates exactly
where bandwidth matters.  This kernel keeps the decode inside the matmul:
quantized weight tiles travel HBM→VMEM at 1 byte (int8) or a nibble
(packed int4) per element, are widened to f32 in-registers after the VMEM
load — extending ``ops/quantization.py``'s per-output-channel scales and
the in-kernel shard decode idea from the data tier — and the MXU consumes
the decoded tile directly.  Weight HBM traffic is 1/4 (int8) or 1/8
(int4) of the f32 leg; the per-channel rescale folds into the K-loop
finalize.

int4 packing is two's-complement nibbles along the K axis: packed byte
``(q[2k+1] << 4) | (q[2k] & 0xF)``, odd K padded with a zero nibble
(``rows`` carries the true K).  Autodiff: ``jax.custom_vjp`` — serving
never differentiates this, but the parity suites do; the backward is the
pure-JAX ``dx = g @ dequant(w).T`` (materialising f32 weights is fine off
the hot path), with ``float0``/zero cotangents for ``q``/``scale``.

Off-TPU, and for the K the kernel's blocking cannot tile (``_BLOCK_K``),
``ops.dispatch.select_path`` takes ``dequant_matmul_reference``; off-TPU
the kernel runs only under the ``interpret=True`` the tests pass.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import dispatch

BITS = (8, 4)
# K is blocked in tiles of at most this many rows (halved until they divide
# K).  Mosaic takes a block whose lane dim is a multiple of 128 or the whole
# dim, so K must either fit one block or be a multiple of 128: K=1000 picks
# an 8-wide block and is refused ("last two dimensions of your block shape
# are divisible by 8 and 128").  Pinned by tests/test_dequant_matmul.py.
_BLOCK_K = 512


def pack_int4(q4):
    """(K, N) int8 values in [-8, 7] → (ceil(K/2), N) packed bytes."""
    k = q4.shape[0]
    if k % 2:
        q4 = jnp.pad(q4, ((0, 1), (0, 0)))
    q32 = q4.astype(jnp.int32)
    # (hi << 4) | (lo & 0xF) stays in [-128, 127]: exact int8 round-trip
    packed = (q32[1::2] << 4) | (q32[0::2] & 0xF)
    return packed.astype(jnp.int8)


def unpack_int4(packed, rows: int):
    """Inverse of ``pack_int4``: (Kp, N) bytes → (rows, N) int8 nibbles."""
    b32 = packed.astype(jnp.int32)
    lo = (b32 << 28) >> 28                       # sign-extend low nibble
    hi = b32 >> 4                                # arithmetic: sign-extends
    full = jnp.stack([lo, hi], axis=1).reshape(2 * packed.shape[0],
                                               packed.shape[1])
    return full[:rows].astype(jnp.int8)


def quantize_weights(w, bits: int = 8):
    """Symmetric per-output-channel quantization of a (K, N) weight.

    Returns ``(q, scale)``: ``q`` int8 — (K, N) values for ``bits=8``
    (same scheme as ``quantize_tensor(w, axis=-1)``), nibble-packed
    (ceil(K/2), N) for ``bits=4`` — and ``scale`` f32 (1, N).
    """
    if bits not in BITS:
        raise ValueError(f"bits must be one of {BITS}, got {bits}")
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"weights must be (in, out), got {w.shape}")
    qmax = 127.0 if bits == 8 else 7.0
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / qmax).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8)
    return (pack_int4(q) if bits == 4 else q), scale


def _dequant(q, scale, bits: int, rows: Optional[int]):
    """f32 weight matrix back from its quantized storage (oracle path)."""
    if bits == 4:
        q = unpack_int4(q, rows if rows is not None else 2 * q.shape[0])
    return q.astype(jnp.float32) * scale


def dequant_matmul_reference(x, q, scale, bits: int = 8,
                             rows: Optional[int] = None):
    """Pure-JAX oracle: ``x @ (unpack(q) * scale)`` — XLA materialises
    the dequantized f32 weight; the fused kernel never does."""
    w = _dequant(q, jnp.reshape(scale, (1, -1)), bits, rows)
    out = jax.lax.dot_general(
        x.astype(jnp.float32), w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# kernel


def _dq_kernel(x_ref, w_ref, s_ref, o_ref, acc, *, bits: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    wq = w_ref[...]                              # int8 tile, VMEM
    if bits == 4:                                # in-register nibble decode
        b32 = wq.astype(jnp.int32)
        lo = (b32 << 28) >> 28
        hi = b32 >> 4
        wq = jnp.stack([lo, hi], axis=1).reshape(2 * wq.shape[0],
                                                 wq.shape[1])
    w = wq.astype(jnp.float32)                   # the MXU sees f32 tiles
    acc[:] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[:] = (acc[:] * s_ref[0][None, :]).astype(o_ref.dtype)


def _pick_block(block: int, length: int) -> int:
    b = min(block, length)
    while length % b:
        b //= 2
    return b


def _pad_to(a, dim: int, size: int, value=0):
    rem = (-a.shape[dim]) % size
    if not rem:
        return a
    pads = [(0, 0)] * a.ndim
    pads[dim] = (0, rem)
    return jnp.pad(a, pads, constant_values=value)


def _dq_forward(x, q, scale, bits, rows, interpret):
    m, k = x.shape
    n = q.shape[1]
    k_store = 2 * q.shape[0] if bits == 4 else q.shape[0]
    if k > k_store:
        raise ValueError(f"x K dim {k} exceeds stored weight rows "
                         f"{k_store}")
    if k < k_store:                      # odd-K int4: one zero nibble row
        x = jnp.pad(x, ((0, 0), (0, k_store - k)))
    # block the (possibly padded) problem; every dim padded up to its
    # block so index maps stay dense
    bm = _pick_block(128, ((m + 7) // 8) * 8)
    bn = _pick_block(128, ((n + 127) // 128) * 128)
    bk = _pick_block(_BLOCK_K, ((k_store + 1) // 2) * 2)
    if bk % 2:
        bk *= 2                          # int4 tiles cover whole bytes
    x = _pad_to(_pad_to(x, 0, bm), 1, bk)
    q = _pad_to(_pad_to(q, 0, bk // 2 if bits == 4 else bk), 1, bn)
    scale = _pad_to(jnp.reshape(scale, (1, -1)), 1, bn)
    mp, kp = x.shape
    np_ = q.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)
    wblk = bk // 2 if bits == 4 else bk
    out = pl.pallas_call(
        functools.partial(_dq_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((wblk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dq(x, q, scale, bits, rows, interpret):
    return _dq_forward(x, q, scale, bits, rows, interpret)


def _dq_fwd_rule(x, q, scale, bits, rows, interpret):
    return _dq_forward(x, q, scale, bits, rows, interpret), (q, scale)


def _dq_bwd_rule(bits, rows, interpret, res, g):
    q, scale = res
    w = _dequant(q, jnp.reshape(scale, (1, -1)), bits, rows)
    dx = jax.lax.dot_general(
        g.astype(jnp.float32), w, (((g.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (dx, np.zeros(q.shape, jax.dtypes.float0),
            jnp.zeros_like(scale))


_dq.defvjp(_dq_fwd_rule, _dq_bwd_rule)


# ---------------------------------------------------------------------------
# public entry


def dequant_matmul(x, q, scale, bits: int = 8, rows: Optional[int] = None,
                   interpret: bool = False):
    """``x @ dequant(q, scale)`` with the dequantize fused into the matmul.

    ``x`` (..., K) float; ``q`` int8 weight storage — (K, N) for
    ``bits=8``, nibble-packed (ceil(K/2), N) for ``bits=4`` (``rows=K``
    disambiguates odd K); ``scale`` f32 per-output-channel, (N,) or
    (1, N).  Returns (..., N) in ``x.dtype``.

    Dispatch: the Pallas kernel on TPU where its K blocking compiles
    (``_BLOCK_K``), the pure-JAX reference elsewhere; ``interpret=True``
    forces the kernel in interpreter mode (tests).  Differentiable wrt
    ``x`` on every path.
    """
    if bits not in BITS:
        raise ValueError(f"bits must be one of {BITS}, got {bits}")
    k = x.shape[-1]
    lead = x.shape[:-1]
    k_store = (2 if bits == 4 else 1) * q.shape[0]
    path = dispatch.select_path(
        "dequant_matmul",
        shapes_ok=q.ndim == 2 and (k_store <= _BLOCK_K
                                   or k_store % 128 == 0),
        # tiny matmuls: XLA's fused dequant+dot already runs at latency,
        # the kernel pays off once weights are HBM-resident
        min_work_met=q.size >= 256 * 256,
        force=dispatch.PATH_INTERPRET if interpret else None,
    )
    if path == dispatch.PATH_REFERENCE:
        return dequant_matmul_reference(x, q, scale, bits, rows)
    x2 = x.reshape((-1, k))
    out = _dq(x2, q, scale, bits, rows, path == dispatch.PATH_INTERPRET)
    return out.reshape(lead + (q.shape[1],))
