"""Pallas TPU fused embedding-bag kernel (multi-hot gather + combine).

Replaces the recommenders' hottest loop — the reference served NCF /
Wide&Deep through BigDL's MKL-DNN ``LookupTable`` + ``Sum`` pair
(SURVEY §L3); the XLA equivalent (``jnp.take`` + masked segment-sum)
materialises the per-index gathered rows as a (B, N, D) intermediate in
HBM: written once by the gather, read once by the reduction.  This kernel
fuses the two: per bag, the N table rows stream HBM→VMEM by async row DMA
(double-buffered across bags, so bag b+1's rows are in flight while bag b
reduces), the masked combine runs on the just-landed VMEM tile, and only
the (B, D) result ever touches HBM.  Ideal traffic drops from
``3·B·N·D`` words to ``B·N·D + B·D`` — neither the one-hot matrix nor
the gathered rows exist outside VMEM scratch.

Autodiff: ``jax.custom_vjp`` with a HAND-WRITTEN Pallas backward that
scatters dTable in the same blocked layout — grid over bag blocks, each
valid (bag, slot) doing a read-modify-write row DMA into the dTable
buffer (aliased in-place over a zeros input).  The RMW chain is fully
serialised per element, which keeps duplicate indices exact everywhere
(including interpret mode); a later revision can sort-and-combine
duplicates to recover DMA overlap.  ``ids`` take the documented
``float0`` zero cotangent.

Dispatch (``ops.dispatch.select_path``, knob ``ZooConfig.fused_embedding``):
the compiled kernel is selected on TPU only for the shapes Mosaic accepts
(``_mosaic_accepts``: float32 tables of width exactly 128, bags of at most
128 ids); everything else — every table the bundled recommenders build
(NCF's width-20 tables, Wide&Deep's ``class_num``-wide wide bag), every
bf16-cast table — takes ``embedding_bag_reference``.  Off-TPU the kernel
runs only under the explicit ``interpret=True`` the tests pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import dispatch

COMBINERS = ("sum", "mean", "sqrtn")
# out block is (_BAG_BLOCK, D): 8 bags per grid step keeps the f32 sublane
# tile full while the SMEM ids block stays tiny (8·N int32 scalars)
_BAG_BLOCK = 8
# What Mosaic compiles for this kernel on v5e (jax 0.9.0 / libtpu 0.0.34);
# each bound is the shape on the accepted side of a refusal, pinned by
# tests/test_embedding_bag.py:
# - the per-id row DMA slices ONE row out of the HBM table's (8, 128)
#   tiling.  Width 20/64: "Slice shape along dimension 1 must be aligned
#   to tiling (128)"; width 256+ and every 16-bit table (two rows per
#   packed sublane): "Slice shape along dimension 0 must be aligned to
#   tiling (8), but is 1".  Only float32 rows of exactly one lane tile
#   pass.
# - one DMA semaphore per in-flight row, double-buffered: (2, N).  N=256
#   exhausts the semaphore space ("Ran out of memory in memory space
#   sflag"); N=128 compiles.
_ROW_WIDTH = 128
_MAX_NNZ = 128


def _check_args(table, ids, combiner):
    if table.ndim != 2:
        raise ValueError(f"table must be (vocab, dim), got {table.shape}")
    if ids.ndim != 2:
        raise ValueError(f"ids must be (bags, max_nnz), got {ids.shape}")
    if combiner not in COMBINERS:
        raise ValueError(f"combiner must be one of {COMBINERS}, "
                         f"got {combiner!r}")


def _bag_mask(ids, pad_id):
    """(B, N) f32 validity mask; ``pad_id=None`` means every slot counts."""
    if pad_id is None:
        return jnp.ones(ids.shape, jnp.float32)
    return (ids != pad_id).astype(jnp.float32)


def _combiner_scale(mask, combiner):
    """(B, 1) f32 per-bag weight applied after the masked sum."""
    if combiner == "sum":
        return jnp.ones((mask.shape[0], 1), jnp.float32)
    n = jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
    return 1.0 / (n if combiner == "mean" else jnp.sqrt(n))


def embedding_bag_reference(table, ids, combiner: str = "sum",
                            pad_id=0):
    """Pure-JAX oracle: gather + masked segment combine.

    Same math as the kernel, and the numerics source of truth for the
    parity suites.  XLA materialises the (B, N, D) gathered rows here —
    that intermediate is exactly what the fused kernel removes.
    """
    _check_args(table, ids, combiner)
    mask = _bag_mask(ids, pad_id)
    rows = jnp.take(table, ids.astype(jnp.int32), axis=0)    # (B, N, D)
    out = jnp.sum(rows.astype(jnp.float32) * mask[..., None], axis=1)
    out = out * _combiner_scale(mask, combiner)
    return out.astype(table.dtype)


# ---------------------------------------------------------------------------
# forward kernel


def _fwd_kernel(ids_smem, ids_vmem, table_ref, out_ref, rows, sem, *,
                combiner: str, pad_id, vocab: int):
    bb, n = ids_smem.shape

    def _row_copy(b, j, slot):
        idx = jnp.clip(ids_smem[b, j], 0, vocab - 1)  # jnp.take clip parity
        return pltpu.make_async_copy(table_ref.at[idx], rows.at[slot, j],
                                     sem.at[slot, j])

    def _start(b):
        for j in range(n):
            _row_copy(b, j, b % 2).start()

    def _wait(b):
        for j in range(n):
            _row_copy(b, j, b % 2).wait()

    _start(0)
    for b in range(bb):
        if b + 1 < bb:
            _start(b + 1)                      # overlap next bag's DMAs
        _wait(b)
        if pad_id is None:
            mask = jnp.ones((1, n), jnp.float32)
        else:
            mask = (ids_vmem[b, :] != pad_id).astype(jnp.float32)[None, :]
        # masked combine as a (1, N) x (N, D) contraction on the MXU, no
        # per-slot control flow.  HIGHEST: at the default precision the
        # MXU rounds the f32 rows to bf16 (measured on v5e: 1.9e-3 off the
        # reference); a lookup must return the table's values
        acc = jax.lax.dot_general(
            mask, rows[b % 2].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)            # (1, D)
        if combiner != "sum":
            cnt = jnp.maximum(jnp.sum(mask), 1.0)
            acc = acc / (cnt if combiner == "mean" else jnp.sqrt(cnt))
        out_ref[b, :] = acc[0].astype(out_ref.dtype)


def _pad_bags(ids, pad_fill):
    """Pad the bag dim to a multiple of the block; returns (ids', B)."""
    b = ids.shape[0]
    rem = (-b) % _BAG_BLOCK
    if rem:
        ids = jnp.pad(ids, ((0, rem), (0, 0)), constant_values=pad_fill)
    return ids, b


def _bag_forward(table, ids, combiner, pad_id, interpret):
    vocab, dim = table.shape
    ids = ids.astype(jnp.int32)
    # padded bags gather row 0 and are sliced off; with a pad_id they are
    # also fully masked
    ids, b_real = _pad_bags(ids, pad_fill=pad_id if pad_id is not None
                            else 0)
    b_pad, n = ids.shape
    kernel = functools.partial(_fwd_kernel, combiner=combiner,
                               pad_id=pad_id, vocab=vocab)
    out = pl.pallas_call(
        kernel,
        grid=(b_pad // _BAG_BLOCK,),
        in_specs=[
            pl.BlockSpec((_BAG_BLOCK, n), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((_BAG_BLOCK, n), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # table stays in HBM
        ],
        out_specs=pl.BlockSpec((_BAG_BLOCK, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, dim), table.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, n, dim), table.dtype),   # double-buffered rows
            pltpu.SemaphoreType.DMA((2, n)),
        ],
        interpret=interpret,
    )(ids, ids, table)
    return out[:b_real]


# ---------------------------------------------------------------------------
# backward kernel: blocked dTable scatter


def _bwd_kernel(ids_smem, g_ref, _dtab_in, dtab_ref, row, sem, *,
                pad_id, vocab: int):
    bb, n = ids_smem.shape
    for b in range(bb):
        for j in range(n):
            raw = ids_smem[b, j]
            idx = jnp.clip(raw, 0, vocab - 1)
            live = (raw >= 0) if pad_id is None else (raw != pad_id)

            @pl.when(live)
            def _rmw(idx=idx, b=b):
                rd = pltpu.make_async_copy(dtab_ref.at[idx], row.at[0],
                                           sem.at[0])
                rd.start()
                rd.wait()
                row[0, :] = row[0, :] + g_ref[b, :]
                wr = pltpu.make_async_copy(row.at[0], dtab_ref.at[idx],
                                           sem.at[0])
                wr.start()
                wr.wait()


def _bag_backward(table_shape, table_dtype, ids, g_scaled, pad_id,
                  interpret):
    vocab, dim = table_shape
    ids = ids.astype(jnp.int32)
    # padded bags must scatter nothing: fill with pad_id, or with -1 when
    # pad_id is None (the kernel's `live` guard skips negatives then)
    ids, _ = _pad_bags(ids, pad_fill=pad_id if pad_id is not None else -1)
    b_pad, n = ids.shape
    g_scaled = jnp.pad(
        g_scaled, ((0, b_pad - g_scaled.shape[0]), (0, 0)))
    kernel = functools.partial(_bwd_kernel, pad_id=pad_id, vocab=vocab)
    return pl.pallas_call(
        kernel,
        grid=(b_pad // _BAG_BLOCK,),
        in_specs=[
            pl.BlockSpec((_BAG_BLOCK, n), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((_BAG_BLOCK, dim), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((vocab, dim), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, dim), jnp.float32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        input_output_aliases={2: 0},        # accumulate into the zeros
        interpret=interpret,
    )(ids, g_scaled.astype(jnp.float32),
      jnp.zeros((vocab, dim), jnp.float32)).astype(table_dtype)


# ---------------------------------------------------------------------------
# custom_vjp plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _bag(table, ids, combiner, pad_id, interpret):
    return _bag_forward(table, ids, combiner, pad_id, interpret)


def _bag_fwd_rule(table, ids, combiner, pad_id, interpret):
    out = _bag_forward(table, ids, combiner, pad_id, interpret)
    return out, (table, ids)


def _bag_bwd_rule(combiner, pad_id, interpret, res, g):
    table, ids = res
    mask = _bag_mask(ids, pad_id)
    g_scaled = g.astype(jnp.float32) * _combiner_scale(mask, combiner)
    dtable = _bag_backward(table.shape, table.dtype, ids, g_scaled,
                           pad_id, interpret)
    # integer primal: float0 cotangent (documented custom_vjp idiom)
    return dtable, np.zeros(ids.shape, jax.dtypes.float0)


_bag.defvjp(_bag_fwd_rule, _bag_bwd_rule)


# ---------------------------------------------------------------------------
# within-batch duplicate-id dedup (ISSUE 19)
#
# Recommender id streams repeat heavily inside a batch (zipfian traffic):
# the naive lookup pays one table-row DMA per SLOT, duplicates included.
# The dedup path collapses the flattened id block to its unique set with
# ``jnp.unique(size=B*N)`` — static output shape, so it jits — gathers
# each distinct row from the big table exactly once, and scatters back
# through the inverse index (a gather from the SMALL unique set, never
# from HBM-resident table rows).  Big-table rows touched per batch drop
# from ``B*N`` to ``U`` (the distinct count).  The custom_vjp keeps the
# training contract exact: gradients accumulate PER OCCURRENCE (segment-
# summed over the inverse index, then one scatter-add per unique row).


def _dedup_unique(ids, vocab, pad_id):
    """Static-shape unique decomposition of a ``(B, N)`` id block.

    Returns ``(mask, uniq, inv)``: the (B, N) f32 validity mask, the
    length-``B*N`` unique key vector (clipped ids; pad slots collapse to
    the ``-1`` fill so they unify with the tail padding), and the (B, N)
    inverse index with ``uniq[inv] == key``.
    """
    mask = _bag_mask(ids, pad_id)
    clipped = jnp.clip(ids.astype(jnp.int32), 0, vocab - 1)  # take parity
    key = jnp.where(mask > 0, clipped, -1)
    uniq, inv = jnp.unique(key.reshape(-1), size=key.size,
                           fill_value=-1, return_inverse=True)
    return mask, uniq, inv.reshape(ids.shape)


def _dedup_forward(table, ids, combiner, pad_id):
    vocab, _ = table.shape
    mask, uniq, inv = _dedup_unique(ids, vocab, pad_id)
    live = (uniq >= 0).astype(jnp.float32)
    rows_u = jnp.take(table, jnp.clip(uniq, 0, vocab - 1), axis=0)
    rows_u = rows_u.astype(jnp.float32) * live[:, None]      # (U, D)
    gathered = jnp.take(rows_u, inv, axis=0)                 # small-set
    out = jnp.sum(gathered * mask[..., None], axis=1)
    out = out * _combiner_scale(mask, combiner)
    return out.astype(table.dtype), (table, ids)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dedup_bag(table, ids, combiner, pad_id):
    return _dedup_forward(table, ids, combiner, pad_id)[0]


def _dedup_bag_fwd(table, ids, combiner, pad_id):
    return _dedup_forward(table, ids, combiner, pad_id)


def _dedup_bag_bwd(combiner, pad_id, res, g):
    table, ids = res
    vocab, dim = table.shape
    mask, uniq, inv = _dedup_unique(ids, vocab, pad_id)
    live = (uniq >= 0).astype(jnp.float32)
    g_scaled = g.astype(jnp.float32) * _combiner_scale(mask, combiner)
    # per-occurrence contribution, segment-summed per unique id first so
    # the big-table scatter touches each distinct row exactly once
    contrib = (g_scaled[:, None, :] * mask[..., None]).reshape(-1, dim)
    d_u = jnp.zeros((uniq.shape[0], dim), jnp.float32)
    d_u = d_u.at[inv.reshape(-1)].add(contrib) * live[:, None]
    dtable = jnp.zeros((vocab, dim), jnp.float32)
    dtable = dtable.at[jnp.clip(uniq, 0, vocab - 1)].add(d_u)
    return (dtable.astype(table.dtype),
            np.zeros(ids.shape, jax.dtypes.float0))


_dedup_bag.defvjp(_dedup_bag_fwd, _dedup_bag_bwd)


def embedding_bag_dedup(table, ids, combiner: str = "sum", pad_id=0):
    """``embedding_bag`` through the within-batch dedup path: the same
    bag math (same mask/clip/combiner semantics, parity at rtol 1e-6),
    but each distinct id reads its table row exactly once per batch and
    the backward scatter-adds exactly once per distinct row — duplicate
    ids are free on both sides.  Differentiable wrt ``table``."""
    _check_args(table, ids, combiner)
    return _dedup_bag(table, ids, combiner, pad_id)


def dedup_wanted(*, sharded: bool) -> bool:
    """Resolve the ``dedup_ids`` knob for one lookup site and count the
    decision (``table_dedup_selected_total{decision,reason}``) — the
    PR 12 counted-dispatch contract for the dedup tier.  ``auto`` turns
    dedup ON for sharded lookups (where the unique set also shrinks the
    psum-side work and HBM row traffic pays full price) and OFF for the
    dense path (the fused kernel already streams rows at line rate)."""
    from analytics_zoo_tpu.observe import metrics as _metrics

    knob = dispatch.config_knob("dedup_ids", "auto")
    if knob == "off":
        decision, reason = "off", "knob_off"
    elif knob == "on":
        decision, reason = "on", "knob_on"
    else:
        decision, reason = (("on", "auto_sharded") if sharded
                            else ("off", "auto_dense"))
    _metrics.count("table_dedup_selected_total", 1,
                   flat=f"ops/dedup_{decision}",
                   decision=decision, reason=reason)
    return decision == "on"


# ---------------------------------------------------------------------------
# public entry


def _mosaic_accepts(table, max_nnz: int) -> bool:
    """True for the shapes the compiled kernel accepts (see ``_ROW_WIDTH``
    / ``_MAX_NNZ``) — auto-dispatch must never select one it refuses."""
    return (table.dtype == jnp.float32 and table.shape[0] >= 1
            and table.shape[1] == _ROW_WIDTH and 1 <= max_nnz <= _MAX_NNZ)


def embedding_bag(table, ids, combiner: str = "sum", pad_id=0,
                  interpret: bool = False):
    """Fused multi-hot lookup: ``combine_j table[ids[b, j]]`` per bag.

    ``table`` (vocab, dim) float; ``ids`` (bags, max_nnz) int.  Slots
    equal to ``pad_id`` contribute nothing (``pad_id=None`` counts every
    slot — dense multi-hot like Wide&Deep's wide tower).  ``combiner``
    is ``"sum" | "mean" | "sqrtn"`` over each bag's valid slots.
    Out-of-range ids clip, matching ``jnp.take``.

    Dispatch: the Pallas kernel on TPU for the shapes it compiles at
    (``_mosaic_accepts``; ``fused_embedding`` knob: auto/on/off), the
    pure-JAX reference elsewhere; ``interpret=True`` forces the kernel
    in interpreter mode (tests).  Differentiable wrt ``table`` on every
    path.
    """
    _check_args(table, ids, combiner)
    path = dispatch.select_path(
        "embedding_bag",
        shapes_ok=_mosaic_accepts(table, ids.shape[1]),
        # below ~4k rows the whole table sits happily in cache/VMEM and
        # XLA's gather wins; the DMA kernel pays off once the table is
        # HBM-resident
        min_work_met=table.shape[0] >= 4096,
        knob=dispatch.config_knob("fused_embedding", "auto"),
        force=dispatch.PATH_INTERPRET if interpret else None,
    )
    if path == dispatch.PATH_REFERENCE:
        return embedding_bag_reference(table, ids, combiner, pad_id)
    return _bag(table, ids, combiner, pad_id,
                path == dispatch.PATH_INTERPRET)


def embedding_gather(table, ids, interpret: bool = False):
    """Plain ``table[ids]`` lookup routed through the bag kernel.

    A gather is the degenerate bag (one id per bag, no combine), so the
    recommenders' single-id and sequence lookups (NCF, the session GRU)
    share the fused DMA pipeline transparently: ids of any shape flatten
    to (num, 1) singleton bags and the result folds back to
    ``ids.shape + (dim,)``.  Off-TPU this is exactly ``jnp.take`` — no
    mask, no reduction — so the XLA graph is unchanged there.
    """
    if table.ndim != 2:
        raise ValueError(f"table must be (vocab, dim), got {table.shape}")
    path = dispatch.select_path(
        "embedding_gather",
        shapes_ok=_mosaic_accepts(table, 1),
        min_work_met=table.shape[0] >= 4096,
        knob=dispatch.config_knob("fused_embedding", "auto"),
        force=dispatch.PATH_INTERPRET if interpret else None,
    )
    if path == dispatch.PATH_REFERENCE:
        return jnp.take(table, ids.astype(jnp.int32), axis=0)
    flat = ids.astype(jnp.int32).reshape((-1, 1))
    out = _bag(table, flat, "sum", None, path == dispatch.PATH_INTERPRET)
    return out.reshape(ids.shape + (table.shape[1],))
