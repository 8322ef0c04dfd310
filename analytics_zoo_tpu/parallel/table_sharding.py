"""Row-sharded giant embedding tables over the mesh's ``model`` axis.

The recommenders' north-star claim ("serve millions of users",
ROADMAP item 1) is capped by one chip's HBM as long as every device
replicates the full embedding table — a 10⁸-row production table at
D=64 is ~25 GiB of f32, several chips' worth on its own.  This module
partitions a table **row-wise** over the model axis and keeps the
minibatch lookup fully on-device:

- each model shard holds ``rows/ways`` contiguous table rows (plus the
  matching slice of the Adam moments — train/optimizers.py
  ``opt_state_shardings`` makes optimizer state follow the params);
- the lookup runs inside ``shard_map``: every shard masks the batch's
  ids down to the rows it owns (unowned slots become a ``-1`` pad the
  fused ``ops.embedding_bag`` kernel already ignores), gathers/combines
  **locally**, and a single ``psum`` over the model axis exchanges only
  the combined ``(B, D)`` partials — the gathered ``(B, N, D)`` rows
  never leave their owning shard, so the per-step exchange is
  ``B·D·4`` bytes per table instead of the allgathered table itself.

Placement is decided per table by :func:`choose_table_placement` — the
same bounded-reason-code router style as the Estimator's data-path
router (``data_path_selected_total``), counted in
``table_placement_selected_total{placement,reason}``:

========== =============================================================
replicated table fits ``data_device_budget_bytes`` (or no model axis)
sharded    over budget but ``nbytes/ways`` fits — row-shard it
stream     over budget even sharded: row-shard AND stream-initialize
           each shard straight onto its devices from a lazy row source
           (:func:`init_table_sharded`), never materializing a host
           mirror — the cold-row tier for tables bigger than the mesh
========== =============================================================

Tables pad their row count to ``ROW_ALIGN`` (a topology-independent
multiple that covers 1/2/4/8-way meshes), so a checkpoint written at
one sharding width restores at any other through the existing
``tree_put_global`` reshard seam; :func:`grow_restored_tree` handles
the elastic case where the restored table has FEWER rows than the
freshly built one (new rows keep their fresh initialization).
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.observe import metrics as obs
from analytics_zoo_tpu.parallel.sharding import (DataParallel,
                                                 ShardingStrategy,
                                                 path_str)

logger = logging.getLogger("analytics_zoo_tpu.parallel")

# Topology-independent row padding: a table padded to a multiple of 8
# row-shards evenly at every mesh width in {1, 2, 4, 8}, so the param
# SHAPE (and therefore the checkpoint layout) never depends on the mesh
# the model happened to be built on — that invariance is what lets a
# 2-way snapshot restore 1-way or 4-way via plain tree_put_global.
ROW_ALIGN = 8

TABLE_PLACEMENTS = ("replicated", "sharded", "stream")


def padded_rows(rows: int) -> int:
    """``rows`` rounded up to the topology-independent ``ROW_ALIGN``."""
    return int(-(-int(rows) // ROW_ALIGN) * ROW_ALIGN)


def resolve_table_ways(mesh, axis: str, rows: int) -> int:
    """How many ways a ``rows``-row table shards on ``mesh`` — 1 means
    "don't": the axis is missing, trivial, or does not divide the
    (already ROW_ALIGN-padded) row count.  The strategy's param specs
    and the layer's trace-time lowering both call this, so placement
    and compute can never disagree."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    ways = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    if ways <= 1 or rows % ways:
        return 1
    return ways


def _data_axis(mesh, own_axis: str) -> Optional[str]:
    names = [a for a in mesh.axis_names if a != own_axis]
    if not names:
        return None
    return "data" if "data" in names else names[0]


# ---------------------------------------------------------------------------
# the sharded lookup: local gather + one (B, D) psum exchange
# ---------------------------------------------------------------------------


def sharded_bag(table, ids, combiner: str = "sum", pad_id=None, *,
                mesh, axis: str = "model", dedup: Optional[bool] = None):
    """``embedding_bag`` over a row-sharded table: ``(B, N)`` ids against
    a ``(rows, D)`` table laid out ``P(axis, None)`` -> ``(B, D)``.

    Inside ``shard_map`` each model shard rewrites the bag ids it does
    NOT own to ``-1`` — the fused kernel's mask is computed from the raw
    ids before clipping, so those slots contribute exact zeros — runs
    the PR 12 fused ``embedding_bag`` on its local rows, and one
    ``psum`` over ``axis`` assembles the global combine.  mean/sqrtn
    scaling applies AFTER the exchange from the global validity count
    (ids are replicated over the model axis, so every shard derives the
    same count).  Exchange bytes per step: ``B * D * 4`` per table.

    ``dedup`` routes the local gather through the within-batch unique-id
    path (``ops.embedding_bag.embedding_bag_dedup``: duplicate ids cost
    one row read, grads still accumulate per occurrence); ``None``
    resolves the ``dedup_ids`` knob, whose ``auto`` default turns dedup
    ON here — this is exactly the lookup where duplicate rows pay full
    HBM price on every shard.
    """
    from analytics_zoo_tpu.ops.embedding_bag import (dedup_wanted,
                                                     embedding_bag,
                                                     embedding_bag_dedup)

    rows = int(table.shape[0])
    ways = resolve_table_ways(mesh, axis, rows)
    if ways <= 1:
        return embedding_bag(table, ids, combiner, pad_id)
    if dedup is None:
        dedup = dedup_wanted(sharded=True)
    local_bag = embedding_bag_dedup if dedup else (
        lambda tab, i, c, pad_id: embedding_bag(tab, i, c, pad_id=pad_id))
    rows_local = rows // ways
    batch_ax = _data_axis(mesh, axis)

    def local(tab, ids_l):
        ids_l = ids_l.astype(jnp.int32)
        shard = jax.lax.axis_index(axis)
        lo = shard * rows_local
        valid = (jnp.ones(ids_l.shape, jnp.bool_) if pad_id is None
                 else ids_l != pad_id)
        owned = valid & (ids_l >= lo) & (ids_l < lo + rows_local)
        local_ids = jnp.where(owned, ids_l - lo, -1)
        part = local_bag(tab, local_ids, "sum", -1)
        total = jax.lax.psum(part.astype(jnp.float32), axis)
        if combiner != "sum":
            n = jnp.maximum(
                jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True),
                1.0)
            total = total / (n if combiner == "mean" else jnp.sqrt(n))
        return total.astype(tab.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(batch_ax, None)),
        out_specs=P(batch_ax, None),
        check_vma=False,
    )(table, ids)


def sharded_gather(table, ids, *, mesh, axis: str = "model",
                   dedup: Optional[bool] = None):
    """``table[ids]`` over a row-sharded table: ids of any shape ->
    ``ids.shape + (D,)`` — the degenerate single-slot bag, same local
    gather + psum exchange (and the same ``dedup_ids``-resolved
    unique-id routing) as :func:`sharded_bag`."""
    flat = ids.astype(jnp.int32).reshape((-1, 1))
    out = sharded_bag(table, flat, "sum", pad_id=None, mesh=mesh,
                      axis=axis, dedup=dedup)
    return out.reshape(tuple(ids.shape) + (int(table.shape[1]),))


# ---------------------------------------------------------------------------
# placement router (the data-path router's sibling)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TablePlacement:
    """One router decision: where a table's rows live, and why."""
    placement: str          # replicated | sharded | stream
    ways: int               # model-axis split the decision assumed
    reason_code: str        # bounded vocabulary (docs/OBSERVABILITY.md)
    reason: str             # human-readable


def choose_table_placement(*, nbytes: int, rows: int,
                           requested: str = "auto",
                           mesh=None, axis: str = "model",
                           budget_bytes: Optional[int] = None
                           ) -> TablePlacement:
    """Per-table placement: replicated < sharded < stream, decided from
    the table's bytes against ``data_device_budget_bytes`` and the mesh
    shape — the same decision style (and counter discipline) as the
    Estimator's FeatureSet path router.  Every decision is counted in
    ``table_placement_selected_total{placement,reason}`` with a bounded
    reason vocabulary; downgrades are automatic and logged, never an
    error."""
    if requested not in ("auto",) + TABLE_PLACEMENTS:
        raise ValueError(
            f"table_placement must be auto|replicated|sharded|stream, "
            f"got {requested!r}")
    if mesh is None or budget_bytes is None:
        from analytics_zoo_tpu.core.context import get_zoo_context
        ctx = get_zoo_context()
        if mesh is None:
            mesh = ctx.mesh
        if budget_bytes is None:
            budget_bytes = int(ctx.config.data_device_budget_bytes)
    rows_p = padded_rows(rows)
    ways = resolve_table_ways(mesh, axis, rows_p)
    axis_size = 0
    if mesh is not None and axis in mesh.axis_names:
        axis_size = int(dict(zip(mesh.axis_names,
                                 mesh.devices.shape))[axis])
    # no_model_axis: the mesh can't shard anything; axis_indivisible:
    # the axis exists but this table's (padded) rows don't split on it
    no_ways_code = ("axis_indivisible" if axis_size > 1 and ways <= 1
                    else "no_model_axis")

    def pick(placement: str, code: str, reason: str) -> TablePlacement:
        obs.count("table_placement_selected_total", placement=placement,
                  reason=code, flat=f"parallel/table_placement_{placement}")
        return TablePlacement(placement, ways if placement != "replicated"
                              else 1, code, reason)

    if requested == "replicated":
        return pick("replicated", "requested", "placement requested")
    if ways <= 1:
        if requested in ("sharded", "stream"):
            logger.warning(
                "table_placement=%r requested but the mesh %s has no "
                "usable %r axis for a %d-row table; placing replicated",
                requested, tuple(mesh.axis_names), axis, rows)
            return pick("replicated", no_ways_code,
                        f"no usable {axis!r} axis on this mesh for "
                        f"{rows_p} rows")
        if int(nbytes) <= int(budget_bytes):
            return pick("replicated", "fits_budget", "fits device budget")
        return pick("replicated", no_ways_code,
                    f"table {int(nbytes)}B over budget "
                    f"{int(budget_bytes)}B but no usable {axis!r} axis")
    if requested in ("sharded", "stream"):
        return pick(requested, "requested", "placement requested")
    if int(nbytes) <= int(budget_bytes):
        return pick("replicated", "fits_budget", "fits device budget")
    if int(nbytes) // ways <= int(budget_bytes):
        return pick("sharded", "over_budget",
                    f"table {int(nbytes)}B over device budget "
                    f"{int(budget_bytes)}B; {ways}-way rows fit")
    return pick("stream", "sharded_over_budget",
                f"table {int(nbytes)}B exceeds budget even {ways}-way "
                f"sharded; shard + stream-initialize cold rows")


# ---------------------------------------------------------------------------
# sharding strategy wrapper: listed tables ride P(axis, None)
# ---------------------------------------------------------------------------


class TableShardedStrategy(ShardingStrategy):
    """Wrap any base strategy so the listed layers' ``<name>/table``
    params split row-wise over the model axis; everything else (and any
    table the live mesh cannot shard) falls through to the base.

    ``activate`` publishes a :class:`~analytics_zoo_tpu.parallel.mode.
    TableShardMode` for the trace, which is how
    ``ShardedEmbeddingTable.forward`` knows to lower to the
    local-gather + psum exchange — placement and compute agree by
    construction because both sides call :func:`resolve_table_ways`.
    """

    def __init__(self, base: Optional[ShardingStrategy] = None,
                 tables: Sequence[str] = (), axis: str = "model"):
        self.base = base if base is not None else DataParallel()
        self.tables = tuple(tables)
        self.axis = axis
        self._pats = [re.compile(rf"(^|/){re.escape(t)}/table$")
                      for t in self.tables]

    def _is_table(self, path: str) -> bool:
        return any(p.search(path) for p in self._pats)

    def param_shardings(self, mesh, params):
        base_sh = self.base.param_shardings(mesh, params)

        def one(path, leaf, base_leaf):
            p = path_str(path)
            shape = getattr(leaf, "shape", ())
            if (self._is_table(p) and len(shape) == 2
                    and resolve_table_ways(mesh, self.axis, shape[0]) > 1):
                return NamedSharding(mesh, P(self.axis, None))
            return base_leaf

        return jax.tree_util.tree_map_with_path(one, params, base_sh)

    def activate(self, mesh):
        import contextlib

        from analytics_zoo_tpu.parallel.mode import (TableShardMode,
                                                     table_mode)

        stack = contextlib.ExitStack()
        stack.enter_context(self.base.activate(mesh))
        if self.axis in mesh.axis_names:
            stack.enter_context(table_mode(TableShardMode(
                mesh, self.axis, self.tables)))
        return stack


def ensure_table_sharding(strategy: ShardingStrategy,
                          tables: Sequence[str],
                          axis: str = "model") -> ShardingStrategy:
    """Idempotently wrap ``strategy`` so ``tables`` shard over ``axis``
    (the Estimator calls this when the compiled model carries a
    ``_sharded_tables`` manifest)."""
    if not tables:
        return strategy
    if isinstance(strategy, TableShardedStrategy) \
            and strategy.tables == tuple(tables):
        return strategy
    return TableShardedStrategy(base=strategy, tables=tables, axis=axis)


def per_chip_weight_nbytes(params, tables: Sequence[str], mesh,
                           axis: str = "model") -> int:
    """The PER-CHIP byte footprint of ``params`` when the listed
    tables row-shard over ``mesh``'s ``axis``: sharded 2-D table leaves
    charge ``nbytes / ways``, everything else (replicated) charges its
    full bytes.  This is the number the serving executor's HBM-budget
    planner must use for a mesh-replica slot — charging a sharded
    table's FULL bytes per chip is exactly the over-estimate that makes
    the over-budget giant-table model look unservable."""
    pats = table_leaf_patterns(tables)
    total = 0

    def one(path, leaf):
        nonlocal total
        shape = getattr(leaf, "shape", ())
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        ways = 1
        if (any(p.search(path_str(path)) for p in pats)
                and len(shape) == 2):
            ways = resolve_table_ways(mesh, axis, int(shape[0]))
        total += nbytes // max(1, ways)
        return leaf

    jax.tree_util.tree_map_with_path(one, params)
    return int(total)


# ---------------------------------------------------------------------------
# STREAM-cold-rows initialization: shards land on-device, no host mirror
# ---------------------------------------------------------------------------


def init_table_sharded(mesh, rows: int, dim: int, row_source, *,
                       axis: str = "model", dtype=np.float32):
    """Materialize a row-sharded ``(padded_rows(rows), dim)`` table
    straight onto the mesh from a lazy ``row_source.rows(lo, hi)``
    generator (e.g. ``data.giant_table.SyntheticGiantTable``) — each
    device's row range is generated on demand and uploaded, so the full
    table NEVER exists on the host (the stream-cold-rows tier for
    tables bigger than host RAM).  Rows past ``rows`` (the ROW_ALIGN
    padding tail) are zero."""
    rows_p = padded_rows(rows)
    ways = resolve_table_ways(mesh, axis, rows_p)
    spec = P(axis, None) if ways > 1 else P()
    sharding = NamedSharding(mesh, spec)

    def shard_for(index) -> np.ndarray:
        lo, hi, _ = index[0].indices(rows_p)
        block = np.zeros((hi - lo, dim), dtype)
        live = min(hi, rows) - lo
        if live > 0:
            block[:live] = row_source.rows(lo, lo + live)
        return block

    # the explicit staging chokepoint, like device_put_global — guarded
    # training paths stay runnable (transfers here are the one upload)
    with jax.transfer_guard("allow"):
        return jax.make_array_from_callback(
            (rows_p, dim), sharding, shard_for)


# ---------------------------------------------------------------------------
# elastic growth on restore: more rows than the snapshot
# ---------------------------------------------------------------------------


def table_leaf_patterns(tables: Sequence[str]):
    return [re.compile(rf"(^|/){re.escape(t)}/table$") for t in tables]


def grow_restored_tree(restored, built, tables: Sequence[str]):
    """Merge a restored params tree into a freshly built one whose
    elastic tables have MORE rows: snapshot rows are kept bit-exact,
    rows beyond the snapshot keep the fresh build's initialization.
    Non-table leaves (and tables whose shapes already match) pass
    through untouched; a restored table LARGER than the built one is an
    error (shrinking a vocabulary would silently drop live rows)."""
    pats = table_leaf_patterns(tables)

    def one(path, new_leaf, old_leaf):
        p = path_str(path)
        old = np.asarray(old_leaf)
        if not any(pat.search(p) for pat in pats):
            return old
        new_shape = tuple(np.shape(new_leaf))
        if tuple(old.shape) == new_shape:
            return old
        if (len(old.shape) != 2 or len(new_shape) != 2
                or old.shape[1] != new_shape[1]):
            raise ValueError(
                f"restored table {p!r} has shape {tuple(old.shape)}, "
                f"incompatible with the built {new_shape}")
        if old.shape[0] > new_shape[0]:
            raise ValueError(
                f"restored table {p!r} has {old.shape[0]} rows but the "
                f"model was built with {new_shape[0]} — shrinking an "
                "embedding table on restore would drop live rows")
        tail = np.asarray(jax.device_get(new_leaf))[old.shape[0]:]
        logger.info("elastic table growth: %s %d -> %d rows (%d new rows "
                    "keep fresh init)", p, old.shape[0], new_shape[0],
                    new_shape[0] - old.shape[0])
        return np.concatenate([old.astype(tail.dtype), tail], axis=0)

    return jax.tree_util.tree_map_with_path(one, built, restored)


def grow_restored_opt_state(restored_opt, target_shapes):
    """The optimizer-state side of elastic growth: any restored leaf
    whose leading dim is SHORTER than the fresh ``tx.init`` shape (same
    trailing dims) zero-pads up to it — zeros ARE the fresh Adam/momentum
    state for the new rows, so grown rows optimize exactly like a cold
    start while snapshot rows keep their moments."""

    def one(old_leaf, tgt):
        old = np.asarray(old_leaf)
        tgt_shape = tuple(tgt.shape)
        if tuple(old.shape) == tgt_shape or old.ndim == 0:
            return old
        if (old.ndim == len(tgt_shape)
                and old.shape[1:] == tgt_shape[1:]
                and old.shape[0] < tgt_shape[0]):
            pad = np.zeros((tgt_shape[0] - old.shape[0],) + old.shape[1:],
                           old.dtype)
            return np.concatenate([old, pad], axis=0)
        raise ValueError(
            f"restored optimizer leaf shape {tuple(old.shape)} cannot "
            f"grow to {tgt_shape}")

    return jax.tree_util.tree_map(one, restored_opt, target_shapes)
