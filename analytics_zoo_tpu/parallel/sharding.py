"""Parallelism strategies: parameter-sharding rules over the device mesh.

Reference capability (SURVEY.md §2.4): the reference has ONE strategy —
synchronous data parallelism via Spark-block-manager allreduce
(InternalDistriOptimizer, Topology.scala:1069-1267; wp-bigdl.md:113-160) —
and explicitly lacks TP/PP/SP.  The TPU build gets data parallelism as the
degenerate case of GSPMD, and tensor parallelism "for free" by annotating
parameter shardings: XLA inserts the all-gathers/reduce-scatters over ICI.

Design: a strategy is a function ``spec(path, leaf) -> PartitionSpec``
applied over the params pytree.  The Estimator puts params on the mesh with
those specs; batch inputs shard over the data axis; jit does the rest.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

SpecFn = Callable[[str, Any], P]


def _infer_batch_axis(mesh, own_axis: str) -> Optional[str]:
    """The mesh axis the batch shards over when composing with dp:
    prefer an axis literally named 'data', else the first axis that is
    not the strategy's own — None on a single-axis mesh."""
    names = [a for a in mesh.axis_names if a != own_axis]
    if not names:
        return None
    return "data" if "data" in names else names[0]


def path_str(path) -> str:
    """jax tree path -> 'a/b/c' string for regex matching."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


class ShardingStrategy:
    """Base: fully replicated parameters (pure data parallelism)."""

    def spec(self, path: str, leaf) -> P:
        return P()

    def param_shardings(self, mesh, params):
        """Pytree of NamedShardings matching ``params``."""
        def one(path, leaf):
            return NamedSharding(mesh, self.spec(path_str(path), leaf))

        return jax.tree_util.tree_map_with_path(one, params)

    def activate(self, mesh):
        """Context manager active while the Estimator traces its steps.

        Strategies that change the model's *forward lowering* (ring
        attention for SP, the GPipe schedule for PP) publish themselves
        through parallel.mode here; pure param-placement strategies
        (DP/TP/EP) need no hook.
        """
        import contextlib
        return contextlib.nullcontext()


class DataParallel(ShardingStrategy):
    """Replicate params, shard the batch (the reference's only mode)."""


class TensorParallel(ShardingStrategy):
    """Shard large parameters along ``axis`` (the mesh's model axis).

    Rules (applied in order):
    - explicit ``rules``: list of (regex on param path, PartitionSpec);
    - otherwise any leaf with ≥ ``min_size`` elements is sharded along its
      largest dimension divisible by the axis size (embedding tables split
      over vocab, Dense kernels over the wider of in/out) — the standard
      Megatron-style layout expressed as GSPMD annotations.

    ``mesh_axis_size`` may be omitted — ``param_shardings`` derives it from
    the mesh (and validates that ``axis`` exists there).
    """

    def __init__(self, axis: str = "model", mesh_axis_size: Optional[int] = None,
                 rules: Optional[Sequence] = None, min_size: int = 2 ** 16):
        self.axis = axis
        self.axis_size = mesh_axis_size
        self.rules = [(re.compile(pat), spec) for pat, spec in (rules or [])]
        self.min_size = min_size

    def _resolve(self, mesh):
        """Per-call (axis, axis_size) for ``mesh`` — never cached on self,
        so one strategy object works across different meshes."""
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if self.axis not in sizes:
            raise ValueError(
                f"TensorParallel axis {self.axis!r} not in mesh axes "
                f"{tuple(mesh.axis_names)}; build the context with a model "
                "axis, e.g. init_zoo_context(mesh_shape=(d, t), "
                "axis_names=('data', 'model'))")
        if self.axis_size is not None and self.axis_size != sizes[self.axis]:
            raise ValueError(
                f"mesh_axis_size {self.axis_size} != mesh's "
                f"{self.axis!r} size {sizes[self.axis]}")
        return self.axis, sizes[self.axis]

    def param_shardings(self, mesh, params):
        axis, axis_size = self._resolve(mesh)

        def one(path, leaf):
            return NamedSharding(
                mesh, self._spec(path_str(path), leaf, axis, axis_size))

        return jax.tree_util.tree_map_with_path(one, params)

    def spec(self, path: str, leaf) -> P:
        if self.axis_size is None:
            raise ValueError(
                "TensorParallel.spec() without mesh_axis_size — use "
                "param_shardings(mesh, params), which resolves the axis "
                "size from the mesh")
        return self._spec(path, leaf, self.axis, self.axis_size)

    def _spec(self, path: str, leaf, axis: str, axis_size: int) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return spec
        shape = getattr(leaf, "shape", ())
        if not shape or int(np.prod(shape)) < self.min_size:
            return P()
        if not axis_size or axis_size <= 1:
            return P()
        # largest dim divisible by the axis size
        cands = [(d, i) for i, d in enumerate(shape)
                 if d % axis_size == 0]
        if not cands:
            return P()
        _, dim = max(cands)
        spec = [None] * len(shape)
        spec[dim] = axis
        return P(*spec)


class ExpertParallel(ShardingStrategy):
    """Shard MoE expert weights (leading ``n_experts`` dim) over the
    mesh's ``expert`` axis — pairs with ``nn.layers.moe.SparseMoE``,
    whose per-expert weights are stacked on dim 0.  Non-expert params
    stay replicated (combine with TensorParallel via explicit rules if
    both regimes are wanted).
    """

    def __init__(self, axis: str = "expert",
                 pattern: str = r"(^|/)(w1|b1|w2|b2)$"):
        # matches SparseMoE's expert-stacked leaves both as a bare param
        # tree ("w1") and nested under a layer name ("sparsemoe_1/w1");
        # the gate kernel never matches and stays replicated
        self.axis = axis
        self.pattern = re.compile(pattern)

    def param_shardings(self, mesh, params):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if self.axis not in sizes:
            raise ValueError(
                f"ExpertParallel axis {self.axis!r} not in mesh axes "
                f"{tuple(mesh.axis_names)}; build the context with an "
                "expert axis, e.g. init_zoo_context(mesh_shape=(d, e), "
                "axis_names=('data', 'expert'))")
        n = sizes[self.axis]

        def one(path, leaf):
            p = path_str(path)
            shape = getattr(leaf, "shape", ())
            if self.pattern.search(p) and shape:
                if shape[0] % n:
                    raise ValueError(
                        f"expert param {p!r} has {shape[0]} experts, not "
                        f"divisible by the {self.axis!r} axis size {n} — "
                        "silently replicating would discard the requested "
                        "expert partitioning; adjust n_experts or the mesh")
                return NamedSharding(
                    mesh, P(self.axis, *([None] * (len(shape) - 1))))
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map_with_path(one, params)


class SequenceParallel(ShardingStrategy):
    """Sequence/context parallelism: parameters replicated, attention
    computed as ring attention with K/V rotating over ``mesh[axis]``
    (parallel/sequence.py).

    The regime the reference cannot reach (SURVEY §5.7: sequence length
    bounded by single-node memory): per-device attention memory is
    O(L·L/n) and the KV exchange rides ICI neighbour hops.  Activated
    through ``Estimator`` — ``compile(sharding="sp")`` on a mesh with a
    sequence axis makes every ``MultiHeadAttention`` in the model lower
    to the ring. Constraints: self-attention only, no padding masks
    (causal is fine), attention-prob dropout is skipped on the ring.
    """

    def __init__(self, axis: str = "seq"):
        self.axis = axis

    def activate(self, mesh):
        from analytics_zoo_tpu.parallel.mode import (SeqParallelMode,
                                                     parallel_mode)
        if self.axis not in mesh.axis_names:
            raise ValueError(
                f"SequenceParallel axis {self.axis!r} not in mesh axes "
                f"{tuple(mesh.axis_names)}; use init_zoo_context("
                "mesh_shape=(d, s), axis_names=('data', 'seq'))")
        return parallel_mode(seq=SeqParallelMode(
            mesh, self.axis,
            batch_axis=_infer_batch_axis(mesh, self.axis)))


_SEQ_MESH_CACHE: dict = {}


def seq_mesh(ways: int, axis: str = "seq"):
    """A 1-D ``(ways,)`` mesh over the first ``ways`` devices with a
    sequence axis — what the ``seq_shards`` config knob hands to
    ``ops.ring_attention`` when no explicit sequence-parallel regime is
    active (nn/layers/attention.py).  Cached per (ways, axis): layer
    forwards run at trace time and must not rebuild meshes per call.
    Asking for more shards than there are devices raises: a model
    configured for a ``ways``-chip ring must not quietly run its
    attention on one chip.
    """
    import jax
    from jax.sharding import Mesh

    key = (int(ways), axis, jax.default_backend())
    got = _SEQ_MESH_CACHE.get(key)
    if got is not None:
        return got
    devs = jax.devices()
    if ways < 2 or len(devs) < ways:
        raise ValueError(
            f"seq_shards={ways} needs a ring of at least 2 and at most "
            f"{len(devs)} device(s) ({jax.default_backend()}); unset the "
            "knob to run attention on one device")
    mesh = Mesh(np.asarray(devs[:ways]), (axis,))
    _SEQ_MESH_CACHE[key] = mesh
    return mesh


class PipelineStrategy(ShardingStrategy):
    """GPipe pipeline parallelism as an Estimator regime.

    Stage weights are the model's stacked homogeneous block subtree
    (``TransformerLayer(stacked=True)`` stores its blocks as one pytree
    with leading dim ``n_block``); leaves under a ``blocks`` path shard
    over ``mesh[axis]`` (each device holds 1/S of the stack) and the
    forward routes through the microbatched ppermute ring
    (parallel/pipeline.py).  Everything outside the block stack
    (embeddings, heads) stays replicated and runs outside the pipeline.

    Composes with data parallelism: build the mesh as
    ``axis_names=('data', 'pipe')`` — the batch shards over ``data``,
    each data group runs its own pipeline over its ``pipe`` ring.
    """

    def __init__(self, axis: str = "pipe", n_microbatches: int = 4,
                 remat: bool = False,
                 pattern: str = r"(^|/)blocks(/|$)"):
        self.axis = axis
        self.n_microbatches = n_microbatches
        self.remat = remat
        self.pattern = re.compile(pattern)

    def _axis_size(self, mesh) -> int:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if self.axis not in sizes:
            raise ValueError(
                f"PipelineStrategy axis {self.axis!r} not in mesh axes "
                f"{tuple(mesh.axis_names)}; use init_zoo_context("
                "mesh_shape=(d, p), axis_names=('data', 'pipe'))")
        return sizes[self.axis]

    def param_shardings(self, mesh, params):
        n = self._axis_size(mesh)
        matched = []

        def one(path, leaf):
            p = path_str(path)
            shape = getattr(leaf, "shape", ())
            if self.pattern.search(p) and shape:
                if shape[0] != n:
                    # the GPipe body takes exactly one stage per device
                    # (pipeline_spmd reads p[0]); multiples cannot work
                    raise ValueError(
                        f"stacked block param {p!r} has {shape[0]} stages "
                        f"but the {self.axis!r} axis has {n} devices — "
                        "n_block must equal the pipe axis size")
                matched.append(p)
                return NamedSharding(
                    mesh, P(self.axis, *([None] * (len(shape) - 1))))
            return NamedSharding(mesh, P())

        out = jax.tree_util.tree_map_with_path(one, params)
        if not matched:
            raise ValueError(
                "sharding='pp' found no stacked block subtree (no param "
                "path matches 'blocks') — pipeline the model by stacking "
                "its homogeneous blocks, e.g. TransformerLayer("
                "stacked=True)")
        return out

    def activate(self, mesh):
        from analytics_zoo_tpu.parallel.mode import (PipelineMode,
                                                     parallel_mode)
        self._axis_size(mesh)
        return parallel_mode(pipe=PipelineMode(
            mesh, self.axis, n_microbatches=self.n_microbatches,
            remat=self.remat,
            batch_axis=_infer_batch_axis(mesh, self.axis)))


class AutoSharding(TensorParallel):
    """Mesh-adaptive: tensor-parallel over the mesh's last axis when it has
    a dedicated (non-data) axis, plain data parallelism otherwise."""

    def __init__(self, rules: Optional[Sequence] = None,
                 min_size: int = 2 ** 16):
        super().__init__(axis="", mesh_axis_size=None, rules=rules,
                         min_size=min_size)

    def _resolve(self, mesh):
        axis = mesh.axis_names[-1]
        return axis, dict(zip(mesh.axis_names,
                              mesh.devices.shape))[axis]

    def param_shardings(self, mesh, params):
        if len(mesh.axis_names) < 2:
            return DataParallel().param_shardings(mesh, params)
        return super().param_shardings(mesh, params)


def dataset_sharding(mesh, n_rows: int, ndim: int,
                     axis: str = "data") -> NamedSharding:
    """Placement for a DEVICE-cached (HBM-resident) dataset array.

    Rows split over the mesh's data axis so an N-device mesh holds 1/N of
    the dataset per chip (the capacity analog of the reference's
    partition-per-executor caching); every other dim is replicated.  When
    the row count doesn't divide the axis — or the axis is missing, e.g.
    a pure model-parallel mesh — the array is replicated instead: the
    resident epoch body gathers by *global* permutation indices, so a
    replicated copy is always correct, just not capacity-optimal.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis in sizes and sizes[axis] > 1 and n_rows % sizes[axis] == 0:
        return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))
    return NamedSharding(mesh, P())


def replica_devices(mesh, axis: str = "data") -> list:
    """Devices hosting one independent serving replica each.

    Serving wants full-model replicas round-robined by the device
    executor — the inference analog of data parallelism — so the natural
    replica set is the mesh's data axis: one device per data-axis index,
    fixed at index 0 along every model axis (those devices hold complete
    weight copies under DataParallel; a model-parallel serving path would
    need a sharded forward, which is the training stack's job).  Falls
    back to the mesh's flat device list when the axis is missing.
    """
    devs = np.asarray(mesh.devices)
    if axis in mesh.axis_names and devs.ndim == len(mesh.axis_names):
        idx = tuple(slice(None) if a == axis else 0
                    for a in mesh.axis_names)
        return list(np.atleast_1d(devs[idx]).ravel())
    return list(devs.ravel())


def device_span(tree) -> int:
    """How many devices the widest-placed array leaf of ``tree`` lives
    on — what a smoke asserts to know a regime really spans the mesh."""
    import jax

    return max(len(leaf.sharding.device_set)
               for leaf in jax.tree_util.tree_leaves(tree))


def spec_str(arr) -> str:
    """Compact description of a jax.Array's sharding for checkpoint
    manifests: ``"replicated"``, a PartitionSpec repr for NamedShardings,
    or the sharding class name otherwise.  Informational only — restore
    re-lays arrays out onto the *current* mesh (reshard-on-restore), so
    the recorded spec never constrains the topology a run resumes at."""
    sharding = getattr(arr, "sharding", None)
    if sharding is None or getattr(arr, "is_fully_replicated", True):
        return "replicated"
    spec = getattr(sharding, "spec", None)
    if spec is not None:
        return str(spec)
    return type(sharding).__name__


def device_put_global(x, sharding):
    """Place one host array onto a (possibly process-spanning) sharding.

    Single-controller: plain ``device_put``.  Multi-controller: every
    process holds the full host value (the distributed checkpoint
    restore reassembles the global tree on every host), so
    ``make_array_from_callback`` carves out each process's addressable
    chunks locally — no cross-host traffic, and it works for ANY target
    sharding, which is what makes restore elastic: a tree saved at one
    process count lays out onto whatever mesh is live now.

    This IS the explicit staging chokepoint (the multi-controller
    analog of a bare ``device_put``, which ``jax.transfer_guard``
    exempts), so the callback's internal puts are locally exempted too
    — transfer-guarded training paths stay runnable multi-controller.
    """
    if jax.process_count() > 1:
        a = np.asarray(x)
        with jax.transfer_guard("allow"):
            return jax.make_array_from_callback(
                a.shape, sharding, lambda idx: a[idx])
    import jax.numpy as jnp

    return jax.device_put(jnp.asarray(x), sharding)


def tree_put_global(tree, shardings):
    """``device_put_global`` over a pytree of host arrays against a
    matching pytree of shardings (or one sharding for the whole tree)."""
    import jax.tree_util as jtu

    is_sharding = lambda s: hasattr(s, "device_set")  # noqa: E731
    if is_sharding(shardings):
        return jtu.tree_map(
            lambda x: device_put_global(x, shardings), tree)
    return jtu.tree_map(device_put_global, tree, shardings)


def make_strategy(name: str, mesh, **kw) -> ShardingStrategy:
    """String lowering (config-system entry point)."""
    name = name.lower()
    if name in ("dp", "data", "data_parallel", "replicated"):
        return DataParallel()
    if name in ("auto",):
        return AutoSharding(**kw)
    if name in ("ep", "expert", "expert_parallel"):
        axis = kw.pop("axis", "expert")
        if axis not in mesh.axis_names:
            raise ValueError(
                f"sharding='ep' needs a mesh with an {axis!r} axis (got "
                f"axes {tuple(mesh.axis_names)}); use "
                "init_zoo_context(mesh_shape=(d, e), "
                "axis_names=('data', 'expert'))")
        return ExpertParallel(axis=axis, **kw)
    if name in ("sp", "seq", "sequence", "sequence_parallel", "ring"):
        axis = kw.pop("axis", "seq")
        if axis not in mesh.axis_names:
            raise ValueError(
                f"sharding='sp' needs a mesh with a {axis!r} axis (got "
                f"axes {tuple(mesh.axis_names)}); use "
                "init_zoo_context(mesh_shape=(d, s), "
                "axis_names=('data', 'seq'))")
        return SequenceParallel(axis=axis, **kw)
    if name in ("pp", "pipe", "pipeline", "pipeline_parallel", "gpipe"):
        axis = kw.pop("axis", "pipe")
        if axis not in mesh.axis_names:
            raise ValueError(
                f"sharding='pp' needs a mesh with a {axis!r} axis (got "
                f"axes {tuple(mesh.axis_names)}); use "
                "init_zoo_context(mesh_shape=(d, p), "
                "axis_names=('data', 'pipe'))")
        return PipelineStrategy(axis=axis, **kw)
    if name in ("tp", "tensor", "tensor_parallel"):
        axis = kw.pop("axis", None)
        if axis is None:
            if len(mesh.axis_names) < 2:
                raise ValueError(
                    "sharding='tp' needs a mesh with a model axis (got "
                    f"axes {tuple(mesh.axis_names)}); use "
                    "init_zoo_context(mesh_shape=(d, t), "
                    "axis_names=('data', 'model')) or sharding='auto'")
            axis = mesh.axis_names[-1]
        return TensorParallel(axis=axis, **kw)
    raise ValueError(f"unknown sharding strategy {name!r}; "
                     "known: dp, tp, ep, sp, pp, auto")
