"""Pipeline parallelism: GPipe-style microbatched SPMD pipeline.

Reference capability: **absent** (SURVEY.md §2.4 — the reference's only
distributed-training strategy is synchronous data parallelism; PP is an
explicit gap).  This module is the TPU-native upgrade: layer *stages* are
sharded over a ``pipe`` mesh axis (each device holds one stage's weights),
microbatches flow through the ring via ``lax.ppermute`` neighbour
exchanges over ICI, and the whole schedule — fill, steady state, drain —
is one ``lax.scan`` inside one jitted SPMD program.  No send/recv runtime,
no scheduler thread: the schedule is data.

Design notes (the scaling-book recipe, not a torch-pipe translation):
- All devices run the SAME program (SPMD).  Stage identity comes from
  ``lax.axis_index``; a device computes its stage function on whatever
  activation it currently holds.
- Stage weights live stacked along a leading ``n_stages`` dim which is
  sharded over the pipe axis, so each device materialises only its own
  stage (1/S of the pipeline's parameters) — the PP memory win.
- The loop runs ``n_micro + n_stages - 1`` ticks.  At tick ``t`` stage
  ``s`` computes microbatch ``t - s``; bubbles at fill/drain are the
  standard GPipe cost (fraction ``(S-1)/(M+S-1)``).
- Everything (ppermute, where, dynamic slicing) is differentiable, so
  ``jax.grad`` of a pipelined forward IS pipelined backward — the reverse
  schedule falls out of autodiff, with activations rematerialised per
  ``jax.checkpoint`` policy if requested.

Constraint: ``stage_fn`` must be shape-preserving (activation in == out),
the canonical homogeneous-stack regime (transformer blocks, MLP blocks).
Embedding/head layers run outside the pipeline — apply them before/after.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.parallel.sequence import mark_varying as _pvary

StageFn = Callable[[Any, jax.Array], jax.Array]


def pipeline_spmd(stage_fn: StageFn, stage_params, x, axis_name: str,
                  n_microbatches: int, remat: bool = False,
                  vary_axes=None, aux=None):
    """Per-device body — call inside shard_map/pjit with ``axis_name``.

    ``stage_params``: this device's stage slice, leading dim 1 (the shard
    of the stacked (S, ...) pytree).  ``x``: the (B, ...) batch local to
    this device's data group (replicated over the pipe axis — every
    stage sees it; only stage 0 reads it).
    Returns the (B, ...) output, replicated over the pipe axis via a
    final psum.  ``vary_axes``: all shard_map axes the scan carries are
    device-varying over — pass ``(pipe, data)`` when composing with a
    data axis (defaults to ``(axis_name,)``).
    ``aux``: optional pytree of per-row side inputs (leading dim B —
    attention masks, segment ids) consumed by EVERY stage alongside its
    activation.  Aux never rides the ppermute ring: it is replicated
    over the pipe axis, and stage ``s`` at tick ``t`` indexes microbatch
    ``t - s`` directly (the one whose activation it holds), so
    ``stage_fn(params, x, aux)`` sees matched pairs.
    """
    S = lax.psum(1, axis_name)
    s = lax.axis_index(axis_name)
    local = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    M = n_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    mb = x.reshape((M, B // M) + x.shape[1:])
    aux_mb = jax.tree_util.tree_map(
        lambda a: a.reshape((M, B // M) + a.shape[1:]), aux)

    perm = [(i, (i + 1) % S) for i in range(S)]
    vary = vary_axes or (axis_name,)
    state0 = _pvary(jnp.zeros_like(mb[0]), vary)
    out0 = _pvary(jnp.zeros_like(mb), vary)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clip: drained ticks recompute the
        # last microbatch; their results are never collected)
        inj = lax.dynamic_index_in_dim(mb, jnp.clip(t, 0, M - 1), 0,
                                       keepdims=False)
        state = jnp.where(s == 0, inj, state)
        if aux is None:
            out = fn(local, state)
        else:
            # the microbatch whose activation this stage holds at tick t
            ai = jnp.clip(t - s, 0, M - 1)
            aux_t = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, ai, 0,
                                                   keepdims=False), aux_mb)
            out = fn(local, state, aux_t)
        # last stage emits microbatch t-(S-1) once the pipeline is full
        oi = t - (S - 1)
        upd = lax.dynamic_update_index_in_dim(
            outputs, out, jnp.clip(oi, 0, M - 1), 0)
        outputs = jnp.where((s == S - 1) & (oi >= 0), upd, outputs)
        # rotate activations one stage forward around the ring (ICI
        # neighbour exchange; the wraparound into stage 0 is overwritten
        # by the next injection)
        state = lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(M + S - 1))
    # outputs are zero except on the last stage; psum replicates them
    outputs = lax.psum(outputs, axis_name)
    return outputs.reshape((B,) + x.shape[1:])


def pipeline_apply(stage_fn: StageFn, stacked_params, x, mesh: Mesh,
                   axis_name: str = "pipe", n_microbatches: int = 4,
                   remat: bool = False, batch_axis: str = None, aux=None):
    """Run a homogeneous stage stack as a pipeline over ``mesh[axis_name]``.

    ``stacked_params``: pytree whose leaves have leading dim
    ``n_stages == mesh axis size`` (stage i's weights at index i).
    ``x``: (B, ...) batch.  Shape-preserving ``stage_fn(params, x) -> x``
    — or ``stage_fn(params, x, aux_microbatch)`` when ``aux`` is given.

    ``batch_axis``: compose pp×dp — shard the batch dim over this mesh
    axis; each data group runs its own pipeline over its pipe ring (the
    per-group microbatch count is still ``n_microbatches``, so the local
    B/dp must divide by it).
    ``aux``: pytree of (B, ...) side inputs (attention masks etc.) every
    stage reads alongside its activation — see ``pipeline_spmd``.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis_name not in sizes:
        raise ValueError(f"pipeline axis {axis_name!r} not in mesh axes "
                         f"{tuple(mesh.axis_names)}")
    S = sizes[axis_name]
    for path, leaf in jax.tree_util.tree_leaves_with_path(stacked_params):
        if leaf.shape[:1] != (S,):
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} has leading "
                f"dim {leaf.shape[:1]}, expected ({S},) to shard over "
                f"{axis_name!r}")

    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), stacked_params)
    x_spec = P(batch_axis) if batch_axis else P()
    vary = (axis_name, batch_axis) if batch_axis else (axis_name,)
    body = functools.partial(pipeline_spmd, stage_fn,
                             axis_name=axis_name,
                             n_microbatches=n_microbatches, remat=remat,
                             vary_axes=vary)
    if aux is None:
        fn = shard_map(lambda ps, xs: body(ps, xs), mesh=mesh,
                       in_specs=(param_specs, x_spec), out_specs=x_spec)
        return fn(stacked_params, x)
    aux_specs = jax.tree_util.tree_map(lambda a: x_spec, aux)
    fn = shard_map(lambda ps, xs, au: body(ps, xs, aux=au), mesh=mesh,
                   in_specs=(param_specs, x_spec, aux_specs),
                   out_specs=x_spec)
    return fn(stacked_params, x, aux)


def stack_stage_params(params_list):
    """Stack S per-stage pytrees (identical structure) into one pytree
    with leading dim S — the layout ``pipeline_apply`` shards."""
    return jax.tree_util.tree_map(
        lambda *ps: jnp.stack(ps, axis=0), *params_list)


def stage_shardings(mesh: Mesh, stacked_params, axis_name: str = "pipe"):
    """NamedShardings placing each stage's slice on its pipe device —
    feed to device_put so stage weights never materialise replicated."""
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh,
                                P(axis_name, *([None] * (p.ndim - 1)))),
        stacked_params)


class PipelineParallel:
    """Convenience harness: pipeline a stack of homogeneous blocks with a
    (non-pipelined) head and tail, and train it with any optax-style
    optimizer — the PP counterpart of the TensorParallel strategy.

    The reference has no pipeline engine to mirror (SURVEY §2.4 lists PP
    as an explicit gap); the API here follows this framework's layer
    protocol instead: ``stage_fn(params, x)`` pure functions.
    """

    def __init__(self, mesh: Mesh, axis_name: str = "pipe",
                 n_microbatches: int = 4, remat: bool = False):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if axis_name not in sizes:
            raise ValueError(f"axis {axis_name!r} not in mesh "
                             f"{tuple(mesh.axis_names)}")
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_stages = sizes[axis_name]
        self.n_microbatches = n_microbatches
        self.remat = remat

    def apply(self, stage_fn: StageFn, stacked_params, x):
        return pipeline_apply(stage_fn, stacked_params, x, self.mesh,
                              self.axis_name, self.n_microbatches,
                              self.remat)

    def shard_params(self, stacked_params):
        return jax.device_put(
            stacked_params,
            stage_shardings(self.mesh, stacked_params, self.axis_name))
