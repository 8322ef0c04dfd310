"""Estimator — the training/eval/predict engine.

Reference capability: ``InternalDistriOptimizer`` + ``Estimator``
(api/keras/models/Topology.scala:962-1598, pipeline/estimator/Estimator.scala:65).
The reference runs 2 Spark jobs per iteration (forward/backward tasks, then
a block-manager gradient shuffle + weight re-broadcast, wp-bigdl.md:113-160).

TPU-native design: ONE jitted SPMD step.  Parameters/optimizer state are
replicated over the mesh; the batch is sharded along the ``data`` axis;
``jax.grad`` of a sharded-batch loss makes XLA insert a single fused
all-reduce (psum) over ICI for the gradients.  The whole iteration —
forward, backward, allreduce, optimizer update — is one XLA program with
donated buffers, so there is no parameter server, no task launch overhead,
and no host round-trip in the hot loop.

Also carried over, re-designed:
- trigger-driven validation/checkpointing (`ZooTrigger` → core.triggers)
- failure retry from latest checkpoint within a sliding time window
  (Topology.scala:1179-1261; ``bigdl.failure.retryTimes`` /
  ``retryTimeInterval`` sysprops → ``failure_retry_times`` /
  ``failure_retry_interval_s`` config knobs)
- LocalEstimator (LocalEstimator.scala:39) collapses into this same class
  on a 1-device mesh.

TPU perf levers wired through config:
- ``compute_dtype="bfloat16"`` — mixed precision: master params/opt-state
  stay float32, forward/backward run in bf16 (MXU-native), loss and
  gradients accumulate in float32.
- ``data_prefetch`` — background-thread batch prep + device_put overlap
  (train/prefetch.py) so the chip never waits on host indexing.
- ``async_checkpoint`` — snapshot writes happen off-thread
  (train/checkpoint.py::save_async).
"""

from __future__ import annotations

import logging
import math
import pickle
import signal
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.core.context import (ZooContext, dist_barrier,
                                             explicit_prng_key,
                                             get_zoo_context)
from analytics_zoo_tpu.core.profiling import TIMERS, timeit
from analytics_zoo_tpu.core.triggers import (EveryEpoch, Trigger, TriggerState)
from analytics_zoo_tpu.observe import metrics as obs
from analytics_zoo_tpu.observe.export import publish_to_summary, to_prometheus
from analytics_zoo_tpu.observe.trace import TRACER
from analytics_zoo_tpu.nn import metrics as metrics_lib
from analytics_zoo_tpu.nn import objectives
from analytics_zoo_tpu.robust import (HostLostError, RetryPolicy,
                                      TrainingPreempted, faults)
from analytics_zoo_tpu.train import checkpoint as ckpt_lib
from analytics_zoo_tpu.train import optimizers as optim_lib
from analytics_zoo_tpu.train import prefetch as prefetch_lib

logger = logging.getLogger("analytics_zoo_tpu.train")


def _as_list(x) -> List[np.ndarray]:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _cast_floats(tree, dtype):
    """Cast floating leaves of a pytree to ``dtype`` (ints/bools pass)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, tree)


def _cast_like(tree, ref):
    """Cast every leaf of ``tree`` to the dtype of the matching ``ref``
    leaf (restores e.g. float32 BN statistics after a bf16 forward)."""
    return jax.tree_util.tree_map(
        lambda a, r: a.astype(jnp.asarray(r).dtype), tree, ref)


def _has_token_axis(y, kind: str) -> bool:
    """Integer labels (B, L) with L > 1 (a ``K`` chunk: (K, B, L)): one
    label a token, as a language model's next-token targets are."""
    return (jnp.issubdtype(y.dtype, jnp.integer)
            and y.ndim == (3 if kind == "K" else 2) and y.shape[-1] > 1)


def resident_epoch_indices(rng, n: int, shuffle: bool = True,
                           pair_structured: bool = False):
    """Gather order for ONE device-resident epoch over ``n`` rows.

    Runs INSIDE the jitted epoch body (``jax.random.permutation`` on
    device): every row index in [0, n) appears exactly once — full
    epoch coverage, unlike a with-replacement sampler.  Pair-structured
    losses (rank_hinge) permute (pos, neg) couples so partners stay
    adjacent (mirrors the host path's pair shuffle).  The tail beyond
    ``steps * batch`` is dropped by the caller's fori bound, matching
    the host path's ``drop_remainder`` — reshuffling each epoch varies
    which rows fall there.
    """
    if not shuffle:
        return jnp.arange(n)
    if pair_structured:
        pairs = jax.random.permutation(rng, n // 2)
        idx = jnp.stack([pairs * 2, pairs * 2 + 1], axis=1).reshape(-1)
        if n % 2:
            idx = jnp.concatenate([idx, jnp.asarray([n - 1])])
        return idx
    return jax.random.permutation(rng, n)


class Estimator:
    """fit/evaluate/predict over a model following the Layer protocol."""

    def __init__(self, model, optimizer="adam", loss="mse",
                 metrics: Optional[Sequence] = None,
                 ctx: Optional[ZooContext] = None,
                 grad_clip_norm: Optional[float] = None,
                 grad_clip_value: Optional[float] = None,
                 sharding="dp", compute_dtype: Optional[str] = None,
                 aux_loss_weight: float = 0.01,
                 grad_accum_steps: int = 1):
        self.model = model
        self.aux_loss_weight = aux_loss_weight
        self.tx = optim_lib.get(optimizer)
        # clip wraps the base optimizer BEFORE MultiSteps so that with
        # grad accumulation the clip sees the accumulated/averaged
        # gradient (conventional clip-after-accumulate semantics), not
        # each micro-batch gradient
        if grad_clip_norm is not None:
            self.tx = optax.chain(optax.clip_by_global_norm(grad_clip_norm), self.tx)
        elif grad_clip_value is not None:
            self.tx = optax.chain(optax.clip(grad_clip_value), self.tx)
        if grad_accum_steps > 1:
            # one optimizer update per A micro-batches: grads average in
            # f32 inside opt-state, params stay fixed between updates —
            # the A-times-larger effective batch without A-times the
            # activation memory (complements steps_per_execution, which
            # fuses real updates per dispatch)
            self.tx = optax.MultiSteps(self.tx, grad_accum_steps)
        self.grad_accum_steps = grad_accum_steps
        self._sharding_strategy = sharding  # "dp" | "tp" | ShardingStrategy
        self.loss_fn = objectives.get(loss)
        self.metrics = [metrics_lib.get(m) for m in (metrics or [])]
        self.ctx = ctx or get_zoo_context()
        # mixed precision: config `compute_dtype` knob, overridable per-run
        cd = compute_dtype or self.ctx.config.compute_dtype
        self.compute_dtype = jnp.dtype(cd) if cd not in (None, "float32") \
            else None

        # mutable training state (host handles to device arrays)
        self.params = None
        self.state = None
        self.opt_state = None
        self.global_step = 0
        self.finished_epochs = 0
        self.history: List[Dict[str, float]] = []

        self._ckpt_mgr: Optional[ckpt_lib.CheckpointManager] = None
        self._ckpt_trigger: Trigger = EveryEpoch()
        self._val_trigger: Optional[Trigger] = None
        self._val_batch: Optional[int] = None
        self._last_val_iter = -1
        self._last_val_result: Optional[Dict[str, float]] = None
        self._tb_writer = None
        self._rng = explicit_prng_key(self.ctx.config.seed)
        # resilience state (docs/ROBUSTNESS.md): the host-side shuffle rng
        # is an attribute (not a fit() local) so checkpoints can capture it
        # and fit(resume=True) can continue the exact shuffle stream
        self._host_rng = np.random.RandomState(self.ctx.config.seed)
        self._lr_scale = 1.0            # NaN-rollback learning-rate backoff
        self._guard = None              # device-resident NaN-guard carry
        self._pending_resume: Optional[Tuple[int, int, Any]] = None
        self._preempt = threading.Event()

        self._train_step = None
        self._multi_step = None
        self._eval_step = None
        self._predict_step = None
        self._resident_epoch = None
        self._resident_epoch_key = None
        self._stream_shard = None
        self._stream_shard_key = None
        self._stream_plan = None        # set by _resolve_data_path
        # which input path the last fit() ran ("device_resident" /
        # "stream" / "host_prefetch") and why — tests read these
        self.last_data_path: Optional[str] = None
        self.last_data_path_reason: Optional[str] = None
        # observability: the fit-level root span, the current epoch's
        # child span, and the metric snapshot taken at fit() entry
        # (training_report() deltas it)
        self._fit_span = None
        self._epoch_span = None
        self._fit_metrics_mark = None
        # training-side flight recorder (arm_flight_recorder): checked
        # at epoch boundaries, tripped manually on a HostLostError
        self._flight_recorder = None
        # monotone stream-rotation counter: makes the zoo_data_* barrier
        # names unique across NaN-rollback replays of the same epoch
        self._data_rotation = 0

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def set_checkpoint(self, path: str, over_write: bool = True,
                       trigger: Optional[Trigger] = None, keep: int = 3):
        cfg = self.ctx.config
        # Multi-controller runs get the sharded two-phase manager; so
        # does ANY run resuming a directory that already holds the
        # distributed layout — that's the elastic path (a 1-process run
        # restoring a 2-process run's shards).
        distributed = cfg.ckpt_distributed and (
            jax.process_count() > 1
            or ckpt_lib.has_distributed_layout(path))
        if distributed:
            self._ckpt_mgr = ckpt_lib.DistributedCheckpointManager(
                path, keep=keep, verify=cfg.ckpt_verify,
                barrier_timeout_s=cfg.dist_barrier_timeout_s)
        else:
            self._ckpt_mgr = ckpt_lib.CheckpointManager(
                path, keep=keep, verify=cfg.ckpt_verify)
        if trigger is not None:
            self._ckpt_trigger = trigger
        return self

    def set_tensorboard(self, log_dir: str):
        from analytics_zoo_tpu.core.summary import SummaryWriter
        self._tb_writer = SummaryWriter(log_dir)
        return self

    def arm_flight_recorder(self, *, window_s: float = 5.0,
                            out_dir: Optional[str] = None,
                            watch: Optional[Sequence] = None,
                            **kw):
        """Arm a training-side flight recorder (docs/OBSERVABILITY.md):
        windows are evaluated at epoch boundaries, watching the data
        tier's failure counters — a ``zoo_data_*`` barrier breach
        (``dist_barrier_timeouts_total``) or a stream-path downgrade
        (``data_stream_fallbacks_total``) trips a snapshot of the span
        ring + metric window.  A fatal ``HostLostError`` during fit()
        also trips it manually, so the mesh-death post-mortem keeps its
        evidence.  Extra ``watch`` pairs and FlightRecorder kwargs pass
        through.  Returns the recorder."""
        from analytics_zoo_tpu.observe.recorder import FlightRecorder

        counters = [("dist_barrier_timeouts_total", {}),
                    ("data_stream_fallbacks_total", {})]
        if watch:
            counters.extend(watch)
        self._flight_recorder = FlightRecorder(
            watch_counters=counters, window_s=window_s, out_dir=out_dir,
            **kw)
        self._flight_recorder.check()       # open the first window
        return self._flight_recorder

    # ------------------------------------------------------------------
    # initialization & compiled steps
    # ------------------------------------------------------------------
    def set_initial_weights(self, params, state=None):
        """Weights applied instead of random init at first build
        (used by ZooModel.load_model)."""
        self._initial_weights = (params, state or {})
        if self.params is not None:
            rep = self.ctx.replicated_sharding()
            self.params = jax.device_put(params, self._param_shardings(params))
            self.state = jax.device_put(state or {}, rep)
            self.opt_state = jax.jit(
                self.tx.init, out_shardings=self._opt_shardings())(self.params)
        return self

    def _strategy(self):
        """The resolved ShardingStrategy (strings lowered per-call against
        the current mesh, so one Estimator works across meshes)."""
        from analytics_zoo_tpu.parallel.sharding import (
            ShardingStrategy, make_strategy)

        strat = self._sharding_strategy
        if isinstance(strat, str):
            strat = make_strategy(strat, self.ctx.mesh)
        assert isinstance(strat, ShardingStrategy)
        # models that routed embedding tables to the sharded placement
        # carry a ``_sharded_tables`` manifest (models/recommendation.py);
        # wrap the user's strategy so those tables split row-wise over
        # the model axis and the trace sees the sharded lowering
        tables = getattr(self.model, "_sharded_tables", None)
        if tables:
            from analytics_zoo_tpu.parallel.table_sharding import \
                ensure_table_sharding
            strat = ensure_table_sharding(strat, tables)
        return strat

    def _param_shardings(self, params):
        """Per-parameter shardings from the strategy (replicated for DP;
        Megatron-style model-axis splits for TP; stacked block splits for
        PP — parallel/sharding.py)."""
        return self._strategy().param_shardings(self.ctx.mesh, params)

    def _opt_shardings(self):
        """Sharding tree for the optimizer state: subtrees shaped like the
        params pytree (adam mu/nu, momentum...) take the param shardings —
        so e.g. a row-sharded embedding table's Adam moments are sharded
        identically — everything else (step counts) is replicated
        (train/optimizers.py opt_state_shardings)."""
        from analytics_zoo_tpu.train.optimizers import opt_state_shardings
        return opt_state_shardings(
            self.tx, self.params, self._param_shardings(self.params),
            self.ctx.replicated_sharding())

    def _ensure_built(self, inputs: List[np.ndarray]):
        if self.params is not None:
            return
        self._rng, init_rng = jax.random.split(self._rng)
        shapes = [(2,) + tuple(x.shape[1:]) for x in inputs]
        # jit the one-time build: layer initializers create constants
        # (jnp.zeros biases, glorot scale factors) that are implicit
        # host->device transfers when run eagerly; inside jit they are
        # baked into the executable, so the build is silent under
        # jax.transfer_guard("disallow") and the params never bounce
        # through host numpy.  PRNG results are bit-identical either way.
        self.params, self.state = jax.jit(
            lambda r: self.model.init(r, *shapes))(init_rng)
        pending = getattr(self, "_initial_weights", None)
        if pending is not None:
            # merge by layer name so a superset (e.g. the full model a
            # sub-graph was cut from — nn/net.py new_graph) loads cleanly;
            # layers NOT covered keep random init, which is almost always
            # a bug on the user's side (renamed layer, wrong checkpoint) —
            # say so loudly
            pp, ps = pending
            if isinstance(pp, dict) and isinstance(self.params, dict):
                missing = sorted(set(self.params) - set(pp))
                if missing:
                    logger.warning(
                        "initial weights cover %d/%d layers; these keep "
                        "their RANDOM init: %s", len(pp), len(self.params),
                        missing)
                self.params = {k: pp.get(k, v)
                               for k, v in self.params.items()}
                self.state = {k: (ps or {}).get(k, v)
                              for k, v in self.state.items()}
            else:
                self.params, self.state = pending
        # place params per strategy; state replicated (small BN buffers);
        # optimizer state takes the matching param shardings explicitly
        # (tx.init's zeros_like would otherwise constant-fold onto one dev).
        rep = self.ctx.replicated_sharding()
        self.params = jax.device_put(self.params, self._param_shardings(self.params))
        self.state = jax.device_put(self.state, rep)
        # the step carry also includes the PRNG key: replicate it
        # EXPLICITLY here, or the first jitted step does an implicit
        # single-device -> mesh reshard (a hidden d2d transfer that
        # jax.transfer_guard("disallow") rejects)
        self._rng = jax.device_put(self._rng, rep)
        self.opt_state = jax.jit(
            self.tx.init, out_shardings=self._opt_shardings())(self.params)

    # ------------------------------------------------------------------
    # NaN/Inf guard (docs/ROBUSTNESS.md)
    # ------------------------------------------------------------------
    def _fresh_guard(self):
        """Device-resident guard carry: bad/consecutive-bad step counters
        plus the rollback learning-rate scale.  Rides the donated step
        carry so the happy path costs ZERO extra host syncs — the host
        reads it back once per epoch (``_check_nan_guard``)."""
        rep = self.ctx.replicated_sharding()
        # host numpy scalars + ONE explicit device_put: eager jnp.zeros
        # would be an implicit h2d transfer per leaf (trips
        # jax.transfer_guard("disallow") — the runtime twin of
        # zoolint JG-TRANSFER-HOT)
        return jax.device_put(
            {"bad": np.zeros((), np.int32),
             "consec": np.zeros((), np.int32),
             "max_consec": np.zeros((), np.int32),
             "lr_scale": np.float32(self._lr_scale)}, rep)

    @staticmethod
    def _guard_step(guard, finite):
        """One step's guard-carry update (traced inside the jitted step)."""
        bad_inc = jnp.where(finite, 0, 1).astype(jnp.int32)
        consec = jnp.where(finite, 0, guard["consec"] + 1).astype(jnp.int32)
        return {"bad": guard["bad"] + bad_inc,
                "consec": consec,
                "max_consec": jnp.maximum(guard["max_consec"], consec),
                "lr_scale": guard["lr_scale"]}

    def _check_nan_guard(self, steps_in_window: int) -> bool:
        """Epoch-boundary policy check: ONE host sync reads the guard
        carry back, applies ``nan_policy``, and re-arms a fresh guard.
        Returns True when the policy rolled training back to the last
        checkpoint (the caller must re-run from ``finished_epochs``)."""
        cfg = self.ctx.config
        g = jax.device_get(self._guard)
        TIMERS.incr("robust/guard_check")
        self._guard = self._fresh_guard()
        bad = int(g["bad"])
        max_consec = int(g["max_consec"])
        if bad == 0:
            return False
        TIMERS.incr("robust/nan_steps", bad)
        logger.warning("%d/%d steps had a non-finite loss (max %d "
                       "consecutive); nan_policy=%s", bad, steps_in_window,
                       max_consec, cfg.nan_policy)
        if cfg.nan_policy == "raise":
            TIMERS.incr("robust/nan_raised")
            raise FloatingPointError(
                f"{bad} non-finite training step(s) in the last "
                f"{steps_in_window} (nan_policy=raise); the bad updates "
                f"were skipped on device, params remain finite")
        TIMERS.incr("robust/nan_skipped", bad)
        if cfg.nan_policy == "rollback" and max_consec >= cfg.max_bad_steps:
            if self._ckpt_mgr is not None:
                self._ckpt_mgr.wait(raise_errors=False)
            if (self._ckpt_mgr is None
                    or self._ckpt_mgr.latest_step() is None):
                raise FloatingPointError(
                    f"{max_consec} consecutive non-finite steps >= "
                    f"max_bad_steps={cfg.max_bad_steps} but no checkpoint "
                    "to roll back to (set_checkpoint first)")
            # back off from the LIVE scale (restore would reset it to the
            # checkpoint's value, so repeated rollbacks must compound past
            # the restore)
            backed_off = self._lr_scale * cfg.nan_backoff_factor
            TIMERS.incr("robust/nan_rollbacks")
            logger.warning(
                "rolling back to last checkpoint after %d consecutive "
                "non-finite steps; learning-rate scale backed off to %.4g",
                max_consec, backed_off)
            self._restore_checkpoint()
            self._lr_scale = backed_off
            self._guard = self._fresh_guard()   # picks up the new lr_scale
            return True
        if cfg.nan_policy == "skip" and max_consec >= cfg.max_bad_steps:
            raise FloatingPointError(
                f"{max_consec} consecutive non-finite steps >= "
                f"max_bad_steps={cfg.max_bad_steps} under nan_policy=skip "
                "— training is making no progress")
        return False

    def _build_train_step(self):
        model, loss_fn, tx = self.model, self.loss_fn, self.tx
        data_shard = self.ctx.data_sharding()
        rep = self.ctx.replicated_sharding()
        cdtype = self.compute_dtype
        aux_w = self.aux_loss_weight
        # transfer-learning freeze (nn/net.py GraphNet.freeze): frozen
        # top-level param subtrees get zero updates inside the jitted step
        frozen = frozenset(getattr(model, "_frozen", ()))
        self._frozen_built = frozen

        strat = self._strategy()
        mesh = self.ctx.mesh

        guard_step = self._guard_step

        def step(params, state, opt_state, rng, guard, xs, y):
            # rng is carried ON DEVICE and split inside the step — passing
            # a host step counter per step would cost a blocking scalar
            # transfer per iteration
            rng, sub = jax.random.split(rng)

            def lossf(p, rng=sub):
                # Mixed precision: params + float inputs cast to the
                # compute dtype for forward/backward (bf16 on the MXU);
                # the cast's transpose re-accumulates grads in f32 against
                # the f32 master params, and the loss is taken in f32.
                if cdtype is not None:
                    p_c = _cast_floats(p, cdtype)
                    xs_c = _cast_floats(xs, cdtype)
                    st_c = _cast_floats(state, cdtype)
                else:
                    p_c, xs_c, st_c = p, xs, state
                # the strategy context is live while jit TRACES this body:
                # layers with a parallel lowering (ring attention for SP,
                # the GPipe block stack for PP) read it and bake the
                # regime into the compiled program (parallel/mode.py)
                with strat.activate(mesh):
                    preds, new_state = model.call(p_c, st_c, *xs_c,
                                                  training=True, rng=rng)
                if cdtype is not None:
                    preds = _cast_floats(preds, jnp.float32)
                    new_state = _cast_like(new_state, state)
                loss = loss_fn(y, preds)
                # weight-decay regularizers on the f32 master params (a
                # literal 0.0 when no layer has one) + layer auxiliary
                # losses (SparseMoE load balancing, surfaced via state)
                reg = getattr(model, "regularization_loss", None)
                if reg is not None:
                    loss = loss + reg(p)
                if aux_w:
                    from analytics_zoo_tpu.nn.layers.moe import moe_aux_loss
                    loss = loss + aux_w * moe_aux_loss(new_state)
                return loss, new_state

            (loss, new_state), grads = jax.value_and_grad(
                lossf, has_aux=True)(params)
            updates, new_opt = tx.update(grads, opt_state, params)
            # NaN-rollback LR backoff: a replicated scalar in the guard
            # carry scales the update — changing it costs no recompile
            updates = jax.tree_util.tree_map(
                lambda u: u * guard["lr_scale"].astype(u.dtype)
                if jnp.issubdtype(jnp.asarray(u).dtype, jnp.floating) else u,
                updates)
            if frozen:
                updates = {
                    k: (jax.tree_util.tree_map(jnp.zeros_like, u)
                        if k in frozen else u)
                    for k, u in updates.items()}
            new_params = optax.apply_updates(params, updates)
            # NaN/Inf guard: a non-finite loss means this update is junk —
            # discard it ON DEVICE (params/state/opt keep their pre-step
            # values) and count it in the carried guard; the host applies
            # the nan_policy at epoch granularity (zero per-step syncs)
            finite = jnp.isfinite(loss)

            def keep_if_finite(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(finite, a, b), new, old)

            new_params = keep_if_finite(new_params, params)
            new_state = keep_if_finite(new_state, state)
            new_opt = keep_if_finite(new_opt, opt_state)
            return (new_params, new_state, new_opt, rng,
                    guard_step(guard, finite), loss)

        # params/state/opt shardings are inherited from their device_put
        # placement (replicated for DP, model-axis split for TP) — pinning
        # only the batch keeps one step implementation for every strategy.
        self._train_step = jax.jit(
            step,
            in_shardings=(None, None, None, rep, rep, data_shard,
                          data_shard),
            donate_argnums=(0, 1, 2, 3, 4),
        )
        self._single_step_fn = step

    def _build_multi_step(self):
        """K steps per dispatch: lax.scan over a (K, B, ...) superbatch
        uploaded in ONE transfer (``steps_per_execution`` config knob).

        Amortizes per-step host->device latency — the TPU-native answer to
        the reference's per-iteration Spark job launches (wp-bigdl.md:171
        measured >10%% overhead at 500 tasks/iter; here the dispatch cost
        goes to ~zero for K >> 1).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._train_step is None:
            self._build_train_step()
        single = self._single_step_fn
        rep = self.ctx.replicated_sharding()
        # batch axis is axis 1 of the (K, B, ...) superbatch
        chunk_shard = NamedSharding(self.ctx.mesh, P(None, self.ctx.data_axis))

        def multi(params, state, opt_state, rng, guard, xs_stack, y_stack):
            def body(carry, batch):
                p, s, o, r, g = carry
                bxs, by = batch
                p, s, o, r, g, loss = single(p, s, o, r, g, bxs, by)
                return (p, s, o, r, g), loss

            (params, state, opt_state, rng, guard), losses = jax.lax.scan(
                body, (params, state, opt_state, rng, guard),
                (xs_stack, y_stack))
            return params, state, opt_state, rng, guard, losses

        self._multi_step = jax.jit(
            multi,
            in_shardings=(None, None, None, rep, rep, chunk_shard,
                          chunk_shard),
            donate_argnums=(0, 1, 2, 3, 4),
        )

    def _build_resident_epoch(self, n: int, eff_batch: int, steps: int,
                              shuffle: bool):
        """ONE jitted program per epoch over HBM-resident arrays: an
        on-device ``jax.random.permutation`` picks the epoch's gather
        order, and a ``fori_loop`` of ``steps`` train steps slices the
        permutation and gathers each minibatch from the resident arrays
        in-step.  The carry (params/state/opt/rng) is donated, the data
        arrays are NOT (they feed every epoch) — so an epoch moves zero
        bytes host→device and costs one dispatch (the TPU answer to the
        reference's per-iteration Spark jobs AND to per-batch
        ``device_put``)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (n, eff_batch, steps, bool(shuffle))
        if self._resident_epoch is not None \
                and self._resident_epoch_key == key:
            return self._resident_epoch
        if self._train_step is None:
            self._build_train_step()
        single = self._single_step_fn
        mesh = self.ctx.mesh
        data_axis = self.ctx.data_axis
        pair_structured = getattr(self.loss_fn, "batch_structured", False)

        def constrain(v):
            # gathered minibatches shard over the data axis like any
            # host-fed batch, whatever the resident arrays' placement
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(data_axis,
                                         *([None] * (v.ndim - 1)))))

        def epoch(params, state, opt_state, rng, guard, xs, y):
            rng, prm = jax.random.split(rng)
            perm = resident_epoch_indices(
                prm, n, shuffle=shuffle, pair_structured=pair_structured)

            def body(i, carry):
                p, s, o, r, g, loss_sum, good = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * eff_batch,
                                                   eff_batch)
                bxs = [constrain(jnp.take(a, idx, axis=0)) for a in xs]
                by = constrain(jnp.take(y, idx, axis=0))
                p, s, o, r, g, loss = single(p, s, o, r, g, bxs, by)
                # NaN guard: bad-step counts accumulate in the carried
                # guard; the epoch-mean loss aggregates finite steps only
                # so one bad step cannot poison the reported loss
                finite = jnp.isfinite(loss)
                loss_sum = loss_sum + jnp.where(finite, loss, 0.0)
                good = good + finite.astype(jnp.int32)
                return (p, s, o, r, g, loss_sum, good)

            carry = (params, state, opt_state, rng, guard,
                     jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
            (params, state, opt_state, rng, guard, loss_sum,
             good) = jax.lax.fori_loop(0, steps, body, carry)
            mean = loss_sum / jnp.maximum(good, 1).astype(jnp.float32)
            return params, state, opt_state, rng, guard, mean

        self._resident_epoch = jax.jit(epoch, donate_argnums=(0, 1, 2, 3, 4))
        self._resident_epoch_key = key
        return self._resident_epoch

    def _put_sharded(self, arrs: List[np.ndarray], shard):
        """Host batch → device arrays under ``shard``.  Multi-controller
        processes hold only their LOCAL rows of the global batch; the
        runtime assembles the global array without cross-host copies
        (every process must supply the same row count per step)."""
        TIMERS.incr("estimator/host_device_put", len(arrs))
        obs.count("data_upload_bytes_total", sum(a.nbytes for a in arrs))
        if self.ctx.process_count > 1:
            return [jax.make_array_from_process_local_data(
                shard, np.asarray(a)) for a in arrs]
        return [jax.device_put(jnp.asarray(a), shard) for a in arrs]

    @property
    def _data_div(self) -> int:
        """Row-count divisor for batches: local devices under
        multi-controller (batches count process-local rows), the full
        mesh otherwise."""
        return (self.ctx.local_device_count if self.ctx.process_count > 1
                else self.ctx.num_devices)

    def _global_eff_batch(self, batch_size: int) -> int:
        """The GLOBAL effective batch the resident/stream programs
        dispatch: ``batch_size`` rounded up to the per-process divisor,
        times the process count — ``batch_size`` follows the host
        path's convention of counting PROCESS-LOCAL rows under
        multi-controller, so a worker passing
        ``global_batch // process_count`` yields the same global
        geometry (and therefore the same stream plan / shard cursor) at
        every topology.  That invariance is what makes preempt-resume
        elastic across process counts."""
        d = self._data_div
        eff = int(math.ceil(max(batch_size, d) / d)) * d
        if self.ctx.process_count > 1:
            eff *= self.ctx.process_count
        return eff

    def _commit_carry(self, tree):
        """Commit the training carry (params/state/opt/rng)
        mesh-replicated before the first resident/stream dispatch —
        compile stability (see the call sites) AND, under
        multi-controller, the host-local leaves must become
        process-spanning global arrays or the jitted shard program
        would see mixed layouts.  Leaves already laid out on the
        global mesh (a reshard-on-restore) pass through untouched."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.ctx.mesh, P())
        if self.ctx.process_count == 1:
            return jax.device_put(tree, rep)
        from analytics_zoo_tpu.parallel.sharding import device_put_global

        def put(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                return x
            return device_put_global(x, rep)

        return jax.tree_util.tree_map(put, tree)

    def _shard_chunk(self, arrs: List[np.ndarray]):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # batch axis is axis 1 of the (K, B, ...) superbatch
        shard = NamedSharding(self.ctx.mesh, P(None, self.ctx.data_axis))
        with timeit("estimator/shard_chunk"):
            return self._put_sharded(arrs, shard)

    def _build_eval_step(self):
        model, loss_fn, mets = self.model, self.loss_fn, self.metrics
        data_shard = self.ctx.data_sharding()
        rep = self.ctx.replicated_sharding()

        batch_structured = getattr(loss_fn, "batch_structured", False)
        supports_mask = getattr(loss_fn, "supports_mask", False)
        mask_count = getattr(loss_fn, "mask_count", None)
        cdtype = self.compute_dtype
        strat = self._strategy()
        mesh = self.ctx.mesh

        def step(params, state, xs, y, mask):
            if cdtype is not None:
                params = _cast_floats(params, cdtype)
                state = _cast_floats(state, cdtype)
                xs = _cast_floats(xs, cdtype)
            with strat.activate(mesh):
                preds, _ = model.call(params, state, *xs, training=False,
                                      rng=None)
            if cdtype is not None:
                preds = _cast_floats(preds, jnp.float32)
            if batch_structured and supports_mask:
                # Loss couples rows across the batch (e.g. rank_hinge) but
                # can exclude padded rows exactly via its mask support;
                # aggregation weight = the loss's own unit count (pairs).
                cnt = mask_count(mask) if mask_count else jnp.sum(mask)
                stats = {"loss_sum": loss_fn(y, preds, mask=mask) * cnt,
                         "count": cnt}
            elif batch_structured:
                # Couples rows and has no mask support: compute over the
                # whole batch; padded rows are a small approximation on
                # the final partial batch only.
                stats = {"loss_sum": loss_fn(y, preds) * jnp.sum(mask),
                         "count": jnp.sum(mask)}
            else:
                # Per-sample losses (vmap over the mean-reduced loss, B=1)
                # so padded rows are excluded exactly via the mask.
                per = jax.vmap(
                    lambda yt, yp: loss_fn(yt[None], yp[None]))(y, preds)
                stats = {"loss_sum": jnp.sum(per * mask),
                         "count": jnp.sum(mask)}
            out = {"__loss": stats}
            for m in mets:
                out[m.name] = m.update(y, preds, mask)
            return out

        self._eval_step = jax.jit(
            step, in_shardings=(None, None, data_shard, data_shard, data_shard),
            out_shardings=rep)

    def _build_predict_step(self):
        model = self.model
        data_shard = self.ctx.data_sharding()
        rep = self.ctx.replicated_sharding()
        cdtype = self.compute_dtype

        strat = self._strategy()
        mesh = self.ctx.mesh

        def step(params, state, xs):
            if cdtype is not None:
                params = _cast_floats(params, cdtype)
                state = _cast_floats(state, cdtype)
                xs = _cast_floats(xs, cdtype)
            with strat.activate(mesh):
                preds, _ = model.call(params, state, *xs, training=False,
                                      rng=None)
            if cdtype is not None:
                preds = _cast_floats(preds, jnp.float32)
            return preds

        # Multi-controller: a data-sharded output spans non-addressable
        # devices, so each process could not read its rows back —
        # replicate the (small, batch-sized) predictions instead and let
        # predict_raw slice out the local rows.
        out_shard = (rep if self.ctx.process_count > 1 else data_shard)
        self._predict_step = jax.jit(
            step, in_shardings=(None, None, data_shard),
            out_shardings=out_shard)

    # ------------------------------------------------------------------
    # data plumbing
    # ------------------------------------------------------------------
    def _pad_to_devices(self, arrs: List[np.ndarray], batch: int
                        ) -> Tuple[List[np.ndarray], int]:
        """Pad batch dim up to ``batch`` (already a mesh-size multiple) so
        every step sees ONE static shape (no per-remainder recompiles);
        returns the real row count."""
        n = arrs[0].shape[0]
        d = self._data_div
        target = max(batch, d, int(math.ceil(n / d)) * d)
        if target == n:
            return arrs, n
        padded = []
        for a in arrs:
            pad = np.zeros((target - n,) + a.shape[1:], a.dtype)
            padded.append(np.concatenate([a, pad], axis=0))
        return padded, n

    def _shard_batch(self, arrs: List[np.ndarray]):
        with timeit("estimator/shard_batch"):
            return self._put_sharded(arrs, self.ctx.data_sharding())

    def _maybe_midepoch_validation(self, validation_data, epoch: int,
                                   train_batch: int):
        """Iteration-granular validation: when a ``validation_trigger``
        (e.g. SeveralIteration) fires between epoch boundaries, evaluate
        now and record a history row (reference validates at arbitrary
        trigger points inside the optimizer loop, Topology.scala:223-244).
        Loss is not materialised here to avoid a per-step device sync."""
        if validation_data is None or self._val_trigger is None:
            return
        tstate = TriggerState(epoch=epoch, iteration=self.global_step,
                              epoch_finished=False)
        if not self._val_trigger(tstate):
            return
        self._last_val_iter = self.global_step
        val = self.evaluate(validation_data[0], validation_data[1],
                            batch_size=self._val_batch or train_batch)
        self._last_val_result = val
        rec = {"iteration": self.global_step}
        rec.update({f"val_{k}": v for k, v in val.items()})
        self.history.append(rec)
        if self._tb_writer is not None:
            for k, v in rec.items():
                if k != "iteration":
                    self._tb_writer.add_scalar(k, v, self.global_step)

    # ------------------------------------------------------------------
    # fit
    # ------------------------------------------------------------------
    def fit(self, x, y=None, batch_size: int = 32, epochs: int = 1,
            validation_data=None, end_trigger: Optional[Trigger] = None,
            shuffle: bool = True, verbose: bool = True,
            validation_trigger: Optional[Trigger] = None,
            validation_batch_size: Optional[int] = None,
            resume: bool = False):
        """Synchronous SPMD training with retry-from-checkpoint.

        ``x`` — array or list of arrays (multi-input models); or a
        FeatureSet/dataset yielding ``(inputs..., y)`` batches.
        ``validation_trigger`` — evaluate only when it fires (default:
        every epoch); ``validation_batch_size`` defaults to the training
        batch (reference setValidation trigger/batch semantics,
        Topology.scala:223-244).
        ``resume`` — continue from the newest intact checkpoint (set via
        ``set_checkpoint`` or the ``checkpoint_dir`` config knob): full
        training state — params, optimizer, device AND host rng streams,
        epoch/step position — is restored, so an interrupted run re-run
        with ``resume=True`` reproduces the uninterrupted run exactly
        (docs/ROBUSTNESS.md).  A SIGTERM during fit flushes one final
        synchronous checkpoint and raises
        :class:`~analytics_zoo_tpu.robust.TrainingPreempted`.
        """
        from analytics_zoo_tpu.data.featureset import FeatureSet

        self._val_trigger = validation_trigger
        self._val_batch = validation_batch_size
        if resume:
            self._try_resume()
        else:
            # a non-resuming fit() replays the configured shuffle stream
            # from its seed (deterministic runs); resume instead restores
            # the stream position from the checkpoint manifest
            self._host_rng = np.random.RandomState(self.ctx.config.seed)
            self._pending_resume = None
        self._preempt.clear()
        # freeze()/unfreeze() after a previous fit must take effect: the
        # compiled step captured the old frozen set, so rebuild it
        cur_frozen = frozenset(getattr(self.model, "_frozen", ()))
        if (self._train_step is not None
                and cur_frozen != getattr(self, "_frozen_built", cur_frozen)):
            self._train_step = None
            self._multi_step = None
            self._resident_epoch = None
            self._stream_shard = None
        restore_sig = self._install_preempt_handler()
        # fit-level root span + metric mark: every epoch and checkpoint
        # span chains under this trace, and training_report() deltas the
        # registry against the mark so it covers exactly this run
        self._fit_metrics_mark = obs.METRICS.snapshot()
        self._fit_span = TRACER.start("train/fit", epochs=epochs,
                                      batch_size=batch_size)
        try:
            if isinstance(x, FeatureSet):
                path, reason = self._resolve_data_path(x, batch_size)
                self.last_data_path, self.last_data_path_reason = \
                    path, reason
                if path == "device_resident":
                    out = self._fit_device_resident(
                        x, batch_size, epochs, validation_data,
                        end_trigger, verbose, shuffle)
                elif path == "stream":
                    out = self._fit_stream(
                        x, batch_size, epochs, validation_data,
                        end_trigger, verbose, shuffle)
                else:
                    out = self._fit_featureset(x, batch_size, epochs,
                                               validation_data, end_trigger,
                                               verbose, shuffle)
            else:
                out = self._fit_arrays(x, y, batch_size, epochs,
                                       validation_data, end_trigger, shuffle,
                                       verbose)
            self._fit_span.end(epochs_done=self.finished_epochs)
            return out
        except BaseException as e:
            if self._epoch_span is not None:
                self._epoch_span.end(status="error", error=str(e))
                self._epoch_span = None
            if (self._flight_recorder is not None
                    and isinstance(e, HostLostError)):
                # a mesh-death is exactly the moment operators need the
                # span ring + metric window preserved — trip manually,
                # the periodic check never runs again in this process
                self._flight_recorder.trigger(
                    "host_lost", {"barrier": e.barrier,
                                  "timeout_s": e.timeout_s})
            self._fit_span.end(status=type(e).__name__, error=str(e))
            raise
        finally:
            restore_sig()

    # ------------------------------------------------------------------
    # observability (docs/OBSERVABILITY.md)
    # ------------------------------------------------------------------
    def training_report(self) -> Dict[str, Any]:
        """Training-side observability rollup — the fit() analog of
        serving ``health()``: progress counters, the labeled-metric
        delta since the last ``fit()`` entered (step/epoch timings,
        checkpoint ops, loss/throughput gauges), and span-ring stats so
        a run's timeline is known to be reconstructable."""
        report: Dict[str, Any] = {
            "global_step": self.global_step,
            "finished_epochs": self.finished_epochs,
            "last_data_path": self.last_data_path,
            "history": list(self.history),
            "spans": {
                "completed": TRACER.completed_count(),
                "active": TRACER.active_count(),
                "ring": TRACER.ring_size(),
            },
        }
        if self._fit_span is not None:
            report["fit_trace"] = self._fit_span.trace
        if self._fit_metrics_mark is not None:
            report["metrics_delta"] = obs.METRICS.delta(
                self._fit_metrics_mark)
        return report

    def metrics_text(self) -> str:
        """The labeled metric registry in Prometheus text format."""
        return to_prometheus(obs.METRICS)

    def publish_metrics(self, step: Optional[int] = None) -> int:
        """Bridge the labeled registry into the TensorBoard writer set
        via ``set_tensorboard`` (no-op 0 without one); returns the
        number of scalars written."""
        if self._tb_writer is None:
            return 0
        return publish_to_summary(self._tb_writer,
                                  step if step is not None
                                  else self.global_step)

    # ------------------------------------------------------------------
    # resilience plumbing (docs/ROBUSTNESS.md)
    # ------------------------------------------------------------------
    def _try_resume(self) -> bool:
        """``fit(resume=True)``: restore full training state from the
        newest intact checkpoint; a missing checkpoint is a fresh start,
        never an error (so the same command line works for attempt #1
        and every restart after a preemption)."""
        cfg = self.ctx.config
        if self._ckpt_mgr is None and cfg.checkpoint_dir:
            self.set_checkpoint(cfg.checkpoint_dir)
        if self._ckpt_mgr is None or self._ckpt_mgr.latest_step() is None:
            logger.info("fit(resume=True): no checkpoint found; "
                        "starting fresh")
            self._host_rng = np.random.RandomState(cfg.seed)
            self._pending_resume = None
            return False
        self._restore_checkpoint()
        TIMERS.incr("robust/auto_resume")
        return True

    def _install_preempt_handler(self) -> Callable[[], None]:
        """SIGTERM → request a final synchronous checkpoint at the next
        step boundary (the preemption story: lose at most one step, not
        the run).  Returns a callable restoring the previous handler.
        No-op off the main thread (signal.signal would raise)."""
        if threading.current_thread() is not threading.main_thread():
            return lambda: None

        def _on_sigterm(signum, frame):
            logger.warning("SIGTERM received: flushing a final checkpoint "
                           "at the next step boundary")
            self._preempt.set()

        try:
            prev = signal.signal(signal.SIGTERM, _on_sigterm)
        except (ValueError, OSError):
            return lambda: None

        def restore():
            try:
                signal.signal(signal.SIGTERM, prev)
            except (ValueError, OSError, TypeError):
                pass

        return restore

    def _flush_preempt(self, epoch: int, in_epoch_step: int,
                       epoch_rng_state) -> None:
        """Preemption (SIGTERM or injected): flush ONE synchronous
        checkpoint carrying the mid-epoch resume manifest, then abort
        fit with :class:`TrainingPreempted`."""
        step = self.global_step
        if self._ckpt_mgr is not None:
            # DistributedCheckpointManager flushes barrier-free (peers
            # are dying on their own schedule); the single-process
            # manager's plain save is already barrier-free
            saver = getattr(self._ckpt_mgr, "save_preempt",
                            self._ckpt_mgr.save)
            saver(step, self._snapshot(
                resume_epoch=epoch, in_epoch_step=in_epoch_step,
                epoch_rng_state=epoch_rng_state))
            TIMERS.incr("robust/preempt_flush")
            logger.warning(
                "preempted at global step %d (epoch %d, in-epoch step %d): "
                "final synchronous checkpoint flushed; fit(resume=True) "
                "continues exactly here", step, epoch + 1, in_epoch_step)
        else:
            logger.warning("preempted at global step %d with NO checkpoint "
                           "manager set; training state is lost", step)
        raise TrainingPreempted(
            f"training preempted at global step {step}", step=step)

    def _maybe_preempt(self, epoch: int, in_epoch_step: int,
                       epoch_rng_state=None) -> None:
        """Per-step preemption check (host paths; the device-resident
        path checks between its one-dispatch epochs)."""
        if faults.fire("estimator.preempt") is not None:
            self._preempt.set()
        if self._preempt.is_set():
            self._flush_preempt(epoch, in_epoch_step, epoch_rng_state)

    @staticmethod
    def _inject_step_faults(bx, by):
        """Chaos hook consulted once per prepared dispatch: a planned
        ``estimator.step`` fault either raises (pipeline failure) or
        NaN-poisons the batch (numerical blow-up) — both exactly at the
        planned dispatch index."""
        plan = faults.fire("estimator.step")
        if plan is not None:
            if plan.exc is not None:
                raise plan.exc
            if plan.action == "nan":
                poisoned = faults.poison_nan(list(bx) + [by])
                bx, by = poisoned[:-1], poisoned[-1]
        return bx, by

    def _dispatch_step(self, kind, batch_x, batch_y, *, epoch_fn=None,
                       epoch_steps=None):
        """THE training dispatch point — every fit path funnels here.

        All three compiled step shapes share one calling convention (a
        6-tuple donated carry in, the advanced carry + loss out), so
        folding them lets both humans and static analysis reason about
        one step-fn dispatch instead of three:

        - ``"1"``     — one jitted train step on a (B, ...) batch
        - ``"K"``     — the lax.scan multi-step on a (K, B, ...)
                        superbatch (``steps_per_execution``)
        - ``"epoch"`` — the device-resident whole-epoch program
                        (caller supplies ``epoch_fn`` + ``epoch_steps``)
        - ``"shard"`` — the STREAM tier's whole-shard program (same
                        calling convention as "epoch"; ``batch_x``
                        carries the epoch loss accumulator as its first
                        leaf and the loss out is the advanced
                        accumulator)

        Returns ``(advanced_steps, loss)`` with ``loss`` still on
        device: per-step losses for "1"/"K", the epoch mean for
        "epoch", the accumulator for "shard".  ``global_step`` advances
        here and nowhere else during fit.
        """
        if kind in ("epoch", "shard"):
            fn, k = epoch_fn, int(epoch_steps)
        elif kind == "K":
            # the superbatch leading axis IS the step count (tail
            # chunks shorter than steps_per_execution included)
            fn, k = self._multi_step, int(batch_y.shape[0])
        else:
            fn, k = self._train_step, 1
        # dispatch-side wall time: the carry returns while the device
        # still computes, so this is host dispatch latency, not step math
        with obs.time_stage("train_step_seconds", kind=kind):
            (self.params, self.state, self.opt_state, self._rng,
             self._guard, loss) = fn(self.params, self.state,
                                     self.opt_state, self._rng,
                                     self._guard, batch_x, batch_y)
        obs.count("train_steps_total", k, kind=kind)
        if kind in ("1", "K") and _has_token_axis(batch_y, kind):
            obs.count("train_tokens_total", int(batch_y.size))
        self.global_step += k
        return k, loss

    def _fit_arrays(self, x, y, batch_size, epochs, validation_data,
                    end_trigger, shuffle, verbose):
        from analytics_zoo_tpu.data.gather import gather_rows

        xs = _as_list(x)
        assert y is not None, "y required for array training"
        n = xs[0].shape[0]
        # multi-controller: x/y are the process-LOCAL shard of the dataset
        # and batch_size counts local rows, so divisibility is against the
        # local device count (the global batch is local x process_count).
        d = self._data_div
        eff_batch = max(batch_size, d)
        if batch_size % d != 0:
            eff_batch = int(math.ceil(batch_size / d)) * d
            logger.warning("batch_size %d not divisible by %d devices; "
                           "using %d", batch_size, d, eff_batch)
        steps_per_epoch = n // eff_batch
        if steps_per_epoch == 0:
            raise ValueError(f"dataset ({n}) smaller than batch ({eff_batch})")
        dropped = n - steps_per_epoch * eff_batch
        if dropped:
            logger.warning(
                "dropping %d/%d samples per epoch (dataset not a multiple of "
                "batch %d); reshuffling each epoch varies which are dropped",
                dropped, n, eff_batch)

        self._ensure_built(xs)
        if self._train_step is None:
            self._build_train_step()

        cfg = self.ctx.config
        # Failure-retry semantics of the reference's retryTimes /
        # retryTimeInterval pair (Topology.scala:1179-1261), now expressed
        # through the reusable RetryPolicy: failures age out of a sliding
        # window, and each retry backs off exponentially before restoring
        # the last checkpoint.
        retry = RetryPolicy.from_config(
            cfg, max_attempts=cfg.failure_retry_times,
            window_s=cfg.failure_retry_interval_s,
            name="estimator_fit").state()
        K = max(1, int(cfg.steps_per_execution))
        if K > 1 and self._val_trigger is not None:
            logger.warning(
                "steps_per_execution=%d: validation/trigger checks happen "
                "every K-th iteration (K-step chunks are one dispatch)", K)
        if K > 1 and self._multi_step is None:
            self._build_multi_step()
        n_chunks = steps_per_epoch // K if K > 1 else 0
        rem = steps_per_epoch - n_chunks * K
        epoch = self.finished_epochs
        self._guard = self._fresh_guard()
        # Device-resident mode: when the caller hands in jax.Arrays, every
        # epoch's shuffle permutation, gather, and (K, B) reshape happen ON
        # DEVICE — an epoch moves zero bytes host→device.  This is the hot
        # path for data that fits HBM (e.g. the NCF north-star convergence
        # run pre-samples all epochs on device and trains from the
        # resident arrays).
        # (multi-controller is excluded: _put_sharded must pull chunks to
        # host for make_array_from_process_local_data there, which would
        # make device inputs a device→host→device round trip per batch)
        device_resident = (all(isinstance(a, jax.Array) for a in xs)
                           and isinstance(y, jax.Array)
                           and self.ctx.process_count == 1)
        self.last_data_path = ("device_resident" if device_resident
                               else "host_prefetch")
        self.last_data_path_reason = ("jax.Array inputs" if device_resident
                                      else "host array inputs")
        # labels that numpy can only build row by row (lazy rows with a
        # shape and fancy indexing but no ``__array__``: a data set's
        # next tokens) stay what they are, as the inputs do, and
        # gather_rows indexes them a batch at a time; a memmap stays a view
        lazy = hasattr(y, "shape") and not hasattr(y, "__array__")
        y_arr = y if (device_resident or lazy) else np.asarray(y)

        # Pair-structured losses (rank_hinge: (pos, neg) rows interleaved)
        # must shuffle PAIRS, not rows — a row-level permutation would
        # scramble which positive faces which negative every epoch and
        # silently train on random pairings.
        pair_structured = getattr(self.loss_fn, "batch_structured", False)

        def _pair_perm_np(rng):
            pairs = rng.permutation(n // 2)
            idx = np.empty((n // 2) * 2, np.int64)
            idx[0::2] = pairs * 2
            idx[1::2] = pairs * 2 + 1
            if n % 2:
                idx = np.concatenate([idx, [n - 1]])
            return idx

        def rows(a, sl):
            # a host permutation's rows are copied by the data tier's one
            # gather; a slice stays a view and a device permutation
            # indexes on the device
            return (gather_rows(a, sl) if isinstance(sl, np.ndarray)
                    else a[sl])

        while epoch < epochs:
            batches = None
            try:
                t0 = time.time()
                if self._fit_span is not None:
                    self._epoch_span = TRACER.start(
                        "train/epoch", trace=self._fit_span.trace,
                        parent=self._fit_span.sid, epoch=epoch + 1)
                # Mid-epoch resume (preemption manifest): rewind the host
                # shuffle rng to the interrupted epoch's start state so the
                # SAME permutation is redrawn, then skip the steps the
                # interrupted run already trained — the step sequence seen
                # by the optimizer is bit-identical to an uninterrupted run.
                start_step = 0
                if (self._pending_resume is not None
                        and self._pending_resume[0] == epoch):
                    _, start_step, rng_state = self._pending_resume
                    self._pending_resume = None
                    if rng_state is not None:
                        self._host_rng.set_state(rng_state)
                    # steps advance K at a time inside chunks; align down so
                    # resume never starts mid-chunk (the flush only happens
                    # at dispatch boundaries, so this is exact in practice)
                    if K > 1 and start_step < n_chunks * K:
                        start_step = (start_step // K) * K
                    logger.info("resuming epoch %d at in-epoch step %d",
                                epoch + 1, start_step)
                elif self._pending_resume is not None:
                    self._pending_resume = None
                epoch_rng_state = self._host_rng.get_state()
                if not shuffle:
                    perm = None         # contiguous slices in both modes
                elif device_resident and pair_structured:
                    pairs = jax.random.permutation(
                        explicit_prng_key(cfg.seed + 7919 * epoch), n // 2)
                    perm = jnp.stack([pairs * 2, pairs * 2 + 1],
                                     axis=1).reshape(-1)
                    if n % 2:
                        perm = jnp.concatenate(
                            [perm, jnp.asarray([n - 1])])
                elif device_resident:
                    perm = jax.random.permutation(
                        explicit_prng_key(cfg.seed + 7919 * epoch), n)
                elif pair_structured:
                    perm = _pair_perm_np(self._host_rng)
                else:
                    perm = self._host_rng.permutation(n)
                losses = []

                def gen(perm=perm, start=start_step):
                    for ci in range(n_chunks):
                        s0 = ci * K
                        if s0 < start:      # resume: already trained
                            continue
                        ofs = s0 * eff_batch
                        sl = (slice(ofs, ofs + K * eff_batch)
                              if perm is None
                              else perm[ofs:ofs + K * eff_batch])
                        yield ("K",
                               [rows(a, sl).reshape(
                                   (K, eff_batch) + a.shape[1:])
                                for a in xs],
                               rows(y_arr, sl).reshape(
                                   (K, eff_batch) + y_arr.shape[1:]))
                    for ri in range(rem):
                        s0 = n_chunks * K + ri
                        if s0 < start:
                            continue
                        ofs = s0 * eff_batch
                        sl = (slice(ofs, ofs + eff_batch) if perm is None
                              else perm[ofs:ofs + eff_batch])
                        yield ("1", [rows(a, sl) for a in xs],
                               rows(y_arr, sl))

                def prep(item):
                    kind, bx, by = item
                    bx, by = self._inject_step_faults(bx, by)
                    put = self._shard_chunk if kind == "K" else \
                        self._shard_batch
                    return kind, put(list(bx)), put([by])[0]

                # overlap host batch prep + device_put with device compute
                batches = prefetch_lib.prefetch(gen(), prep,
                                                depth=cfg.data_prefetch)
                in_epoch = start_step
                for kind, batch_x, batch_y in batches:
                    # pre-dispatch check: a flush here can never mark a
                    # fully-trained epoch as mid-epoch (in_epoch stays
                    # strictly below steps_per_epoch)
                    self._maybe_preempt(epoch, in_epoch, epoch_rng_state)
                    k, loss = self._dispatch_step(kind, batch_x, batch_y)
                    in_epoch += k
                    losses.append(loss)
                    self._maybe_midepoch_validation(validation_data,
                                                    epoch + 1, eff_batch)
                # ONE host sync per epoch reads the NaN-guard counters that
                # rode the device carry (policy: skip / rollback / raise)
                if self._check_nan_guard(in_epoch - start_step):
                    if self._epoch_span is not None:
                        self._epoch_span.end(status="rollback")
                        self._epoch_span = None
                    epoch = self.finished_epochs   # rolled back
                    continue
                epoch += 1
                self.finished_epochs = epoch
                # nanmean: skipped (non-finite) steps must not poison the
                # epoch metric — their updates were discarded on device
                mean_loss = (float(jnp.nanmean(jnp.concatenate(
                    [jnp.atleast_1d(l) for l in losses])))
                    if losses else float("nan"))
                dt = time.time() - t0
                rec = {"epoch": epoch, "loss": mean_loss,
                       "throughput": steps_per_epoch * eff_batch / dt}
                obs.observe("train_epoch_seconds", dt)
                obs.set_gauge("train_loss", mean_loss)
                obs.set_gauge("train_throughput_rows_per_s",
                              rec["throughput"])
                if self._epoch_span is not None:
                    self._epoch_span.end(loss=mean_loss)
                    self._epoch_span = None
                tstate = TriggerState(epoch=epoch, iteration=self.global_step,
                                      epoch_finished=True, loss=mean_loss)
                if validation_data is not None and (
                        self._val_trigger is None
                        or self._val_trigger(tstate)):
                    # reuse a mid-epoch eval that just ran on this exact
                    # step instead of evaluating twice
                    if self._last_val_iter == self.global_step:
                        val = self._last_val_result
                    else:
                        val = self.evaluate(validation_data[0],
                                            validation_data[1],
                                            batch_size=self._val_batch
                                            or eff_batch)
                    rec.update({f"val_{k}": v for k, v in val.items()})
                    tstate.score = val.get(
                        self.metrics[0].name if self.metrics else "loss")
                self.history.append(rec)
                if self._tb_writer is not None:
                    for k, v in rec.items():
                        if k != "epoch":
                            self._tb_writer.add_scalar(k, v, self.global_step)
                    self._tb_writer.flush()
                if verbose:
                    logger.info("epoch %d: %s", epoch,
                                {k: round(v, 5) for k, v in rec.items()
                                 if k != "epoch"})
                if self._ckpt_mgr is not None and self._ckpt_trigger(tstate):
                    self._save_checkpoint()
                if end_trigger is not None and end_trigger(tstate):
                    break
            except (KeyboardInterrupt, TrainingPreempted,
                    FloatingPointError, HostLostError):
                # release the prefetch producer (its sentinel delivery
                # waits for close() on abandonment); preemption, the
                # "raise" NaN policy, and a dead peer must surface, never
                # be retried (retrying solo past a lost host would fork
                # the SPMD program)
                if batches is not None and hasattr(batches, "close"):
                    batches.close()
                raise
            except Exception as e:  # failure-retry (Topology.scala:1179-1261)
                if batches is not None and hasattr(batches, "close"):
                    batches.close()
                if self._epoch_span is not None:
                    self._epoch_span.end(status="retry", error=str(e))
                    self._epoch_span = None
                if self._ckpt_mgr is not None:
                    # an async write may still be in flight — land it so
                    # the retry decision sees the newest snapshot
                    self._ckpt_mgr.wait(raise_errors=False)
                if (self._ckpt_mgr is None
                        or self._ckpt_mgr.latest_step() is None
                        or not retry.record_failure()):
                    raise
                logger.warning("step failed (%s); retry %s from checkpoint",
                               e, retry.describe())
                retry.backoff()
                self._restore_checkpoint()
                self._guard = self._fresh_guard()
                # re-sync the loop counter so rolled-back epochs re-train
                epoch = self.finished_epochs
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()   # join any in-flight async write
        return self.history

    def _resolve_data_path(self, fs, batch_size: int = 32
                           ) -> Tuple[str, str]:
        """Which input path a FeatureSet trains through:
        ``("device_resident" | "stream" | "host_prefetch", reason)``.

        Tier router (reference tier-selection semantics,
        feature/FeatureSet.scala:690-722), keyed on the FeatureSet's
        pinned cache level (else the ``data_cache_level`` config
        default) and ``data_device_budget_bytes``:

        - fits the budget           → device_resident (per-host HBM
                                      residency of the rows each
                                      process's devices own)
        - over budget / sliced      → stream (double-buffered shard
                                      rotation), when a feasible
                                      :func:`~analytics_zoo_tpu.data.streaming.plan_stream`
                                      geometry exists
        - stream infeasible / HOST  → host prefetch

        Multi-controller runs route through the SAME matrix — each
        process materializes or streams only its own rows
        (docs/DATA.md "Multi-controller") — except that the quantized
        stream cache is disabled (per-host scale/zero scalars would
        disagree).

        Every downgrade is automatic and logged, never an error; every
        decision is counted in
        ``data_path_selected_total{path,reason}`` with a bounded
        reason-code vocabulary so production downgrades alert instead
        of hiding in logs."""
        from analytics_zoo_tpu.data import streaming as stream_lib
        from analytics_zoo_tpu.data.featureset import (CacheLevel,
                                                       SlicedFeatureSet)

        def pick(path: str, code: str, reason: str) -> Tuple[str, str]:
            obs.count("data_path_selected_total", path=path, reason=code,
                      flat=f"estimator/data_path_{path}")
            return path, reason

        cfg = self.ctx.config
        self._stream_plan = None
        level = fs.cache_level or CacheLevel.normalize(cfg.data_cache_level)
        if level == CacheLevel.HOST:
            return pick("host_prefetch", "cache_level_host",
                        "cache level HOST")
        budget = int(cfg.data_device_budget_bytes)
        sliced = isinstance(fs, SlicedFeatureSet)
        if not sliced and fs.nbytes <= budget:
            # whole-dataset residency beats any rotation whenever it
            # fits — a STREAM request downgrades to plain DEVICE
            return pick("device_resident", "fits_budget",
                        "fits device budget")
        eff_batch = self._global_eff_batch(batch_size)
        cache_dtype = cfg.data_cache_dtype
        if cache_dtype is not None and self.ctx.process_count > 1:
            logger.warning(
                "quantized stream cache (%s) is single-controller only "
                "— per-host quantization would derive disagreeing "
                "replicated scale/zero scalars; streaming uncompressed",
                cache_dtype)
            cache_dtype = None
        plan, why = stream_lib.plan_stream(
            fs, budget, eff_batch, slots=cfg.data_stream_slots,
            cache_dtype=cache_dtype)
        over = ("sliced (beyond-memory) featureset" if sliced else
                f"dataset {fs.nbytes}B over device budget {budget}B")
        if plan is None:
            logger.warning(
                "%s and streaming is infeasible (%s); falling back to "
                "the host prefetch path", over, why)
            return pick("host_prefetch", "stream_infeasible",
                        f"{over}; stream infeasible: {why}")
        logger.info(
            "STREAM tier engaged: %s; rotating %d shards of %d rows "
            "(%.1f MiB/shard in HBM, %d slots%s)", over, plan.n_shards,
            plan.shard_rows, plan.device_shard_bytes / 2 ** 20, plan.slots,
            f", {plan.cache_dtype} device cache" if plan.cache_dtype
            else "")
        self._stream_plan = plan
        return pick("stream", "sliced" if sliced else "over_budget",
                    f"{over}; streaming {plan.n_shards} shards of "
                    f"{plan.shard_rows} rows")

    def _epoch_bookkeeping(self, epoch1, mean_loss, dt, count,
                           validation_data, val_batch_default, verbose,
                           end_trigger) -> bool:
        """Shared end-of-epoch tail (history row, validation trigger,
        tensorboard, checkpoint trigger); True = end_trigger fired."""
        self.finished_epochs = epoch1
        rec = {"epoch": epoch1, "loss": mean_loss,
               "throughput": count / dt}
        tstate = TriggerState(epoch=epoch1, iteration=self.global_step,
                              epoch_finished=True, loss=mean_loss)
        if validation_data is not None and (
                self._val_trigger is None
                or self._val_trigger(tstate)):
            if self._last_val_iter == self.global_step:
                val = self._last_val_result
            else:
                val = self.evaluate(validation_data[0],
                                    validation_data[1],
                                    batch_size=self._val_batch
                                    or val_batch_default)
            rec.update({f"val_{k}": v for k, v in val.items()})
            tstate.score = val.get(
                self.metrics[0].name if self.metrics else "loss")
        self.history.append(rec)
        if self._tb_writer is not None:
            for k, v in rec.items():
                if k != "epoch":
                    self._tb_writer.add_scalar(k, v, self.global_step)
            self._tb_writer.flush()
        if verbose:
            logger.info("epoch %d: %s", epoch1, rec)
        if self._ckpt_mgr is not None and self._ckpt_trigger(tstate):
            self._save_checkpoint()
        if self._flight_recorder is not None:
            self._flight_recorder.check()
        return end_trigger is not None and end_trigger(tstate)

    def _fit_device_resident(self, fs, batch_size, epochs, validation_data,
                             end_trigger, verbose, shuffle):
        """The HBM-resident fast path: materialize the FeatureSet into
        device memory once (``FeatureSet.device_arrays``), then train
        each epoch as ONE jitted dispatch (``_build_resident_epoch``) —
        no per-batch host indexing, no per-batch ``device_put``, no
        per-step dispatch."""
        arrays = fs.device_arrays(self.ctx)
        xs, y = list(arrays[:-1]), arrays[-1]
        if not xs:          # single-array FeatureSet has no label split
            raise ValueError(
                "device-resident training needs (inputs..., label) arrays")
        self._ensure_built(xs)
        n = int(arrays[0].shape[0])
        eff_batch = self._global_eff_batch(batch_size)
        steps = n // eff_batch
        if steps == 0:
            raise ValueError(
                f"FeatureSet ({n} rows) yields no full batch of "
                f"{eff_batch} (drop_remainder)")
        if self._val_trigger is not None:
            logger.warning(
                "device-resident path runs each epoch as one dispatch; "
                "validation_trigger is evaluated at epoch boundaries only")
        epoch_fn = self._build_resident_epoch(n, eff_batch, steps, shuffle)
        if self._pending_resume is not None:
            # resident epochs are one dispatch, so resume granularity is
            # the epoch boundary: a mid-epoch manifest (written by a host
            # input path) restarts its epoch from the restored weights
            if self._pending_resume[1] > 0:
                logger.warning("device-resident path resumes at epoch "
                               "boundaries; dropping mid-epoch resume marker")
            self._pending_resume = None
        # commit the carry under the mesh BEFORE the first dispatch: the
        # epoch outputs come back mesh-replicated, and a first call with
        # uncommitted host-placed params would compile a second, separate
        # executable for epoch 2+ (measured: epochs 1-2 each ~40x slower
        # than steady state on the CPU mesh)
        (self.params, self.state, self.opt_state, self._rng) = \
            self._commit_carry(
                (self.params, self.state, self.opt_state, self._rng))
        self._guard = self._fresh_guard()
        epoch = self.finished_epochs
        while epoch < epochs:
            self._maybe_preempt(epoch, 0)
            # chaos hook: poison planned rows of this epoch's (copy-on-
            # write) inputs so the in-dispatch NaN guard has real work
            xs_e, y_e = xs, y
            plan = faults.fire("estimator.resident_nan_rows")
            if plan is not None and plan.action == "nan":
                rows = jnp.asarray(plan.payload)

                def _poison(a):
                    if jnp.issubdtype(a.dtype, jnp.floating):
                        return a.at[rows].set(jnp.nan)
                    return a

                xs_e = [_poison(a) for a in xs]
                y_e = _poison(y)
            t0 = time.time()
            with timeit("estimator/resident_epoch"):
                _, mean_loss = self._dispatch_step(
                    "epoch", xs_e, y_e, epoch_fn=epoch_fn,
                    epoch_steps=steps)
                # epoch-granular sync: the entire epoch is ONE jitted
                # dispatch, so this float() blocks once per epoch, not
                # per batch — exactly the granularity we want
                mean_loss = float(mean_loss)  # zoolint: disable=JG-TRANSFER-HOT(one sync per epoch by design; the loop variable here is epochs, not batches)
            if self._check_nan_guard(steps):
                epoch = self.finished_epochs    # rolled back
                continue
            dt = time.time() - t0
            epoch += 1
            if self._epoch_bookkeeping(epoch, mean_loss, dt,
                                       steps * eff_batch, validation_data,
                                       batch_size, verbose, end_trigger):
                break
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()   # join any in-flight async write
        return self.history

    def _build_stream_shard(self, plan, shuffle: bool):
        """ONE jitted program per STREAM shard: permute the shard's rows
        on device (level 2 of the two-level shuffle), then a
        ``fori_loop`` of ``steps_per_shard`` train steps gathers each
        minibatch from the resident shard in-step — the shard analog of
        ``_build_resident_epoch``, compiled once and reused for every
        shard of every epoch (all shards share one static shape).

        Differences from the resident epoch program:

        - the epoch loss accumulator ``{"sum", "good"}`` rides through
          ``xs[0]`` instead of starting at zero, so per-step losses
          accumulate across shards in the SAME device-side add order as
          the resident single-dispatch epoch (bit-exact parity);
        - the in-shard permutation arrives as ``xs[1]`` — a replicated
          int32 vector the uploader derives host-side from
          ``(seed, epoch, shard_id)`` alone
          (data/streaming.shard_permutation), NOT from the carried
          device rng: every host of a multi-controller mesh gathers by
          the identical permutation with zero coordination, and a
          resumed shard cursor replays it exactly at any topology;
        - quantized feature leaves arrive as ``{"q", "scale", "zero"}``
          pytrees and are decoded in-kernel AFTER the minibatch gather
          (ops/quantization.dequantize_features) — only the gathered
          rows pay the decode, and HBM holds 1-byte rows."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from analytics_zoo_tpu.ops.quantization import dequantize_features

        key = (plan.shard_rows, plan.eff_batch, plan.steps_per_shard,
               bool(shuffle), plan.cache_dtype, plan.quantized)
        if self._stream_shard is not None and self._stream_shard_key == key:
            return self._stream_shard
        if self._train_step is None:
            self._build_train_step()
        single = self._single_step_fn
        mesh = self.ctx.mesh
        data_axis = self.ctx.data_axis
        eff_batch = plan.eff_batch
        steps = plan.steps_per_shard

        def constrain(v):
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(data_axis,
                                         *([None] * (v.ndim - 1)))))

        def gather(leaf, idx):
            if isinstance(leaf, dict):
                q = jnp.take(leaf["q"], idx, axis=0)
                return constrain(
                    dequantize_features(q, leaf["scale"], leaf["zero"]))
            return constrain(jnp.take(leaf, idx, axis=0))

        def shard(params, state, opt_state, rng, guard, xs, y):
            acc, perm, arrays = xs[0], xs[1], xs[2:]

            def body(i, carry):
                p, s, o, r, g, loss_sum, good = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * eff_batch,
                                                   eff_batch)
                bxs = [gather(a, idx) for a in arrays]
                by = gather(y, idx)
                p, s, o, r, g, loss = single(p, s, o, r, g, bxs, by)
                finite = jnp.isfinite(loss)
                loss_sum = loss_sum + jnp.where(finite, loss, 0.0)
                good = good + finite.astype(jnp.int32)
                return (p, s, o, r, g, loss_sum, good)

            carry = (params, state, opt_state, rng, guard,
                     acc["sum"], acc["good"])
            (params, state, opt_state, rng, guard, loss_sum,
             good) = jax.lax.fori_loop(0, steps, body, carry)
            return (params, state, opt_state, rng, guard,
                    {"sum": loss_sum, "good": good})

        # carry donated; the shard arrays are NOT (their HBM slots are
        # recycled by the uploader via the lease protocol), and neither
        # is the accumulator (its leaf doubles as the release sync
        # handle, so the buffer must survive the dispatch)
        self._stream_shard = jax.jit(shard, donate_argnums=(0, 1, 2, 3, 4))
        self._stream_shard_key = key
        return self._stream_shard

    def _stream_host_tail(self, fs, plan, order, from_shard, acc,
                          perm_fn=None):
        """Finish a STREAM epoch on the host path after an uploader
        failure: the remaining shards of the epoch's order train through
        per-batch ``device_put`` dispatches (each shard's rows in the
        same ``perm_fn`` order the stream program would have gathered) —
        degraded throughput, but the epoch completes with full row
        coverage and the losses fold into the same device accumulator.
        Returns ``(acc, steps_trained)``."""
        steps = 0
        losses = []
        for pos in range(from_shard, plan.n_shards):
            shard_id = int(order[pos])
            arrays = plan.load_shard(fs, shard_id)
            if perm_fn is not None:
                perm = np.asarray(perm_fn(shard_id))
                arrays = [np.asarray(a)[perm] for a in arrays]
            for s in range(plan.steps_per_shard):
                sl = slice(s * plan.eff_batch, (s + 1) * plan.eff_batch)
                bx = [np.asarray(a[sl]) for a in arrays[:-1]]
                by = np.asarray(arrays[-1][sl])
                bx, by = self._inject_step_faults(bx, by)
                batch = self._shard_batch(bx + [by])
                _, loss = self._dispatch_step("1", batch[:-1], batch[-1])
                losses.append(loss)
                steps += 1
        if losses:
            # fold the host-path step losses into the device accumulator
            # (device->device, eager) so the epoch mean covers every
            # trained step with the resident finite-only semantics
            stack = jnp.stack([jnp.asarray(l) for l in losses])
            finite = jnp.isfinite(stack)
            acc = {"sum": acc["sum"]
                   + jnp.sum(jnp.where(finite, stack, 0.0)),
                   "good": acc["good"]
                   + jnp.sum(finite.astype(jnp.int32))}
        return acc, steps

    def _fit_stream(self, fs, batch_size, epochs, validation_data,
                    end_trigger, verbose, shuffle):
        """The STREAM tier: rotate budget-sized shards through HBM with
        a double-buffered background uploader
        (data/streaming.ShardUploader) while each resident shard trains
        as ONE jitted dispatch (``_build_stream_shard``) — datasets
        bigger than the device budget keep the resident path's
        zero-per-batch-transfer property, paying ``n_shards`` uploads
        per epoch that overlap compute.

        Failure story: a mid-rotation uploader crash
        (:class:`~analytics_zoo_tpu.data.streaming.StreamUploadError`)
        finishes the epoch's remaining shards through the host path —
        the epoch is never lost — and the next epoch retries a fresh
        uploader.  Preemption flushes a manifest whose
        ``in_epoch_step`` encodes the shard cursor
        (``shards_done * steps_per_shard``); resume re-derives the
        epoch's shard order from (seed, epoch) and restarts at that
        exact shard.

        Multi-controller: each process streams only the shard rows its
        devices own (``plan.process_view``), the rotation rendezvouses
        at ``zoo_data_*`` deadline barriers (epoch start on this
        thread, per staged shard on the uploader thread) so a dead or
        straggling peer surfaces as a typed ``HostLostError`` on every
        survivor instead of a hang, and the host-tail fallback is
        DISABLED — one host degrading to per-batch dispatches while its
        peers run the shard program would deadlock the mesh's
        collectives, so an upload failure is fatal here.  The plan's
        geometry is a pure function of (budget, global batch), so a
        preempted shard cursor resumes at any process count."""
        from analytics_zoo_tpu.data import streaming as stream_lib

        cfg = self.ctx.config
        plan = self._stream_plan
        if plan is None:    # direct call without the router: re-derive
            plan, why = stream_lib.plan_stream(
                fs, int(cfg.data_device_budget_bytes),
                self._global_eff_batch(batch_size),
                slots=cfg.data_stream_slots,
                cache_dtype=(None if self.ctx.process_count > 1
                             else cfg.data_cache_dtype))
            if plan is None:
                raise ValueError(f"stream fit infeasible: {why}")
        self._ensure_built(plan.probe_inputs(fs))
        shard_fn = self._build_stream_shard(plan, shuffle)
        steps = plan.steps_per_shard
        mc = self.ctx.process_count > 1
        view = plan.process_view(self.ctx) if mc else None
        pair_structured = getattr(self.loss_fn, "batch_structured", False)
        if self._val_trigger is not None:
            logger.warning(
                "stream path dispatches whole shards; validation_trigger "
                "is evaluated at epoch boundaries only")
        # shard-granular resume: the manifest's in_epoch_step was written
        # as shards_done * steps_per_shard, and the shard order re-derives
        # from (seed, epoch) — no carried rng state to restore
        start_shard = 0
        if self._pending_resume is not None:
            r_epoch, r_step, _ = self._pending_resume
            self._pending_resume = None
            if r_epoch == self.finished_epochs and r_step > 0:
                start_shard = min(r_step // steps, plan.n_shards)
                logger.info("stream resume: epoch %d restarts at shard "
                            "%d/%d", r_epoch + 1, start_shard,
                            plan.n_shards)
        # commit the carry under the mesh BEFORE the first dispatch
        # (same compile-stability reasoning as _fit_device_resident)
        (self.params, self.state, self.opt_state, self._rng) = \
            self._commit_carry(
                (self.params, self.state, self.opt_state, self._rng))
        self._guard = self._fresh_guard()
        epoch = self.finished_epochs
        while epoch < epochs:
            t0 = time.time()
            order = plan.epoch_order(cfg.seed, epoch, shuffle)
            acc = self._commit_carry({"sum": np.zeros((), np.float32),
                                      "good": np.zeros((), np.int32)})

            def perm_fn(shard_id, _epoch=epoch):
                return plan.shard_perm(cfg.seed, _epoch, shard_id,
                                       shuffle=shuffle,
                                       pair_structured=pair_structured)

            barrier_fn = None
            if mc:
                # a fresh monotone rotation id per uploader keeps the
                # zoo_data_* barrier names unique for the life of the
                # coordination service (a NaN rollback replays an epoch,
                # and wait_at_barrier rejects name reuse)
                self._data_rotation += 1
                rot = self._data_rotation
                w = dist_barrier(f"zoo_data_epoch_r{rot}",
                                 phase="zoo_data_epoch")
                obs.observe("checkpoint_barrier_wait_ms", w * 1e3,
                            phase="zoo_data_epoch",
                            flat="checkpoint/barrier_zoo_data_epoch_ms")

                def barrier_fn(pos, _rot=rot):
                    bw = dist_barrier(f"zoo_data_shard_r{_rot}_p{pos}",
                                      phase="zoo_data_shard")
                    obs.observe("checkpoint_barrier_wait_ms", bw * 1e3,
                                phase="zoo_data_shard",
                                flat="checkpoint/barrier_zoo_data_shard_ms")

            uploader = stream_lib.ShardUploader(
                fs, plan, order, self.ctx, start=start_shard, view=view,
                perm_fn=perm_fn, barrier_fn=barrier_fn)
            wait_ms = 0.0
            trained = 0
            try:
                shards_done = start_shard
                while shards_done < plan.n_shards:
                    self._maybe_preempt(epoch, shards_done * steps)
                    try:
                        tw = time.perf_counter()
                        lease = uploader.get()
                        wait_ms += (time.perf_counter() - tw) * 1e3
                    except stream_lib.StreamUploadError as e:
                        if mc:
                            # one host finishing on per-batch dispatches
                            # while its peers run the shard program
                            # would deadlock the mesh's collectives —
                            # surface the failure instead of degrading
                            raise
                        obs.count("data_stream_fallbacks_total",
                                  reason="upload_error",
                                  flat="estimator/stream_fallbacks")
                        logger.warning(
                            "shard uploader failed mid-rotation (%s); "
                            "finishing epoch %d on the host path (%d/%d "
                            "shards remain)", e, epoch + 1,
                            plan.n_shards - shards_done, plan.n_shards)
                        acc, tail = self._stream_host_tail(
                            fs, plan, order, shards_done, acc,
                            perm_fn=perm_fn)
                        trained += tail
                        break
                    with timeit("estimator/stream_shard"):
                        _, acc = self._dispatch_step(
                            "shard", [acc, lease.perm] + list(lease.xs),
                            lease.y, epoch_fn=shard_fn, epoch_steps=steps)
                    # the accumulator leaf is this shard's sync handle:
                    # its HBM slot may be overwritten only after this
                    # shard's compute has finished
                    lease.release(after=acc["sum"])
                    trained += steps
                    if plan.decode_bytes_per_shard:
                        obs.count("data_decode_bytes_total",
                                  plan.decode_bytes_per_shard,
                                  dtype=plan.cache_dtype,
                                  flat="stream/decode_bytes")
                    shards_done += 1
            finally:
                up_stats = uploader.stats()
                uploader.close()
            start_shard = 0
            if self._check_nan_guard(max(trained, 1)):
                epoch = self.finished_epochs    # rolled back
                continue
            # epoch-granular sync: the mean divides in f32 host-side so
            # it matches the resident program's on-device division bit
            # for bit
            g = jax.device_get(acc)  # zoolint: disable=JG-TRANSFER-HOT(one sync per epoch by design; the loop variable here is epochs, not batches)
            mean_loss = float(np.float32(g["sum"])
                              / np.maximum(g["good"], 1).astype(np.float32))
            # overlap counter-proof: 1 - (consumer blocked on uploads /
            # total upload wall time).  ~1.0 = uploads fully hidden
            # behind compute; ~0.0 = the rotation is upload-bound
            up = up_stats["upload_ms_total"]
            overlap = 1.0 if up <= 0 else min(
                1.0, max(0.0, 1.0 - wait_ms / up))
            obs.set_gauge("data_stream_overlap_frac", overlap,
                          flat="stream/overlap_frac")
            dt = time.time() - t0
            epoch += 1
            if self._epoch_bookkeeping(epoch, mean_loss, dt,
                                       trained * plan.eff_batch,
                                       validation_data, batch_size,
                                       verbose, end_trigger):
                break
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()   # join any in-flight async write
        return self.history

    def _fit_featureset(self, fs, batch_size, epochs, validation_data,
                        end_trigger, verbose, shuffle=True):
        """Train from a FeatureSet (iterator-based, supports DISK_AND_DRAM)."""
        first = True
        cfg = self.ctx.config
        K = max(1, int(cfg.steps_per_execution))
        # bounded shuffle window keeps disk-backed tiers near-sequential
        shuffle_buffer = (cfg.shuffle_buffer
                          if fs.memory_type != "DRAM" else None)
        if self._pending_resume is not None:
            # FeatureSet iterators own their shuffle stream, so resume
            # granularity is the epoch boundary: restart the interrupted
            # epoch from the restored (mid-epoch) weights
            if self._pending_resume[1] > 0:
                logger.warning("FeatureSet path resumes at epoch "
                               "boundaries; restarting the interrupted epoch")
            self._pending_resume = None
        self._guard = self._fresh_guard()
        epoch = self.finished_epochs
        while epoch < epochs:
            t0 = time.time()
            losses = []
            count = 0
            in_epoch = 0
            raw = fs.batches(batch_size, shuffle=shuffle,
                             drop_remainder=True,
                             pad_to=self.ctx.num_devices,
                             shuffle_buffer=shuffle_buffer)
            if first:
                # peek one batch to build params/steps, then chain it back
                import itertools
                raw = iter(raw)
                try:
                    peek = next(raw)
                except StopIteration:
                    raise ValueError(
                        f"FeatureSet ({len(fs)} rows) yields no full batch "
                        f"of {batch_size} (drop_remainder)") from None
                self._ensure_built(list(peek[:-1]))
                if self._train_step is None:
                    self._build_train_step()
                if K > 1 and self._multi_step is None:
                    self._build_multi_step()
                first = False
                raw = itertools.chain([peek], raw)

            def chunked(it):
                """Group K same-shape batches into (K, B, ...) stacks
                (drop_remainder=True guarantees uniform shapes)."""
                buf = []
                for b in it:
                    buf.append(b)
                    if len(buf) == K:
                        yield ("K", [np.stack([bb[j] for bb in buf])
                                     for j in range(len(buf[0]))])
                        buf = []
                for b in buf:
                    yield ("1", list(b))

            def prep(item):
                kind, arrs = item
                *bx, by = arrs
                bx, by = self._inject_step_faults(bx, by)
                put = self._shard_chunk if kind == "K" else self._shard_batch
                rows = (by.shape[0] * by.shape[1] if kind == "K"
                        else by.shape[0])
                return kind, put(list(bx)), put([by])[0], rows

            src = chunked(raw) if K > 1 else (("1", list(b)) for b in raw)
            batches = prefetch_lib.prefetch(src, prep,
                                            depth=cfg.data_prefetch)
            try:
                for kind, batch_x, batch_y, bn in batches:
                    self._maybe_preempt(epoch, in_epoch)
                    k, loss = self._dispatch_step(kind, batch_x, batch_y)
                    in_epoch += k
                    count += bn
                    losses.append(loss)
                    self._maybe_midepoch_validation(validation_data,
                                                    epoch + 1, batch_size)
            except BaseException:
                if hasattr(batches, "close"):
                    batches.close()
                raise
            if self._check_nan_guard(in_epoch):
                epoch = self.finished_epochs    # rolled back
                continue
            mean_loss = float(jnp.nanmean(jnp.concatenate(
                    [jnp.atleast_1d(l) for l in losses])))
            dt = time.time() - t0
            epoch += 1
            if self._epoch_bookkeeping(epoch, mean_loss, dt, count,
                                       validation_data, batch_size,
                                       verbose, end_trigger):
                break
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait()   # join any in-flight async write
        return self.history

    # ------------------------------------------------------------------
    # evaluate / predict
    # ------------------------------------------------------------------
    def evaluate(self, x, y=None, batch_size: int = 32) -> Dict[str, float]:
        xs = _as_list(x)
        self._ensure_built(xs)
        if self._eval_step is None:
            self._build_eval_step()
        n = xs[0].shape[0]
        d = self.ctx.num_devices
        eff_batch = int(math.ceil(max(batch_size, d) / d)) * d
        y = np.asarray(y)
        agg = None
        for s in range(int(math.ceil(n / eff_batch))):
            sl = slice(s * eff_batch, min((s + 1) * eff_batch, n))
            bx = [a[sl] for a in xs]
            by = y[sl]
            mask = np.ones((by.shape[0],), np.float32)
            (bx_p, real) = self._pad_to_devices(bx, eff_batch)
            (by_p, _) = self._pad_to_devices([by], eff_batch)
            (mask_p, _) = self._pad_to_devices([mask], eff_batch)
            stats = self._eval_step(self.params, self.state,
                                    self._shard_batch(bx_p),
                                    self._shard_batch(by_p)[0],
                                    self._shard_batch(mask_p)[0])
            # accumulate ON DEVICE (async dispatch) — device_get here
            # would force a host sync every batch (JG-TRANSFER-HOT)
            agg = stats if agg is None else jax.tree_util.tree_map(
                jnp.add, agg, stats)
        # finalize ON DEVICE in one jitted call (metrics are
        # jit-friendly by design; eager finalize would re-upload its
        # scalar constants), then ONE device->host transfer for the
        # whole evaluation pass
        def _finalize(a):
            out = {"loss": a["__loss"]["loss_sum"] / a["__loss"]["count"]}
            for m in self.metrics:
                out[m.name] = m.finalize(a[m.name])
            return out

        finals = jax.device_get(jax.jit(_finalize)(agg))
        return {k: float(v) for k, v in finals.items()}

    def predict(self, x, batch_size: int = 32) -> np.ndarray:
        out = self.predict_raw(x, batch_size=batch_size)
        return out[0]

    def predict_classes(self, x, batch_size: int = 32,
                        zero_based_label: bool = True) -> np.ndarray:
        """Class indices from the model's scores (reference
        Predictable.predictClasses, Predictor.scala:226-416); 1-based
        when ``zero_based_label=False`` (BigDL convention)."""
        scores = self.predict(x, batch_size=batch_size)
        scores = np.asarray(scores)
        if scores.ndim == 1 or scores.shape[-1] == 1:
            cls = (scores.reshape(len(scores)) > 0.5).astype(np.int64)
        else:
            cls = np.argmax(scores, axis=-1).astype(np.int64)
        return cls if zero_based_label else cls + 1

    def predict_raw(self, x, batch_size: int = 32) -> List[np.ndarray]:
        """Like predict but preserves multi-output models: returns one
        array per model output (single-output models → a 1-list)."""
        xs = _as_list(x)
        self._ensure_built(xs)
        if self._predict_step is None:
            self._build_predict_step()
        n = xs[0].shape[0]
        d = self._data_div
        eff_batch = int(math.ceil(max(batch_size, d) / d)) * d
        # Multi-controller: the replicated global output interleaves every
        # process's rows at the global indices its addressable devices own
        # under the data sharding.  create_device_mesh permutes devices for
        # ICI topology, so those rows are NOT necessarily a contiguous
        # process-major slice — derive the index set from the sharding.
        multiproc = self.ctx.process_count > 1
        # every batch is padded to eff_batch rows, so the index map is the
        # same for all of them — compute it once
        row_idx = (self._local_row_indices(
            eff_batch * self.ctx.process_count) if multiproc else None)
        outs: Optional[List[List[np.ndarray]]] = None
        for s in range(int(math.ceil(n / eff_batch))):
            sl = slice(s * eff_batch, min((s + 1) * eff_batch, n))
            bx = [a[sl] for a in xs]
            bx_p, real = self._pad_to_devices(bx, eff_batch)
            preds = self._predict_step(self.params, self.state,
                                       self._shard_batch(bx_p))
            # predictions ARE the output: they must land on host, and
            # fetching per batch bounds peak HBM for arbitrarily large n
            preds = jax.device_get(preds)  # zoolint: disable=JG-TRANSFER-HOT(outputs must reach the host; per-batch readback bounds device memory for large inputs)
            if not isinstance(preds, (list, tuple)):
                preds = [preds]
            if outs is None:
                outs = [[] for _ in preds]
            for o, p in zip(outs, preds):
                p = np.asarray(p)
                if row_idx is not None:
                    p = p[row_idx]
                o.append(p[:real])
        return [np.concatenate(o, axis=0) for o in outs]

    def _local_row_indices(self, global_rows: int) -> np.ndarray:
        """Ascending global row indices owned by THIS process's devices
        under the data sharding.  ``make_array_from_process_local_data``
        lays a process's local rows into exactly these positions (local
        order ↔ ascending global shard index), so gathering them back
        recovers the local batch — including padding at the tail —
        regardless of how ``create_device_mesh`` permuted the devices."""
        shard = self.ctx.data_sharding()
        idx_map = shard.addressable_devices_indices_map((global_rows,))
        spans = {(s[0].start or 0,
                  global_rows if s[0].stop is None else s[0].stop)
                 for s in idx_map.values()}   # dedup: tp/pp replicas share rows
        return np.concatenate(
            [np.arange(a, b) for a, b in sorted(spans)])

    # ------------------------------------------------------------------
    # checkpoint plumbing
    # ------------------------------------------------------------------
    def _snapshot(self, resume_epoch: Optional[int] = None,
                  in_epoch_step: int = 0, epoch_rng_state=None):
        """Full training state: model/opt/rng plus the resume manifest
        (docs/ROBUSTNESS.md).  Host rng states are pickled numpy
        ``RandomState`` tuples stored as uint8 arrays — ``epoch_rng`` is
        the stream position at the START of the (possibly interrupted)
        epoch so a mid-epoch resume can redraw the identical shuffle."""
        if epoch_rng_state is None:
            epoch_rng_state = self._host_rng.get_state()
        meta = {"global_step": np.asarray(self.global_step),
                "finished_epochs": np.asarray(self.finished_epochs),
                "rng": np.asarray(self._rng),
                "lr_scale": np.asarray(self._lr_scale, np.float32),
                "resume_epoch": np.asarray(
                    self.finished_epochs if resume_epoch is None
                    else resume_epoch),
                "in_epoch_step": np.asarray(in_epoch_step),
                "data_path": np.asarray(self.last_data_path or "unset"),
                "host_rng": np.frombuffer(
                    pickle.dumps(self._host_rng.get_state()), np.uint8),
                "epoch_rng": np.frombuffer(
                    pickle.dumps(epoch_rng_state), np.uint8)}
        return {"params": self.params, "state": self.state,
                "opt_state": self.opt_state, "meta": meta}

    def _save_checkpoint(self):
        with timeit("estimator/checkpoint_save"):
            if self.ctx.config.async_checkpoint:
                path = self._ckpt_mgr.save_async(self.global_step,
                                                 self._snapshot())
            else:
                path = self._ckpt_mgr.save(self.global_step, self._snapshot())
        logger.info("checkpoint saved: %s", path)

    def _restore_checkpoint(self):
        from analytics_zoo_tpu.parallel.sharding import tree_put_global
        step, tree = self._ckpt_mgr.restore()
        rep = self.ctx.replicated_sharding()
        # Elastic table growth: if the live model was built with MORE
        # embedding rows than the snapshot (vocabulary grew between
        # runs), merge the restored rows into the freshly built tables —
        # snapshot rows bit-exact, new rows keep fresh init, new rows'
        # optimizer moments zero (== fresh tx.init).
        tables = getattr(self.model, "_sharded_tables", None) or \
            getattr(self.model, "_elastic_tables", None)
        if tables and self.params is not None:
            from analytics_zoo_tpu.parallel.table_sharding import (
                grow_restored_opt_state, grow_restored_tree)
            tree["params"] = grow_restored_tree(
                tree["params"], self.params, tables)
            tree["opt_state"] = grow_restored_opt_state(
                tree["opt_state"], jax.eval_shape(self.tx.init, self.params))
        # tree_put_global is the reshard-on-restore seam: restore hands
        # back the FULL global host tree on every process, and placement
        # re-lays it onto whatever mesh is live now — so a checkpoint
        # written at one process count resumes at another
        self.params = tree_put_global(tree["params"],
                                      self._param_shardings(tree["params"]))
        self.state = tree_put_global(tree["state"], rep)
        try:
            # mirror a fresh init's shardings (matches TP param splits)
            self.opt_state = tree_put_global(tree["opt_state"],
                                             self._opt_shardings())
        except (ValueError, TypeError) as e:
            logger.warning(
                "optimizer-state shardings could not be mirrored (%s); "
                "restoring replicated — TP runs lose opt-state sharding", e)
            self.opt_state = tree_put_global(tree["opt_state"], rep)
        self.global_step = int(tree["meta"]["global_step"])
        self.finished_epochs = int(tree["meta"]["finished_epochs"])
        meta = tree["meta"]
        if "rng" in meta:   # resume the dropout/shuffle rng stream
            self._rng = jnp.asarray(meta["rng"])
        else:
            # pre-rng-meta checkpoint: the live key may be a donated
            # (deleted) buffer after a failed step — re-seed so retry works
            self._rng = jax.random.fold_in(
                explicit_prng_key(self.ctx.config.seed), step)
        if "lr_scale" in meta:
            self._lr_scale = float(meta["lr_scale"])
        if "host_rng" in meta and np.asarray(meta["host_rng"]).size:
            st = pickle.loads(np.asarray(meta["host_rng"]).tobytes())
            self._host_rng = np.random.RandomState()
            self._host_rng.set_state(st)
        # Resume manifest.  Armed whenever an epoch-start rng state was
        # recorded, even at in_epoch_step == 0: a preemption flush on the
        # FIRST iteration of an epoch happens after that epoch's shuffle
        # permutation was already drawn, so the restart must rewind the
        # host rng to the epoch start or it redraws a different perm.
        # (For ordinary boundary snapshots epoch_rng equals host_rng and
        # the rewind is a no-op.)
        self._pending_resume = None
        r_step = int(meta["in_epoch_step"]) if "in_epoch_step" in meta else 0
        rng_state = None
        if "epoch_rng" in meta and np.asarray(meta["epoch_rng"]).size:
            rng_state = pickle.loads(
                np.asarray(meta["epoch_rng"]).tobytes())
        if r_step > 0 or rng_state is not None:
            r_epoch = int(meta.get("resume_epoch", self.finished_epochs))
            self._pending_resume = (r_epoch, r_step, rng_state)
        logger.info("restored checkpoint step %d", step)

    def load_checkpoint(self, directory: str):
        self.set_checkpoint(directory)
        self._restore_checkpoint()
        return self
