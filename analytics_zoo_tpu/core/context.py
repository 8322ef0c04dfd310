"""Framework context: device discovery + mesh construction.

Replaces the reference's ``NNContext.initNNContext`` (common/NNContext.scala:133-148)
which creates a SparkContext, applies engine config and calls BigDL
``Engine.init``.  On TPU there is no cluster-manager handshake: a single
controller process discovers the devices JAX exposes, builds a
``jax.sharding.Mesh`` over them, and all parallelism is expressed as
shardings over that mesh (XLA inserts the ICI/DCN collectives).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.core.config import ZooConfig

logger = logging.getLogger("analytics_zoo_tpu")

_GLOBAL_CONTEXT: Optional["ZooContext"] = None
# coordination args of the live jax.distributed cluster (None = never
# initialised through this module; _EXTERNAL_CLUSTER = initialised by a
# launcher outside this module, so no args to compare against)
_EXTERNAL_CLUSTER = ("<external>",)
_DISTRIBUTED_ARGS: Optional[tuple] = None


def explicit_prng_key(seed: int) -> "jax.Array":
    """``jax.random.PRNGKey`` with an EXPLICIT host->device transfer of
    the seed.  ``PRNGKey(int)`` converts the Python scalar implicitly,
    which trips ``jax.transfer_guard("disallow")`` — the runtime guard
    the transfer-audited test suites (and zoolint's JG-TRANSFER-HOT
    rule) use to prove hot paths move no hidden bytes.  Routing the one
    real transfer through ``device_put`` keeps it visible and keeps
    seed-derived keys bit-identical to ``PRNGKey(seed)``."""
    import jax

    return jax.random.PRNGKey(jax.device_put(np.uint32(seed)))


@dataclass
class ZooContext:
    """Holds the device mesh and global config.

    The mesh always exists (1-device meshes are fine) so every code path is
    written SPMD-first; single-chip is just the degenerate mesh.
    """

    config: ZooConfig
    mesh: "jax.sharding.Mesh"

    # ------------------------------------------------------------------
    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    @property
    def data_axis(self) -> str:
        return self.config.mesh_axis_names[0]

    def data_sharding(self, ndim: int = 1):
        """NamedSharding that shards dim 0 over the data axis, replicates rest."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.data_axis, *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    @property
    def process_count(self) -> int:
        import jax

        return jax.process_count()

    @property
    def local_device_count(self) -> int:
        import jax

        return jax.local_device_count()


def init_zoo_context(
    config: Optional[ZooConfig] = None,
    *,
    mesh_shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Sequence[str]] = None,
    multihost: bool = False,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    **config_overrides,
) -> ZooContext:
    """Initialise (or re-initialise) the global framework context.

    Parameters mirror capabilities of ``init_nncontext`` /
    ``init_spark_on_local`` / ``init_spark_on_yarn``
    (reference pyzoo/zoo/common/nncontext.py:23-104): instead of a Spark
    master/cores/executors topology the caller describes a device mesh.

    ``multihost=True`` runs ``jax.distributed.initialize()`` so the same
    program scales to multi-host pods over DCN (replacing the reference's
    Spark-driver + block-manager transport, wp-bigdl.md:140-160).
    """
    global _GLOBAL_CONTEXT
    import jax

    if config is None:
        config = ZooConfig.from_env(**config_overrides)
    elif config_overrides:
        config = config.replace(**config_overrides)

    logging.basicConfig(level=getattr(logging, config.log_level.upper(), 20))

    if multihost:
        # On TPU pods the three coordination args are discovered from the
        # environment; on CPU/GPU clusters (or tests) they are explicit.
        # NOTE: must run before anything touches the XLA backend (even
        # jax.process_count()), so initialisation state is tracked here
        # explicitly rather than by string-matching the RuntimeError
        # (whose message changes across JAX versions).
        global _DISTRIBUTED_ARGS
        args = (coordinator_address, num_processes, process_id)
        if _DISTRIBUTED_ARGS is None and _distributed_client_live():
            # initialised outside this module (e.g. directly by the
            # launcher): adopt the live cluster; the caller's args were
            # never applied, so there is nothing to compare against later
            logger.warning(
                "jax.distributed was initialised outside init_zoo_context;"
                " multihost coordination args are ignored")
            _DISTRIBUTED_ARGS = _EXTERNAL_CLUSTER
        elif _DISTRIBUTED_ARGS is None:
            if _initialize_distributed(config, coordinator_address,
                                       num_processes, process_id):
                _DISTRIBUTED_ARGS = args
            else:
                _DISTRIBUTED_ARGS = _EXTERNAL_CLUSTER
        elif _DISTRIBUTED_ARGS is _EXTERNAL_CLUSTER:
            logger.warning(
                "jax.distributed cluster was initialised externally; "
                "multihost coordination args are ignored")
        elif args != _DISTRIBUTED_ARGS:
            # Re-init with DIFFERENT coordination args cannot be honored —
            # the live cluster keeps its topology; silently dropping the
            # new args would hide a real misconfiguration.
            raise RuntimeError(
                "jax.distributed already initialised with "
                f"{_DISTRIBUTED_ARGS}; cannot re-initialise with {args}. "
                "Restart the process to change cluster coordination.")

    if mesh_shape is not None:
        config = config.replace(mesh_shape=tuple(mesh_shape))
    if axis_names is not None:
        config = config.replace(mesh_axis_names=tuple(axis_names))

    devices = jax.devices(config.platform) if config.platform else jax.devices()
    mesh = make_mesh(devices, config.mesh_shape, config.mesh_axis_names)

    _GLOBAL_CONTEXT = ZooContext(config=config, mesh=mesh)
    logger.info(
        "init_zoo_context: %d device(s) %s, mesh %s axes %s",
        len(devices),
        devices[0].platform,
        dict(zip(mesh.axis_names, mesh.devices.shape)),
        mesh.axis_names,
    )
    return _GLOBAL_CONTEXT


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    The directory is part of the cache key, so it must not move between
    runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
    and no directory is set in code; otherwise the cache lives at the
    fixed, git-ignored ``<checkout>/.jax_cache``.  Entry points (the chip
    smoke, the benchmark, the loadgen server) call this once, BEFORE the
    first compilation — JAX binds the cache on first use and ignores a
    later change.  Never run on import.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def describe_devices() -> dict:
    """What jax runs on, as jax reports it — the ``device`` object every
    result, status file and benchmark row carries so that a CPU run can
    never pass for a chip run."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _initialize_distributed(config: ZooConfig, coordinator_address,
                            num_processes, process_id) -> bool:
    """Join (or start) the jax.distributed coordination service, with
    bounded retry: a slow-starting coordinator, a just-released port
    still in TIME_WAIT, or a transient DNS hiccup must not fail a worker
    on first contact — the whole point of elastic restarts is that
    workers come back at slightly different times.

    Returns True when this call initialised the cluster, False when a
    live cluster was adopted instead (initialised concurrently by a
    launcher).  Retries count in ``dist_init_retries_total``.
    """
    import jax

    from analytics_zoo_tpu.observe import metrics as obs
    from analytics_zoo_tpu.robust.retry import RetryPolicy

    # The CPU backend refuses computations that span processes unless an
    # explicit cross-process collectives layer is configured ("Multiprocess
    # computations aren't implemented on the CPU backend"), so multihost
    # on CPU — local elastic rehearsals, the multi-process test suites —
    # defaults to gloo before the backend client is created.  TPU/GPU
    # platforms never consult the flag, and a user's explicit choice
    # (e.g. "mpi") is left alone.
    try:
        from jax._src import xla_bridge as _xb
        if _xb.CPU_COLLECTIVES_IMPLEMENTATION.value in (None, "none"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        logger.debug("gloo CPU collectives unavailable on this jaxlib",
                     exc_info=True)

    adopted = []

    def _attempt():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        except RuntimeError:
            # "already initialised" must stay a benign adopt (never a
            # retry loop, never a startup crash); anything else — refused
            # connection, bind failure — is transient and retryable
            if not _distributed_client_live():
                raise
            logger.warning(
                "jax.distributed already initialised; multihost "
                "coordination args are ignored")
            adopted.append(True)

    policy = RetryPolicy.from_config(
        config,
        retry_on=(RuntimeError, OSError, ConnectionError),
        name="dist_init",
        on_retry=lambda attempt, exc: obs.count(
            "dist_init_retries_total", flat="robust/dist_init_retries"))
    policy.call(_attempt)
    return not adopted


# Hooks fired (with the lost process ids) when Python-side detection —
# a dispatch-barrier deadline, a harvest timeout — declares a pod
# member dead.  The serving fabric points these at
# ``ClusterServing.notify_host_lost`` so the FIRST detection
# quarantines every model's mesh replica, not just the one whose
# dispatch tripped the deadline.
#
# Detection is deliberately Python-side only.  The coordination
# client's own heartbeat detector cannot be softened on this jaxlib:
# its ``missed_heartbeat_callback`` default is ``LOG(QFATAL)``, and a
# Python replacement is un-invocable (the error-poll thread cannot
# convert the ``absl::Status`` argument, so invoking it terminates the
# process just as fatally).  The fabric therefore keeps pod processes
# off that path entirely — barrier deadlines fire within
# ``dist_barrier_timeout_s`` (seconds), long before the ~100 s
# heartbeat detector, and members never time out a live barrier
# (a member that abandons a barrier seq poisons it for the peers that
# arrive later).
_PEER_LOSS_HOOKS: List[Any] = []


def on_peer_loss(fn) -> None:
    """Register ``fn(process_id)`` to run when a pod member is declared
    dead by Python-side detection (see :func:`report_peer_loss`).  The
    serving fabric points this at ``ClusterServing.notify_host_lost``
    so one detection quarantines every affected mesh replica."""
    _PEER_LOSS_HOOKS.append(fn)


def remove_peer_loss_hook(fn) -> None:
    try:
        _PEER_LOSS_HOOKS.remove(fn)
    except ValueError:
        pass


def report_peer_loss(process_ids: Sequence[int], reason: str = "") -> None:
    """Declare pod members dead and fan the loss out to every
    registered hook.  Called by the serving fabric's barrier-deadline
    path (``PodCoordinator.host_lost``); counts
    ``dist_peer_loss_total`` so survived peer losses are visible next
    to the stock client's would-have-been-fatal behavior."""
    from analytics_zoo_tpu.observe import metrics as obs

    lost = sorted({int(p) for p in process_ids})
    logger.warning(
        "peer loss reported for process(es) %s%s (continuing — host "
        "loss is survivable)", lost, f": {reason}" if reason else "")
    obs.count("dist_peer_loss_total", flat="robust/dist_peer_loss")
    for fn in list(_PEER_LOSS_HOOKS):
        for pid in lost:
            try:
                fn(pid)
            except Exception:
                logger.exception("peer-loss hook %r failed", fn)


def dist_barrier(name: str, timeout_s: Optional[float] = None,
                 phase: str = "other") -> float:
    """Deadline-bounded cross-process barrier over the jax.distributed
    coordination service; returns the seconds spent waiting.

    A peer that fails to reach the barrier within ``timeout_s`` (default
    ``dist_barrier_timeout_s`` from the active config) is presumed dead:
    the wait raises a typed :class:`~analytics_zoo_tpu.robust.errors.HostLostError`
    instead of hanging, and the timeout counts in
    ``dist_barrier_timeouts_total{phase=...}``.  Single-process runs
    return immediately (0.0) — every caller can be written SPMD-first.

    ``name`` must be unique per synchronisation point (the checkpoint
    protocol embeds the step number); ``phase`` is the bounded metric
    label (``write`` / ``commit`` / ``other``).
    """
    import time as _time

    import jax

    from analytics_zoo_tpu.observe import metrics as obs
    from analytics_zoo_tpu.robust import faults
    from analytics_zoo_tpu.robust.errors import HostLostError

    if timeout_s is None:
        cfg = (_GLOBAL_CONTEXT.config if _GLOBAL_CONTEXT is not None
               else ZooConfig())
        timeout_s = cfg.dist_barrier_timeout_s
    plan = faults.fire("dist.barrier_timeout")
    if plan is not None:
        obs.count("dist_barrier_timeouts_total", phase=phase,
                  flat="robust/dist_barrier_timeouts")
        raise (plan.exc if plan.exc is not None else HostLostError(
            f"barrier {name!r}: injected peer loss "
            f"(deadline {timeout_s}s)", barrier=name, timeout_s=timeout_s))
    if jax.process_count() <= 1:
        return 0.0
    from jax._src.distributed import global_state
    client = global_state.client
    t0 = _time.perf_counter()
    try:
        if client is not None and hasattr(client, "wait_at_barrier"):
            client.wait_at_barrier(name, timeout_in_ms=max(
                1, int(timeout_s * 1000)))
        else:
            # coordination client unavailable (private API moved):
            # fall back to the device-level sync — correct, but a dead
            # peer hangs until the collective layer's own timeout
            from jax.experimental import multihost_utils
            logger.warning("dist_barrier %r: no coordination client; "
                           "falling back to sync_global_devices "
                           "(no deadline)", name)
            multihost_utils.sync_global_devices(name)
    except Exception as e:
        obs.count("dist_barrier_timeouts_total", phase=phase,
                  flat="robust/dist_barrier_timeouts")
        raise HostLostError(
            f"barrier {name!r}: peer missed the {timeout_s}s deadline "
            f"and is presumed dead ({type(e).__name__}: {e})",
            barrier=name, timeout_s=timeout_s) from e
    return _time.perf_counter() - t0


class HostRoster:
    """Epoch-tagged membership view of a serving pod's processes.

    The serving fabric's source of truth for which member hosts of a
    mesh replica are believed alive.  Every membership change bumps the
    ``epoch``; the quarantine broadcast and the supervisor's heal/shed
    decisions key off epochs, so concurrent observers of the same host
    death collapse into one atomic reaction (docs/SERVING.md
    "Pod-scale serving").

    All state transitions happen under one lock (marking a host lost
    and bumping the epoch must be indivisible — an unlocked roster
    write is exactly the THR-SHARED-MUT hazard the lint fixture pins).
    The clock is injectable so fast tests fabricate loss ages instead
    of sleeping; there is no ``jax`` dependency — OS-process pods feed
    it from barrier timeouts, fast tests feed it by hand.
    """

    def __init__(self, process_ids: Sequence[int], *, clock=None):
        import threading
        import time as _time

        self._lock = threading.Lock()
        self._clock = clock or _time.monotonic
        self._expected = tuple(int(p) for p in process_ids)
        self._alive = set(self._expected)
        self._epoch = 0
        self._lost_t: Optional[float] = None

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    @property
    def expected(self) -> Tuple[int, ...]:
        return self._expected

    def alive(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(sorted(self._alive))

    def mark_lost(self, process_id: int) -> int:
        """Record a presumed-dead member; returns the NEW epoch.  A
        repeat loss of an already-lost host does not bump the epoch
        (the same death observed twice is one event)."""
        process_id = int(process_id)
        with self._lock:
            if process_id in self._alive:
                self._alive.discard(process_id)
                self._epoch += 1
                self._lost_t = self._clock()
            return self._epoch

    def mark_alive(self, process_id: int) -> int:
        """Record a (re)joined member; returns the new epoch."""
        process_id = int(process_id)
        with self._lock:
            if process_id in self._expected and \
                    process_id not in self._alive:
                self._alive.add(process_id)
                self._epoch += 1
                if self._alive == set(self._expected):
                    self._lost_t = None
            return self._epoch

    def healed(self) -> bool:
        """True when every expected member is believed alive."""
        with self._lock:
            return self._alive == set(self._expected)

    def lost(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(sorted(set(self._expected) - self._alive))

    def lost_age_s(self) -> float:
        """Seconds the roster has been degraded (0.0 while whole)."""
        with self._lock:
            if self._lost_t is None:
                return 0.0
            return max(0.0, self._clock() - self._lost_t)

    def snapshot(self) -> dict:
        with self._lock:
            return {"epoch": self._epoch,
                    "expected": list(self._expected),
                    "alive": sorted(self._alive),
                    "lost": sorted(set(self._expected) - self._alive),
                    "healed": self._alive == set(self._expected)}


def _distributed_client_live() -> bool:
    """True when a jax.distributed client already exists in this process
    (initialised by a launcher before init_zoo_context ran)."""
    try:
        from jax._src.distributed import global_state
        return global_state.client is not None
    except Exception:       # private API moved: assume not initialised
        return False


def make_mesh(devices, mesh_shape, axis_names) -> "jax.sharding.Mesh":
    from jax.sharding import Mesh

    n = len(devices)
    if mesh_shape is None:
        mesh_shape = (n,) + (1,) * (len(axis_names) - 1)
    if int(np.prod(mesh_shape)) != n:
        raise ValueError(
            f"mesh_shape {mesh_shape} needs {np.prod(mesh_shape)} devices, "
            f"have {n}"
        )
    # ICI-topology-aware device placement: on real TPU slices
    # mesh_utils orders devices so the minor mesh axes ride physical
    # ICI rings (collectives on the model/expert axis stay on-chip
    # links instead of hopping the torus).  A mesh shape the physical
    # topology cannot carry raises here; CPU meshes have no topology and
    # take a plain reshape.
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            tuple(mesh_shape), devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(mesh_shape)
    return Mesh(dev_array, tuple(axis_names))


def get_zoo_context() -> ZooContext:
    """Current global context, creating a default one on first use."""
    global _GLOBAL_CONTEXT
    if _GLOBAL_CONTEXT is None:
        _GLOBAL_CONTEXT = init_zoo_context()
    return _GLOBAL_CONTEXT


def set_zoo_context(ctx: ZooContext) -> None:
    global _GLOBAL_CONTEXT
    _GLOBAL_CONTEXT = ctx
