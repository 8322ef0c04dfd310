"""Unified typed configuration.

The reference spreads configuration over four mechanisms (Spark conf files,
env vars, JVM system properties, per-app CLI/YAML — see
reference common/NNContext.scala:188-237 and
serving/utils/ClusterServingHelper.scala:104-170).  Here a single dataclass
is the source of truth; env vars with the ``ZOO_`` prefix override fields,
and YAML/dict loading covers the serving use-case.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

_ENV_PREFIX = "ZOO_"


@dataclass
class ZooConfig:
    """Global framework configuration.

    Fields mirror the *capabilities* of the reference's config surface:
    engine/thread tuning becomes XLA/mesh settings, failure-retry knobs keep
    their semantics (reference api/keras/models/Topology.scala:1180-1181).
    """

    # --- device / mesh ---------------------------------------------------
    platform: Optional[str] = None          # None = let JAX pick (tpu>cpu)
    mesh_shape: Optional[Tuple[int, ...]] = None   # None = all devices on "data"
    mesh_axis_names: Tuple[str, ...] = ("data",)
    # Preferred compute dtype for matmul-heavy paths (MXU wants bf16).
    compute_dtype: str = "float32"

    # --- training --------------------------------------------------------
    # Steps fused into one XLA dispatch (lax.scan over a device-resident
    # superbatch).  >1 removes the per-step dispatch and host->device
    # overhead from the loop.
    steps_per_execution: int = 1
    # Failure-retry semantics of InternalDistriOptimizer.train
    # (reference Topology.scala:1179-1261).
    failure_retry_times: int = 5
    failure_retry_interval_s: float = 120.0
    checkpoint_dir: Optional[str] = None
    # Async checkpointing (orbax) on by default.
    async_checkpoint: bool = True

    # --- data ------------------------------------------------------------
    # Memory tier for FeatureSet caches: DRAM | DISK_AND_DRAM | DIRECT
    # (reference feature/pmem/NativeArray.scala:21-37; PMEM itself has no
    # TPU-host equivalent — DISK_AND_DRAM covers the capacity use-case).
    default_memory_type: str = "DRAM"
    data_prefetch: int = 2                  # batches prefetched to device
    shuffle_buffer: int = 10000
    # Cache level for FeatureSets that don't pin one themselves: HOST
    # keeps the reference behaviour (host batches + prefetch/device_put);
    # DEVICE materializes the dataset into HBM once and runs the
    # Estimator's device-resident epoch body (on-device shuffle +
    # in-step minibatch gather, zero host→device bytes per epoch) — the
    # TPU analog of the reference's PMEM/DRAM cached partitions
    # (feature/FeatureSet.scala:690-722).
    data_cache_level: str = "HOST"
    # HBM budget for DEVICE caching; datasets above it stream
    # budget-sized shards through HBM (CacheLevel.STREAM — the tier
    # auto-router is replicated < budget < stream < host) with the host
    # prefetch path as the final fallback (4 GiB default leaves room
    # for params/activations on every shipping TPU generation).
    data_device_budget_bytes: int = 4 << 30
    # STREAM tier: HBM shard slots alive at once.  2 = double
    # buffering — shard N+1 uploads on the background uploader thread
    # while the jitted shard program trains on shard N.
    data_stream_slots: int = 2
    # Compressed device cache for STREAM shards: None keeps shards at
    # their native dtype; "uint8" (affine) / "int8" (symmetric) encode
    # FLOAT feature arrays host-side and decode them in-kernel after
    # the minibatch gather (ops/quantization.py), stretching the
    # effective device budget ~4x for image/embedding features.
    # Labels and integer arrays always pass through unquantized.
    data_cache_dtype: Optional[str] = None
    # Fused embedding-bag kernel routing (ops/embedding_bag.py) for the
    # recommenders' multi-hot lookups: "auto" lets ops.dispatch pick
    # (Pallas on TPU above its win threshold), "on" insists on the
    # kernel wherever shapes allow, "off" pins the XLA gather path.
    fused_embedding: str = "auto"
    # Within-batch duplicate-id dedup for embedding lookups
    # (ops/embedding_bag.py embedding_bag_dedup): "auto" dedups the
    # sharded-table lookup path only (where duplicate rows pay full HBM
    # + exchange price), "on" dedups every bag lookup, "off" pins the
    # naive per-slot gather.  Exact-parity custom_vjp either way.
    dedup_ids: str = "auto"
    # Hot-row replication cache for SERVING lookups against row-sharded
    # tables (parallel/hot_cache.py): "auto"/"on" lets deploy serving
    # build a per-table top-K replica cache so hot ids resolve from a
    # chip-local copy and skip the psum exchange; "off" disables cache
    # construction entirely.  Training never reads the cache (optimizer
    # writes stay authoritative).
    table_hot_cache: str = "auto"
    # Rows held per hot cache (top-K by observed lookup frequency).
    table_hot_cache_capacity: int = 1024
    # Seconds between cache refreshes from the authoritative shards; a
    # refresh re-ranks the top-K from the live frequency counts and
    # re-reads the row values, bounding staleness to one period.
    table_hot_cache_refresh_s: float = 30.0
    # Ring-attention routing (ops/ring_attention.py) for sequence-
    # parallel long context: "auto" rings only on a mesh with a >1-way
    # seq axis above RING_MIN_LEN tokens, "on" insists wherever a mesh
    # allows, "off" pins the single-device blockwise path.
    ring_attention: str = "auto"
    # Sequence shards for the attention layers when no explicit
    # sequence-parallel regime is active: >1 makes MultiHeadAttention
    # build a seq mesh over that many devices and route self-attention
    # through the ring (docs/PARALLELISM.md "Sequence parallelism").
    # 0 = off (a compile(sharding="sp") regime still takes precedence).
    seq_shards: int = 0

    # --- serving ---------------------------------------------------------
    # Pipelined serving engine (docs/SERVING.md).  The DynamicBatcher
    # dispatches a shape bucket on whichever comes first: batch-full
    # (serving_batch_size rows) or the serving_max_batch_delay_ms
    # deadline — the continuous-batching tradeoff between latency under
    # trickle load and MXU utilization under saturation.
    serving_batch_size: int = 32
    serving_max_batch_delay_ms: float = 5.0
    # Decode-pool threads: base64/JSON decode + host preprocess run off
    # the device hot path, concurrently with device compute.
    serving_decode_workers: int = 4
    # Model replicas round-robined by the device executor (one full copy
    # per mesh device along the data axis; 1 = single-chip serving).
    serving_replicas: int = 1
    # Batches in flight per executor (2 = double buffering: batch N+1 is
    # enqueued while N computes; also the backpressure bound).
    serving_max_inflight: int = 2
    # Self-healing serving (docs/SERVING.md "Failure semantics"): each
    # replica's circuit breaker quarantines it after this many
    # CONSECUTIVE dispatch/harvest failures...
    serving_breaker_threshold: int = 3
    # ...and lets one half-open probe through after this cooldown; a
    # quarantined replica still open past the cooldown is rebuilt by
    # the supervisor and hot-swapped in.
    serving_breaker_cooldown_s: float = 2.0
    # How often the supervisor thread runs its repair checks (replica
    # rebuild, harvest watchdog, stage restarts, health gauges).
    serving_supervisor_interval_s: float = 0.25
    # A pipeline stage whose heartbeat is older than this while the
    # worker runs is treated as wedged and restarted.
    serving_stage_stall_s: float = 10.0
    # A device harvest readback blocking longer than this is a hung
    # dispatch: the replica is quarantined, its in-flight records are
    # requeued, and the harvest stage restarts.
    serving_harvest_deadline_s: float = 30.0
    # Default client TTL applied to records that don't carry their own
    # ``ttl_ms`` (None = records without a TTL never expire).  Expired
    # work is shed with a structured "expired" error before paying
    # decode/dispatch cost.
    serving_default_ttl_ms: Optional[float] = None
    # Serving SLO for the flight recorder (docs/OBSERVABILITY.md): a
    # p99 bound on serving_stage_seconds{stage=e2e}, evaluated over
    # serving_slo_window_s windows by a supervisor check.  0 disables
    # the watcher entirely.
    serving_slo_p99_ms: float = 0.0
    serving_slo_window_s: float = 5.0
    # Queue transport (docs/SERVING.md "Wire format & queue backends"):
    # "memory" (in-process, legacy json wire), "file" (spool dir, binary
    # framed records), "redis" (reference-compatible distributed), or
    # "shm" — the zero-copy shared-memory ring buffer for single-host
    # serving (deploy.make_queue_from_zoo lowers this).
    serving_queue_backend: str = "memory"
    # ShmQueue arena geometry: ring capacity in records and the byte cap
    # per record slot / per result slot.  slots x slot_bytes is the
    # segment's request-arena footprint in /dev/shm; a record that packs
    # larger than slot_bytes is rejected client-side as malformed.
    serving_shm_slots: int = 256
    serving_shm_slot_bytes: int = 1 << 20
    serving_shm_result_slot_bytes: int = 1 << 20
    # Replica weight storage (deploy/inference.py): "float32" keeps full
    # precision; "int8" / "int4" store weights quantized per output
    # channel (1/4, resp. 1/8 of the f32 HBM footprint) and dequantize
    # inside the serving forward — on TPU through the fused
    # dequantize-matmul kernel (ops/dequant_matmul.py).
    serving_weight_dtype: str = "float32"
    # Persistent AOT compile cache (docs/SERVING.md "Warm start &
    # multi-model"): directory where serialized XLA executables are
    # stored per (model fingerprint, bucket signature, mesh); a
    # restarted worker reaches full bucket coverage from disk instead
    # of re-compiling.  Empty string = off.
    serving_compile_cache_dir: str = ""
    # Shared HBM budget for multi-model replica planning (0 = no cap):
    # a replica-grow request that would push the summed weight bytes of
    # every hosted model's replicas past this is refused.
    serving_hbm_budget_bytes: int = 0
    # Metrics-driven autoscaler (deploy/autoscale.py): grows/shrinks
    # decode workers, per-model replicas and the batch deadline from
    # the stage gauges, with hysteresis + cooldown.
    serving_autoscale: bool = False
    serving_autoscale_cooldown_s: float = 5.0
    serving_autoscale_interval_s: float = 1.0

    # --- observability ---------------------------------------------------
    # Bounded ring of completed spans kept by observe.TRACER; any
    # request's timeline is reconstructable while it's inside the ring.
    observe_span_ring: int = 4096
    # Structured JSONL event log (spans as they complete + metric
    # dumps); empty string = off.
    observe_jsonl_path: str = ""
    # Where flight-recorder snapshots (span ring + metrics delta at the
    # moment of an SLO breach / breaker trip) are written; empty = keep
    # the last few in memory only.
    observe_flight_dir: str = ""
    # Arm a short jax.profiler device trace when the flight recorder
    # trips (written under observe_flight_dir/profile).
    observe_profile_on_breach: bool = False

    # --- robustness ------------------------------------------------------
    # What a non-finite training loss does (docs/ROBUSTNESS.md):
    #   "skip"     — the jitted step discards the bad update on device
    #                (params/opt-state keep their pre-step values) and the
    #                epoch-boundary check counts it; training continues.
    #   "rollback" — like skip, plus: >= max_bad_steps CONSECUTIVE bad
    #                steps restores the last checkpoint and scales the
    #                learning rate by nan_backoff_factor.
    #   "raise"    — any bad step raises FloatingPointError at the next
    #                epoch-boundary check (the update was still skipped,
    #                so the surviving params are finite for post-mortem).
    # Checks are epoch-granular: the bad-step counters ride the device
    # carry, so the happy path costs zero extra host syncs.
    nan_policy: str = "skip"
    max_bad_steps: int = 5
    nan_backoff_factor: float = 0.5
    # Verify per-leaf CRC32 manifests on checkpoint restore; torn/corrupt
    # snapshots quarantine and restore falls back to the newest intact one.
    ckpt_verify: bool = True
    # Multi-controller checkpointing (docs/ROBUSTNESS.md "Distributed
    # checkpoints & elastic resume"): each process writes only the
    # shards it owns plus a global manifest, with a two-phase commit so
    # a host dying mid-save leaves a quarantined partial step, never a
    # torn "latest".  Off → every process would race on one archive, so
    # leave this on for any multi-process run.
    ckpt_distributed: bool = True
    # Deadline for every cross-process coordination barrier (checkpoint
    # write/commit phases): a peer missing the barrier for this long is
    # presumed dead and surfaces as a typed HostLostError instead of a
    # hang.  Generous default — pod-scale saves can be slow; tests dial
    # it down to seconds.
    dist_barrier_timeout_s: float = 120.0
    # RetryPolicy defaults (robust/retry.py) — exponential backoff with
    # jitter, bounded by attempts and an optional wall-clock deadline.
    retry_max_attempts: int = 5
    retry_base_delay_s: float = 0.1
    retry_max_delay_s: float = 30.0
    retry_multiplier: float = 2.0
    retry_jitter: float = 0.1
    retry_deadline_s: Optional[float] = None

    # --- logging / summaries --------------------------------------------
    log_level: str = "INFO"
    tensorboard_dir: Optional[str] = None

    # --- misc ------------------------------------------------------------
    seed: int = 42
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_env(cls, **overrides: Any) -> "ZooConfig":
        """Build a config from defaults <- ZOO_* env vars <- overrides."""
        kwargs: Dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            env_key = _ENV_PREFIX + f.name.upper()
            if env_key in os.environ:
                kwargs[f.name] = _coerce(os.environ[env_key], f.type)
        kwargs.update(overrides)
        return cls(**kwargs)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ZooConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in d.items() if k in names}
        extra = {k: v for k, v in d.items() if k not in names}
        cfg = cls(**known)
        cfg.extra.update(extra)
        return cfg

    @classmethod
    def from_yaml(cls, path: str) -> "ZooConfig":
        try:
            import yaml  # type: ignore

            with open(path) as f:
                d = yaml.safe_load(f) or {}
        except ImportError:
            with open(path) as f:
                d = json.load(f)
        return cls.from_dict(_flatten(d))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def replace(self, **kw: Any) -> "ZooConfig":
        return dataclasses.replace(self, **kw)


def _flatten(d: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in d.items():
        key = f"{prefix}{k}" if not prefix else f"{prefix}_{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key))
        else:
            out[key] = v
    return out


def _coerce(raw: str, typ: Any) -> Any:
    t = str(typ)
    if "int" in t and "Tuple" not in t:
        return int(raw)
    if "float" in t:
        return float(raw)
    if "bool" in t:
        return raw.lower() in ("1", "true", "yes", "on")
    if "Tuple" in t or "Sequence" in t:
        return tuple(
            int(x) if x.strip().isdigit() else x.strip() for x in raw.split(",")
        )
    return raw
