"""Scenario orchestration: wire shapes + mixes + clients to a live
``ClusterServing`` and fold the result into SLO report sections.

Each ``run_*_leg`` function is self-contained — it builds its queue,
server, schedule and client(s), runs to completion, and returns the
JSON-ready section the report writer
(``python -m analytics_zoo_tpu.loadgen``) assembles into one report.
The slow soak tests drive the same functions and assert over the
sections, so the report and the CI proof are the same code path.

The kill leg is the only one that crosses a process boundary: the
server runs as a real OS process (``loadgen/server_main.py``) over a
``FileQueue`` spool with a persistent compile cache, gets SIGKILLed
mid-storm, and is relaunched against the same cache — the client's
schedule never blinks (open loop), and the restarted server's status
file must show ZERO live compiles (warm start through the cache).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from analytics_zoo_tpu.loadgen import slo as slo_mod
from analytics_zoo_tpu.loadgen.adversarial import (SlowClient,
                                                   expired_ttl_flood,
                                                   malformed_flood)
from analytics_zoo_tpu.loadgen.arrivals import (FlashCrowd, Steady,
                                                arrival_times)
from analytics_zoo_tpu.loadgen.client import OpenLoopClient
from analytics_zoo_tpu.loadgen.payloads import PayloadClass, PayloadMix

__all__ = ["two_model_pair", "make_queue", "run_steady_leg",
           "run_burst_leg", "run_mix_shift_leg", "run_adversarial_leg",
           "run_open_loop_check", "run_kill_leg", "run_pod_kill_leg",
           "SERVER_IN_DIM", "SERVER_QUEUE_NAME", "POD_IN_DIM",
           "POD_VOCAB"]

# the deterministic cross-process server contract (server_main.py /
# client_main.py / the kill leg all agree on these)
SERVER_IN_DIM = 12
SERVER_QUEUE_NAME = "loadgen_stream"
# the pod-mode bag model's contract (server_main.build_bag_model)
POD_IN_DIM = 4
POD_VOCAB = 64


def two_model_pair(laggy_sleep_s: float = 0.03, dim: int = 4):
    """The soak's model cast: ``echo`` (can always meet a loose SLO)
    and ``laggy`` (a forward that can never meet a tight one) — same
    cast as ``tests/test_serving_chaos.py``'s autoscale soak."""
    from analytics_zoo_tpu.deploy import InferenceModel

    def fast_fwd(xs):
        return xs[0] * 2.0

    def laggy_fwd(xs):
        time.sleep(laggy_sleep_s)
        return xs[0] * 2.0

    echo = InferenceModel(fast_fwd, batch_buckets=(1, 8))
    laggy = InferenceModel(laggy_fwd, batch_buckets=(1, 8))
    return {"echo": echo, "laggy": laggy}


def make_queue(backend: str = "memory", **kw):
    """Queue for an in-process leg: ``memory``, or ``shm`` when POSIX
    shared memory is usable (silently falls back to memory otherwise —
    the leg's section records which wire actually ran)."""
    if backend == "shm":
        from analytics_zoo_tpu.deploy.shmqueue import ShmQueue, shm_available
        if shm_available():
            kw.setdefault("slots", 256)
            kw.setdefault("slot_bytes", 1 << 16)
            kw.setdefault("push_timeout_s", 5.0)
            return ShmQueue(name=kw.pop("name", "loadgen"), **kw), "shm"
    from analytics_zoo_tpu.deploy.serving import MemoryQueue
    return MemoryQueue(), "memory"


def _lat_stats(records) -> Dict[str, Optional[float]]:
    oks = [r.latency_s * 1e3 for r in records
           if r.outcome == "ok" and r.latency_s is not None]
    lags = [r.lag_s * 1e3 for r in records if r.lag_s is not None]
    return {"latency_p50_ms": slo_mod.percentile(oks, 50),
            "latency_p99_ms": slo_mod.percentile(oks, 99),
            "send_lag_p99_ms": slo_mod.percentile(lags, 99)}


def _section(records, windows, slo_ms_by_model) -> Dict[str, Any]:
    outcomes = slo_mod.outcome_counts(records)
    sec: Dict[str, Any] = {
        "offered": len(records),
        "answered_ok": outcomes.get("ok", 0),
        "outcomes": outcomes,
        "shed_fraction": slo_mod.shed_fraction_by_model(records),
        "sustained_qps_at_slo": slo_mod.sustained_qps_at_slo(
            windows, slo_ms_by_model),
    }
    sec.update(_lat_stats(records))
    return sec


def run_steady_leg(qps: float = 80.0, duration_s: float = 8.0,
                   seed: int = 11, slo_ms: float = 250.0,
                   backend: str = "shm",
                   window_s: float = 1.0) -> Dict[str, Any]:
    """Sustained Poisson load on one model through the full pipeline:
    the sustained-QPS-at-SLO headline row."""
    from analytics_zoo_tpu.deploy import (ClusterServing, InputQueue,
                                          OutputQueue, ServingConfig)

    models = two_model_pair(laggy_sleep_s=0.0)
    model = {"echo": models["echo"]}
    q, wire = make_queue(backend, name="loadgen_steady")
    cfg = ServingConfig(batch_size=8, poll_timeout_s=0.02,
                        max_batch_delay_ms=3, decode_workers=2,
                        slo_p99_ms={"echo": slo_ms})
    srv = ClusterServing(model, q, cfg).start()
    try:
        schedule = arrival_times(Steady(qps), duration_s, seed)
        mix = PayloadMix([PayloadClass("echo", shape=(4,),
                                      dtype="float32")])
        client = OpenLoopClient(InputQueue(q), OutputQueue(q), schedule,
                                mix, leg="steady", seed=seed)
        records = client.run(drain_timeout_s=30.0)
    finally:
        srv.stop()
        if hasattr(q, "stop"):
            q.stop()
    windows = slo_mod.fold_windows(records, window_s, duration_s)
    sec = _section(records, windows, {"echo": slo_ms})
    sec.update({"qps_target": qps, "duration_s": duration_s,
                "slo_p99_ms": slo_ms, "wire": wire,
                "open_loop_drops": client.open_loop_drops})
    return sec


def run_burst_leg(base_qps: float = 40.0, burst_qps: float = 400.0,
                  at_s: float = 3.0, dur_s: float = 2.0,
                  duration_s: float = 14.0, seed: int = 13,
                  slo_ms: float = 400.0, backend: str = "shm",
                  window_s: float = 1.0) -> Dict[str, Any]:
    """Flash crowd: a 10x rectangular burst over a steady floor.  The
    pinned row is recovery-time-to-SLO measured from the burst END."""
    from analytics_zoo_tpu.deploy import (ClusterServing, InputQueue,
                                          OutputQueue, ServingConfig)

    models = {"echo": two_model_pair(laggy_sleep_s=0.002)["laggy"]}
    models["echo"].name = "echo"
    q, wire = make_queue(backend, name="loadgen_burst")
    cfg = ServingConfig(batch_size=8, poll_timeout_s=0.02,
                        max_batch_delay_ms=3, decode_workers=2,
                        slo_p99_ms={"echo": slo_ms})
    srv = ClusterServing(models, q, cfg).start()
    try:
        shape = FlashCrowd(base_qps, burst_qps, at_s, dur_s)
        schedule = arrival_times(shape, duration_s, seed)
        mix = PayloadMix([PayloadClass("echo", shape=(4,),
                                      dtype="float32")])
        client = OpenLoopClient(InputQueue(q), OutputQueue(q), schedule,
                                mix, leg="burst", seed=seed)
        records = client.run(drain_timeout_s=60.0)
    finally:
        srv.stop()
        if hasattr(q, "stop"):
            q.stop()
    windows = slo_mod.fold_windows(records, window_s, duration_s)
    burst_end = at_s + dur_s
    sec = _section(records, windows, {"echo": slo_ms})
    sec.update({
        "base_qps": base_qps, "burst_qps": burst_qps,
        "burst_at_s": at_s, "burst_dur_s": dur_s, "wire": wire,
        "slo_p99_ms": slo_ms,
        "recovery_after_burst_s": slo_mod.recovery_time_to_slo(
            windows, burst_end, {"echo": slo_ms}),
    })
    return sec


def run_mix_shift_leg(duration_s: float = 16.0, qps: float = 60.0,
                      shift_at_s: float = 6.0, seed: int = 17,
                      laggy_sleep_s: float = 0.03,
                      backend: str = "shm",
                      window_s: float = 1.0) -> Dict[str, Any]:
    """The two-model shifting mix under the live autoscaler: balanced
    load, then 85% of traffic shifts onto the model that cannot meet
    its SLO.  Pins selective shed (only the over-SLO model loses
    traffic) and autoscale convergence (actions, zero flaps)."""
    from analytics_zoo_tpu.deploy import (AutoscalePolicy, ClusterServing,
                                          InputQueue, OutputQueue,
                                          ServingConfig)

    models = two_model_pair(laggy_sleep_s=laggy_sleep_s)
    q, wire = make_queue(backend, name="loadgen_mix")
    cfg = ServingConfig(
        batch_size=8, poll_timeout_s=0.02, max_batch_delay_ms=3,
        decode_workers=2, replicas=2, supervisor_interval_s=0.05,
        slo_p99_ms={"echo": 10_000.0, "laggy": 15.0},
        hbm_budget_bytes=1 << 30,
        autoscale=True, autoscale_interval_s=0.05,
        autoscale_cooldown_s=0.25,
        autoscale_policy=AutoscalePolicy(
            hysteresis=2, cooldown_s=0.25, queue_high=8,
            max_decode_workers=4, max_replicas=4,
            min_batch_delay_ms=1.0, max_batch_delay_ms=20.0))
    srv = ClusterServing(models, q, cfg).start()
    try:
        schedule = arrival_times(Steady(qps), duration_s, seed)
        mix = PayloadMix(
            [PayloadClass("echo", shape=(4,), dtype="float32",
                          weight=0.5),
             PayloadClass("laggy", shape=(4,), dtype="float32",
                          weight=0.5)],
            shift_at_s=shift_at_s, shift_weights=[0.15, 0.85])
        client = OpenLoopClient(InputQueue(q), OutputQueue(q), schedule,
                                mix, leg="mix_shift", seed=seed)
        records = client.run(drain_timeout_s=90.0)
        audit = srv.autoscale_audit() or {}
        actions = srv.autoscale_actions()
        health = srv.health()
    finally:
        srv.stop()
        if hasattr(q, "stop"):
            q.stop()
    windows = slo_mod.fold_windows(records, window_s, duration_s)
    shed = slo_mod.shed_fraction_by_model(records)
    outcomes = slo_mod.outcome_counts(records)
    lost = outcomes.get("lost", 0) + outcomes.get("dropped", 0)
    sec = _section(records, windows, {"echo": 10_000.0})
    sec.update({
        "wire": wire, "qps_target": qps, "shift_at_s": shift_at_s,
        "lost": lost,
        "shed_fraction_echo": shed.get("echo", 0.0),
        "shed_fraction_laggy": shed.get("laggy", 0.0),
        # 1.0 iff every shed record belonged to the over-SLO model
        "only_over_slo_shed": float(shed.get("echo", 0.0) == 0.0
                                    and shed.get("laggy", 0.0) > 0.0),
        "autoscale_actions": len(actions),
        "autoscale_flaps": audit.get("flaps"),
        "autoscale_by_label": audit.get("by_label"),
        "observed_p99_laggy_ms":
            health["models"]["laggy"]["observed_p99_ms"],
    })
    return sec


def run_adversarial_leg(backend: str = "shm") -> Dict[str, Any]:
    """Malformed flood + expired-TTL flood + a slow client holding its
    results while a well-behaved neighbour keeps its latency."""
    from analytics_zoo_tpu.deploy import (ClusterServing, InputQueue,
                                          OutputQueue, ServingConfig)

    models = {"echo": two_model_pair(laggy_sleep_s=0.0)["echo"]}
    q, wire = make_queue(backend, name="loadgen_adv")
    cfg = ServingConfig(batch_size=8, poll_timeout_s=0.02,
                        max_batch_delay_ms=3, decode_workers=2)
    srv = ClusterServing(models, q, cfg).start()
    try:
        inp, outp = InputQueue(q), OutputQueue(q)
        # 1. malformed records pushed past client-side validation
        mal_rids = malformed_flood(q, n=12)
        mal_answers = {r: outp.query(r, timeout=30.0) for r in mal_rids}
        mal_typed = sum(
            1 for v in mal_answers.values()
            if isinstance(v, dict) and "error" in v
            and v.get("code") in ("malformed", "decode_error"))
        # 2. expired-TTL flood: shed typed, never served stale
        ttl_uris = expired_ttl_flood(inp, model="echo", n=12,
                                     ttl_ms=0.01)
        ttl_answers = {u: outp.query(u, timeout=30.0) for u in ttl_uris}
        ttl_shed = sum(
            1 for v in ttl_answers.values()
            if isinstance(v, dict)
            and v.get("code") in ("expired", "overloaded"))
        # 3. slow client holds result leases while a neighbour runs
        slow = SlowClient(inp, outp, model="echo", n=8, hold_s=1.0,
                          uri_prefix="slow")
        slow.send()
        lats = []
        rng = np.random.Generator(np.random.PCG64(3))
        for i in range(16):
            x = rng.uniform(0, 1, (4,)).astype(np.float32)
            t0 = time.monotonic()
            inp.enqueue(uri=f"fast-{i:04d}", model="echo", x=x)
            outp.query(f"fast-{i:04d}", timeout=30.0)
            lats.append((time.monotonic() - t0) * 1e3)
        held = slow.collect(timeout_s=30.0)
        slow_ok = sum(1 for v in held.values()
                      if not (isinstance(v, dict) and "error" in v))
    finally:
        srv.stop()
        if hasattr(q, "stop"):
            q.stop()
    return {
        "wire": wire,
        "malformed_offered": len(mal_rids),
        "malformed_typed": mal_typed,
        "malformed_all_typed": float(mal_typed == len(mal_rids)),
        "expired_offered": len(ttl_uris),
        "expired_shed": ttl_shed,
        "expired_all_shed": float(ttl_shed == len(ttl_uris)),
        "slow_client_held": len(held),
        "slow_client_ok": slow_ok,
        "neighbour_p99_ms_while_held": slo_mod.percentile(lats, 99),
    }


def run_open_loop_check(qps: float = 50.0, duration_s: float = 2.0,
                        stall_s: float = 0.5,
                        seed: int = 23) -> Dict[str, Any]:
    """The open-loop property, pinned: a deliberately-stalled executor
    (every forward sleeps ``stall_s`` >> the mean inter-arrival gap)
    must not slow the offered schedule.  Every scheduled send fires,
    and send lag stays bounded by client-side cost alone."""
    from analytics_zoo_tpu.deploy import (ClusterServing, InferenceModel,
                                          InputQueue, MemoryQueue,
                                          OutputQueue, ServingConfig)

    def stalled_fwd(xs):
        time.sleep(stall_s)
        return xs[0] * 2.0

    m = InferenceModel(stalled_fwd, batch_buckets=(1, 8))
    q = MemoryQueue()
    srv = ClusterServing({"stall": m}, q, ServingConfig(
        batch_size=8, poll_timeout_s=0.02, max_batch_delay_ms=3,
        decode_workers=2)).start()
    try:
        schedule = arrival_times(Steady(qps), duration_s, seed)
        mix = PayloadMix([PayloadClass("stall", shape=(4,),
                                      dtype="float32")])
        client = OpenLoopClient(InputQueue(q), OutputQueue(q), schedule,
                                mix, leg="open_loop", seed=seed)
        records = client.finish(drain_timeout_s=90.0) \
            if client.start() else []
    finally:
        srv.stop()
    lags = [r.lag_s for r in records if r.lag_s is not None]
    sent = sum(1 for r in records if r.t_sent is not None)
    lag_p99 = slo_mod.percentile([v * 1e3 for v in lags], 99)
    mean_gap_ms = 1e3 / qps
    # offered rate independent of service time: every slot fired, and
    # p99 send lag stayed under the mean inter-arrival gap even though
    # service time was stall_s >> the gap
    independent = float(sent == len(schedule)
                        and lag_p99 is not None
                        and lag_p99 < mean_gap_ms)
    return {
        "scheduled": len(schedule), "sent": sent,
        "stall_s": stall_s, "qps_target": qps,
        "send_lag_p99_ms": lag_p99,
        "mean_interarrival_ms": mean_gap_ms,
        "service_p99_ms": _lat_stats(records)["latency_p99_ms"],
        "offered_rate_independent": independent,
    }


# -- the kill leg: a real server process dies mid-storm ---------------------

def _loadgen_env() -> Dict[str, str]:
    """The server child's environment: the parent's, platform included
    (``JAX_PLATFORMS`` is inherited, never defaulted), minus the
    parent's ``XLA_FLAGS`` (a child sizes its own virtual devices)."""
    return {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}


def _require_chip_free() -> None:
    """One process per chip: a parent that has initialised an
    accelerator backend holds the chip, and a server child that needs
    it then fails or hangs — refuse to start one."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized() \
            and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"this process already holds the {jax.default_backend()} "
            "backend; a server child cannot share the chip.  Run the "
            "process legs before any in-process JAX work (default_report "
            "does), or run them from a process that stays off JAX")


def start_server_process(spool: str, cache_dir: str, status_file: str,
                         log_path: str, slo_ms: float = 1000.0,
                         extra_args: Optional[List[str]] = None
                         ) -> subprocess.Popen:
    """One ``server_main`` OS process over the FileQueue spool.  The
    caller owns the Popen (the kill leg SIGKILLs it mid-storm)."""
    argv = [sys.executable, "-m",
            "analytics_zoo_tpu.loadgen.server_main",
            "--queue-root", spool, "--cache-dir", cache_dir,
            "--status-file", status_file,
            "--slo-p99-ms", str(slo_ms)]
    argv += list(extra_args or [])
    _require_chip_free()
    logf = open(log_path, "w")
    try:
        return subprocess.Popen(argv, env=_loadgen_env(), stdout=logf,
                                stderr=subprocess.STDOUT)
    finally:
        logf.close()     # the child holds its own fd


def wait_for_status(status_file: str, timeout_s: float = 120.0,
                    require: Optional[str] = None) -> Dict[str, Any]:
    """Block until the server's status JSON exists (and, optionally,
    carries ``require`` as a truthy key) — the 'server is up' barrier."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(status_file):
            try:
                with open(status_file) as f:
                    st = json.load(f)
            except (OSError, ValueError):
                st = None
            if st is not None and (require is None or st.get(require)):
                return st
        time.sleep(0.1)
    raise TimeoutError(f"server status never appeared at {status_file}")


def run_kill_leg(workdir: str, qps: float = 50.0, duration_s: float = 22.0,
                 kill_at_s: float = 7.0, restart_delay_s: float = 0.5,
                 slo_ms: float = 2000.0, seed: int = 29,
                 window_s: float = 1.0) -> Dict[str, Any]:
    """SIGKILL the serving process mid-storm; relaunch it against the
    same compile-cache dir; prove the client returns to SLO and the
    restarted server did ZERO live compiles (pure warm start).

    The FileQueue spool survives the kill, so queued-but-unclaimed
    records are served by the successor; records in flight inside the
    killed process are bounded by the pipeline depth and terminate as
    ``lost`` at the client (counted, not hidden).
    """
    from analytics_zoo_tpu.deploy.serving import (FileQueue, InputQueue,
                                                  OutputQueue)

    os.makedirs(workdir, exist_ok=True)
    spool = os.path.join(workdir, "spool")
    cache = os.path.join(workdir, "cache")
    os.makedirs(spool, exist_ok=True)
    os.makedirs(cache, exist_ok=True)
    status1 = os.path.join(workdir, "server1.status.json")
    status2 = os.path.join(workdir, "server2.status.json")

    proc = start_server_process(
        spool, cache, status1, os.path.join(workdir, "server1.log"),
        slo_ms=slo_ms)
    st1 = wait_for_status(status1, require="ready")
    q = FileQueue(spool, name=SERVER_QUEUE_NAME)
    schedule = arrival_times(Steady(qps), duration_s, seed)
    mix = PayloadMix([PayloadClass("default", shape=(SERVER_IN_DIM,),
                                   dtype="float32")])
    client = OpenLoopClient(InputQueue(q), OutputQueue(q), schedule, mix,
                            leg="kill", seed=seed,
                            query_timeout_s=5.0).start()
    t0 = time.monotonic()
    time.sleep(kill_at_s)
    proc.kill()                                   # SIGKILL, mid-storm
    proc.wait(timeout=30)
    kill_t = time.monotonic() - t0
    time.sleep(restart_delay_s)
    proc2 = start_server_process(
        spool, cache, status2, os.path.join(workdir, "server2.log"),
        slo_ms=slo_ms)
    try:
        records = client.finish(drain_timeout_s=60.0)
        st2 = wait_for_status(status2, require="ready")
    finally:
        proc2.send_signal(signal.SIGTERM)
        try:
            rc2 = proc2.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc2.kill()
            rc2 = proc2.wait(timeout=10)
    # final status the server dumped on SIGTERM (post-traffic truth)
    try:
        with open(status2) as f:
            st2 = json.load(f)
    except (OSError, ValueError):
        pass
    windows = slo_mod.fold_windows(records, window_s, duration_s)
    outcomes = slo_mod.outcome_counts(records)
    sec: Dict[str, Any] = {
        "qps_target": qps, "duration_s": duration_s,
        "kill_at_s": round(kill_t, 3), "slo_p99_ms": slo_ms,
        "offered": len(records),
        "answered_ok": outcomes.get("ok", 0),
        "lost": outcomes.get("lost", 0) + outcomes.get("dropped", 0),
        "outcomes": outcomes,
        "recovery_after_kill_s": slo_mod.recovery_time_to_slo(
            windows, kill_t, {"default": slo_ms}),
        "cold_compile_count": st1.get("compile_count"),
        "warm_compile_count": st2.get("compile_count"),
        "warm_cache_hits": ((st2.get("cache") or {}).get("events")
                            or {}).get("hit"),
        "warm_count": st2.get("warm_count"),
        "server2_exit_rc": rc2,
    }
    sec.update(_lat_stats(records))
    return sec


# -- the pod kill leg: a pod MEMBER HOST dies mid-storm ---------------------

def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _read_status(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def start_pod(workdir: str, spool: str, cache: str, tag: str,
              port: int, slo_ms: float, barrier_timeout_s: float,
              pod_name: str) -> Tuple[subprocess.Popen, subprocess.Popen,
                                      str, str]:
    """Launch one 2-process pod (lead + member host) of ``server_main``
    over the shared FileQueue spool.  Returns (lead, follower,
    lead_status_path, follower_status_path)."""
    procs, statuses = [], []
    for pid in (0, 1):
        status = os.path.join(workdir, f"{tag}_{pid}.status.json")
        procs.append(start_server_process(
            spool, cache, status,
            os.path.join(workdir, f"{tag}_{pid}.log"), slo_ms=slo_ms,
            extra_args=["--model", "bag", "--pod-processes", "2",
                        "--pod-id", str(pid), "--pod-port", str(port),
                        "--pod-name", pod_name, "--local-devices", "2",
                        "--barrier-timeout", str(barrier_timeout_s),
                        "--mesh-replicas", "1"]))
        statuses.append(status)
    return procs[0], procs[1], statuses[0], statuses[1]


def run_pod_kill_leg(workdir: str, qps: float = 40.0,
                     duration_s: float = 16.0, kill_at_s: float = 6.0,
                     tail_duration_s: float = 8.0,
                     barrier_timeout_s: float = 2.0,
                     slo_ms: float = 4000.0, seed: int = 31,
                     window_s: float = 1.0) -> Dict[str, Any]:
    """SIGKILL a pod MEMBER HOST mid-storm (``adversarial.host_kill``);
    prove the surviving lead quarantines the whole mesh replica within
    the barrier deadline and keeps serving degraded with ZERO lost
    requests, then that a successor pod against the same compile cache
    reaches SLO on a tail storm with ZERO live compiles.

    Two storms, overlapping pods: storm 1 runs on pod A; its member is
    SIGKILLed at ``kill_at_s`` and the lead serves the rest on its
    single-chip slot.  Pod B launches as soon as the quarantine is
    observed — warm-starting while A still serves, so the spool never
    loses its claimer — and storm 2 (the tail) runs after A retires
    idle (SIGTERM, exit 0 — its final status is the quarantine proof).
    The FileQueue hands each record to exactly one claimer, so the
    overlap is race-free.
    """
    from analytics_zoo_tpu.deploy.serving import (FileQueue, InputQueue,
                                                  OutputQueue)
    from analytics_zoo_tpu.loadgen.adversarial import host_kill

    os.makedirs(workdir, exist_ok=True)
    spool = os.path.join(workdir, "spool")
    cache = os.path.join(workdir, "cache")
    os.makedirs(spool, exist_ok=True)
    os.makedirs(cache, exist_ok=True)

    lead_a, fol_a, st_a0, _ = start_pod(
        workdir, spool, cache, "podA", _free_port(), slo_ms,
        barrier_timeout_s, "podA")
    sta = wait_for_status(st_a0, require="ready")
    q = FileQueue(spool, name=SERVER_QUEUE_NAME)
    mix = PayloadMix([PayloadClass("default", shape=(POD_IN_DIM,),
                                   dtype="int32", field="ids",
                                   low=0, high=POD_VOCAB)])
    schedule = arrival_times(Steady(qps), duration_s, seed)
    client = OpenLoopClient(InputQueue(q), OutputQueue(q), schedule, mix,
                            leg="pod_kill", seed=seed,
                            query_timeout_s=5.0).start()
    t0 = time.monotonic()
    killer = host_kill(fol_a, at_s=kill_at_s)
    killer.join(timeout=kill_at_s + 30)
    t_kill = time.monotonic() - t0

    # the lead's next mesh dispatch must time its deadline barrier out
    # and quarantine the whole mesh replica — watch the status file
    detect_deadline = time.monotonic() + barrier_timeout_s + 8.0
    quarantine_detect_s = None
    while time.monotonic() < detect_deadline:
        mesh_h = _read_status(st_a0).get("mesh") or {}
        if (mesh_h.get("quarantine_epoch") or 0) >= 1:
            quarantine_detect_s = time.monotonic() - t0 - t_kill
            break
        time.sleep(0.1)

    # successor pod on a FRESH coordination port, same spool + cache:
    # it must warm-start the full executable set (mesh flavor included)
    # while pod A still serves the storm
    lead_b, fol_b, st_b0, _ = start_pod(
        workdir, spool, cache, "podB", _free_port(), slo_ms,
        barrier_timeout_s, "podB")
    rc_a = rc_b = None
    records2: List[Any] = []
    try:
        records = client.finish(drain_timeout_s=90.0)
        wait_for_status(st_b0, require="ready")
        # pod A retires idle; B owns the spool from here — no gap
        lead_a.send_signal(signal.SIGTERM)
        rc_a = lead_a.wait(timeout=30)
        schedule2 = arrival_times(Steady(qps), tail_duration_s, seed + 1)
        client2 = OpenLoopClient(InputQueue(q), OutputQueue(q),
                                 schedule2, mix, leg="pod_kill_tail",
                                 seed=seed + 1, query_timeout_s=5.0)
        client2.start()
        records2 = client2.finish(drain_timeout_s=60.0)
    finally:
        if rc_a is None:
            lead_a.kill()
            rc_a = lead_a.wait(timeout=10)
        lead_b.send_signal(signal.SIGTERM)
        try:
            rc_b = lead_b.wait(timeout=30)
        except subprocess.TimeoutExpired:
            lead_b.kill()
            rc_b = lead_b.wait(timeout=10)
    fol_a.wait(timeout=10)
    try:
        # exits on its own once lead B's coordination service is gone
        rc_fol_b = fol_b.wait(timeout=30)
    except subprocess.TimeoutExpired:
        fol_b.kill()
        rc_fol_b = fol_b.wait(timeout=10)

    fin_a = _read_status(st_a0)          # post-SIGTERM quarantine proof
    fin_b = _read_status(st_b0)
    mesh_a = fin_a.get("mesh") or {}
    windows = slo_mod.fold_windows(records, window_s, duration_s)
    windows2 = slo_mod.fold_windows(records2, window_s, tail_duration_s)
    outcomes = slo_mod.outcome_counts(records)
    outcomes2 = slo_mod.outcome_counts(records2)
    lost = (outcomes.get("lost", 0) + outcomes.get("dropped", 0)
            + outcomes2.get("lost", 0) + outcomes2.get("dropped", 0))
    within = (quarantine_detect_s is not None
              and quarantine_detect_s <= barrier_timeout_s + 8.0)
    sec: Dict[str, Any] = {
        "qps_target": qps, "duration_s": duration_s,
        "tail_duration_s": tail_duration_s,
        "kill_at_s": round(t_kill, 3),
        "barrier_timeout_s": barrier_timeout_s, "slo_p99_ms": slo_ms,
        "offered": len(records) + len(records2),
        "answered_ok": (outcomes.get("ok", 0) + outcomes2.get("ok", 0)),
        "lost": lost,
        "outcomes": outcomes,
        "tail_outcomes": outcomes2,
        "quarantine_detect_s": (None if quarantine_detect_s is None
                                else round(quarantine_detect_s, 3)),
        "quarantine_within_deadline": float(within),
        "quarantine_epoch": mesh_a.get("quarantine_epoch"),
        "roster_lost": (mesh_a.get("roster") or {}).get("lost"),
        "recovery_after_kill_s": slo_mod.recovery_time_to_slo(
            windows, t_kill, {"default": slo_ms}),
        "tail_sustained_qps_at_slo": slo_mod.sustained_qps_at_slo(
            windows2, {"default": slo_ms}),
        "cold_compile_count": sta.get("compile_count"),
        "warm_compile_count": fin_b.get("compile_count"),
        "warm_cache_hits": ((fin_b.get("cache") or {}).get("events")
                            or {}).get("hit"),
        "leadA_exit_rc": rc_a,
        "leadB_exit_rc": rc_b,
        "follower_exit_rc": fol_a.returncode,     # -9: SIGKILLed
        "followerB_exit_rc": rc_fol_b,
    }
    sec.update(_lat_stats(list(records) + list(records2)))
    return sec


def default_report(workdir: str, quick: bool = False) -> Dict[str, Any]:
    """The full artifact: every leg, assembled.  ``quick`` shrinks
    durations for smoke runs (NOT for the pinned artifact)."""
    import platform

    scale = 0.5 if quick else 1.0
    report: Dict[str, Any] = {
        "schema": "slo-artifact-v1",
        "run_metadata": {
            "python": platform.python_version(),
            "platform": platform.platform(),
            "jax_platforms": os.environ.get("JAX_PLATFORMS", ""),
            "quick": bool(quick),
        },
    }
    # the process legs run FIRST, while this process has not touched a
    # backend: their server children own the device for as long as they
    # live (one process per chip); the in-process legs take it afterwards
    report["kill"] = run_kill_leg(os.path.join(workdir, "kill"),
                                  duration_s=22.0 * scale,
                                  kill_at_s=7.0 * scale)
    report["pod_kill"] = run_pod_kill_leg(
        os.path.join(workdir, "pod_kill"), duration_s=16.0 * scale,
        kill_at_s=6.0 * scale, tail_duration_s=8.0 * scale)
    report["steady"] = run_steady_leg(duration_s=8.0 * scale)
    report["burst"] = run_burst_leg(duration_s=14.0 * scale,
                                    at_s=3.0 * scale, dur_s=2.0 * scale)
    report["mix_shift"] = run_mix_shift_leg(duration_s=16.0 * scale,
                                            shift_at_s=6.0 * scale)
    report["adversarial"] = run_adversarial_leg()
    report["open_loop"] = run_open_loop_check()
    from analytics_zoo_tpu.core.context import describe_devices

    # what the in-process legs actually ran on
    report["run_metadata"]["device"] = describe_devices()
    return report
