"""Slow loadgen soaks: the chaos proofs docs/LOADGEN.md names.

Three legs, each a full production-shaped run through the real
pipeline (CI runs these in the multiprocess job and uploads the
``SLO_*.json`` it writes plus the teed process logs as artifacts):

- **shifting mix** — two models under the live autoscaler; 85% of
  traffic shifts onto the model that cannot meet its SLO.  Asserts the
  autoscaler CONVERGES (actions happen, zero hysteresis flaps, every
  action present in the labeled ``serving_autoscale_actions_total``
  series) and that shed is SELECTIVE (only the over-SLO model's
  traffic is shed; the well-behaved neighbour loses nothing).
- **kill mid-storm** — a real ``server_main`` OS process is SIGKILLed
  mid-storm and relaunched over the same FileQueue spool + persistent
  compile cache.  Asserts the client returns to SLO and the successor
  did ZERO live compiles (pure warm start), with bounded loss.
- **multiprocess client fan-in** — several ``client_main`` OS
  processes drive one server through the generalized
  ``mp_harness.run_processes``; every client's schedule fires in full
  (open loop survives process isolation).
"""

import json
import os
import time

import pytest

from analytics_zoo_tpu.loadgen import slo as slo_mod
from analytics_zoo_tpu.observe import metrics as obs


def _artifact_dir(tmp_path) -> str:
    """Write soak artifacts where CI's log-upload step looks."""
    d = os.environ.get("ZOO_MP_LOG_DIR") or str(tmp_path)
    os.makedirs(d, exist_ok=True)
    return d


@pytest.mark.slow
class TestMixShiftSoak:
    def test_autoscaler_converges_and_sheds_selectively(self, tmp_path):
        from analytics_zoo_tpu.loadgen.harness import run_mix_shift_leg
        mark = obs.METRICS.snapshot()
        sec = run_mix_shift_leg(duration_s=14.0, qps=60.0,
                                shift_at_s=5.0, seed=17,
                                backend="memory")
        slo_mod.write_artifact(
            os.path.join(_artifact_dir(tmp_path), "SLO_soak_mix.json"),
            {"mix_shift": sec})

        # nothing silently vanished: every offered request terminated
        # in an answer or a TYPED shed
        assert sec["lost"] == 0, sec["outcomes"]
        assert sec["offered"] > 500

        # selective shed: the 15ms-SLO model shed, the neighbour didn't
        assert sec["shed_fraction_laggy"] > 0.0, sec
        assert sec["shed_fraction_echo"] == 0.0, sec
        assert sec["only_over_slo_shed"] == 1.0
        assert sec["observed_p99_laggy_ms"] > 15.0

        # convergence: the autoscaler acted, with zero hysteresis flaps
        # (no up->down->up churn inside the flap window)
        assert sec["autoscale_actions"] >= 1, sec
        assert sec["autoscale_flaps"] == 0, sec

        # the audit's ledger is fully mirrored in the labeled metric —
        # the hysteresis audit is readable from telemetry alone
        snap = obs.METRICS.snapshot()
        for label, n in (sec["autoscale_by_label"] or {}).items():
            model, resource, direction = label.split("/")
            key = ("serving_autoscale_actions_total",
                   (("direction", direction), ("model", model),
                    ("resource", resource)))
            got = snap.counters.get(key, 0) - mark.counters.get(key, 0)
            assert got >= n, (
                f"action {label} x{n} missing from labeled metric "
                f"(saw {got})")

        # loadgen's own telemetry flowed
        key = ("loadgen_requests_total",
               (("leg", "mix_shift"), ("model", "laggy")))
        assert snap.counters.get(key, 0) > mark.counters.get(key, 0)


@pytest.mark.slow
class TestKillMidStorm:
    def test_sigkill_recovers_to_slo_through_warm_cache(self, tmp_path):
        from analytics_zoo_tpu.loadgen.harness import run_kill_leg
        art_dir = _artifact_dir(tmp_path)
        sec = run_kill_leg(os.path.join(art_dir, "kill_leg"),
                           qps=30.0, duration_s=16.0, kill_at_s=6.0,
                           slo_ms=2000.0, seed=29)
        slo_mod.write_artifact(
            os.path.join(art_dir, "SLO_soak_kill.json"), {"kill": sec})

        # the successor performed ZERO live compiles: every program
        # came from the predecessor's persistent cache
        assert sec["warm_compile_count"] == 0, sec
        assert sec["warm_count"] >= 3, sec
        assert (sec["warm_cache_hits"] or 0) >= 3, sec
        # the cold process compiled live (the cache was actually cold)
        assert sec["cold_compile_count"] >= 3, sec

        # the storm recovered to SLO after the kill, inside the run
        assert sec["recovery_after_kill_s"] is not None, sec
        assert sec["recovery_after_kill_s"] < 10.0, sec

        # bounded loss: only requests in flight INSIDE the killed
        # process may be lost (spool survives; FileQueue's claimed-but-
        # unanswered records are beyond the drain deadline)
        assert sec["lost"] <= 32, sec
        assert sec["answered_ok"] > 0.5 * sec["offered"], sec
        # the relaunched server exited cleanly on SIGTERM
        assert sec["server2_exit_rc"] == 0


@pytest.mark.slow
class TestMultiprocessClientFanIn:
    def test_three_client_processes_hold_their_schedules(self, tmp_path):
        import sys

        from analytics_zoo_tpu.loadgen.harness import (
            SERVER_QUEUE_NAME, start_server_process, wait_for_status)
        from tests.mp_harness import finish_processes, start_processes

        art_dir = _artifact_dir(tmp_path)
        spool = tmp_path / "spool"
        cache = tmp_path / "cache"
        spool.mkdir()
        cache.mkdir()
        status = tmp_path / "server.status.json"
        server = start_server_process(
            str(spool), str(cache), str(status),
            os.path.join(art_dir, "fanin_server.log"), slo_ms=5000.0)
        try:
            wait_for_status(str(status), require="ready")
            outs = [tmp_path / f"client{i}.json" for i in range(3)]
            argvs = [[sys.executable, "-m",
                      "analytics_zoo_tpu.loadgen.client_main",
                      "--queue-root", str(spool),
                      "--queue-name", SERVER_QUEUE_NAME,
                      "--outfile", str(o),
                      "--leg", f"fanin{i}",
                      "--uri-prefix", f"fanin{i}",
                      "--shape", "steady", "--qps", "15",
                      "--duration-s", "8", "--seed", str(100 + i)]
                     for i, o in enumerate(outs)]
            clients = start_processes(
                argvs, env_extra={"JAX_PLATFORMS": "cpu"})
            res = finish_processes(clients, tmp_path, "fanin",
                                   timeout=300, outfiles=outs)
        finally:
            server.terminate()
            server.wait(timeout=30)
        assert server.returncode == 0

        total_ok = 0
        for i, summary in enumerate(res):
            assert summary is not None
            # open loop across a process boundary: every scheduled
            # send fired, none were dropped by the transport
            assert summary["sent"] == summary["scheduled"], (i, summary)
            assert summary["open_loop_drops"] == 0, (i, summary)
            assert summary["outcomes"].get("lost", 0) == 0, (i, summary)
            total_ok += summary["answered_ok"]
            assert summary["answered_ok"] > 0.9 * summary["offered"], (
                i, summary)
        with open(os.path.join(art_dir, "SLO_soak_fanin.json"),
                  "w") as f:
            json.dump({"fanin": {"clients": len(res),
                                 "answered_ok": total_ok,
                                 "t": time.time()}}, f)
