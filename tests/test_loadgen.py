"""Fast (tier-1) contracts for the loadgen subsystem.

The statistical core must be right before any soak number means
anything: Poisson inter-arrival statistics, schedule determinism from
``(shape, duration, seed)``, thinning correctness for ramp/burst
shapes, payload-mix draws, the SLO fold math, the autoscale hysteresis
audit, and — the property the whole harness exists for — the OPEN-LOOP
guarantee: a deliberately-stalled executor cannot slow the offered
schedule (no coordinated omission).
"""

import json
import math
import sys
import time

import numpy as np
import pytest

from analytics_zoo_tpu.loadgen import slo
from analytics_zoo_tpu.loadgen.arrivals import (DiurnalRamp, FlashCrowd,
                                                ShapeSum, Steady,
                                                arrival_times,
                                                interarrivals)
from analytics_zoo_tpu.loadgen.client import RequestRecord, _outcome_of
from analytics_zoo_tpu.loadgen.payloads import (PayloadClass, PayloadMix,
                                                ZipfianIdPayload,
                                                saturated_images)


class TestArrivals:
    def test_schedule_deterministic_in_seed(self):
        a = arrival_times(Steady(100.0), 10.0, seed=7)
        b = arrival_times(Steady(100.0), 10.0, seed=7)
        assert np.array_equal(a, b)
        c = arrival_times(Steady(100.0), 10.0, seed=8)
        assert not np.array_equal(a, c)

    def test_schedule_sorted_and_bounded(self):
        ts = arrival_times(FlashCrowd(10, 200, 2, 1), 6.0, seed=1)
        assert np.all(np.diff(ts) > 0)
        assert ts[0] >= 0.0 and ts[-1] < 6.0

    def test_poisson_interarrival_statistics(self):
        """Exponential gaps: mean 1/rate, CV ~ 1, and the memoryless
        tail P(gap > mean) = 1/e.  Long run so the tolerances are
        tight without flaking (n ~ 20k, se of mean ~ 0.7%)."""
        rate, dur = 200.0, 100.0
        ts = arrival_times(Steady(rate), dur, seed=3)
        n = len(ts)
        assert n == pytest.approx(rate * dur, rel=0.05)
        gaps = interarrivals(ts)
        assert gaps.mean() == pytest.approx(1.0 / rate, rel=0.05)
        cv = gaps.std() / gaps.mean()
        assert cv == pytest.approx(1.0, abs=0.05)
        tail = float((gaps > gaps.mean()).mean())
        assert tail == pytest.approx(math.exp(-1), abs=0.03)

    def test_thinning_matches_burst_profile(self):
        """Non-homogeneous thinning: the flash window's empirical rate
        is the burst rate, the floor's is the base rate."""
        shape = FlashCrowd(base_qps=20, burst_qps=200, at_s=4.0,
                           dur_s=2.0)
        ts = arrival_times(shape, 10.0, seed=5)
        in_burst = ((ts >= 4.0) & (ts < 6.0)).sum()
        outside = len(ts) - in_burst
        assert in_burst == pytest.approx(200 * 2.0, rel=0.15)
        assert outside == pytest.approx(20 * 8.0, rel=0.25)

    def test_ramp_rate_profile_and_sum(self):
        r = DiurnalRamp(low_qps=10, high_qps=110, period_s=60.0)
        assert r.rate(0.0) == pytest.approx(10.0)
        assert r.rate(30.0) == pytest.approx(110.0)
        assert r.peak_rate() == pytest.approx(110.0)
        s = ShapeSum([Steady(5.0), r])
        assert s.rate(30.0) == pytest.approx(115.0)
        assert s.peak_rate() == pytest.approx(115.0)
        # rectangle edges are half-open: [at, at+dur)
        f = FlashCrowd(1, 100, 2.0, 1.0)
        assert f.rate(2.0) == 100.0
        assert f.rate(3.0) == 1.0

    def test_degenerate_inputs_rejected(self):
        with pytest.raises(ValueError):
            Steady(0.0)
        with pytest.raises(ValueError):
            arrival_times(Steady(10.0), 0.0, seed=0)
        with pytest.raises(ValueError):
            FlashCrowd(10.0, 5.0, 1.0, 1.0)   # burst below base
        with pytest.raises(ValueError):
            DiurnalRamp(0.0, 10.0, 60.0)


class TestPayloads:
    def test_payload_class_draw(self):
        rng = np.random.Generator(np.random.PCG64(0))
        img = PayloadClass("m", shape=(8, 8, 3), dtype="uint8").draw(rng)
        assert img.shape == (8, 8, 3) and img.dtype == np.uint8
        assert img.min() >= 0 and img.max() <= 255
        x = PayloadClass("m", shape=(4,), dtype="float32").draw(rng)
        assert x.dtype == np.float32 and x.shape == (4,)

    def test_mix_weights_normalize_and_shift(self):
        mix = PayloadMix([PayloadClass("a", (4,), weight=3.0),
                          PayloadClass("b", (4,), weight=1.0)],
                         shift_at_s=5.0, shift_weights=[0.2, 0.8])
        assert mix.weights(0.0) == pytest.approx([0.75, 0.25])
        assert mix.weights(5.0) == pytest.approx([0.2, 0.8])
        assert mix.model_weights(6.0)["b"] == pytest.approx(0.8)
        assert mix.models() == ["a", "b"]

    def test_mix_draw_deterministic(self):
        mix = PayloadMix([PayloadClass("a", (4,), weight=0.5),
                          PayloadClass("b", (4,), weight=0.5)])
        r1 = np.random.Generator(np.random.PCG64(9))
        r2 = np.random.Generator(np.random.PCG64(9))
        picks1 = [mix.draw(r1, t=0.0)[0].model for _ in range(50)]
        picks2 = [mix.draw(r2, t=0.0)[0].model for _ in range(50)]
        assert picks1 == picks2
        assert set(picks1) == {"a", "b"}

    def test_saturated_images_is_a_plain_randomstate_stream(self):
        """The helper's draw stream is a plain ``RandomState`` one,
        byte for byte, so a caller that interleaves other draws on the
        same stream keeps its sequence."""
        crs = np.random.RandomState(7)
        a = saturated_images(4, rs=crs)
        crs2 = np.random.RandomState(7)
        b = [crs2.randint(0, 256, (224, 224, 3)).astype(np.uint8)
             for _ in range(4)]
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        # seed path builds its own RandomState
        c = saturated_images(2, seed=7)
        assert np.array_equal(c[0], b[0])

    def test_zipfian_payload_matches_the_shared_generator_bytes(self):
        """The skew contract (ISSUE 19): the payload class's id blocks
        are BYTE-IDENTICAL to ``data.zipf.zipfian_ids`` for the same
        generator state — a hit rate asserted at s=1.0 is literally
        about the traffic this class offers."""
        from analytics_zoo_tpu.data.zipf import zipfian_ids

        cls = ZipfianIdPayload("m", shape=(4, 8), vocab=256, s=1.0)
        got = cls.draw(np.random.default_rng(42))
        want = zipfian_ids(256, 32, 1.0, seed=42).reshape(4, 8)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32 and got.shape == (4, 8)
        assert got.min() >= 0 and got.max() < 256

    def test_zipfian_payload_skew_and_mix_wiring(self):
        cls = ZipfianIdPayload("m", shape=(4096,), vocab=64, s=1.0,
                               ttl_ms=50.0)
        ids = cls.draw(np.random.default_rng(0))
        counts = np.bincount(ids, minlength=64)
        # zipf(1): id 0 carries ~1/H(64) ≈ 21% of the mass; uniform
        # would put ~1.6% there — the skew must be unmistakable
        assert counts[0] > 4 * counts[32:].max()
        assert np.argmax(counts) == 0
        # rides a PayloadMix like any other class
        mix = PayloadMix([cls, PayloadClass("m", (4,), weight=1.0)])
        pick, payload = mix.draw(np.random.default_rng(1))
        assert payload is not None and pick.model == "m"
        with pytest.raises(ValueError, match="vocab"):
            ZipfianIdPayload("m", shape=(4,), vocab=0)


def _rec(uri, model, t_sched, latency_s=None, outcome="ok"):
    r = RequestRecord(uri, model, t_sched)
    r.t_sent = t_sched
    if latency_s is not None:
        r.t_done = t_sched + latency_s
    r.outcome = outcome
    return r


class TestSloFold:
    def test_outcome_of_classifies_error_payloads(self):
        assert _outcome_of(np.zeros(4)) == "ok"
        assert _outcome_of({"error": "x", "code": "expired"}) == "expired"
        assert _outcome_of({"error": "x"}) == "internal"
        assert _outcome_of({"no_error_key": 1}) == "ok"

    def test_percentile_nearest_rank(self):
        vals = list(range(1, 101))
        assert slo.percentile(vals, 50) == 50
        assert slo.percentile(vals, 99) == 99
        assert slo.percentile(vals, 100) == 100
        assert slo.percentile([], 99) is None

    def test_fold_windows_accounting(self):
        recs = ([_rec(f"a{i}", "m", 0.1 * i, latency_s=0.01)
                 for i in range(10)]            # window 0: 10 ok
                + [_rec("s0", "m", 1.2, outcome="overloaded"),
                   _rec("s1", "m", 1.3, outcome="expired"),
                   _rec("l0", "m", 1.4, outcome="lost"),
                   _rec("e0", "m", 1.5, latency_s=0.5,
                        outcome="model_error")])
        ws = slo.fold_windows(recs, window_s=1.0, duration_s=2.0)
        assert len(ws) == 2
        assert ws[0]["offered"] == 10 and ws[0]["answered"] == 10
        assert ws[0]["shed"] == 0 and ws[0]["lost"] == 0
        assert ws[0]["offered_qps"] == pytest.approx(10.0)
        assert ws[0]["p99_ms"]["m"] == pytest.approx(10.0)
        # typed non-shed errors are answered; shed codes are shed;
        # lost is lost
        assert ws[1]["offered"] == 4
        assert ws[1]["shed"] == 2 and ws[1]["lost"] == 1
        assert ws[1]["answered"] == 1

    def test_sustained_qps_needs_consecutive_compliance(self):
        slo_ms = {"m": 100.0}
        good = [_rec(f"g{i}", "m", 0.25 * i, latency_s=0.01)
                for i in range(40)]             # 10 windows of 4
        ws = slo.fold_windows(good, 1.0, 10.0)
        q = slo.sustained_qps_at_slo(ws, slo_ms, min_consec=3)
        assert q == pytest.approx(4.0)
        # shorter than min_consec: never "sustained"
        assert slo.sustained_qps_at_slo(ws[:2], slo_ms,
                                        min_consec=3) is None
        # one lost record poisons exactly its window
        bad = good + [_rec("x", "m", 1.5, outcome="lost")]
        ws2 = slo.fold_windows(bad, 1.0, 10.0)
        assert not slo._window_meets(ws2[1], slo_ms, True)
        assert slo._window_meets(ws2[0], slo_ms, True)

    def test_recovery_time_to_slo(self):
        slo_ms = {"m": 100.0}
        # dented for 2 windows after the event, then compliant
        recs = ([_rec(f"a{i}", "m", 0.5 * i, latency_s=0.01)
                 for i in range(8)]                      # 0-4s ok
                + [_rec(f"b{i}", "m", 4.1 + 0.3 * i, latency_s=0.5)
                   for i in range(6)]                    # 4-6s over
                + [_rec(f"c{i}", "m", 6.1 + 0.3 * i, latency_s=0.01)
                   for i in range(12)])                  # 6-10s ok
        ws = slo.fold_windows(recs, 1.0, 10.0)
        r = slo.recovery_time_to_slo(ws, event_t=4.0,
                                     slo_ms_by_model=slo_ms,
                                     min_consec=2)
        assert r == pytest.approx(2.0, abs=0.51)
        # never dented => 0.0
        calm = slo.fold_windows(
            [_rec(f"a{i}", "m", 0.5 * i, latency_s=0.01)
             for i in range(20)], 1.0, 10.0)
        assert slo.recovery_time_to_slo(calm, 2.0, slo_ms) == 0.0
        # never recovers => None
        sick = slo.fold_windows(
            [_rec(f"a{i}", "m", 0.5 * i, latency_s=9.9)
             for i in range(20)], 1.0, 10.0)
        assert slo.recovery_time_to_slo(sick, 2.0, slo_ms) is None

    def test_write_artifact_strict_json(self, tmp_path):
        p = tmp_path / "SLO_test.json"
        slo.write_artifact(str(p), {"b": 1, "a": {"x": 2.5}})
        doc = json.loads(p.read_text())
        assert doc == {"b": 1, "a": {"x": 2.5}}
        with pytest.raises(ValueError):
            slo.write_artifact(str(p), {"bad": float("nan")})
        # the failed write must not clobber the good artifact
        assert json.loads(p.read_text()) == doc


class TestAutoscaleAudit:
    def test_empty_ledger(self):
        from analytics_zoo_tpu.deploy.autoscale import audit_actions
        a = audit_actions([], cooldown_s=1.0, now=10.0)
        assert a["total"] == 0 and a["flaps"] == 0
        assert a["quiet_s"] is None

    def test_flap_is_reversal_within_window(self):
        from analytics_zoo_tpu.deploy.autoscale import audit_actions
        mk = lambda t, d, m="m", r="decode": {
            "t": t, "model": m, "resource": r, "direction": d,
            "value": 1, "detail": ""}
        # up -> down 0.5s later with cooldown 1.0 (window 2.0): flap
        a = audit_actions([mk(0.0, "up"), mk(0.5, "down")],
                          cooldown_s=1.0, now=5.0)
        assert a["flaps"] == 1
        assert a["flap_events"][0]["from"] == "up"
        assert a["quiet_s"] == pytest.approx(4.5)
        # same reversal far outside the window: not a flap
        b = audit_actions([mk(0.0, "up"), mk(10.0, "down")],
                          cooldown_s=1.0)
        assert b["flaps"] == 0
        # reversals on DIFFERENT resources never flap
        c = audit_actions([mk(0.0, "up", r="decode"),
                           mk(0.1, "down", r="replicas")],
                          cooldown_s=1.0)
        assert c["flaps"] == 0
        assert c["by_label"] == {"m/decode/up": 1, "m/replicas/down": 1}

    def test_autoscaler_exports_audit(self):
        """The live Autoscaler's export/audit surface (fabricated
        ledger through the real object)."""
        from analytics_zoo_tpu.deploy.autoscale import (AutoscalePolicy,
                                                        Autoscaler)
        sc = Autoscaler(lambda: {}, policy=AutoscalePolicy(cooldown_s=1.0))
        assert sc.export_actions() == []
        assert sc.audit()["flaps"] == 0


class TestOpenLoopProperty:
    def test_stalled_executor_cannot_slow_the_schedule(self):
        """THE open-loop guarantee: service time 300ms >> mean gap
        25ms, yet every scheduled send fires and p99 send lag stays
        under the mean gap.  A closed-loop (request-response) client
        would have offered ~3 requests/s here."""
        from analytics_zoo_tpu.loadgen.harness import run_open_loop_check
        sec = run_open_loop_check(qps=40.0, duration_s=1.5, stall_s=0.3,
                                  seed=2)
        assert sec["sent"] == sec["scheduled"]
        assert sec["offered_rate_independent"] == 1.0
        assert sec["service_p99_ms"] > sec["mean_interarrival_ms"]


class TestAdversarialLegs:
    def _serve_echo(self):
        from analytics_zoo_tpu.deploy import (ClusterServing,
                                              InferenceModel, MemoryQueue,
                                              ServingConfig)
        m = InferenceModel(lambda xs: xs[0] * 2.0, batch_buckets=(1, 8))
        q = MemoryQueue()
        srv = ClusterServing({"echo": m}, q, ServingConfig(
            batch_size=8, poll_timeout_s=0.02, max_batch_delay_ms=3,
            decode_workers=2)).start()
        return srv, q

    def test_malformed_flood_gets_typed_errors(self):
        from analytics_zoo_tpu.deploy import OutputQueue
        from analytics_zoo_tpu.loadgen.adversarial import malformed_flood
        srv, q = self._serve_echo()
        try:
            rids = malformed_flood(q, n=9)
            outp = OutputQueue(q)
            for rid in rids:
                v = outp.query(rid, timeout=30.0)
                assert isinstance(v, dict) and "error" in v, (rid, v)
                assert v.get("code") in ("malformed",
                                         "decode_error"), (rid, v)
        finally:
            srv.stop()

    def test_expired_ttl_flood_is_shed_not_served(self):
        from analytics_zoo_tpu.deploy import InputQueue, OutputQueue
        from analytics_zoo_tpu.loadgen.adversarial import expired_ttl_flood
        srv, q = self._serve_echo()
        try:
            uris = expired_ttl_flood(InputQueue(q), model="echo", n=8,
                                     ttl_ms=0.01)
            outp = OutputQueue(q)
            for u in uris:
                v = outp.query(u, timeout=30.0)
                assert isinstance(v, dict) \
                    and v.get("code") in ("expired", "overloaded"), (u, v)
        finally:
            srv.stop()

    def test_slow_client_holds_results_without_starving_neighbour(self):
        from analytics_zoo_tpu.deploy import InputQueue, OutputQueue
        from analytics_zoo_tpu.loadgen.adversarial import SlowClient
        srv, q = self._serve_echo()
        try:
            inp, outp = InputQueue(q), OutputQueue(q)
            slow = SlowClient(inp, outp, model="echo", n=4, hold_s=0.5)
            slow.send()
            # neighbour traffic completes while results are held
            inp.enqueue(uri="nb", model="echo",
                        x=np.ones((4,), np.float32))
            v = outp.query("nb", timeout=30.0)
            np.testing.assert_allclose(np.asarray(v),
                                       np.full((4,), 2.0), rtol=1e-6)
            held = slow.collect(timeout_s=30.0)
            assert len(held) == 4
            assert all(not (isinstance(h, dict) and "error" in h)
                       for h in held.values())
        finally:
            srv.stop()


class TestRunProcesses:
    """The generalized mp_harness entrypoint spawner (fast: trivial
    children, no jax imports)."""

    def test_run_processes_parses_outfiles(self, tmp_path):
        from tests.mp_harness import run_processes
        outs = [tmp_path / f"o{i}.json" for i in range(2)]
        argvs = [[sys.executable, "-c",
                  "import json,sys,os;"
                  "json.dump({'pid': %d, 'jp': os.environ.get("
                  "'JAX_PLATFORMS')}, open(sys.argv[1], 'w'))" % i,
                  str(o)] for i, o in enumerate(outs)]
        res = run_processes(argvs, tmp_path, "rp_smoke",
                            env_extra={"JAX_PLATFORMS": "cpu"},
                            timeout=60, outfiles=outs)
        assert [r["pid"] for r in res] == [0, 1]
        # env_extra overlays the stripped env
        assert all(r["jp"] == "cpu" for r in res)
        # logs teed per process
        assert (tmp_path / "rp_smoke_0.log").exists()

    def test_run_processes_asserts_exit_codes(self, tmp_path):
        from tests.mp_harness import run_processes
        argv = [[sys.executable, "-c", "import sys; sys.exit(3)"]]
        with pytest.raises(AssertionError):
            run_processes(argv, tmp_path, "rp_rc", timeout=60)
        res = run_processes(argv, tmp_path, "rp_rc2", timeout=60,
                            expect_rc={0: 3})
        assert res == [None]

    def test_run_workers_still_strips_topology_env(self, monkeypatch):
        """Byte-compatibility of the worker path: XLA_FLAGS and
        JAX_PLATFORMS never leak into children."""
        from tests.mp_harness import _spawn_env
        monkeypatch.setenv("XLA_FLAGS", "--xla_whatever")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        env = _spawn_env()
        assert "XLA_FLAGS" not in env and "JAX_PLATFORMS" not in env
        env2 = _spawn_env({"JAX_PLATFORMS": "cpu"})
        assert env2["JAX_PLATFORMS"] == "cpu"


class TestOneProcessPerChip:
    """The harness's server children: the platform is inherited, never
    defaulted to the CPU, and no child is started from a process that
    already holds an accelerator (it could not share the chip)."""

    def test_server_child_inherits_the_parents_platform(self, monkeypatch):
        from analytics_zoo_tpu.loadgen.harness import _loadgen_env
        monkeypatch.setenv("XLA_FLAGS", "--xla_whatever")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        env = _loadgen_env()
        assert env["JAX_PLATFORMS"] == "tpu" and "XLA_FLAGS" not in env
        monkeypatch.delenv("JAX_PLATFORMS")
        assert "JAX_PLATFORMS" not in _loadgen_env()

    def test_no_server_child_once_this_process_holds_a_chip(
            self, monkeypatch, tmp_path):
        import jax

        from analytics_zoo_tpu.loadgen import harness

        jax.devices()                   # the backend is initialised
        harness._require_chip_free()    # ... on the CPU: children allowed
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="already holds the tpu"):
            harness.start_server_process(
                str(tmp_path / "spool"), str(tmp_path / "cache"),
                str(tmp_path / "status.json"), str(tmp_path / "log"))
        assert not (tmp_path / "log").exists()      # nothing was launched


class TestClientRecordMath:
    def test_latency_is_schedule_to_answer(self):
        """Coordinated-omission resistance lives in this definition:
        latency includes the time a send spent waiting behind schedule
        slippage, not just server time."""
        r = RequestRecord("u", "m", t_sched=10.0)
        r.t_sent = 10.4        # sender fell 400ms behind
        r.t_done = 10.5
        assert r.latency_s == pytest.approx(0.5)
        assert r.lag_s == pytest.approx(0.4)
        assert RequestRecord("u", "m", 1.0).latency_s is None
        d = r.as_dict()
        assert d["uri"] == "u" and d["t_sched"] == 10.0
