"""The observability layer itself: tracer, labeled metrics, exporters,
and the flight recorder.

Everything here runs on isolated ``Tracer()`` / ``MetricsRegistry()``
instances (the recorder takes both via injection, plus a fake clock),
so these tests neither pollute nor depend on the process-wide
``TRACER`` / ``METRICS`` the pipelines write into.
"""

import json
import time

import pytest

from analytics_zoo_tpu.observe.export import (JsonlEventLog,
                                              parse_prometheus,
                                              publish_to_summary,
                                              to_prometheus)
from analytics_zoo_tpu.observe.metrics import (CATALOG, METRICS,
                                               MetricsRegistry,
                                               render_series)
from analytics_zoo_tpu.observe.recorder import SLO, FlightRecorder
from analytics_zoo_tpu.observe.trace import Tracer, find_orphans, span


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# tracing


class TestTracer:
    def test_span_lifecycle_and_chain(self):
        tr = Tracer(ring=64)
        root = tr.start("serving/request", uri="r-1")
        child = tr.start("serving/decode", trace=root.trace,
                         parent=root.sid)
        assert tr.active_count() == 2
        child.end(rows=4)
        root.end()
        assert tr.active_count() == 0
        chain = tr.verify_chain(root.trace)
        assert chain["complete"], chain
        assert chain["terminal"] == "ok"
        assert chain["orphans"] == []
        assert [s["name"] for s in chain["spans"]] == \
            ["serving/request", "serving/decode"]
        assert chain["spans"][0]["attrs"]["uri"] == "r-1"

    def test_first_terminal_status_wins(self):
        tr = Tracer(ring=8)
        sp = tr.start("serving/request")
        sp.end(status="expired")
        sp.end(status="ok")          # no-op: already terminal
        sp.end()                     # still a no-op
        [d] = tr.spans(sp.trace)
        assert d["status"] == "expired"
        assert tr.completed_count() == 1

    def test_orphan_detection(self):
        tr = Tracer(ring=8)
        root = tr.start("serving/request")
        ghost = tr.start("serving/decode", trace=root.trace, parent=9999)
        ghost.end()
        root.end()
        chain = tr.verify_chain(root.trace)
        assert not chain["complete"]
        assert [s["name"] for s in chain["orphans"]] == ["serving/decode"]
        assert find_orphans(chain["spans"]) == chain["orphans"]

    def test_incomplete_until_root_terminal(self):
        tr = Tracer(ring=8)
        root = tr.start("serving/request")
        assert not tr.verify_chain(root.trace)["complete"]
        root.end(status="model_error")
        chain = tr.verify_chain(root.trace)
        assert chain["complete"] and chain["terminal"] == "model_error"

    def test_ring_is_bounded_and_resizable(self):
        tr = Tracer(ring=16)                 # 16 is also the floor
        for i in range(24):
            tr.start("s", n=i).end()
        assert tr.completed_count() == 16
        kept = [d["attrs"]["n"] for d in tr.snapshot()]
        assert kept == list(range(8, 24))    # oldest first
        tr.resize(64)
        assert tr.ring_size() == 64
        assert tr.completed_count() == 16    # resize keeps contents
        assert tr.snapshot(limit=2)[-1]["attrs"]["n"] == 23

    def test_context_manager_marks_error(self):
        tr = Tracer(ring=8)
        with pytest.raises(RuntimeError):
            with tr.start("train/step"):
                raise RuntimeError("boom")
        [d] = tr.snapshot()
        assert d["status"] == "error"
        assert d["t1"] >= d["t0"]

    def test_sinks_see_completed_spans_and_survive_errors(self):
        tr = Tracer(ring=8)
        seen, bad = [], []

        def sink(d):
            seen.append(d["name"])

        def broken(d):
            bad.append(1)
            raise ValueError("sink bug")

        tr.add_sink(broken)
        tr.add_sink(sink)
        tr.start("a").end()
        assert seen == ["a"] and bad == [1]   # broken sink didn't block
        tr.remove_sink(broken)
        tr.start("b").end()
        assert seen == ["a", "b"] and bad == [1]

    def test_module_span_helper_uses_global_tracer(self):
        from analytics_zoo_tpu.observe.trace import TRACER
        before = TRACER.completed_count()
        with span("test/helper") as sp:
            trace_id = sp.trace
        assert TRACER.completed_count() >= min(before + 1,
                                               TRACER.ring_size())
        assert TRACER.verify_chain(trace_id)["terminal"] == "ok"


# ---------------------------------------------------------------------------
# labeled metrics


class TestMetricsRegistry:
    def test_labels_fan_out_into_series(self):
        reg = MetricsRegistry()
        reg.inc("serving_shed_total", code="expired")
        reg.inc("serving_shed_total", 2, code="malformed")
        reg.set("serving_inflight", 7)
        d = reg.delta(None)
        assert d["counters"]['serving_shed_total{code="expired"}'] == 1
        assert d["counters"]['serving_shed_total{code="malformed"}'] == 2
        assert d["gauges"]["serving_inflight"] == 7
        assert reg.series_count() == 3

    def test_delta_reads_only_the_window(self):
        reg = MetricsRegistry()
        reg.inc("serving_records_total", 5, outcome="ok")
        for v in (1.0, 1.0, 1.0, 1.0):
            reg.observe("serving_stage_seconds", v, stage="e2e")
        snap = reg.snapshot()
        reg.inc("serving_records_total", 3, outcome="ok")
        for v in (5.0, 5.0):
            reg.observe("serving_stage_seconds", v, stage="e2e")
        d = reg.delta(snap)
        key = 'serving_records_total{outcome="ok"}'
        assert d["counters"] == {key: 3}
        h = d["histograms"]['serving_stage_seconds{stage="e2e"}']
        # percentiles over ONLY the post-snapshot samples: all 5.0
        assert h["count"] == 2 and h["window_samples"] == 2
        assert h["p50"] == 5.0 and h["p99"] == 5.0 and h["max"] == 5.0
        assert h["mean"] == pytest.approx(5.0)
        assert d["window_s"] is not None and d["window_s"] >= 0

    def test_unchanged_series_omitted_from_delta(self):
        reg = MetricsRegistry()
        reg.inc("serving_records_total", outcome="ok")
        snap = reg.snapshot()
        assert reg.delta(snap)["counters"] == {}
        assert reg.delta(snap)["histograms"] == {}

    def test_undeclared_name_is_counted(self):
        reg = MetricsRegistry()
        reg.inc("totally_made_up_total")
        reg.observe("also_made_up_seconds", 0.1)
        d = reg.delta(None)
        assert d["counters"]["observe_undeclared_metrics_total"] == 2
        assert "totally_made_up_total" not in CATALOG

    def test_catalog_label_keys_are_sorted_tuples(self):
        for name, (typ, help_, labels) in CATALOG.items():
            assert typ in ("counter", "gauge", "histogram"), name
            assert help_, f"{name} has no help text"
            assert tuple(sorted(labels)) == tuple(labels), name

    def test_flat_mirror_bumps_legacy_timers(self):
        from analytics_zoo_tpu.core.profiling import TIMERS
        from analytics_zoo_tpu.observe.metrics import (count, observe,
                                                       set_gauge)
        snap = METRICS.snapshot()
        t0 = TIMERS.count("observe_test/flat_counter")
        count("serving_shed_total", 2, code="test_mirror",
              flat="observe_test/flat_counter")
        observe("serving_stage_seconds", 0.25, stage="test_mirror",
                flat="observe_test/flat_hist")
        set_gauge("serving_inflight", 3, flat="observe_test/flat_gauge")
        assert TIMERS.count("observe_test/flat_counter") == t0 + 2
        assert TIMERS.stats()["observe_test/flat_hist"]["count"] >= 1
        assert TIMERS.gauge("observe_test/flat_gauge") == 3
        d = METRICS.delta(snap)
        assert d["counters"]['serving_shed_total{code="test_mirror"}'] == 2

    def test_time_stage_observes_elapsed(self):
        from analytics_zoo_tpu.observe.metrics import time_stage
        reg = MetricsRegistry()
        orig_observe = METRICS.observe
        # time_stage writes through the module helper -> global METRICS;
        # measure via a registry-level delta instead of monkeypatching.
        del orig_observe
        snap = METRICS.snapshot()
        with time_stage("checkpoint_seconds", op="test_ts"):
            time.sleep(0.01)
        h = METRICS.delta(snap)["histograms"][
            'checkpoint_seconds{op="test_ts"}']
        assert h["count"] == 1 and h["max"] >= 0.01
        assert reg.series_count() == 0

    def test_render_series_stable(self):
        assert render_series("m", ()) == "m"
        assert render_series("m", (("a", "1"), ("b", "x"))) == \
            'm{a="1",b="x"}'


# ---------------------------------------------------------------------------
# exporters


class TestPrometheusRoundTrip:
    def _populated(self):
        reg = MetricsRegistry()
        reg.inc("serving_records_total", 12, outcome="ok")
        reg.inc("serving_records_total", 3, outcome="error")
        reg.set("serving_replicas_healthy", 2)
        for v in (0.1, 0.2, 0.3, 0.4):
            reg.observe("serving_stage_seconds", v, stage="device")
        return reg

    def test_round_trip(self):
        reg = self._populated()
        text = to_prometheus(reg)
        parsed = parse_prometheus(text)
        s = parsed["series"]
        assert s['serving_records_total{outcome="ok"}'] == 12
        assert s['serving_records_total{outcome="error"}'] == 3
        assert s["serving_replicas_healthy"] == 2
        assert s['serving_stage_seconds{quantile="0.5",stage="device"}'] \
            in (0.2, 0.3)
        assert s['serving_stage_seconds_count{stage="device"}'] == 4
        assert s['serving_stage_seconds_sum{stage="device"}'] == \
            pytest.approx(1.0)
        assert parsed["types"]["serving_records_total"] == "counter"
        assert parsed["types"]["serving_replicas_healthy"] == "gauge"
        assert parsed["types"]["serving_stage_seconds"] == "summary"

    def test_help_lines_and_label_escaping(self):
        reg = MetricsRegistry()
        reg.inc("serving_errors_total", code='we"ird\\pa\nth')
        text = to_prometheus(reg)
        assert "# HELP serving_errors_total" in text
        s = parse_prometheus(text)["series"]
        [(key, val)] = [(k, v) for k, v in s.items()
                        if k.startswith("serving_errors_total")]
        assert val == 1 and 'we"ird\\pa\nth' in key

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_prometheus("this is { not prometheus")

    def test_empty_registry_renders_empty(self):
        assert to_prometheus(MetricsRegistry()) == ""
        assert parse_prometheus("") == {"series": {}, "types": {}}


class TestJsonlEventLog:
    def test_emit_span_sink_and_metrics_dump(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        log = JsonlEventLog(path)
        tr = Tracer(ring=8)
        log.attach(tr)
        tr.start("serving/request", uri="u1").end()
        log.emit("marker", note="hello")
        reg = MetricsRegistry()
        reg.inc("serving_records_total", 4, outcome="ok")
        log.metrics_dump(reg)
        log.detach(tr)
        tr.start("after/detach").end()
        log.close()

        lines = [json.loads(l) for l in
                 open(path, encoding="utf-8").read().splitlines()]
        kinds = [l["kind"] for l in lines]
        assert kinds == ["span", "marker", "metrics"]
        assert lines[0]["span"]["name"] == "serving/request"
        assert lines[0]["span"]["status"] == "ok"
        assert lines[1]["note"] == "hello"
        assert lines[2]["dump"]["counters"][
            'serving_records_total{outcome="ok"}'] == 4
        assert all("ts" in l for l in lines)

    def test_emit_after_close_is_noop(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        log = JsonlEventLog(path)
        log.close()
        log.emit("marker")          # must not raise
        assert open(path).read() == ""


class TestSummaryBridge:
    def test_publish_then_read_scalars(self, tmp_path):
        from analytics_zoo_tpu.core.summary import (SummaryWriter,
                                                    read_scalars)
        reg = MetricsRegistry()
        reg.inc("train_steps_total", 20, kind="K")
        reg.set("train_loss", 0.5)
        for v in (0.01, 0.02, 0.03):
            reg.observe("train_step_seconds", v, kind="K")
        w = SummaryWriter(str(tmp_path))
        wrote = publish_to_summary(w, step=7, registry=reg)
        w.close()
        assert wrote == 4  # counter + gauge + p50 + p99
        d = str(tmp_path)
        assert read_scalars(d, 'train_steps_total{kind="K"}') == \
            [(7, 20.0)]
        assert read_scalars(d, "train_loss") == [(7, 0.5)]
        assert read_scalars(d, 'train_step_seconds{kind="K"}/p50') == \
            [(7, pytest.approx(0.02))]
        assert read_scalars(d, 'train_step_seconds{kind="K"}/p99')

    def test_prefix_filters(self, tmp_path):
        from analytics_zoo_tpu.core.summary import (SummaryWriter,
                                                    read_scalars)
        reg = MetricsRegistry()
        reg.set("train_loss", 1.0)
        reg.set("serving_inflight", 2.0)
        w = SummaryWriter(str(tmp_path))
        assert publish_to_summary(w, step=0, registry=reg,
                                  prefix="train_") == 1
        w.close()
        assert read_scalars(str(tmp_path), "train_loss") == [(0, 1.0)]
        assert read_scalars(str(tmp_path), "serving_inflight") == []


# ---------------------------------------------------------------------------
# flight recorder


def _recorder(clock, tmp_path=None, **kw):
    reg = MetricsRegistry()
    tr = Tracer(ring=64)
    kw.setdefault("window_s", 5.0)
    kw.setdefault("cooldown_s", 0.0)
    rec = FlightRecorder(tracer=tr, registry=reg, clock=clock,
                         out_dir=str(tmp_path) if tmp_path else None, **kw)
    return rec, reg, tr


class TestFlightRecorder:
    def test_slo_breach_snapshots_offending_spans(self, tmp_path):
        clock = FakeClock()
        slo = SLO("e2e_p99", "serving_stage_seconds",
                  labels={"stage": "e2e"}, p99_ms=100.0, min_count=5)
        rec, reg, tr = _recorder(clock, tmp_path, slos=[slo])

        assert rec.check() is None          # primes the first window
        # an injected latency fault: slow requests with slow spans
        for i in range(8):
            sp = tr.start("serving/request", uri=f"slow-{i}")
            sp.end()
            reg.observe("serving_stage_seconds", 0.5, stage="e2e")
        clock.tick(6.0)
        out = rec.check()
        assert out is not None and "flight_" in out

        snap = rec.last_record()
        assert snap["reason"] == "slo_breach"
        [detail] = snap["details"]
        assert detail["slo"] == "e2e_p99"
        assert detail["p99_ms"] >= 100.0
        uris = {s["attrs"].get("uri") for s in snap["spans"]}
        assert any(u and u.startswith("slow-") for u in uris)
        h = snap["metrics_delta"]["histograms"][
            'serving_stage_seconds{stage="e2e"}']
        assert h["count"] == 8

        on_disk = json.loads(open(out).read())
        assert on_disk["reason"] == "slo_breach"
        assert on_disk["seq"] == snap["seq"]

    def test_no_breach_below_bound_or_min_count(self, tmp_path):
        clock = FakeClock()
        slo = SLO("e2e_p99", "serving_stage_seconds",
                  labels={"stage": "e2e"}, p99_ms=100.0, min_count=5)
        rec, reg, _tr = _recorder(clock, tmp_path, slos=[slo])
        rec.check()
        # fast traffic: under the bound
        for _ in range(20):
            reg.observe("serving_stage_seconds", 0.001, stage="e2e")
        clock.tick(6.0)
        assert rec.check() is None
        # slow but below min_count
        for _ in range(3):
            reg.observe("serving_stage_seconds", 0.5, stage="e2e")
        clock.tick(6.0)
        assert rec.check() is None
        assert rec.records() == []

    def test_watched_counter_trips(self):
        clock = FakeClock()
        rec, reg, _tr = _recorder(
            clock, watch_counters=[("breaker_transitions_total",
                                    {"to": "open"})])
        rec.check()
        reg.inc("breaker_transitions_total", breaker="replica0",
                to="open")
        reg.inc("breaker_transitions_total", breaker="replica0",
                to="closed")             # must NOT trip
        clock.tick(6.0)
        out = rec.check()
        assert out == "slo_breach"       # no out_dir -> reason string
        snap = rec.last_record()
        [detail] = snap["details"]
        assert detail["counter"] == \
            'breaker_transitions_total{to="open"}'
        assert detail["delta"] == 1

    def test_cooldown_suppresses_storms(self):
        clock = FakeClock()
        slo = SLO("e2e", "serving_stage_seconds",
                  labels={"stage": "e2e"}, p99_ms=1.0, min_count=1)
        rec, reg, _tr = _recorder(clock, slos=[slo], cooldown_s=30.0)
        rec.check()
        for _ in range(4):
            reg.observe("serving_stage_seconds", 0.5, stage="e2e")
        clock.tick(6.0)
        assert rec.check() is not None
        for _ in range(4):
            reg.observe("serving_stage_seconds", 0.5, stage="e2e")
        clock.tick(6.0)
        assert rec.check() is None          # inside cooldown
        for _ in range(4):
            reg.observe("serving_stage_seconds", 0.5, stage="e2e")
        clock.tick(31.0)
        assert rec.check() is not None      # cooldown expired
        assert len(rec.records()) == 2

    def test_manual_trigger_and_stats(self, tmp_path):
        clock = FakeClock()
        rec, _reg, tr = _recorder(clock, tmp_path)
        sp = tr.start("serving/request", uri="bad")
        sp.end(status="model_error")
        out = rec.trigger("operator_request", detail={"who": "test"})
        assert out is not None and "flight_0001" in out
        snap = rec.last_record()
        assert snap["reason"] == "operator_request"
        assert any(s["status"] == "model_error" for s in snap["spans"])
        st = rec.stats()
        assert st["flight_records"] == 1
        assert st["last_reason"] == "operator_request"
        assert st["last_path"] == out

    @pytest.mark.parametrize("armed", [True, False],
                             ids=["profile_dir", "no_profile_dir"])
    def test_trigger_arms_the_profiler_only_with_a_profile_dir(
            self, tmp_path, armed):
        """The recorder's profiler arm: with ``profile_dir`` a capture
        lands its record AND a short device trace there (written by a
        thread of its own, so the trace is waited for); without one it
        lands the record alone."""
        import jax.numpy as jnp

        jnp.zeros(1).block_until_ready()    # backend up pre-profiler
        profile_dir = tmp_path / "profile"
        rec, _reg, _tr = _recorder(
            FakeClock(), tmp_path / "records", profile_ms=50,
            profile_dir=str(profile_dir) if armed else None)
        path = rec.trigger("unit_test", detail={"floor": 1.3})
        assert path and json.load(open(path))["reason"] == "unit_test"
        trace = []
        deadline = time.time() + (20.0 if armed else 0.5)
        while time.time() < deadline and not trace:
            trace = list(profile_dir.glob("plugins/profile/*/*.xplane.pb"))
            time.sleep(0.1)
        assert bool(trace) == armed
        assert sorted(p.name for p in tmp_path.iterdir()) == (
            ["profile", "records"] if armed else ["records"])

    def test_capture_bumps_flight_counter(self):
        clock = FakeClock()
        rec, _reg, _tr = _recorder(clock)
        snap = METRICS.snapshot()
        rec.trigger("unit_test")
        d = METRICS.delta(snap)
        assert d["counters"][
            'observe_flight_records_total{reason="unit_test"}'] == 1

    def test_offending_spans_prefer_bad_and_slow(self, tmp_path):
        clock = FakeClock(t=5000.0)
        rec, _reg, tr = _recorder(clock, tmp_path, max_spans=3)
        for i in range(10):
            tr.start("serving/request", n=i).end()
        bad = tr.start("serving/request", n="bad")
        bad.end(status="decode_error")
        rec.trigger("test")
        snap = rec.last_record()
        assert len(snap["spans"]) <= 3
        assert any(s["status"] == "decode_error" for s in snap["spans"])
