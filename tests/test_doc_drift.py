"""docs must not drift from the artifacts/registries they pin.

r5 shipped a doc quoting flash "8.29x at 1024" while BENCH_r05.json
said 1.13x — interactive-probe numbers leaked into the doc of record.
docs/PERFORMANCE.md now pins its numeric claims in a marker-delimited
table; this test resolves each dotted key into the NEWEST BENCH_*.json
and fails tier-1 when they disagree, so regenerating the artifact
without regenerating the doc is a red build, not silent drift.

The same discipline covers docs/OBSERVABILITY.md: its pinned
metric-names table is machine-checked against the live
``observe.metrics.CATALOG`` (names, types, AND label keys), so adding
or renaming a metric without updating the doc of record is equally
red.

Also guards the instrument itself: the bench ratio/sanitize helpers
must never let Infinity/NaN reach an emitted report again.
"""

import importlib.util
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOC = REPO / "docs" / "PERFORMANCE.md"

_TABLE_RE = re.compile(
    r"<!--\s*BENCH_TABLE:BEGIN([^>]*)-->(.*?)<!--\s*BENCH_TABLE:END\s*-->",
    re.S)


def _newest_artifact():
    arts = sorted(REPO.glob("BENCH_*.json"))
    if not arts:
        pytest.skip("no BENCH_*.json artifact in repo root")
    return arts[-1]


def _pinned_tables():
    """Every BENCH_TABLE block in the doc, not just the first.  A table
    may carry ``requires=<dotted key>``: its claims are only checked
    against artifacts that HAVE that key (so pinning a newly-benched
    number doesn't fail tier-1 against an older artifact that predates
    the bench leg — the claim arms itself on the next regeneration)."""
    tables = []
    for m in _TABLE_RE.finditer(DOC.read_text()):
        attrs = dict(re.findall(r"(\w+)=(\S+)", m.group(1)))
        claims = []
        for line in m.group(2).splitlines():
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if (len(cells) != 2 or cells[0] in ("key", "")
                    or "---" in cells[0]):
                continue
            claims.append((cells[0], float(cells[1])))
        assert claims, "a pinned-claims table is empty"
        tables.append({"requires": attrs.get("requires"),
                       "tolerance": float(attrs.get("tolerance", 0.02)),
                       "claims": claims})
    assert tables, "PERFORMANCE.md lost its BENCH_TABLE markers"
    return tables


def _pinned_claims():
    tables = _pinned_tables()
    return ([c for t in tables for c in t["claims"]],
            tables[0]["tolerance"])


def _resolve(doc, dotted, required=True):
    cur = {"parsed": doc.get("parsed", doc)}
    for part in dotted.split("."):
        if not (isinstance(cur, dict) and part in cur):
            assert not required, \
                f"artifact has no key {dotted!r} (stopped at {part!r})"
            return None
        cur = cur[part]
    return cur


class TestDocDrift:
    def test_pinned_claims_match_newest_artifact(self):
        art = _newest_artifact()
        doc = json.loads(art.read_text())
        bad = []
        for table in _pinned_tables():
            req = table["requires"]
            if req and _resolve(doc, req, required=False) is None:
                continue        # artifact predates this bench leg
            for key, claimed in table["claims"]:
                actual = _resolve(doc, key)
                assert isinstance(actual, (int, float)), \
                    f"{key} resolves to non-numeric {actual!r}"
                if actual != pytest.approx(claimed,
                                           rel=table["tolerance"]):
                    bad.append(f"{key}: doc={claimed} artifact={actual}")
        assert not bad, (f"PERFORMANCE.md drifted from {art.name}:\n  "
                         + "\n  ".join(bad))

    def test_requires_gate_skips_only_missing_keys(self):
        """The requires= mechanism itself: a table gated on a key the
        artifact lacks is skipped; one gated on a present key is
        checked (regression for the multi-table finditer upgrade)."""
        doc = {"parsed": {"extra": {"new_leg": {"speedup": 12.0}}}}
        assert _resolve(doc, "parsed.extra.new_leg.speedup") == 12.0
        assert _resolve(doc, "parsed.extra.absent_leg",
                        required=False) is None
        with pytest.raises(AssertionError):
            _resolve(doc, "parsed.extra.absent_leg")
        # and the doc of record actually uses multi-table pinning
        tables = _pinned_tables()
        assert len(tables) >= 2, \
            "expected the wire-codec claims in their own BENCH_TABLE"
        assert any(t["requires"] for t in tables)

    def test_pinned_claims_are_finite(self):
        import math
        claims, _ = _pinned_claims()
        for key, v in claims:
            assert math.isfinite(v), f"{key} pins a non-finite value"


OBS_DOC = REPO / "docs" / "OBSERVABILITY.md"

_METRICS_TABLE_RE = re.compile(
    r"<!--\s*METRICS_TABLE:BEGIN\s*-->(.*?)<!--\s*METRICS_TABLE:END\s*-->",
    re.S)


def _pinned_metrics():
    m = _METRICS_TABLE_RE.search(OBS_DOC.read_text())
    assert m, "OBSERVABILITY.md lost its METRICS_TABLE markers"
    pinned = {}
    for line in m.group(1).splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) != 3 or cells[0] in ("metric", "") or "---" in cells[0]:
            continue
        labels = tuple(sorted(x.strip() for x in cells[2].split(",")
                              if x.strip()))
        pinned[cells[0]] = (cells[1], labels)
    assert pinned, "pinned metrics table is empty"
    return pinned


class TestObservabilityDocDrift:
    """docs/OBSERVABILITY.md's metric table == observe.metrics.CATALOG."""

    def test_pinned_metric_names_match_catalog(self):
        from analytics_zoo_tpu.observe.metrics import CATALOG
        pinned = _pinned_metrics()
        missing = sorted(set(CATALOG) - set(pinned))
        stale = sorted(set(pinned) - set(CATALOG))
        assert not missing, \
            f"CATALOG metrics missing from OBSERVABILITY.md: {missing}"
        assert not stale, \
            f"OBSERVABILITY.md pins metrics not in CATALOG: {stale}"

    def test_pinned_types_and_labels_match_catalog(self):
        from analytics_zoo_tpu.observe.metrics import CATALOG
        bad = []
        for name, (typ, labels) in _pinned_metrics().items():
            if name not in CATALOG:
                continue
            cat_typ, _, cat_labels = CATALOG[name]
            if typ != cat_typ:
                bad.append(f"{name}: doc type={typ} catalog={cat_typ}")
            if labels != tuple(sorted(cat_labels)):
                bad.append(f"{name}: doc labels={labels} "
                           f"catalog={tuple(sorted(cat_labels))}")
        assert not bad, ("OBSERVABILITY.md drifted from CATALOG:\n  "
                         + "\n  ".join(bad))


SERVING_DOC = REPO / "docs" / "SERVING.md"

_ERROR_CODE_TABLE_RE = re.compile(
    r"<!--\s*ERROR_CODE_TABLE:BEGIN\s*-->(.*?)<!--\s*ERROR_CODE_TABLE:END\s*-->",
    re.S)


def _pinned_error_codes():
    m = _ERROR_CODE_TABLE_RE.search(SERVING_DOC.read_text())
    assert m, "SERVING.md lost its ERROR_CODE_TABLE markers"
    codes = {}
    for line in m.group(1).splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) != 3 or cells[0] in ("code", "") or "---" in cells[0]:
            continue
        codes[cells[0].strip("`")] = cells[2]
    assert codes, "pinned error-code table is empty"
    return codes


class TestServingErrorCodeDocDrift:
    """docs/SERVING.md "Failure semantics" code table ==
    robust.errors.SERVING_ERROR_CODES: every stable code a typed
    serving error payload may carry is pinned in the doc of record,
    and the doc pins nothing the registry doesn't declare."""

    def test_pinned_codes_match_registry(self):
        from analytics_zoo_tpu.robust.errors import SERVING_ERROR_CODES
        pinned = _pinned_error_codes()
        missing = sorted(set(SERVING_ERROR_CODES) - set(pinned))
        stale = sorted(set(pinned) - set(SERVING_ERROR_CODES))
        assert not missing, \
            f"registry codes missing from SERVING.md: {missing}"
        assert not stale, \
            f"SERVING.md pins codes not in SERVING_ERROR_CODES: {stale}"

    def test_every_registry_code_is_a_declared_class_attr(self):
        """The registry is live, not aspirational: each code is the
        ``code`` of a typed exception (or the base class default)."""
        from analytics_zoo_tpu.robust import errors as E
        declared = {getattr(cls, "code")
                    for cls in vars(E).values()
                    if isinstance(cls, type) and hasattr(cls, "code")}
        # decode_error / model_error are emitted via
        # ServingError(code=...) at their stages, not dedicated classes
        assert (set(E.SERVING_ERROR_CODES) - declared
                == {"decode_error", "model_error"})


LOADGEN_DOC = REPO / "docs" / "LOADGEN.md"

_SLO_TABLE_RE = re.compile(
    r"<!--\s*SLO_TABLE:BEGIN([^>]*)-->(.*?)<!--\s*SLO_TABLE:END\s*-->",
    re.S)


def _newest_slo_artifact():
    arts = sorted(REPO.glob("SLO_*.json"))
    if not arts:
        pytest.skip("no SLO_*.json artifact in repo root")
    return arts[-1]


def _pinned_slo_tables():
    """SLO_TABLE blocks in docs/LOADGEN.md — same marker/attr grammar
    as BENCH_TABLE (``requires=`` gates a table on artifacts that have
    the key; ``tolerance=`` sets the relative tolerance, 0 pins an
    exact invariant like warm_compile_count)."""
    tables = []
    for m in _SLO_TABLE_RE.finditer(LOADGEN_DOC.read_text()):
        attrs = dict(re.findall(r"(\w+)=(\S+)", m.group(1)))
        claims = []
        for line in m.group(2).splitlines():
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if (len(cells) != 2 or cells[0] in ("key", "")
                    or "---" in cells[0]):
                continue
            claims.append((cells[0], float(cells[1])))
        assert claims, "a pinned SLO table is empty"
        tables.append({"requires": attrs.get("requires"),
                       "tolerance": float(attrs.get("tolerance", 0.02)),
                       "claims": claims})
    assert tables, "LOADGEN.md lost its SLO_TABLE markers"
    return tables


class TestLoadgenDocDrift:
    """docs/LOADGEN.md's pinned SLO rows == the newest SLO_*.json."""

    def test_pinned_slo_claims_match_newest_artifact(self):
        art = _newest_slo_artifact()
        doc = json.loads(art.read_text())
        bad = []
        for table in _pinned_slo_tables():
            req = table["requires"]
            if req and _resolve(doc, req, required=False) is None:
                continue        # artifact predates this load leg
            for key, claimed in table["claims"]:
                actual = _resolve(doc, key)
                assert isinstance(actual, (int, float)), \
                    f"{key} resolves to non-numeric {actual!r}"
                if actual != pytest.approx(claimed,
                                           rel=table["tolerance"]):
                    bad.append(f"{key}: doc={claimed} artifact={actual}")
        assert not bad, (f"LOADGEN.md drifted from {art.name}:\n  "
                         + "\n  ".join(bad))

    def test_slo_tables_pin_the_hard_invariants(self):
        """Grammar + coverage, artifact or not: the doc of record must
        pin the three invariants the chaos soak proves — zero live
        compiles after a warm restart, shed confined to the over-SLO
        model, and the open-loop property."""
        tables = _pinned_slo_tables()
        keys = {k for t in tables for k, _ in t["claims"]}
        for must in ("parsed.kill.warm_compile_count",
                     "parsed.mix_shift.only_over_slo_shed",
                     "parsed.open_loop.offered_rate_independent"):
            assert must in keys, f"LOADGEN.md no longer pins {must}"
        # exact invariants live in a zero-tolerance table
        strict = [t for t in tables if t["tolerance"] == 0.0]
        assert strict, "LOADGEN.md lost its zero-tolerance SLO table"
        assert any(t["requires"] for t in tables)


def _bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchNeedsAChip:
    """A measurement path that finds no TPU fails: no JSON line, no
    per-chip metric; and a section that raised fails the run."""

    def test_no_tpu_exits_nonzero_and_prints_no_metric(self, capsys):
        import jax

        b = _bench()
        before = jax.config.jax_compilation_cache_dir
        try:
            with pytest.raises(SystemExit) as exc:
                b.main()
        finally:    # main() placed the compile cache; nothing compiled
            jax.config.update("jax_compilation_cache_dir", before)
        assert exc.value.code == 1
        out, err = capsys.readouterr()
        assert out == "" and "measures a TPU" in err

    def test_error_keys_finds_every_recorded_failure(self):
        b = _bench()
        extra = {"int8_error": "ValueError: x", "matmul_4096": {"ms": 1.0},
                 "dlrm": {"child_error": "child rc=1: boom"},
                 "restart": {"cold_error": "rc=2", "error": ""},
                 "parity_max_err": 1e-6}
        assert sorted(b._error_keys(extra)) == [
            "dlrm.child_error", "int8_error", "restart.cold_error"]


class TestBenchNonFiniteGuards:
    """The helpers that keep Infinity/NaN out of future artifacts."""

    def test_safe_ratio_refuses_degenerate_operands(self):
        b = _bench()
        assert b._safe_ratio(2.0, 1.0) == 2.0
        assert b._safe_ratio(1.13, 1.0, nd=3) == 1.13
        for num, den in [(1.0, 0.0), (1.0, -1.0), (0.0, 1.0),
                         (None, 1.0), (1.0, None),
                         (float("inf"), 1.0), (1.0, float("nan")),
                         ("fast", 1.0)]:
            assert b._safe_ratio(num, den) is None, (num, den)

    def test_sanitize_json_strips_non_finite(self):
        b = _bench()
        report = {"a": float("inf"),
                  "b": {"c": float("nan"), "d": 1.5},
                  "e": [1.0, float("-inf"), "x"]}
        clean = b._sanitize_json(report)
        assert clean == {"a": None, "b": {"c": None, "d": 1.5},
                         "e": [1.0, None, "x"]}
        json.dumps(clean, allow_nan=False)   # strict JSON round-trips

    def test_measure_scan_returns_none_below_resolution(self):
        import numpy as np
        b = _bench()
        # an instant program has no measurable slope: the old code
        # clamped to ~0 and downstream ratios minted Infinity
        r = b._measure_scan(lambda c, n: c, np.zeros(4), K=16,
                            rounds=2, probe=False)
        assert r is None

    def test_roofline_rows_guard_degenerate_inputs(self):
        b = _bench()
        row = b._roofline(int(1e8), int(3e8), 1e-3)
        assert row["bytes_ideal"] == int(1e8)
        assert row["bytes_moved"] == int(3e8)
        assert row["traffic_ratio"] == 3.0
        assert row["gbps_achieved"] == 300.0
        # no measured time: the GB/s row is ABSENT, not 0/Infinity
        assert "gbps_achieved" not in b._roofline(100, 300, None)
        assert b._roofline(100, 0, 1.0)["traffic_ratio"] is None


class TestBenchKernelLegProfiler:
    """The FlightRecorder wired through the kernel bench legs: a
    speedup-floor breach lands BOTH a flight record and a device
    profiler trace under BENCH_PROFILE_DIR/<leg>, so the trace that
    explains a regression ships with the artifact."""

    def test_breach_trace_file_lands(self, tmp_path, monkeypatch):
        import time

        import jax.numpy as jnp

        b = _bench()
        monkeypatch.setenv("BENCH_PROFILE_DIR", str(tmp_path))
        jnp.zeros(1).block_until_ready()    # backend up pre-profiler
        out = {"fused_vs_unfused_speedup": 0.5}
        b._breach_check(out, "embedding_bag",
                        "fused_vs_unfused_speedup", 1.3)
        assert "breach_recorder_error" not in out, out
        rec = out.get("breach_flight_record")
        assert rec and Path(rec).exists()
        leg_dir = tmp_path / "embedding_bag"
        deadline = time.time() + 20.0       # trace thread is async
        trace = []
        while time.time() < deadline and not trace:
            trace = list(leg_dir.glob("plugins/profile/*/*.xplane.pb"))
            time.sleep(0.1)
        assert trace, "profiler trace never landed under profile_dir"

    def test_no_breach_no_record(self, tmp_path, monkeypatch):
        b = _bench()
        monkeypatch.setenv("BENCH_PROFILE_DIR", str(tmp_path))
        for spd in (2.0, 1.3, None):        # unresolved is NOT a breach
            out = {"fused_vs_unfused_speedup": spd}
            b._breach_check(out, "embedding_bag",
                            "fused_vs_unfused_speedup", 1.3)
            assert "breach_flight_record" not in out, spd
        assert not list(tmp_path.iterdir())
