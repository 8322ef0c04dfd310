"""CPU rehearsal of the harness at a tiny size, on cells that are ADDED to
a copy of the benchmark as a later PR would add them: new files and new
entries, no edit to a file that is there.  The tiny cells compute in
float32, so each plain reference meets the program to rounding here, before
they meet on the chip: the mean loss of the three compared steps, the first
gradient and the parameters' change after the three."""

import filecmp
import json
import os
import re
import shutil
import subprocess
import sys
import time
import types

import tiny


def _measure(harness, tree, monkeypatch, cell, seed, trace=False):
    bench_run, spec = harness
    c = spec.load_cell(tree, cell)
    tiny.only_chips(monkeypatch, c.chips)
    return c, bench_run.measure(c, seed, 0.3, trace, time.perf_counter())


def test_adding_cells_edits_no_file_that_is_there(tree):
    """Every file of the repo's benchmark is in the tree unchanged; the tiny
    cells are only new files and new entries."""
    src = os.path.join(tiny.REPO, "benchmark")
    added = []
    for d, _, files in os.walk(os.path.join(tree, "benchmark")):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f),
                                  os.path.join(tree, "benchmark"))
            if os.path.exists(os.path.join(src, rel)):
                assert filecmp.cmp(os.path.join(src, rel),
                                   os.path.join(d, f), shallow=False), rel
            else:
                added.append(rel)
    assert sorted(added) == [
        "configs/bert-tiny.json", "configs/resnet50-tiny.json",
        "limits/bert-tiny-dp4.json", "limits/bert-tiny-fit.json",
        "limits/resnet50-tiny-fit.json", "traffic/fit-host-tiny.json"]
    assert not any(a.startswith("harness") for a in added)
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        before = json.load(f)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        after = json.load(f)
    for key in ("configs", "workloads"):
        assert after[key][:len(before[key])] == before[key]
    for key in ("command", "paths", "run_seconds", "end_to_end",
                "per_layer"):
        assert after[key] == before[key]


SCOPE_READER = '''"""A later configuration's scope metric, whole: the marker's milliseconds."""
from harness.trace_reduce import scope_ms


def read(run):
    return scope_ms(run, "zoo:tiny/block")
'''

# the tests that hold BENCHMARK.json and every cell's files to the contract
CONTRACT_TESTS = [
    "test_work_and_peaks.py",
    "test_ouro_cell.py::test_benchmark_json_differs_by_appended_entries",
    "test_ouro_cell.py::test_scope_metrics_list_the_cell_whose_step_has_the_scopes",
    "test_ouro_cell.py::test_each_cell_finds_its_files_by_name",
    "test_ouro_cell.py::test_configuration_keeps_the_published_keys",
    "test_ouro_cell.py::test_flash_reader_reads_nothing_where_nothing_is_to_be_read",
]


def _files(top):
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            yield os.path.relpath(os.path.join(d, f), top)


def test_an_addition_whole_leaves_the_contract_tests_green(tmp_path):
    """What a later PR may bring, all at once: two configurations, two
    one-chip cells, a four-chip cell and a per-layer metric that lists its
    cells, appended to a copy of the tree with these tests in it.  No file
    that was there differs, every accepted entry is still there in its
    order, and the contract tests of ``test_work_and_peaks`` and
    ``test_ouro_cell``, run as they are against that tree, all pass."""
    root = tiny.make_tree(str(tmp_path / "checkout"))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "tiny_scope_ms.fit.py"), "w") as f:
        f.write(SCOPE_READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(
        name="tiny_scope_ms.fit", unit="ms", better="lower",
        source="device_trace", layer="model step",
        moves="fit_samples_per_s",
        workloads=["bert-tiny-fit", "bert-tiny-dp4"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    shutil.copytree(os.path.join(tiny.REPO, "tests", "benchmark"),
                    os.path.join(root, "tests", "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))

    for top in ("benchmark", os.path.join("tests", "benchmark")):
        for rel in _files(os.path.join(tiny.REPO, top)):
            assert filecmp.cmp(os.path.join(tiny.REPO, top, rel),
                               os.path.join(root, top, rel),
                               shallow=False), rel
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        before = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(before[key])] == before[key]
    for key in ("command", "paths", "run_seconds"):
        assert bench[key] == before[key]
    added = [w for w in bench["workloads"] if w not in before["workloads"]]
    assert sorted(w["chips"] for w in added) == [1, 1, 4]
    assert len(bench["configs"]) == len(before["configs"]) + 2
    assert len(bench["per_layer"]) == len(before["per_layer"]) + 1

    # the listed cells read the new metric, by its file; no other cell does
    _, spec = tiny.harness_of(root)
    try:
        for w in bench["workloads"]:
            names = {m["name"] for m in
                     spec.load_cell(root, w["name"]).per_layer}
            assert ("tiny_scope_ms.fit" in names) == (
                w["name"] in ("bert-tiny-fit", "bert-tiny-dp4"))
        read = spec.load_cell(root, "bert-tiny-dp4").layer_metric_reader(
            "tiny_scope_ms.fit")
        red = {"steps": 4, "scope_seconds_under": {"zoo:tiny/block": 0.5}}
        assert read({"trace": red}) == 125.0
        assert read({"trace": dict(red, scope_seconds_under={})}) is None
        assert read({"trace": None}) is None
    finally:
        tiny.harness_of(tiny.REPO)

    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [tiny.REPO] + [p for p in [env.get("PYTHONPATH")] if p]))
    there = os.path.join(root, "tests", "benchmark")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "--rootdir", root]
        + [os.path.join(there, t) for t in CONTRACT_TESTS],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    tail = done.stdout[-3000:] + done.stderr[-1000:]
    assert done.returncode == 0, tail
    # the added cells are parameters of the same tests: each cell is a case
    # of the two that find a cell's files by name
    passed = re.search(r"(\d+) passed", tail)
    assert passed and "failed" not in tail and "error" not in tail, tail
    assert int(passed.group(1)) >= 2 * len(bench["workloads"]) + 10, tail


def test_bert_cell_runs_and_meets_its_reference(harness, tree, monkeypatch):
    cell, out = _measure(harness, tree, monkeypatch, "bert-tiny-fit",
                         2 ** 31 + 77)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"fit_samples_per_s", "setup_s"}
    assert out["metrics"]["fit_samples_per_s"]["value"] > 0
    assert out["device"]["count"] == 1
    assert out["notes"]["data path"] == "host_prefetch"
    exact = {"rows_asked_twice", "compiles_in_window", "data_path_differs"}
    assert set(out["checks"]) == {"loss", "grad_norm", "delta_norm"} | exact
    assert all(out["checks"][k] == [0.0, 0.0] for k in exact)
    # float32 against float32: far inside the tiny limits
    assert max(v for v, _ in out["checks"].values()) < 1e-4
    win = out["run"]["window"]
    assert win["samples"] == win["steps"] * 8 and win["seconds"] >= 0.3
    assert win["steps_before"] == 2 and win["steps"] > 0
    assert win["rows_asked"] >= (win["steps"] + 2) * 8


def test_resnet_cell_traced_runs_and_meets_its_reference(harness, tree,
                                                         monkeypatch):
    """``--trace 1`` on the CPU: the readers that need a device trace find
    nothing and leave their metrics out, never a 0."""
    import jax

    handed, start = [], jax.profiler.start_trace

    def start_trace(log_dir, *a, profiler_options=None, **kw):
        handed.append(profiler_options)
        return start(log_dir, *a, profiler_options=profiler_options, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    cell, out = _measure(harness, tree, monkeypatch, "resnet50-tiny-fit", 5,
                         trace=True)
    assert out["correct"], out["checks"]
    # the profiler is started once and records no host events: the device
    # planes are all that the layer metrics read
    assert [(o.host_tracer_level, o.python_tracer_level)
            for o in handed] == [(0, 0)]
    assert 0 < out["host_rss_peak_gib"] < 1024
    # the first step is held tight: rounding has had no steps to grow in
    assert out["checks"]["grad_norm_median"][0] < 0.01
    names = {m["name"] for m in cell.per_layer}
    assert set(out["metrics"]) <= names
    assert "dispatch_ms.fit" in out["metrics"]
    assert "mfu_pct.fit" not in out["metrics"]
    assert out["run"]["window"]["traced"]["steps"] >= 8
    assert not os.path.exists(os.path.join(tree, ".bench_trace",
                                           "resnet50-tiny-fit"))


def test_window_clock_starts_the_profiler_with_both_host_tracers_off(
        monkeypatch, tmp_path):
    """``WindowClock`` alone, its steps finished at once: the profiler
    starts ``TRACE_AFTER_STEPS`` into the window, on options that switch
    the host's and Python's tracers off, whatever the cell; it stops on the
    first dispatch ``TRACE_SECONDS`` later, and the window closes after."""
    import jax

    tiny.harness_of(tiny.REPO)
    from harness.drivers import fit

    calls = []
    monkeypatch.setattr(fit, "TRACE_SECONDS", 0.0)
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, profiler_options=None: calls.append(
            ("start", log_dir, profiler_options)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    clock = fit.WindowClock(lambda: None, dict, seconds=0.0, open_after=2,
                            trace_dir=str(tmp_path))
    closed_at = None
    for it in range(100, 140):
        try:
            clock(it)
        except fit.WindowClosed:
            closed_at = it
            break
    assert clock.open["iteration"] == 101
    assert [c[0] for c in calls] == ["start", "stop"]
    _, log_dir, opts = calls[0]
    assert log_dir == str(tmp_path)
    assert opts.host_tracer_level == 0 and opts.python_tracer_level == 0
    assert clock.traced["steps"] == 1
    assert closed_at == 101 + fit.TRACE_AFTER_STEPS + 1
    assert clock.close["iteration"] == closed_at


def test_measure_reports_the_process_peak_resident_memory():
    """``run.measure`` on a driver that does nothing: the peak resident
    memory of the process is in what it returns, in GiB, traced or not."""
    bench_run, _ = tiny.harness_of(tiny.REPO)
    driver = types.SimpleNamespace(run=lambda cell, seed, seconds, trace, t0:
                                   {"run": {"seen": (seed, trace)}})
    cell = types.SimpleNamespace(
        driver=lambda: driver, per_layer=[], end_to_end=[],
        layer_metric_reader=None, end_to_end_reader=None)
    for trace in (False, True):
        out = bench_run.measure(cell, 11, 0.1, trace, time.perf_counter())
        assert out["run"]["seen"] == (11, trace) and out["metrics"] == {}
        # a Python process with JAX imported holds tens of MiB at the least
        assert 0.01 < out["host_rss_peak_gib"] < 1024


def test_four_device_cell_runs_and_meets_its_reference(harness, tree,
                                                       monkeypatch):
    cell, out = _measure(harness, tree, monkeypatch, "bert-tiny-dp4",
                         123456789)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["run"]["batch"] == 32
