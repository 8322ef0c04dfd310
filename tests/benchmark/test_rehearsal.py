"""CPU rehearsal of the harness at a tiny size, on cells that are ADDED to
a copy of the benchmark as a later PR would add them: new files and new
entries, no edit to a file that is there.  The tiny cells compute in
float32, so each plain reference meets the program to rounding here, before
they meet on the chip: the mean loss of the three compared steps, the first
gradient and the parameters' change after the three."""

import filecmp
import json
import os
import time

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
    cell, out = _measure(harness, tree, monkeypatch, "resnet50-tiny-fit", 5,
                         trace=True)
    assert out["correct"], out["checks"]
    # the first step is held tight: rounding has had no steps to grow in
    assert out["checks"]["grad_norm_median"][0] < 0.01
    names = {m["name"] for m in cell.per_layer}
    assert set(out["metrics"]) <= names
    assert "dispatch_ms.fit" in out["metrics"]
    assert "mfu_pct.fit" not in out["metrics"]
    assert out["run"]["window"]["traced"]["steps"] >= 8
    assert not os.path.exists(os.path.join(tree, ".bench_trace",
                                           "resnet50-tiny-fit"))


def test_four_device_cell_runs_and_meets_its_reference(harness, tree,
                                                       monkeypatch):
    cell, out = _measure(harness, tree, monkeypatch, "bert-tiny-dp4",
                         123456789)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["run"]["batch"] == 32
