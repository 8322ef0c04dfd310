"""The program-build layer's four readers, ``setup_<phase>_s``: each on a
hand-made run, and all four from a traced CPU rehearsal of a tiny cell in a
copy of the benchmark whose ``BENCHMARK.json`` has them appended, listing
the three cells that are to report them."""

import json
import os
import time

import pytest

import tiny

PHASES = ("trace", "lower", "compile", "load")
CELLS = ["resnet50-fit-host", "bert-base-fit-host", "ouro-2.6b-fit-packed4k"]


def _gauge(phase):
    return f'jit_build_seconds{{phase="{phase}"}}'


def _entry(phase, cells):
    return dict(name=f"setup_{phase}_s", unit="s", better="lower",
                source="program_counter", layer="program build",
                moves="setup_s", workloads=cells)


def _run(gauges):
    return {"window": {"registry": {"gauges": gauges}}, "trace": None}


@pytest.mark.parametrize("phase", PHASES)
def test_reader_gives_the_phase_seconds_or_nothing(phase):
    """Its own phase's gauge, 0 where nothing was built; nothing where
    the program books no builds, never a 0."""
    _, spec = tiny.harness_of(tiny.REPO)
    read = spec.load_cell(tiny.REPO, "resnet50-fit-host").layer_metric_reader(
        f"setup_{phase}_s")
    gauges = {_gauge(p): i + 0.5 for i, p in enumerate(PHASES)}
    assert read(_run(gauges)) == PHASES.index(phase) + 0.5
    assert read(_run({_gauge(phase): 0.0})) == 0.0
    del gauges[_gauge(phase)]
    assert read(_run(gauges)) is None
    assert read(_run({})) is None


@pytest.fixture(scope="module")
def listed(tmp_path_factory):
    """A tiny tree with the four entries appended to ``per_layer``, each
    listing the three cells and the tiny BERT cell."""
    root = tiny.make_tree(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"] += [_entry(p, CELLS + ["bert-tiny-fit"])
                           for p in PHASES]
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


def test_the_listed_cells_alone_read_the_four(listed):
    _, spec = tiny.harness_of(listed)
    try:
        with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
            cells = [w["name"] for w in json.load(f)["workloads"]]
        for cell in cells:
            names = [m["name"] for m in spec.load_cell(listed, cell).per_layer]
            got = [n for n in names if n.startswith("setup_")]
            assert got == ([f"setup_{p}_s" for p in PHASES]
                           if cell in CELLS else []), cell
    finally:
        tiny.harness_of(tiny.REPO)


def test_traced_rehearsal_reports_the_four_within_set_up(listed, monkeypatch):
    """``--trace 1`` on the CPU, as ``run.py`` drives it: the four are in
    the result line, each at least 0, and what this run's set-up added to
    them is more than nothing and no more than ``opened_after_s`` (the
    test's process has built programs before)."""
    from analytics_zoo_tpu.observe import METRICS

    bench_run, spec = tiny.harness_of(listed)
    try:
        cell = spec.load_cell(listed, "bert-tiny-fit")
        tiny.only_chips(monkeypatch, cell.chips)
        before = METRICS.delta(None)["gauges"]
        out = bench_run.measure(cell, 2 ** 31 + 40, 0.3, True,
                                time.perf_counter())
    finally:
        tiny.harness_of(tiny.REPO)
    assert out["correct"], out["checks"]
    added = 0.0
    for phase in PHASES:
        metric = out["metrics"][f"setup_{phase}_s"]
        assert metric["unit"] == "s" and metric["value"] >= 0
        added += metric["value"] - before.get(_gauge(phase), 0.0)
    assert 0 < added <= out["run"]["window"]["opened_after_s"]
    assert out["run"]["window"]["registry"]["gauges"][_gauge("trace")] > \
        before.get(_gauge("trace"), 0.0)
