"""The data tier's three layer metrics: each reader on a hand-made run, all
three from the traced CPU rehearsal of a tiny cell, and the chip trace that
was recorded with the program's own stages on it."""

import os
import time

import pytest

import tiny

tiny.harness_of(tiny.REPO)
from harness import trace_reduce as tr  # noqa: E402

STAGES = ("wait", "gather", "upload")
SPANS = os.path.join(tiny.REPO, "benchmark", "fixtures",
                     "resnet50-fit-host.spans.json.gz")


def _series(stage):
    return f'data_stage_seconds{{stage="{stage}"}}'


def _run(hists):
    return {"window": {"registry": {"histograms": hists}}, "trace": None}


@pytest.mark.parametrize("stage", STAGES)
def test_reader_gives_the_mean_in_ms_or_nothing(stage):
    """3 samples that took 0.6 s together: 200 ms.  Another stage's series,
    the step's, an empty one or none at all: nothing, never a 0."""
    _, spec = tiny.harness_of(tiny.REPO)
    cell = spec.load_cell(tiny.REPO, "resnet50-fit-host")
    assert f"data_{stage}_ms.fit" in {m["name"] for m in cell.per_layer}
    read = cell.layer_metric_reader(f"data_{stage}_ms.fit")
    other = next(s for s in STAGES if s != stage)
    hists = {_series(stage): {"count": 3, "total": 0.6},
             _series(other): {"count": 1, "total": 5.0},
             'train_step_seconds{kind="1"}': {"count": 3, "total": 9.0}}
    assert read(_run(hists)) == pytest.approx(200.0)
    del hists[_series(stage)]
    assert read(_run(hists)) is None
    assert read(_run({_series(stage): {"count": 0, "total": 0.0}})) is None
    assert read(_run({})) is None


def test_traced_rehearsal_prints_the_three_with_a_sample_a_dispatch(
        harness, tree, monkeypatch):
    """``--trace 1`` on the CPU, as ``run.py`` drives it: the three metrics
    are in the result, and the window holds one ``wait`` a dispatch; the
    prefetch thread runs at most its queue and the batch in hand ahead."""
    bench_run, spec = harness
    cell = spec.load_cell(tree, "resnet50-tiny-fit")
    tiny.only_chips(monkeypatch, cell.chips)
    out = bench_run.measure(cell, 26, 0.3, True, time.perf_counter())
    assert out["correct"], out["checks"]
    hists = out["run"]["window"]["registry"]["histograms"]
    steps = out["run"]["window"]["steps"]
    assert sum(h["count"] for name, h in hists.items()
               if name.startswith("train_step_seconds")) == steps
    ahead = int(cell.traffic.get("context", {}).get("data_prefetch", 2)) + 1
    for stage in STAGES:
        metric = out["metrics"][f"data_{stage}_ms.fit"]
        assert metric["unit"] == "ms" and metric["value"] > 0
        h = hists[_series(stage)]
        assert metric["value"] == pytest.approx(
            1e3 * h["total"] / h["count"])
        assert abs(h["count"] - steps) <= (0 if stage == "wait" else ahead)


def _host_events(trace, name):
    """Sorted (start, end) of the host planes' events of that name."""
    return sorted((int(s), int(s + d))
                  for p in trace.host_planes() for l in p.lines
                  for s, d, n in zip(l.starts, l.durs, l.names) if n == name)


def test_recorded_chip_trace_carries_the_programs_stages():
    """``resnet50-fit-host.spans.json.gz``: cut with ``trace_reduce.cut``
    from the traced window of PR 26's second chip run of
    ``resnet50-fit-host`` on a TPU v5e (seed 26003; a scratch script drove
    ``harness/drivers/fit.py``'s ``Session`` and kept the trace that
    ``run.py`` deletes), from the profiler's start to the start of the
    device's sixth traced step: five whole steps.  Device events are all
    kept, of the host's events those of a millisecond or longer; times are
    shifted to start near 0.

    Gather, upload and dispatch are on it as ``zoo:`` events, on the one
    clock: every dispatch precedes the device's run of its step.  What the
    issue hoped for, a stage of the program open during the longest idle
    gap, is not what the chip showed.  Under the profiler the host keeps
    its pace (a gather every 160 ms) and the runtime's ``XlaLinearize`` of
    each uploaded batch, which starts inside the ``upload`` call that
    enqueues it, takes seconds, not a tenth of one: the device runs its
    steps 7 s after their dispatch, and its idle gaps are the tails of
    uploads made 5 to 7 s earlier.  So the gap is put down to a stage
    through the runtime events that cover it."""
    trace = tr.load_json(SPANS)
    plane = trace.device_planes()[0]
    ops = tr.ops_line(plane)
    red = tr.reduce(trace)
    assert red["steps"] == 5
    stages = {name: _host_events(trace, name) for name in (
        "zoo:data_stage_seconds/gather", "zoo:data_stage_seconds/upload",
        "zoo:train_step_seconds/1")}
    assert all(len(ev) >= 5 for ev in stages.values())
    names = {n for p in trace.host_planes() for l in p.lines
             for n in l.names if n.startswith("zoo:")}
    assert names == set(stages)        # the two waits stay off the trace
    # one clock: inside the trace's span, a dispatch before its step
    first = min(int(l.starts.min()) for p in trace.planes for l in p.lines)
    last = int(ops.ends.max())
    assert all(first <= a and b <= last
               for ev in stages.values() for a, b in ev)
    steps = tr.step_starts(plane, red["step_program"])
    for (a, _), began in zip(stages["zoo:train_step_seconds/1"], steps):
        assert a < began
    # the producer thread does one thing at a time
    cycle = sorted(stages["zoo:data_stage_seconds/gather"]
                   + stages["zoo:data_stage_seconds/upload"])
    assert all(b <= c for (_, b), (c, _) in zip(cycle, cycle[1:]))
    # the longest idle gap and the stage it is put down to
    named, seconds = red["idle_gaps"][0]
    a, b = max(tr.idle_gaps(ops), key=lambda g: g[1] - g[0])
    assert seconds == pytest.approx((b - a) / 1e9) and seconds > 0.5
    open_in_gap = [n for n, ev in stages.items()
                   for s, e in ev if s < b and e > a]
    assert "/zoo:" not in named and not open_in_gap
    assert named.split("/")[0] in ("futex-default-SDomainT",
                                   "pjrt-tpu-tasks")
    uploads = stages["zoo:data_stage_seconds/upload"]
    tails = [s for s, e in _host_events(trace, "XlaLinearize")
             if s <= a and e >= b
             and any(u0 <= s <= u1 for u0, u1 in uploads)]
    assert tails and a - max(tails) > 5e9
