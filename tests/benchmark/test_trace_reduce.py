"""``trace_reduce`` against hand-worked values: a synthetic trace written
through the reader's own JSON form (overlapping operations, an idle gap at
either end), and a few steps cut from a trace recorded on the chip."""

import os

import numpy as np
import pytest

import tiny

tiny.harness_of(tiny.REPO)
from harness import trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(tiny.REPO, "benchmark", "fixtures")


def _line(name, events):
    return tr.Line(name, np.asarray([e[0] for e in events], np.int64),
                   np.asarray([e[1] for e in events], np.int64),
                   [e[2] for e in events])


@pytest.fixture()
def synthetic(tmp_path):
    """Device 0 runs A [100,200) and B [150,300), which overlap, then C
    [400,450) and D [450,500), which touch.  The step program runs at 100,
    400 and 700; another, shorter program once.  A host thread uploads
    over [290,410)."""
    trace = tr.Trace([
        tr.Plane("/device:TPU:0", [
            _line("XLA Ops", [(100, 100, "fusion.1"), (150, 150, "fusion.2"),
                              (400, 50, "fusion.1"), (450, 50, "copy.3")]),
            _line("XLA Modules", [(100, 200, "jit_step(1)"),
                                  (50, 10, "jit_other(2)"),
                                  (400, 100, "jit_step(1)"),
                                  (700, 100, "jit_step(1)")]),
        ]),
        tr.Plane("/host:CPU", [_line("prefetch", [(290, 120, "device_put"),
                                                  (0, 40, "gather")])]),
        tr.Plane("/host:metadata", []),
    ])
    path = str(tmp_path / "synthetic.json.gz")
    tr.dump_json(trace, path)
    return tr.load_json(path)


def test_round_trip_keeps_every_event(synthetic):
    dev = synthetic.device_planes()
    assert [p.name for p in dev] == ["/device:TPU:0"]
    ops = dev[0].line("XLA Ops")
    assert ops.starts.tolist() == [100, 150, 400, 450]
    assert ops.names == ["fusion.1", "fusion.2", "fusion.1", "copy.3"]


def test_busy_is_the_union_of_overlapping_operations(synthetic):
    ops = synthetic.device_planes()[0].line("XLA Ops")
    # [100,300) and [400,500): 200 + 100, not the 350 the durations sum to
    assert tr.busy_ns(ops) == 300
    assert int(ops.durs.sum()) == 350


def test_idle_gaps_inside_and_at_either_end(synthetic):
    ops = synthetic.device_planes()[0].line("XLA Ops")
    assert tr.idle_gaps(ops) == [(300, 400)]
    assert tr.idle_gaps(ops, 0, 600) == [(0, 100), (300, 400), (500, 600)]


def test_step_program_and_gaps(synthetic):
    plane = synthetic.device_planes()[0]
    assert tr.step_program(plane) == "jit_step(1)"
    assert tr.step_starts(plane, "jit_step(1)").tolist() == [100, 400, 700]


def test_step_span_and_the_readers_that_count_steps_from_the_trace(synthetic):
    """Three executions of the step program start at 100, 400 and 700: two
    steps were done in the 600 ns between the first start and the last.
    The model step's share of the peak and the kernels' roofline take their
    steps and times from the trace alone."""
    red = tr.reduce(synthetic)
    assert red["steps"] == 3 and red["step_span_s"] == pytest.approx(600e-9)
    _, spec = tiny.harness_of(tiny.REPO)
    cell = spec.load_cell(tiny.REPO, "resnet50-fit-host")
    run = {"trace": red, "chips": 1,
           "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
           "work": {"flops": 60e3, "bytes": 1.0}, "window": {}}
    # 2 steps x 60,000 FLOPs in 600 ns at 1e12 FLOP/s: 20 %
    assert cell.layer_metric_reader("mfu_pct.fit")(run) == pytest.approx(20.0)
    # the FLOPs bind (60 ns a step against 1 ns for the bytes); the device
    # is busy 300 ns over 3 steps
    assert cell.layer_metric_reader("kernels_roofline_pct.fit")(run) == \
        pytest.approx(60.0)
    run["trace"] = None
    assert cell.layer_metric_reader("mfu_pct.fit")(run) is None
    assert cell.layer_metric_reader("kernels_roofline_pct.fit")(run) is None


def test_top_operations_sum_by_name(synthetic):
    ops = synthetic.device_planes()[0].line("XLA Ops")
    assert tr.top_ops(ops, 2) == [["fusion.1", 150e-9], ["fusion.2", 150e-9]]
    assert tr.top_ops(ops)[-1] == ["copy.3", 50e-9]


def test_reduce_gives_the_idle_share_and_names_the_gap(synthetic):
    red = tr.reduce(synthetic, window_s=600e-9)
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["window_s"] == pytest.approx(600e-9)
    assert red["span_s"] == pytest.approx(400e-9)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.5)
    assert red["steps"] == 3 and red["step_gaps_ms"] == [300e-6, 300e-6]
    assert red["idle_gaps"] == [["prefetch/device_put", 100e-9]]
    assert red["device_ops"][0] == ["fusion.1", 150e-9]


def test_a_trace_without_device_operations_reduces_to_nothing(synthetic):
    host_only = tr.Trace(synthetic.host_planes())
    assert tr.reduce(host_only) is None


def test_cut_keeps_events_that_start_in_the_range(synthetic):
    part = tr.cut(synthetic, 400, 700)
    ops = part.device_planes()[0].line("XLA Ops")
    assert ops.starts.tolist() == [400, 450]
    assert part.device_planes()[0].line("XLA Modules").names == [
        "jit_step(1)"]


def _slow_union(starts, ends):
    """An independent count: sweep over the sorted edges."""
    edges = sorted([(int(s), 1) for s in starts] + [(int(e), -1)
                                                    for e in ends])
    busy = depth = 0
    last = None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(FIXTURES) if f.endswith(".json.gz"))
    if os.path.isdir(FIXTURES) else [])
def test_recorded_chip_trace(name):
    """A few steps cut from a traced run on the chip: the reduction agrees
    with a slow independent count, and finds the step program."""
    trace = tr.load_json(os.path.join(FIXTURES, name))
    red = tr.reduce(trace)
    assert red is not None and red["steps"] >= 3
    ops = tr.ops_line(trace.device_planes()[0])
    assert tr.busy_ns(ops) == _slow_union(ops.starts, ops.ends)
    assert red["busy_s"] <= red["span_s"]
    gaps = tr.idle_gaps(ops)
    assert sum(b - a for a, b in gaps) + tr.busy_ns(ops) == int(
        ops.ends.max() - ops.starts.min())
    assert len(red["device_ops"]) <= 10 and red["device_ops"][0][1] > 0
    assert len(red["step_gaps_ms"]) == red["steps"] - 1
