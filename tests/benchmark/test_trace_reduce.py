"""``trace_reduce`` against hand-worked values: a synthetic trace written
through the reader's own JSON form (overlapping operations, an idle gap at
either end), and a few steps cut from a trace recorded on the chip."""

import hashlib
import json
import os

import numpy as np
import pytest

import tiny

tiny.harness_of(tiny.REPO)
from harness import trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(tiny.REPO, "benchmark", "fixtures")


def _line(name, events):
    """Events as (start, duration, name) or (start, duration, name, JAX name
    stack)."""
    op_names = [e[3] if len(e) > 3 else "" for e in events]
    return tr.Line(name, np.asarray([e[0] for e in events], np.int64),
                   np.asarray([e[1] for e in events], np.int64),
                   [e[2] for e in events],
                   op_names if any(op_names) else [])


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


def test_a_trace_without_host_planes_reduces_and_keeps_every_operation():
    """What the traced run records since the host tracer is off: device
    planes alone.  Twelve operations of 10, 20 ... 120 ns, 5 ns apart but
    for 50 ns after the fourth and 30 ns after the ninth: the gaps keep
    their lengths and are ``"unnamed"``; ``op_seconds`` holds all twelve
    names, ``device_ops`` the ten longest of them."""
    events, t = [], 1000
    for i in range(1, 13):
        events.append((t, 10 * i, f"fusion.{i}"))
        t += 10 * i + {4: 50, 9: 30}.get(i, 5)
    trace = tr.Trace([tr.Plane("/device:TPU:0", [
        _line("XLA Ops", events + [(t, 10, "fusion.12")]),
        _line("XLA Modules", [(1000, 400, "jit_step(1)"),
                              (1500, 400, "jit_step(1)")])])])
    assert trace.host_planes() == []
    red = tr.reduce(trace, window_s=2000e-9)
    assert red["busy_s"] == pytest.approx(790e-9)
    gaps = red["idle_gaps"]
    assert len(gaps) == 10 and {name for name, _ in gaps} == {"unnamed"}
    assert [s for _, s in gaps[:3]] == pytest.approx([50e-9, 30e-9, 5e-9])
    assert sum(s for _, s in tr.name_gaps(trace, tr.idle_gaps(
        tr.ops_line(trace.planes[0])), n=99)) == pytest.approx(130e-9)
    assert set(red["op_seconds"]) == {f"fusion.{i}" for i in range(1, 13)}
    assert red["op_seconds"]["fusion.12"] == pytest.approx(130e-9)
    assert red["op_seconds"]["fusion.1"] == pytest.approx(10e-9)
    assert red["device_ops"] == [
        [f"fusion.{i}", pytest.approx(red["op_seconds"][f"fusion.{i}"])]
        for i in range(12, 2, -1)]
    assert red["device_ops"] == tr.top_ops(tr.ops_line(trace.planes[0]))
    assert red["steps"] == 2 and red["step_gaps_ms"] == [500e-6]


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


# ------------------------------------------------- device time by scope ---

STACK, HEADS, ATTN = "zoo:lm/stack", "zoo:lm/head_loss", "zoo:nn/attn"


def _scoped_trace():
    """One step of 1,600 ns and the start of the next.  A ``while`` of the
    stack's forward over [0,1000) holds two body events: a product of the
    stack's backward, [100,300), and a softmax under a scope nested in the
    stack's, [400,700).  After it a copy the profiler gives no name stack,
    [1000,1100); idle to 1200; a product of the heads' backward,
    [1200,1500); an addition outside every scope, [1500,1600)."""
    return tr.Trace([tr.Plane("/device:TPU:0", [
        _line("XLA Ops", [
            (0, 1000, "while.1", f"jit(step)/jvp({STACK})/while"),
            (100, 200, "fusion.a",
             f"jit(step)/transpose(jvp({STACK}))/while/body/dot_general:"),
            (400, 300, "fusion.b",
             f"jit(step)/jvp({STACK})/while/body/{ATTN}/softmax/exp:"),
            (1000, 100, "copy.9"),
            (1200, 300, "fusion.c",
             f"jit(step)/transpose(jvp({HEADS}))/mul:"),
            (1500, 100, "fusion.d", "jit(step)/add:"),
        ]),
        _line("XLA Modules", [(0, 1600, "jit_step(1)"),
                              (1600, 1, "jit_step(1)")]),
    ])])


def test_self_time_leaves_a_while_what_its_body_does_not_take():
    ops = tr.ops_line(_scoped_trace().planes[0])
    assert tr.self_ns(ops).tolist() == [500, 200, 300, 100, 300, 100]
    assert int(tr.self_ns(ops).sum()) == tr.busy_ns(ops) == 1500


def test_self_time_adds_up_to_busy_time_where_operations_overlap(synthetic):
    """A [100,200) and B [150,300) overlap without one holding the other:
    each busy moment goes to the one that started last."""
    ops = synthetic.device_planes()[0].line("XLA Ops")
    assert tr.self_ns(ops).tolist() == [50, 150, 50, 50]
    assert int(tr.self_ns(ops).sum()) == tr.busy_ns(ops)


def test_scope_seconds_by_innermost_marker_add_up_to_busy_time():
    red = tr.reduce(_scoped_trace())
    own = red["scope_seconds"]
    # the while's own 500 ns and the backward product's 200 under the
    # stack's marker, forward and backward one sum; the softmax under the
    # marker nested in it; the copy without a name stack and the addition
    # without a marker under ""
    assert own == {STACK: pytest.approx(700e-9), ATTN: pytest.approx(300e-9),
                   HEADS: pytest.approx(300e-9), "": pytest.approx(200e-9)}
    assert sum(own.values()) == pytest.approx(red["busy_s"], rel=1e-12)
    assert red["busy_s"] == pytest.approx(1500e-9)
    # under every marker of the name: the stack holds the scope nested in it
    assert red["scope_seconds_under"] == {
        STACK: pytest.approx(1000e-9), ATTN: pytest.approx(300e-9),
        HEADS: pytest.approx(300e-9)}


def test_scope_ms_is_a_step_s_milliseconds_or_nothing():
    red = tr.reduce(_scoped_trace())
    assert red["steps"] == 2
    run = {"trace": red}
    assert tr.scope_ms(run, STACK) == pytest.approx(1000e-6 / 2)
    assert tr.scope_ms(run, ATTN) == pytest.approx(300e-6 / 2)
    assert tr.scope_ms(run, HEADS) == pytest.approx(300e-6 / 2)
    assert tr.scope_ms(run, "zoo:lm/embed") is None
    assert tr.scope_ms(run, "") is None
    assert tr.scope_ms({"trace": None}, STACK) is None
    assert tr.scope_ms({"trace": dict(red, steps=0)}, STACK) is None


@pytest.mark.parametrize("op_name,markers", [
    ("jit(step)/jvp(zoo:lm/stack)/while/body/dot_general:", [STACK]),
    ("jit(step)/transpose(jvp(zoo:lm/stack))/while", [STACK]),
    ("jit(step)/zoo:lm/stack/while/body/closed_call", [STACK]),
    ("jit(step)/zoo:lm/head_loss", [HEADS]),
    ("jit(step)/jvp(zoo:lm/stack)/zoo:nn/attn/exp:", [STACK, ATTN]),
    ("jit(step)/zoo:lm2/head_loss_3/x", ["zoo:lm2/head_loss_3"]),
    ("jit(step)/jit(_where)/select_n:", []),
    ("jit(step)/zoo:lm", []),
    ("", []),
])
def test_a_marker_is_two_components_and_ends_where_they_end(op_name,
                                                            markers):
    assert tr.MARKER.findall(op_name) == markers


def test_json_round_trip_and_cut_keep_the_name_stacks(tmp_path):
    trace = _scoped_trace()
    path = str(tmp_path / "scoped.json.gz")
    tr.dump_json(trace, path)
    back = tr.load_json(path)
    ops, want = tr.ops_line(back.planes[0]), tr.ops_line(trace.planes[0])
    assert ops.op_names == want.op_names and ops.names == want.names
    assert back.planes[0].line("XLA Modules").op_names == []
    assert tr.reduce(back) == tr.reduce(trace)
    part = tr.ops_line(tr.cut(back, 400, 1500).planes[0])
    assert part.names == ["fusion.b", "copy.9", "fusion.c"]
    assert part.op_names == want.op_names[2:5]


def test_a_trace_from_before_the_name_stacks_loads_with_none(synthetic):
    """The synthetic trace is written without ``op_names``, as every fixture
    of before was: it loads with the field empty, counts under no scope and
    reduces to everything it reduced to."""
    ops = synthetic.device_planes()[0].line("XLA Ops")
    assert ops.op_names == []
    red = tr.reduce(synthetic, window_s=600e-9)
    assert red["scope_seconds"] == {} == red["scope_seconds_under"]
    assert tr.scope_ms({"trace": red}, STACK) is None
    assert red["busy_s"] == pytest.approx(300e-9) and red["steps"] == 3


# What the two fixtures recorded before the name stacks reduced to, by the
# reduction as it was then (commit 9aa3987): busy seconds, steps, operations
# by name, and the SHA-256 of the whole reduction as ``json.dumps(...,
# sort_keys=True)``.
REDUCED_BEFORE = {
    "resnet50-fit-host.4steps.json.gz": (
        0.419286019, 4, 3766,
        "2f7d814ef3ed7a45f10d0a23b69b1ed2993e90b97c4b07bde72383c32b2c7a30"),
    "resnet50-fit-host.spans.json.gz": (
        0.524279836, 5, 3766,
        "dfed0faf3f544fdaec7a799b0c2aa0985444805102afe24281675b2d19406298"),
}


@pytest.mark.parametrize("name", sorted(REDUCED_BEFORE))
def test_fixtures_without_name_stacks_reduce_to_what_they_reduced_to(name):
    """Every key that the reduction had, equal to the last digit; the two
    new ones empty."""
    trace = tr.load_json(os.path.join(FIXTURES, name))
    assert all(l.op_names == [] for p in trace.planes for l in p.lines)
    red = tr.reduce(trace)
    assert red.pop("scope_seconds") == {} == red.pop("scope_seconds_under")
    busy_s, steps, n_ops, digest = REDUCED_BEFORE[name]
    assert (red["busy_s"], red["steps"], len(red["op_seconds"])) == (
        busy_s, steps, n_ops)
    assert hashlib.sha256(json.dumps(red, sort_keys=True).encode()
                          ).hexdigest() == digest


def test_recorded_chip_trace_of_the_looped_decoder_by_scope():
    """``ouro-2.6b-fit-packed4k.3steps.json.gz``: three whole steps of PR
    35's traced run of the cell on a TPU v5e (seed 3500201), with the name
    stacks.  The two scope metrics read from these three steps what that
    run printed over its 35: ``stack_ms.fit`` 733.9219898285714 and
    ``head_loss_ms.fit`` 245.53455899999997; with the embedding's scope and
    what is under none they add up to the busy time a step."""
    _, spec = tiny.harness_of(tiny.REPO)
    trace = tr.load_json(os.path.join(
        FIXTURES, "ouro-2.6b-fit-packed4k.3steps.json.gz"))
    ops = tr.ops_line(trace.device_planes()[0])
    assert len(ops.op_names) == len(ops.starts) > 30_000
    assert int(tr.self_ns(ops).sum()) == tr.busy_ns(ops)
    red = tr.reduce(trace)
    assert red["steps"] == 3
    cell = spec.load_cell(tiny.REPO, "ouro-2.6b-fit-packed4k")
    run = {"trace": red}
    stack = cell.layer_metric_reader("stack_ms.fit")(run)
    heads = cell.layer_metric_reader("head_loss_ms.fit")(run)
    assert stack == pytest.approx(733.9219898285714, rel=1e-4)
    assert heads == pytest.approx(245.53455899999997, rel=1e-4)
    own = red["scope_seconds"]
    assert set(own) == {"", "zoo:lm/embed", "zoo:lm/stack",
                        "zoo:lm/head_loss"}
    # no marker is nested in another in this program
    assert red["scope_seconds_under"] == {m: s for m, s in own.items() if m}
    assert sum(own.values()) == pytest.approx(red["busy_s"], rel=1e-12)
    rest = 1e3 * (own[""] + own["zoo:lm/embed"]) / 3
    assert stack + heads + rest == pytest.approx(
        1e3 * red["busy_s"] / 3, rel=1e-9)
    assert 25 < rest < 35
    # the backward pass is in the sums: most of the stack's time is under
    # ``transpose(jvp(zoo:lm/stack))``
    back = sum(ns for ns, n in zip(tr.self_ns(ops).tolist(), ops.op_names)
               if "transpose(jvp(zoo:lm/stack))" in n)
    assert 0.6 < back / 1e9 / own["zoo:lm/stack"] < 0.9
    # and the other readers of the cell's line read the same trace
    assert cell.layer_metric_reader("step_p95_ms.fit")(run) == \
        pytest.approx(1009.0, rel=1e-3)


# ---------------------------------------- the profiler's file, by hand ----

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A message's bytes from (field number, value): an int is a varint, a
    float a fixed 64-bit double, bytes or text a length-delimited field."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(number << 3 | 1) + np.float64(value).tobytes()
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return bytes(out)


def _xspace():
    """``/device:TPU:0`` with three operations in its event-metadata: one
    whose ``tf_op`` is a string, one whose ``tf_op`` refers to a
    stat-metadata entry's name, one with other stats only; an ``XLA Ops``
    line of four events at 5 us, and a host plane."""
    stats = {1: "tf_op", 2: "flops", 3: f"jit(step)/jvp({HEADS})/mul:",
             4: "Time Scale Multiplier"}
    events = {
        7: ("%while.1 = (s32[]) while(%t), body=%b", "while.1",
            [_msg((1, 2), (4, 12345)),
             _msg((1, 1), (5, f"jit(step)/jvp({STACK})/while"))]),
        8: ("%fusion.c = f32[8] fusion(%p), kind=kLoop", "fusion.c",
            [_msg((1, 1), (7, 3))]),
        9: ("%copy.9 = f32[8] copy(%p)", "copy.9",
            [_msg((1, 2), (3, 64)), _msg((1, 4), (2, 1.0))]),
    }
    ops = _msg((1, 1), (2, "XLA Ops"), (3, 5000),
               (4, _msg((1, 7), (2, 0), (3, 1000_000))),
               (4, _msg((1, 8), (2, 100_000), (3, 300_000))),
               (4, _msg((1, 9), (2, 1000_000), (3, 100_000))),
               (4, _msg((1, 8), (2, 1200_000), (3, 300_000))))
    device = _msg(
        (1, 1), (2, "/device:TPU:0"), (3, ops),
        *[(4, _msg((1, k), (2, _msg((1, k), (2, name), (4, shown),
                                    *[(5, s) for s in st]))))
          for k, (name, shown, st) in events.items()],
        *[(5, _msg((1, k), (2, _msg((1, k), (2, name)))))
          for k, name in stats.items()])
    host = _msg((1, 2), (2, "/host:CPU"),
                (4, _msg((1, 1), (2, _msg((1, 1), (2, "gather"))))))
    return _msg((1, device), (1, host), (4, "host-0"))


def test_name_stacks_are_read_from_the_files_event_metadata():
    assert tr.op_names_by_event_name(_xspace()) == {"/device:TPU:0": {
        "%while.1 = (s32[]) while(%t), body=%b":
            f"jit(step)/jvp({STACK})/while",
        "%fusion.c = f32[8] fusion(%p), kind=kLoop":
            f"jit(step)/jvp({HEADS})/mul:"}}
    assert tr.op_names_by_event_name(b"") == {}


def test_load_xplane_gives_each_device_operation_its_name_stack(tmp_path):
    """The same bytes through ``load_xplane``: JAX's reader gives the
    events, the file's event-metadata their name stacks."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    trace = tr.load_xplane(str(path))
    ops = tr.ops_line(trace.device_planes()[0])
    assert ops.names == ["while.1", "fusion.c", "copy.9", "fusion.c"]
    assert ops.starts.tolist() == [5000, 5100, 6000, 6200]
    assert ops.durs.tolist() == [1000, 300, 100, 300]
    assert ops.op_names == [f"jit(step)/jvp({STACK})/while",
                            f"jit(step)/jvp({HEADS})/mul:", "",
                            f"jit(step)/jvp({HEADS})/mul:"]
    own, under = tr.scope_seconds(ops)
    assert own == {STACK: pytest.approx(700e-9), "": pytest.approx(100e-9),
                   HEADS: pytest.approx(600e-9)}
    assert under == {STACK: pytest.approx(700e-9),
                     HEADS: pytest.approx(600e-9)}
