"""Reduction from a profiler trace to numbers: device busy time, idle gaps,
gaps between successive executions of the step program, the operations that
took most time, and device time by the program's own ``jax.named_scope``s.

A trace is read into plain arrays first (``Trace`` of ``Plane`` of ``Line``),
from the profiler's ``.xplane.pb`` or from this module's own JSON form, which
the fixtures use; every reduction works on those arrays.  All times in the
arrays are nanoseconds on the trace's clock.

The JSON form: ``{"planes": [{"name", "lines": [{"name", "names",
"name_ids", "starts", "durs"}]}]}``, an event's name being
``names[name_ids[i]]``.  A line whose events carry the JAX name stack
(``Line.op_names``) has two more keys, ``op_names`` and ``op_name_ids``, read
the same way; a file written before they existed loads with ``op_names``
empty, and every event of it then counts under no scope.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


@dataclass
class Line:
    name: str
    starts: np.ndarray          # int64 ns
    durs: np.ndarray            # int64 ns
    names: List[str]
    # the JAX name stack of each event (``jit(step)/.../zoo:lm/stack/...``),
    # "" where the profiler gives none; an empty list where none was read
    op_names: List[str] = field(default_factory=list)

    @property
    def ends(self) -> np.ndarray:
        return self.starts + self.durs


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)

    def line(self, name: str) -> Optional[Line]:
        return next((l for l in self.lines if l.name == name), None)


@dataclass
class Trace:
    planes: List[Plane] = field(default_factory=list)

    def device_planes(self) -> List[Plane]:
        found = [(int(_DEVICE.match(p.name).group(2)), p)
                 for p in self.planes if _DEVICE.match(p.name)]
        return [p for _, p in sorted(found, key=lambda t: t[0])]

    def host_planes(self) -> List[Plane]:
        return [p for p in self.planes if p.name.startswith("/host:")]


# ---------------------------------------------------------------- readers --

def find_xplane(log_dir: str) -> str:
    """Newest ``*.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def short_name(name: str) -> str:
    """A device operation is named by its whole HLO line; keep what stands
    before the ``=``, without the ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str, min_host_ns: int = 20_000) -> Trace:
    """Read the profiler's file with JAX's own reader.  Host events shorter
    than ``min_host_ns`` are dropped: they only serve to name idle gaps.
    An operation's JAX name stack is not among what that reader shows: it
    comes from the file's event-metadata (``op_names_by_event_name``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    op_name_of = op_names_by_event_name(data)
    trace = Trace()
    for plane in ProfileData.from_serialized_xspace(data).planes:
        is_device = bool(_DEVICE.match(plane.name))
        if not is_device and not plane.name.startswith("/host:"):
            continue
        out = Plane(plane.name)
        scoped = op_name_of.get(plane.name, {}) if is_device else {}
        for line in plane.lines:
            starts, durs, names, op_names = [], [], [], []
            for ev in line.events:
                dur = int(ev.duration_ns)
                if not is_device and dur < min_host_ns:
                    continue
                starts.append(int(ev.start_ns))
                durs.append(dur)
                names.append(short_name(ev.name))
                op_names.append(scoped.get(ev.name, ""))
            if starts:
                out.lines.append(Line(
                    line.name, np.asarray(starts, np.int64),
                    np.asarray(durs, np.int64), names,
                    op_names if any(op_names) else []))
        trace.planes.append(out)
    return trace


# The five messages of ``xplane.proto`` that lead to an operation's name
# stack, read from their wire format (field numbers as in the profiler's
# ``tsl/profiler/protobuf/xplane.proto``):
#   XSpace          1 planes
#   XPlane          2 name, 4 event_metadata (a map: 1 key, 2 value),
#                   5 stat_metadata (a map)
#   XEventMetadata  2 name, 5 stats
#   XStat           1 metadata_id, 5 str_value, 7 ref_value
#   XStatMetadata   1 id, 2 name
# The lines and their events (field 3 of XPlane, nearly all of the file) are
# skipped by their length.
OP_NAME_STAT = "tf_op"         # where XLA puts an instruction's ``op_name``


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message's fields: an ``int`` for a varint,
    a ``memoryview`` for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_value(entry):
    return next((v for k, v in _fields(entry) if k == 2), b"")


def op_names_by_event_name(data: bytes) -> Dict[str, Dict[str, str]]:
    """{plane name: {event name: JAX name stack}} from the bytes of an
    ``.xplane.pb``: of every event-metadata entry that has one, the string
    of its ``tf_op`` stat, held by the stat or by the stat-metadata entry it
    refers to."""
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(memoryview(data)):
        if number != 1:
            continue
        plane_name, events, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                plane_name = bytes(v).decode()
            elif k == 4:
                events.append(_map_value(v))
            elif k == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        named: Dict[str, str] = {}
        for meta in events:
            name, op_name = "", ""
            for k, v in _fields(meta):
                if k == 2:
                    name = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if op_name:
                named[name] = op_name
        if named:
            out[plane_name] = named
    return out


def _interned(values: List[str]) -> Tuple[List[str], List[int]]:
    table = sorted(set(values))
    index = {v: i for i, v in enumerate(table)}
    return table, [index[v] for v in values]


def dump_json(trace: Trace, path: str) -> None:
    """This module's own form: names interned, times as lists."""
    doc = {"planes": []}
    for p in trace.planes:
        lines = []
        for l in p.lines:
            names, name_ids = _interned(l.names)
            lines.append({"name": l.name, "names": names,
                          "name_ids": name_ids,
                          "starts": l.starts.tolist(),
                          "durs": l.durs.tolist()})
            if l.op_names:
                lines[-1]["op_names"], lines[-1]["op_name_ids"] = \
                    _interned(l.op_names)
        doc["planes"].append({"name": p.name, "lines": lines})
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(doc, f, separators=(",", ":"))


def load_json(path: str) -> Trace:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    trace = Trace()
    for p in doc["planes"]:
        plane = Plane(p["name"])
        for l in p["lines"]:
            plane.lines.append(Line(
                l["name"], np.asarray(l["starts"], np.int64),
                np.asarray(l["durs"], np.int64),
                [l["names"][i] for i in l["name_ids"]],
                [l["op_names"][i] for i in l.get("op_name_ids", [])]))
        trace.planes.append(plane)
    return trace


def cut(trace: Trace, lo: int, hi: int) -> Trace:
    """The events that start in ``[lo, hi)``: how a fixture is cut from a
    chip trace."""
    out = Trace()
    for p in trace.planes:
        plane = Plane(p.name)
        for l in p.lines:
            keep = (l.starts >= lo) & (l.starts < hi)
            if keep.any():
                plane.lines.append(Line(
                    l.name, l.starts[keep], l.durs[keep],
                    [n for n, k in zip(l.names, keep) if k],
                    [n for n, k in zip(l.op_names, keep) if k]))
        if plane.lines:
            out.planes.append(plane)
    return out


# ------------------------------------------------------------- reductions --

def merged_intervals(starts: np.ndarray, ends: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Union of possibly overlapping intervals, as sorted disjoint ones."""
    if len(starts) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return s[first], reach[last]


def busy_ns(line: Line) -> int:
    """Time in which at least one of the line's events ran."""
    s, e = merged_intervals(line.starts, line.ends)
    return int((e - s).sum())


def idle_gaps(line: Line, lo: Optional[int] = None,
              hi: Optional[int] = None) -> List[Tuple[int, int]]:
    """Intervals of ``[lo, hi]`` in which none of the line's events ran;
    the bounds default to the line's first start and last end, which leaves
    no gap at either end."""
    s, e = merged_intervals(line.starts, line.ends)
    if len(s) == 0:
        return [] if lo is None or hi is None else [(lo, hi)]
    lo = int(s[0]) if lo is None else lo
    hi = int(e[-1]) if hi is None else hi
    gaps = []
    for a, b in zip(np.concatenate([[lo], e]), np.concatenate([s, [hi]])):
        if b > a:
            gaps.append((int(a), int(b)))
    return gaps


def ops_line(plane: Plane) -> Optional[Line]:
    """The line of single device operations."""
    return plane.line(OPS_LINE)


def step_program(plane: Plane) -> Optional[str]:
    """The whole-program execution that took most device time: the step."""
    line = plane.line(MODULES_LINE)
    if line is None:
        return None
    total: Dict[str, int] = {}
    for n, d in zip(line.names, line.durs):
        total[n] = total.get(n, 0) + int(d)
    return max(total, key=total.get)


def step_starts(plane: Plane, program: str) -> np.ndarray:
    line = plane.line(MODULES_LINE)
    keep = np.asarray([n == program for n in line.names])
    return np.sort(line.starts[keep])


def op_seconds(line: Line) -> Dict[str, float]:
    """{name: seconds} of every operation of the line, summed by name."""
    total: Dict[str, int] = {}
    for name, d in zip(line.names, line.durs):
        total[name] = total.get(name, 0) + int(d)
    return {name: ns / 1e9 for name, ns in total.items()}


def self_ns(line: Line) -> np.ndarray:
    """Each event's duration less that of the events it holds (a ``while``
    holds its body's events): every moment in which the line is busy goes to
    the event that started last of those running then, so the self times
    add up to ``busy_ns`` exactly, overlaps or not."""
    n = len(line.starts)
    out = np.zeros(n, np.int64)
    starts, ends = line.starts.tolist(), line.ends.tolist()
    starts.append(max(ends, default=0))     # closes whatever still runs
    running, cursor = [], 0                 # outermost first
    for i in np.lexsort((-line.ends, line.starts)).tolist() + [n]:
        start = starts[i]
        while running and ends[running[-1]] <= start:
            j = running.pop()
            if ends[j] > cursor:
                out[j] += ends[j] - cursor
                cursor = ends[j]
        if running and start > cursor:
            out[running[-1]] += start - cursor
        cursor = max(cursor, start)
        running.append(i)
    return out


# a ``jax.named_scope`` of the program's: ``zoo:<group>/<name>``.  The name
# stack joins its scopes with ``/`` too, so a marker has exactly these two
# components, of letters, digits and ``_``, and ends where they end
MARKER = re.compile(r"zoo:[A-Za-z0-9_]+/[A-Za-z0-9_]+")


def scope_seconds(line: Line) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Self time of the line's events by the markers in their name stack:
    ``({marker: seconds}, {marker: seconds})``.  The first counts an event
    under the innermost (last) marker of its name, ``""`` where it has
    none, so its values add up to the line's busy time; the second under
    every marker of its name, so a marker's value there holds the markers
    nested in it.  Forward and backward are one sum:
    ``transpose(jvp(zoo:lm/stack))`` counts under ``zoo:lm/stack``.  A
    fusion across a scope's border counts where XLA names it: at its
    root."""
    if not line.op_names:
        return {}, {}
    own: Dict[str, int] = {}
    under: Dict[str, int] = {}
    found: Dict[str, List[str]] = {}
    for op_name, ns in zip(line.op_names, self_ns(line).tolist()):
        markers = found.get(op_name)
        if markers is None:
            markers = found[op_name] = MARKER.findall(op_name)
        inner = markers[-1] if markers else ""
        own[inner] = own.get(inner, 0) + ns
        for m in set(markers):
            under[m] = under.get(m, 0) + ns
    return ({m: ns / 1e9 for m, ns in own.items()},
            {m: ns / 1e9 for m, ns in under.items()})


def scope_ms(run: Dict, marker: str) -> Optional[float]:
    """Milliseconds a step that the first device spent under ``marker`` and
    every marker nested in it; ``None`` where the trace holds no such event.
    What a scope's layer metric is: ``read = lambda run: scope_ms(run,
    "zoo:<group>/<name>")``."""
    red = run["trace"]
    if red is None or not red.get("steps"):
        return None
    seconds = red.get("scope_seconds_under", {}).get(marker)
    if not seconds:
        return None
    return 1e3 * seconds / red["steps"]


def longest(seconds: Dict[str, float], n: int = 10) -> List[List]:
    """[[name, seconds], ...] of the ``n`` names that took most time."""
    best = sorted(seconds.items(), key=lambda kv: -kv[1])[:n]
    return [[name, s] for name, s in best]


def top_ops(line: Line, n: int = 10) -> List[List]:
    """[[name, seconds], ...] of the operations that took most time, summed
    by name."""
    return longest(op_seconds(line), n)


def name_gaps(trace: Trace, gaps: Sequence[Tuple[int, int]], n: int = 10
              ) -> List[List]:
    """[[what the host was doing, seconds], ...] for the ``n`` longest
    gaps: the host event that overlaps the gap most, as ``thread/event``."""
    host = [(p, l) for p in trace.host_planes() for l in p.lines]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_ns = "unnamed", 0
        for _, l in host:
            over = np.minimum(l.ends, b) - np.maximum(l.starts, a)
            i = int(np.argmax(over)) if len(over) else -1
            if i >= 0 and over[i] > best_ns:
                best, best_ns = f"{l.name}/{l.names[i]}", int(over[i])
        out.append([best, (b - a) / 1e9])
    return out


def reduce(trace: Trace, window_s: Optional[float] = None) -> Optional[Dict]:
    """Everything the layer metrics read.  ``None`` when no operation ran on
    a device plane (a CPU rehearsal)."""
    devices = trace.device_planes()
    per_device = []
    for p in devices:
        line = ops_line(p)
        if line is not None and len(line.starts):
            per_device.append((p, line))
    if not per_device:
        return None
    busy = [busy_ns(l) / 1e9 for _, l in per_device]
    plane0, line0 = per_device[0]
    span_s = float(line0.ends.max() - line0.starts.min()) / 1e9
    # every operation by name: a reader finds its kernel here however many
    # others took longer; the result line prints the ten longest
    seconds = op_seconds(line0)
    own, under = scope_seconds(line0)
    out: Dict = {
        "devices": [p.name for p, _ in per_device],
        "busy_s_each": busy,
        "busy_s": float(np.mean(busy)),
        "span_s": span_s,
        "window_s": float(window_s) if window_s else span_s,
        "op_seconds": seconds,
        "device_ops": longest(seconds),
        # device-0 self time by the program's named scopes: by innermost
        # marker (adds up to its busy time), and by every marker of the name
        "scope_seconds": own,
        "scope_seconds_under": under,
    }
    gaps = idle_gaps(line0)
    out["idle_gaps"] = name_gaps(trace, gaps)
    program = step_program(plane0)
    out["step_program"] = program
    if program is not None:
        starts = step_starts(plane0, program)
        # ``steps`` executions of the step program; from the first one's
        # start to the last one's start the device did ``steps - 1`` steps
        out["steps"] = int(len(starts))
        out["step_span_s"] = float(starts[-1] - starts[0]) / 1e9
        out["step_gaps_ms"] = (np.diff(starts) / 1e6).tolist()
    return out
