"""Reduction from a profiler trace to numbers: device busy time, idle gaps,
gaps between successive executions of the step program, and the operations
that took most time.

A trace is read into plain arrays first (``Trace`` of ``Plane`` of ``Line``),
from the profiler's ``.xplane.pb`` or from this module's own JSON form, which
the fixtures use; every reduction works on those arrays.  All times in the
arrays are nanoseconds on the trace's clock.
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
    than ``min_host_ns`` are dropped: they only serve to name idle gaps."""
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        is_device = bool(_DEVICE.match(plane.name))
        if not is_device and not plane.name.startswith("/host:"):
            continue
        out = Plane(plane.name)
        for line in plane.lines:
            starts, durs, names = [], [], []
            for ev in line.events:
                dur = int(ev.duration_ns)
                if not is_device and dur < min_host_ns:
                    continue
                starts.append(int(ev.start_ns))
                durs.append(dur)
                names.append(short_name(ev.name))
            if starts:
                out.lines.append(Line(line.name,
                                      np.asarray(starts, np.int64),
                                      np.asarray(durs, np.int64), names))
        trace.planes.append(out)
    return trace


def dump_json(trace: Trace, path: str) -> None:
    """This module's own form: names interned, times as lists."""
    doc = {"planes": []}
    for p in trace.planes:
        lines = []
        for l in p.lines:
            table = sorted(set(l.names))
            index = {n: i for i, n in enumerate(table)}
            lines.append({"name": l.name, "names": table,
                          "name_ids": [index[n] for n in l.names],
                          "starts": l.starts.tolist(),
                          "durs": l.durs.tolist()})
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
                [l["names"][i] for i in l["name_ids"]]))
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
                    [n for n, k in zip(l.names, keep) if k]))
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


def top_ops(line: Line, n: int = 10) -> List[List]:
    """[[name, seconds], ...] of the operations that took most time, summed
    by name."""
    total: Dict[str, int] = {}
    for name, d in zip(line.names, line.durs):
        total[name] = total.get(name, 0) + int(d)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


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
    out: Dict = {
        "devices": [p.name for p, _ in per_device],
        "busy_s_each": busy,
        "busy_s": float(np.mean(busy)),
        "span_s": span_s,
        "window_s": float(window_s) if window_s else span_s,
        "device_ops": top_ops(line0),
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
