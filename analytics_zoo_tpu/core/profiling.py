"""Tracing / profiling (SURVEY §5.1).

Reference capability: ``Utils.timeIt(name){...}`` debug-log timers around
hot calls (pipeline/api/net/TFNet.scala:179, tfpark/GraphRunner.scala:132)
and per-iteration BigDL ``Metrics`` aggregation (Topology.scala:1192).

TPU-native design: two complementary mechanisms —
- ``timeit`` / ``scoped_timer``: host-side wall-clock scopes aggregated in
  a process-wide registry (mean/total/count per name), for spotting
  host-bound stages (data prep, device_put, checkpoint writes).
- ``trace``: a context manager around ``jax.profiler`` that captures an
  xprof/TensorBoard-viewable device trace.  Every stage the program
  times through ``observe.metrics.time_stage`` shows in it as a
  ``zoo:<metric>/<labels>`` host event, on the clock of the device's ops.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, Optional

logger = logging.getLogger("analytics_zoo_tpu.profiling")


# per-stat reservoir of recent durations for percentile rollups; 512
# samples bound memory while keeping p99 meaningful over the last ~minutes
# of a serving stage (the serving pipeline reads p50/p99 per stage)
_MAX_SAMPLES = 512


@dataclass
class _Stat:
    count: int = 0
    total_s: float = 0.0
    max_s: float = 0.0
    samples: list = field(default_factory=list)  # ring of recent durations
    cursor: int = 0  # next ring slot to overwrite once the ring is full

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.max_s = max(self.max_s, dt)
        if len(self.samples) < _MAX_SAMPLES:
            self.samples.append(dt)
        else:
            # explicit cursor: deriving the slot from the already-
            # incremented count skipped slot 0 a full lap
            self.samples[self.cursor] = dt
            self.cursor = (self.cursor + 1) % _MAX_SAMPLES

    def percentile(self, q: float) -> float:
        """q-th percentile (0-100) over the recent-sample ring."""
        if not self.samples:
            return 0.0
        s = sorted(self.samples)
        i = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
        return s[i]


class Timers:
    """Process-wide named wall-clock scopes + event counters (thread-safe).

    Counters record *how often* something happened (per-batch
    ``device_put`` dispatches, which data path an Estimator.fit took)
    where a duration would be meaningless; tests assert on them to prove
    hot-path properties ("zero host→device transfers per epoch") instead
    of eyeballing traces."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stats: Dict[str, _Stat] = {}
        self._counts: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}

    @contextlib.contextmanager
    def scope(self, name: str, log: bool = False) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.observe(name, dt)
            if log:
                logger.info("[timeit] %s: %.3fms", name, dt * 1e3)

    def observe(self, name: str, seconds: float) -> None:
        """Record one duration measured externally (a cross-thread span —
        e.g. request enqueue → response written — that no single
        ``scope`` block can bracket)."""
        with self._lock:
            self._stats.setdefault(name, _Stat()).add(seconds)

    def percentile(self, name: str, q: float) -> float:
        """q-th percentile (0-100) of the named timer's recent samples."""
        with self._lock:
            s = self._stats.get(name)
            return s.percentile(q) if s else 0.0

    def incr(self, name: str, n: int = 1) -> None:
        """Bump the named event counter by ``n``."""
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def count(self, name: str) -> int:
        """Current value of the named counter (0 if never bumped)."""
        with self._lock:
            return self._counts.get(name, 0)

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def set_gauge(self, name: str, value: float) -> None:
        """Record a point-in-time level (replicas healthy, heartbeat
        age, queue depth) — unlike counters these overwrite, so the
        reader always sees the current state, not an accumulation."""
        with self._lock:
            self._gauges[name] = float(value)

    def gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def stats(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"count": v.count, "total_s": v.total_s,
                        "mean_s": v.mean_s, "max_s": v.max_s,
                        "p50_s": v.percentile(50), "p99_s": v.percentile(99)}
                    for k, v in self._stats.items()}

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._counts.clear()
            self._gauges.clear()

    def report(self) -> str:
        lines = ["name count total_s mean_ms p50_ms p99_ms max_ms"]
        for k, v in sorted(self.stats().items(),
                           key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"{k} {v['count']} {v['total_s']:.3f} "
                         f"{v['mean_s'] * 1e3:.2f} {v['p50_s'] * 1e3:.2f} "
                         f"{v['p99_s'] * 1e3:.2f} {v['max_s'] * 1e3:.2f}")
        counts = self.counts()
        if counts:
            lines.append("-- counters --")
            for k, n in sorted(counts.items()):
                lines.append(f"{k} {n}")
        gauges = self.gauges()
        if gauges:
            lines.append("-- gauges --")
            for k, v in sorted(gauges.items()):
                lines.append(f"{k} {v:g}")
        return "\n".join(lines)


TIMERS = Timers()


def timeit(name: str, log: bool = False):
    """``with timeit("shard_batch"): ...`` — scoped wall-clock timer."""
    return TIMERS.scope(name, log=log)


def count_event(name: str, n: int = 1) -> None:
    """Bump a process-wide event counter (``TIMERS.counts()`` reads it)."""
    TIMERS.incr(name, n)


# jax.profiler supports exactly one active trace per process; track it
# so a nested trace() fails loudly instead of corrupting the session
_trace_lock = threading.Lock()
_active_trace_dir: Optional[str] = None


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a ``jax.profiler`` device trace into ``log_dir``
    (view with TensorBoard's profile plugin / xprof).

    Nested ``trace()`` calls raise ``RuntimeError`` (the profiler is a
    process-wide singleton), and a failed ``start_trace`` propagates
    without attempting ``stop_trace`` on a never-started profiler."""
    import jax

    global _active_trace_dir
    with _trace_lock:
        if _active_trace_dir is not None:
            raise RuntimeError(
                f"profiling.trace({log_dir!r}) called while a trace into "
                f"{_active_trace_dir!r} is active; jax.profiler supports "
                "one trace per process — end the outer trace first")
        _active_trace_dir = log_dir
    started = False
    try:
        jax.profiler.start_trace(log_dir)
        started = True
        yield
    finally:
        with _trace_lock:
            _active_trace_dir = None
        if started:
            jax.profiler.stop_trace()
            logger.info("profiler trace written to %s", log_dir)
