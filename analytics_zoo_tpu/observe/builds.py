"""Program builds on the program's own clock.

JAX reports every step of building a program through ``jax.monitoring``
as a time span (start, end, on ``time.time()``) with the program's
``fun_name``, when the step ends; a backend compile that the persistent
cache served records ``/jax/compilation_cache/cache_hits`` on the same
thread, inside the compile's span.  The steps:

- ``trace``: ``jaxpr_trace_duration``, Python to jaxpr (``fun_name`` is
  the function's own: ``step``, ``sin``);
- ``lower``: ``jaxpr_to_mlir_module_duration``, jaxpr to StableHLO, where
  Pallas kernels become Mosaic (``jit(step)``);
- ``compile``: ``backend_compile_duration`` where XLA compiled;
- ``load``: the same event where the persistent cache returned the
  executable.

Each step is booked at its **self time**: a step nested in another on the
same thread (tracing ``jit(outer)`` traces ``jit(inner)`` and every eager
primitive it meets) is taken out of the step that encloses it, so the
phases never add up to more than the wall time they cover.  A step ends
after every step it encloses, so its span arrives after theirs: each
thread keeps the spans that ended, and a new span takes out those that
started inside it.

``jit_build_seconds{phase}`` is a gauge, the seconds this process has
spent on the phase so far: ``METRICS.delta`` returns gauges whole, so a
window opened after set-up still reads the whole set-up's account, and a
build later in the process shows as a rise.  ``jit_builds_total{phase,
program}`` counts the steps: in a window of a steady ``fit`` it stays
empty, and a series there names the program that was built again.

The callbacks run only when JAX builds something; they take a lock only
to add a float.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Deque, Dict, Tuple

import jax

from analytics_zoo_tpu.observe.metrics import METRICS

__all__ = ["PHASES", "install_build_listener"]

_STEPS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
PHASES = ("trace", "lower", "compile", "load")
# ended spans a thread keeps for a step that may yet enclose them; beyond
# it the oldest go (a step that encloses more is booked too high)
_KEPT = 1 << 16

_lock = threading.Lock()
_seconds: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
_installed = False
_local = threading.local()


def _ended() -> Deque[Tuple[float, float]]:
    """This thread's ended steps, ``(start, end)`` in the order they
    ended, none of them yet inside a step that ended after it."""
    ended = getattr(_local, "ended", None)
    if ended is None:
        ended = _local.ended = collections.deque(maxlen=_KEPT)
    return ended


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _local.hit_at = time.time()


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_) -> None:
    phase = _STEPS.get(event)
    if phase is None:
        return
    ended, nested = _ended(), 0.0
    while ended and ended[-1][0] >= start:
        s, e = ended.pop()
        nested += e - s
    ended.append((start, end))
    if phase == "compile" and start <= getattr(_local, "hit_at", -1.0) <= end:
        phase = "load"
    with _lock:
        _seconds[phase] += max(0.0, end - start - nested)
        METRICS.set("jit_build_seconds", _seconds[phase], phase=phase)
    METRICS.inc("jit_builds_total", phase=phase, program=fun_name)


def install_build_listener() -> bool:
    """Listen to JAX's build events for the rest of the process and set
    the four ``jit_build_seconds`` gauges (0 until a build).
    ``init_zoo_context`` calls it; a process that skips the context can
    call it itself.  Returns False, and does nothing, where it was
    installed already."""
    global _installed
    with _lock:
        if _installed:
            return False
        _installed = True
        for phase, seconds in _seconds.items():
            METRICS.set("jit_build_seconds", seconds, phase=phase)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_span)
    return True
