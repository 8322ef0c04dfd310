"""Shared backend routing for the Pallas kernels in ``ops/``.

Every hand-written kernel in this package (flash_attention, embedding_bag,
dequant_matmul, ssm_scan) faces the same three-way choice:

- ``"pallas"``     — compiled Mosaic kernel; requires a TPU backend and
  the kernel's shape limits met.  ``shapes_ok`` must be False for every
  shape Mosaic refuses: a refused shape is excluded here, at selection
  time, never caught around ``pallas_call`` at run time.
- ``"interpret"``  — the same kernel run under ``pallas_call(interpret=
  True)``; bit-faithful to the kernel's math on any backend, used by the
  CPU test tier and debugging (never auto-selected: it is orders of
  magnitude slower than XLA).
- ``"reference"``  — the pure-JAX oracle; XLA-compiled, differentiable,
  runs anywhere.

``select_path`` is the single predicate behind all of them instead of a
private copy each, and records every decision in the
``ops_kernel_selected_total{kernel,path}`` counter so a serving or
training job can assert from metrics alone that the hot loop actually hit
the fused kernel (a silent fall-back to "reference" is a perf bug, not an
error).
"""

from __future__ import annotations

from typing import Optional

import jax

PATH_PALLAS = "pallas"
PATH_INTERPRET = "interpret"
PATH_REFERENCE = "reference"
_PATHS = (PATH_PALLAS, PATH_INTERPRET, PATH_REFERENCE)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def config_knob(name: str, default=None):
    """Read one knob off the global ZooConfig without *creating* a context.

    Kernels dispatch from inside layer forwards; forcing a mesh into
    existence there would be a side effect, so an uninitialised context
    just yields ``default``.
    """
    from analytics_zoo_tpu.core import context as _context

    ctx = _context._GLOBAL_CONTEXT
    if ctx is None:
        return default
    return getattr(ctx.config, name, default)


def record_selection(kernel: str, path: str) -> None:
    """Count one routing decision (trace-time: once per compilation)."""
    from analytics_zoo_tpu.observe import metrics as _metrics

    _metrics.count("ops_kernel_selected_total", 1,
                   flat=f"{kernel}/{path}", kernel=kernel, path=path)


def select_path(kernel: str, *, shapes_ok: bool = True,
                min_work_met: bool = True,
                knob: Optional[str] = None,
                force: Optional[str] = None) -> str:
    """The one backend-routing predicate shared by the ops/ kernels.

    ``shapes_ok``     kernel-specific hard limits (what Mosaic compiles:
                      tile divisibility, dtype, scratch budgets; and
                      unsupported features like masks/dropout) — when
                      False the reference path is the only correct one.
    ``min_work_met``  the kernel only *wins* above some problem size;
                      below it the XLA path is faster (grid overhead).
    ``knob``          value of the governing config knob: "auto"/None
                      defers to the predicate, "off" pins the reference
                      path, "on" insists on the kernel wherever shapes
                      allow (overriding min_work_met).
    ``force``         explicit caller override (tests, benches); must be
                      one of the three path names.

    Returns the chosen path name and records it in
    ``ops_kernel_selected_total``.
    """
    if force is not None:
        if force not in _PATHS:
            raise ValueError(f"unknown kernel path {force!r}; "
                             f"expected one of {_PATHS}")
        path = force
    elif knob == "off" or not shapes_ok:
        path = PATH_REFERENCE
    elif on_tpu() and (min_work_met or knob == "on"):
        path = PATH_PALLAS
    else:
        path = PATH_REFERENCE
    record_selection(kernel, path)
    return path
