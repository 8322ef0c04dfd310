"""The ``fit`` window: one ``KerasNet.fit`` call over host rows, measured
from inside the call by a clock that the harness hands in as the
``validation_trigger`` (the estimator consults it after every step it
dispatches; it never asks for a validation).

A run is one ``Session``: set-up (context, the seed's rows and weights, build
and compile); the first three steps, driven through ``fit`` as one shuffled
three-step epoch whose order the rows themselves note; then the measured
call over the whole data set.  Its first steps fill the pipeline; the window
opens on a finished step, closes on the first step finished at or after
``--seconds``, and ends the call there, inside the epoch.  The compared
steps and the window go through the same estimator and the same compiled
step.  Once the window has closed and the peak memory is read, the program's
state is freed and the plain reference follows the same three batches.
"""

from __future__ import annotations

import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import compare, optim, trace_reduce
from ..peaks import peaks_for

TRACE_SECONDS = 3.0             # the traced part ends at the first step
                                # finished this long after the profiler began
TRACE_AFTER_STEPS = 8           # ... which begins this far into the window


class CompileCounter:
    """Counts programs built (compiled or fetched from the persistent
    cache) from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _shared: Optional["CompileCounter"] = None

    @classmethod
    def shared(cls) -> "CompileCounter":
        """One listener a process, however many sessions it makes."""
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def _on_event(self, event: str, **kw) -> None:
        if "compilation_cache" in event:
            key = event.rsplit("/", 1)[-1]
            self.cache[key] = self.cache.get(key, 0) + 1


class Rows:
    """A data set of ``n`` rows as ``fit`` takes one: an array-like with a
    shape and fancy indexing, as ``numpy.memmap`` or an HDF5 data set is
    for data that host memory does not hold.  The host holds ``pool``;
    row ``i`` is ``pool[i % len(pool)]``.  Every index array it is asked
    for is noted in ``asked``, when that is a list: the order in which the
    data tier gathers its batches."""

    def __init__(self, pool: np.ndarray, n: int,
                 asked: Optional[List[np.ndarray]] = None):
        self.pool, self.asked = pool, asked
        self.shape = (int(n),) + pool.shape[1:]
        self.dtype, self.ndim = pool.dtype, pool.ndim

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            idx = np.arange(*idx.indices(self.shape[0]))
        idx = np.asarray(idx)
        if self.asked is not None:
            self.asked.append(idx)
        return self.pool[idx % len(self.pool)]


class WindowClosed(KeyboardInterrupt):
    """Ends the measured ``fit`` from the step hook: the estimator releases
    its prefetch producer on a ``KeyboardInterrupt`` and hands it on."""


class StepHook:
    """A ``validation_trigger`` that only looks: ``on_step(iteration)`` after
    every dispatch, in the thread that dispatches."""

    def __init__(self, on_step: Callable[[int], None]):
        self.on_step = on_step

    def __call__(self, tstate) -> bool:
        if not tstate.epoch_finished:
            self.on_step(int(tstate.iteration))
        return False


class WindowClock:
    """Opens the window once ``open_after`` steps of the call are finished,
    closes it on the first step finished at or after ``seconds`` and ends
    the call.  Finished: the hook waits for the step's carry.  With a trace
    directory the profiler runs over whole steps inside the window."""

    def __init__(self, finished: Callable[[], None],
                 mark: Callable[[], Dict[str, Any]], seconds: float,
                 open_after: int, trace_dir: Optional[str] = None):
        self.finished, self.mark = finished, mark
        self.seconds, self.open_after = seconds, open_after
        self.trace_dir = trace_dir
        self.first: Optional[int] = None
        self.open: Optional[Dict[str, Any]] = None
        self.close: Optional[Dict[str, Any]] = None
        self.traced: Optional[Dict[str, float]] = None
        self._trace0: Optional[Dict[str, Any]] = None

    def _now(self, iteration: int) -> Dict[str, Any]:
        self.finished()
        return {"t": time.perf_counter(), "iteration": iteration,
                **self.mark()}

    def __call__(self, iteration: int) -> None:
        import jax

        if self.first is None:
            self.first = iteration
        if self.open is None:
            if iteration - self.first + 1 >= self.open_after:
                self.open = self._now(iteration)
            return
        if self.trace_dir and self.traced is None:
            if self._trace0 is None:
                if iteration - self.open["iteration"] >= TRACE_AFTER_STEPS:
                    self.finished()
                    # the host's own events at their coarsest level: the
                    # idle gaps need the long ones' names only.  (It does
                    # not stop the profiler from slowing a host-bound
                    # cell's layout threads: PERF.md section 5.)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(self.trace_dir,
                                             profiler_options=opts)
                    self._trace0 = {"t": time.perf_counter(),
                                    "iteration": iteration}
                return
            if time.perf_counter() - self._trace0["t"] < TRACE_SECONDS:
                return
            self.finished()
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.traced = {"seconds": t1 - self._trace0["t"],
                           "steps": iteration - self._trace0["iteration"]}
        if time.perf_counter() - self.open["t"] >= self.seconds:
            self.close = self._now(iteration)
            raise WindowClosed()


class Session:
    def __init__(self, cell, seed: int, t0: float):
        self.cell, self.seed, self.t0 = cell, int(seed), t0
        self.cfg = cell.config
        self.dep = cell.config["deployment"]
        self.opt = self.dep["optimizer"]
        self.batch = int(self.dep["batch_per_chip"]) * cell.chips
        self.compiles = CompileCounter.shared()
        self.net = None
        self.epochs_done = 0

    # ------------------------------------------------------------ set-up --
    def _context(self):
        from analytics_zoo_tpu import init_zoo_context

        return init_zoo_context(compute_dtype=self.dep["compute_dtype"],
                                seed=self.seed % (2 ** 31 - 1),
                                **self.cell.traffic.get("context", {}))

    def _key(self):
        import jax

        return jax.random.PRNGKey(np.uint32(self.seed % 2 ** 32))

    def _weights(self):
        """The reference's own initialiser, one jitted call from the seed."""
        import jax

        return jax.jit(lambda k: self.cell.reference.init_params(
            k, self.cfg))(self._key())

    def setup(self, pool_rows: Optional[int] = None) -> None:
        self._context()
        self.load_rows(pool_rows)
        self.net = self.cell.config_mod.build(self.cfg)
        self.load_weights()

    def load_rows(self, pool_rows: Optional[int] = None) -> None:
        """The seed's rows: the traffic's pool, or only the first few."""
        need = compare.STEPS * self.batch
        n = int(self.cell.traffic["pool_rows"]) if pool_rows is None \
            else pool_rows
        if n < need:
            raise ValueError(f"the pool holds {n} rows; the compared steps "
                             f"need {need}")
        self.pool, self.pool_y = self.cell.config_mod.make_data(
            self.cfg, self.seed, n)

    def load_weights(self) -> None:
        """Give the program a copy of the seed's weights (its step donates
        what it is given) and keep the original for the change's norm."""
        import jax
        import jax.numpy as jnp

        self.p0 = self._weights()
        copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(self.p0)
        shapes = [(2,) + a.shape[1:] for a in self.pool]
        self.net.set_initial_weights(
            self.cell.config_mod.to_program(copy, self.net, shapes))

    def _fit(self, n: int, hook: Callable[[int], None],
             asked: List[np.ndarray], epochs: int):
        """The one way this driver calls ``fit``: ``n`` rows of the data
        set, shuffled as the traffic says, the hook as the validation
        trigger over a validation set that is never evaluated."""
        xs = [Rows(a, n, asked if i == 0 else None)
              for i, a in enumerate(self.pool)]
        y = np.resize(self.pool_y, n)
        few = ([a[:self.batch] for a in self.pool], self.pool_y[:self.batch])
        return self.net.fit(xs, y, batch_size=self.batch, nb_epoch=epochs,
                            shuffle=bool(self.cell.traffic["shuffle"]),
                            validation_data=few,
                            validation_trigger=StepHook(hook), verbose=False)

    # ------------------------------------------------------- first steps --
    def first_steps(self) -> Dict[str, Any]:
        """Three steps through ``fit`` as one epoch over the data set's
        first rows: the epoch's mean loss, the first gradient's norms from
        the optimizer's state after step 1, the change's norms after step
        3, and the rows each step was given, as the data tier asked for
        them."""
        import jax

        mod = self.cell.config_mod
        norms = jax.jit(compare.leaf_norms)
        seen: Dict[str, Any] = {}

        def after_step(iteration: int) -> None:
            if "grad_norms" not in seen:
                seen["grad_norms"] = np.asarray(norms(mod.from_program(
                    optim.first_gradient(
                        self.opt, self.net.estimator.opt_state), self.net)))

        asked: List[np.ndarray] = []
        self.epochs_done += 1
        hist = self._fit(compare.STEPS * self.batch, after_step, asked,
                         self.epochs_done)
        est = self.net.estimator
        delta = np.asarray(jax.jit(lambda a, b: compare.leaf_norms(
            jax.tree_util.tree_map(lambda u, v: u - v, a, b)))(
                mod.from_program(est.params, self.net), self.p0))
        self.p0 = None
        self.asked = compare.batches_asked(asked, self.batch)
        return {"loss": float(hist[-1]["loss"]),
                "grad_norms": seen["grad_norms"], "delta_norms": delta}

    # ------------------------------------------------------------ window --
    def window(self, seconds: float, trace_dir: Optional[str]
               ) -> Dict[str, Any]:
        import jax

        from analytics_zoo_tpu.core.profiling import TIMERS
        from analytics_zoo_tpu.observe.metrics import METRICS

        est = self.net.estimator

        def mark():
            return {"compiles": self.compiles.n,
                    "bad_steps": TIMERS.counts().get("robust/nan_steps", 0),
                    "registry": METRICS.snapshot()}

        clock = WindowClock(lambda: jax.block_until_ready(est.params), mark,
                            seconds, int(self.cell.traffic["open_after_steps"]),
                            trace_dir)
        asked: List[np.ndarray] = []
        try:
            self._fit(int(self.cell.traffic["rows"]), clock, asked, 10 ** 9)
        except WindowClosed:
            pass
        if clock.close is None:
            raise RuntimeError("fit returned before the window closed")
        a, b = clock.open, clock.close
        steps = b["iteration"] - a["iteration"]
        rows = np.concatenate(asked) if asked else np.zeros(0, np.int64)
        out = {
            "opened_after_s": a["t"] - self.t0,
            "seconds": b["t"] - a["t"],
            "steps": steps, "samples": steps * self.batch,
            "steps_before": a["iteration"] - clock.first + 1,
            "compiles_in_window": b["compiles"] - a["compiles"],
            "bad_steps": int(b["bad_steps"] - a["bad_steps"]),
            "registry": METRICS.delta(a["registry"]),
            "data_path": est.last_data_path,
            # an epoch asks for every row once: within one, none twice
            "rows_asked": int(len(rows)),
            "rows_asked_twice": int(len(rows) - len(np.unique(rows)))
            if len(rows) <= int(self.cell.traffic["rows"]) else None,
        }
        if clock.traced:
            out["traced"] = {"seconds": clock.traced["seconds"],
                             "steps": clock.traced["steps"],
                             "samples": clock.traced["steps"] * self.batch}
        return out

    def memory_peaks(self) -> Dict[str, int]:
        """Of the fullest chip.  The runtime keeps live buffers
        (``peak_bytes_in_use``: weights, optimizer state, uploaded batches)
        apart from what it reserves for a running program's temporaries
        (``peak_bytes_reserved``).  Each is a peak of its own; the chip
        held at least the larger of the two, and that is what is
        reported."""
        import jax

        stats = [d.memory_stats() or {}
                 for d in jax.devices()[:self.cell.chips]]
        print(f"[bench] memory_stats of device 0: {stats[0]}",
              file=sys.stderr)
        in_use = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        reserved = max(int(s.get("peak_bytes_reserved", 0)) for s in stats)
        return {"memory_peak_bytes": max(in_use, reserved),
                "peak_bytes_in_use": in_use,
                "peak_bytes_reserved": reserved}

    # --------------------------------------------------------- reference --
    def free(self) -> None:
        """Drop the program's state and everything else on the devices."""
        import jax

        self.net = None
        self.p0 = None
        for a in jax.live_arrays():
            a.delete()
        jax.clear_caches()

    def reference_inputs(self, asked: List[np.ndarray]):
        """The seed's weights again and the rows of ``asked``, placed as the
        cell places them: rows split over the chips, weights on each."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[:self.cell.chips]), ("rows",))
        split, whole = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())
        params = jax.device_put(self._weights(), whole)
        batches = []
        for idx in asked:
            idx = idx % len(self.pool_y)
            batches.append((tuple(jax.device_put(a[idx], split)
                                  for a in self.pool),
                            jax.device_put(self.pool_y[idx], split)))
        return params, batches, (whole, split)

    def reference(self, asked: Optional[List[np.ndarray]] = None,
                  rows: Optional[slice] = None, **kw) -> Dict[str, Any]:
        """The plain reference over the batches ``asked`` (by default the
        data set's first rows in their stored order)."""
        if asked is None:
            asked = [np.arange(k * self.batch, (k + 1) * self.batch)
                     for k in range(compare.STEPS)]
        if rows is not None:
            # a planted fault: only these rows of each batch count.  They
            # are repeated to the batch's size, which leaves every mean
            # over rows what it is over them alone, and the program compiled
            asked = [np.resize(idx[rows], len(idx)) for idx in asked]
        params, batches, placed = self.reference_inputs(asked)
        return compare.reference_steps(self.cell.reference, self.cfg, params,
                                       batches, placed=placed, **kw)


def run(cell, seed: int, seconds: float, trace: bool, t0: float
        ) -> Dict[str, Any]:
    """One run of a cell.  Returns what ``run.py`` prints."""
    import jax

    def said(what):
        print(f"[bench] {time.perf_counter() - t0:7.1f}s {what}",
              file=sys.stderr, flush=True)

    s = Session(cell, seed, t0)
    s.setup()
    said("rows, net and weights made")
    observed = s.first_steps()
    said("first three steps driven; the measured fit starts")
    trace_dir = None
    if trace:
        trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    win = s.window(seconds, trace_dir)
    said(f"window closed: {win['steps']} steps in {win['seconds']:.3f}s "
         f"after {win['steps_before']} to fill the pipeline")
    peaks = s.memory_peaks()
    kind = jax.devices()[0].device_kind
    device = {"platform": jax.devices()[0].platform, "kind": kind,
              "count": cell.chips, **peaks}
    run_info: Dict[str, Any] = {
        "window": win, "chips": cell.chips, "batch": s.batch,
        "memory_peak_bytes": peaks["memory_peak_bytes"],
        "work": cell.config_mod.work(cell.config, s.batch),
        "trace": None, "peaks": None,
    }
    breakdown = None
    if trace and win.get("traced"):
        run_info["peaks"] = peaks_for(kind) if device["platform"] != "cpu" \
            else None
        red = trace_reduce.reduce(
            trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir)),
            win["traced"]["seconds"])
        run_info["trace"] = red
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    asked = s.asked
    s.free()
    said("trace reduced, program freed; reference starts")
    want = s.reference(asked["batches"])
    said(f"reference done; {s.compiles.n} programs built in "
         f"{s.compiles.seconds:.0f}s, persistent cache {s.compiles.cache}")
    nums = compare.numbers(observed, want)
    ok, checks = compare.judge(nums, cell.limits)
    names = compare.leaf_names(jax.eval_shape(
        lambda k: cell.reference.init_params(k, cell.config),
        jax.random.PRNGKey(0)))
    # what is held exactly: limit 0
    checks["rows_asked_twice"] = [
        float(asked["twice"] + (win["rows_asked_twice"] or 0)), 0.0]
    checks["compiles_in_window"] = [float(win["compiles_in_window"]), 0.0]
    checks["data_path_differs"] = [
        float(win["data_path"] != cell.traffic["data_path"]), 0.0]
    ok = ok and all(v <= lim for v, lim in checks.values())
    notes = {
        "loss program/reference": [observed["loss"], want["losses"]],
        "worst leaf": {k: names[i] for k, i in nums["_worst_leaf"].items()},
        "leaves left out of the change": nums["_left_out"],
        "numbers with no limit": {k: v for k, v in nums.items()
                                  if not k.startswith("_")
                                  and k not in checks},
        "data path": win["data_path"],
    }
    return {"correct": ok, "checks": checks, "notes": notes,
            "attempted": win["steps"], "failed": win["bad_steps"],
            "device": device, "run": run_info, "breakdown": breakdown}
