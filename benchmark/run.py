"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints one JSON object as the last line of standard output.  Exits non-zero
and prints no result where JAX finds no TPU, or another number of chips
than the cell asks for.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # set-up is counted from process start

import argparse                 # noqa: E402
import os                       # noqa: E402
import resource                 # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one; every program is kept, however quick."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def require_chips(chips: int) -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        # the program's context takes every device JAX finds, so a cell
        # runs on a machine that holds its chips and no more
        print(f"benchmark: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(3)


def measure(cell, seed: int, seconds: float, trace: bool, t0: float):
    """Drive one run and turn it into the result line's parts: every metric
    is read by the file of its own name, and one that finds nothing to read
    is left out.  ``host_rss_peak_gib`` is the process's peak resident
    memory (Linux counts it in KiB): how near the run came to its machine."""
    out = cell.driver().run(cell, seed, seconds, trace, t0)
    out["host_rss_peak_gib"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    wanted, reader = ((cell.per_layer, cell.layer_metric_reader) if trace
                      else (cell.end_to_end, cell.end_to_end_reader))
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(out["run"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import analytics_zoo_tpu  # noqa: F401  (absent: no system to measure)
    from harness import result, spec

    cell = spec.load_cell(ROOT, args.workload)
    cache_dir = place_compile_cache()
    require_chips(cell.chips)
    print(f"[bench] cell {cell.name}: config {cell.config_name}, traffic "
          f"{cell.traffic_name}, chips {cell.chips}, seed {args.seed}, "
          f"cache {cache_dir}", file=sys.stderr)
    out = measure(cell, args.seed, args.seconds, bool(args.trace), _T0)
    win = out["run"]["window"]
    extra = {"window": {k: v for k, v in win.items() if k != "registry"},
             "notes": out["notes"],
             "host_rss_peak_gib": out["host_rss_peak_gib"]}
    if out["run"]["trace"]:
        red = out["run"]["trace"]
        extra["trace"] = {k: red.get(k) for k in
                          ("busy_s_each", "span_s", "steps", "step_span_s",
                           "step_program", "scope_seconds")}
        extra["trace"]["step_gap_samples"] = len(red.get("step_gaps_ms", []))
    result.print_checks(out["checks"], out["notes"])
    result.print_result(correct=out["correct"], attempted=out["attempted"],
                        failed=out["failed"], metrics=out["metrics"],
                        device=out["device"], breakdown=out["breakdown"],
                        extra=extra, checks=out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
