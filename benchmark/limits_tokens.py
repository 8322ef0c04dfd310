"""``limits.py`` for a cell of the ``fit_tokens`` driver (``limits.py`` is
bound to ``drivers/fit.py``'s session): the readings that the limits of a
token-level cell's compared numbers are set from, taken on the chip at the
cell's own size, in one process.

    python3 benchmark/limits_tokens.py --workload <cell> --seeds 6 --control-seeds 2 --out <file>

As ``limits.py``: for every seed the program's first three steps through
``fit`` against the plain reference on the batches the data tier asked for
(the largest reading of each number is its lower reading; every run of
``run.py`` prints the same numbers for its seed, and they count too); on the
first few seeds the reference put in the program's place again, computed in
fp8 (the control) and with half of every batch left out.  And two readings
of the mechanism, which a looped model's limits have to tell from the model:
the reference with one pass fewer (``total_ut_steps`` - 1), and the reference
with only the last pass's gradient reaching the layers (the reference's
``planted_fault``).  The smallest reading of each is an upper reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run as bench_run
    from harness import compare, spec
    from harness.drivers.fit_tokens import Session

    cell = spec.load_cell(ROOT, args.workload)
    bench_run.place_compile_cache()
    bench_run.require_chips(cell.chips)
    # large seeds and small, as the driver's are
    seeds = [args.first_seed + 7919 * i + (2 ** 31 if i % 3 == 2 else 0)
             for i in range(args.seeds)]
    t0 = time.perf_counter()

    def said(what):
        print(f"[limits] {time.perf_counter() - t0:6.0f}s {what}",
              file=sys.stderr, flush=True)

    def strip(nums):
        return {k: v for k, v in nums.items() if not k.startswith("_")}

    cfg = cell.config
    faults = {
        "control_fp8": dict(quant=compare.fp8_round, act=compare.fp8_round),
        "half_batch": dict(rows=slice(0, max(1, int(
            cfg["deployment"]["batch_per_chip"]) * cell.chips // 2))),
        "one_pass_fewer": dict(config=dict(
            cfg, total_ut_steps=cfg["total_ut_steps"] - 1)),
        "last_pass_gradient_only": dict(config=dict(
            cfg, planted_fault="last_pass_gradient_only")),
    }
    doc = {"cell": cell.name, "seeds": seeds, "program": {},
           "faults": {k: {} for k in faults}}
    for i, seed in enumerate(seeds):
        s = Session(cell, seed, t0)
        s.setup(pool_rows=compare.STEPS * s.batch)
        observed = s.first_steps()
        asked = s.asked["batches"]
        s.free()
        said(f"program seed {seed}: loss {observed['loss']}")
        want = s.reference(asked)
        doc["program"][str(seed)] = strip(compare.numbers(observed, want))
        said(f"reference seed {seed}: {doc['program'][str(seed)]}")
        if i < args.control_seeds:
            for name, kw in faults.items():
                doc["faults"][name][str(seed)] = strip(compare.numbers(
                    s.reference(asked, **kw), want))
                said(f"{name} seed {seed}: {doc['faults'][name][str(seed)]}")
        s.free()

    def over(table, fn):
        keys = next(iter(table.values())).keys()
        return {k: fn(r[k] for r in table.values()) for k in keys}

    doc["lower"] = over(doc["program"], max)
    doc["upper"] = {k: over(v, min) for k, v in doc["faults"].items() if v}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({k: doc[k] for k in ("lower", "upper", "program")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
