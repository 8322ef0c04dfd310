"""Readings that the limits of a cell's compared numbers are set from, taken
on the chip at the cell's own size, in one process.

    python3 benchmark/limits.py --workload <cell> --seeds 12 --control-seeds 3 --out <file>

For every seed the program's first three steps are driven through ``fit`` as
a run drives them (one shuffled three-step epoch) and held against the plain
reference on the batches the data tier asked for: the largest reading of each
number over the seeds is its lower reading; every run of ``run.py`` prints
the same numbers for its seed, and they count too.  On the first few seeds
the reference is then put in the program's place again: computed in fp8
(the control: every operand and every activation a layer hands on rounded to
the precision below the configuration's bfloat16), with half of every batch
left out, and, for a cell on several chips, with only
the first chip's share of every batch (the exchange left out).  The
smallest reading of each is an upper reading.  A state left unchanged reads
1 by the measure and needs no run.  ``PERF.md`` records the readings and
the limits set from them; the benchmark's own runs never run this file.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--witness", type=int, default=0,
                    help="1: also the reference in the configuration's own "
                         "compute type, a second witness of what it costs")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run as bench_run
    from harness import compare, spec
    from harness.drivers.fit import Session

    cell = spec.load_cell(ROOT, args.workload)
    bench_run.place_compile_cache()
    bench_run.require_chips(cell.chips)
    # large seeds and small, as the driver's are
    seeds = [args.first_seed + 7919 * i + (2 ** 31 if i % 3 == 2 else 0)
             for i in range(args.seeds)]
    t0 = time.perf_counter()
    observed, asked = {}, {}
    for seed in seeds:
        s = Session(cell, seed, t0)
        s.setup(pool_rows=compare.STEPS * s.batch)
        observed[seed] = s.first_steps()
        asked[seed] = s.asked["batches"]
        s.free()
        print(f"[limits] program seed {seed}: loss "
              f"{observed[seed]['loss']}  ({time.perf_counter() - t0:.0f}s)",
              file=sys.stderr, flush=True)

    def strip(nums):
        return {k: v for k, v in nums.items() if not k.startswith("_")}

    rows = {"half_batch": lambda b: slice(0, b // 2)}
    if cell.chips > 1:
        rows["exchange_left_out"] = lambda b: slice(0, b // cell.chips)
    doc = {"cell": cell.name, "seeds": seeds, "program": {}, "control": {},
           "witness": {}, "faults": {k: {} for k in rows}}
    for i, seed in enumerate(seeds):
        s = Session(cell, seed, t0)
        s.load_rows(compare.STEPS * s.batch)
        want = s.reference(asked[seed])
        doc["program"][str(seed)] = strip(compare.numbers(observed[seed],
                                                          want))
        if i < args.control_seeds:
            doc["control"][str(seed)] = strip(compare.numbers(
                s.reference(asked[seed], quant=compare.fp8_round,
                            act=compare.fp8_round), want))
            if args.witness:
                doc["witness"][str(seed)] = strip(compare.numbers(
                    s.reference(asked[seed], dtype=cell.config["deployment"][
                        "compute_dtype"]), want))
            for name, pick in rows.items():
                doc["faults"][name][str(seed)] = strip(compare.numbers(
                    s.reference(asked[seed], rows=pick(s.batch)), want))
        s.free()
        print(f"[limits] reference seed {seed}: "
              f"{doc['program'][str(seed)]}  ({time.perf_counter() - t0:.0f}s)",
              file=sys.stderr, flush=True)

    def over(table, fn):
        keys = next(iter(table.values())).keys()
        return {k: fn(r[k] for r in table.values()) for k in keys}

    doc["lower"] = over(doc["program"], max)
    doc["upper_control"] = over(doc["control"], min)
    doc["witness_max"] = over(doc["witness"], max) if doc["witness"] else {}
    doc["upper_faults"] = {k: over(v, min) for k, v in doc["faults"].items()}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({k: doc[k] for k in
                      ("lower", "upper_control", "witness_max",
                       "upper_faults", "program")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
