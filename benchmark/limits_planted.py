"""``limits_tokens.py`` for a token-level cell whose configuration names its
own planted faults (``limits_tokens.py`` names ``ouro-2.6b``'s): the readings
that the limits of the cell's compared numbers are set from, taken on the
chip at the cell's own size, in one process.

    python3 benchmark/limits_planted.py --workload <cell> --seeds 8 --control-seeds 2 --out <file>

For every seed the program's first three steps through ``fit`` against the
plain reference on the batches the data tier asked for (the largest reading
of each number is its lower reading; every run of ``run.py`` prints the same
numbers for its seed, and they count too); on the first few seeds the
reference put in the program's place again: computed in fp8 (the control),
with half of every batch left out, and once with each fault the reference
can plant (``PLANTED_FAULTS`` of the reference's module: values of
``cfg["planted_fault"]``).  The smallest reading of each is an upper reading.
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
    ap.add_argument("--seeds", type=int, default=8)
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
        **{name: dict(config=dict(cfg, planted_fault=name))
           for name in cell.reference.PLANTED_FAULTS},
    }
    doc = {"cell": cell.name, "seeds": seeds, "program": {},
           "faults": {k: {} for k in faults}}

    def write():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)

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
        write()                 # what was read so far outlives a cut run

    def over(table, fn):
        keys = next(iter(table.values())).keys()
        return {k: fn(r[k] for r in table.values()) for k in keys}

    doc["lower"] = over(doc["program"], max)
    doc["upper"] = {k: over(v, min) for k, v in doc["faults"].items() if v}
    write()
    print(json.dumps({k: doc[k] for k in ("lower", "upper", "program")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
