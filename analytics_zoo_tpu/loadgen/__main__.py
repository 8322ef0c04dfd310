"""Report generator: run every load leg and write the SLO report.

::

    JAX_PLATFORMS=cpu python -m analytics_zoo_tpu.loadgen \
        --out <report>.json [--workdir /tmp/loadgen] [--quick]

The report's schema and the invariants its legs hold (with the test
that asserts each) are described in docs/LOADGEN.md.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True,
                   help="where to write the report")
    p.add_argument("--workdir", default=None,
                   help="scratch dir for the kill leg's spool/cache "
                        "(a fresh tempdir when omitted)")
    p.add_argument("--quick", action="store_true",
                   help="halved durations for smoke runs")
    args = p.parse_args(argv)

    from analytics_zoo_tpu.loadgen.harness import default_report
    from analytics_zoo_tpu.loadgen.slo import write_artifact

    workdir = args.workdir or tempfile.mkdtemp(prefix="loadgen-")
    t0 = time.monotonic()
    report = default_report(workdir, quick=args.quick)
    report["run_metadata"]["wall_s"] = round(time.monotonic() - t0, 2)
    write_artifact(args.out, report)
    print(f"wrote {os.path.abspath(args.out)} "
          f"({report['run_metadata']['wall_s']}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
