"""The one line a run prints last, and the numbers compared beside their
limits."""

from __future__ import annotations

import json
import sys
from typing import Any, Dict


def print_checks(checks: Dict[str, Any], notes: Dict[str, Any]) -> None:
    """Last lines on standard error: each number compared and its limit."""
    for k, v in notes.items():
        print(f"[check] {k}: {v}", file=sys.stderr)
    for name, (value, limit) in checks.items():
        verdict = "ok" if value <= limit else "OVER"
        print(f"[check] {name} = {value:.6g}  limit {limit:.6g}  {verdict}",
              file=sys.stderr)
    sys.stderr.flush()


def print_result(*, correct: bool, attempted: int, failed: int,
                 metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                 breakdown: Dict[str, Any] = None,
                 extra: Dict[str, Any] = None,
                 checks: Dict[str, Any] = None) -> None:
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["checks"] = checks or {}       # comes last, as the contract asks
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
