"""The run's last line, in the form the benchmark's contract fixes."""

from __future__ import annotations

import json
import sys
from typing import Any, Dict

from chipbench import trace as TR
from chipbench.readers import ops, window


def line(run, metrics: Dict[str, Dict], checks: Dict[str, Dict],
         device: Dict[str, Any]) -> Dict[str, Any]:
    """``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
    ``breakdown`` when traced, and ``checks`` last."""
    out: Dict[str, Any] = {
        "correct": all(v["ok"] for v in checks.values()),
        "attempted": len(run.reqs),
        "failed": int(checks.get("bad_requests", {}).get("value", 0)),
        "metrics": metrics,
        "device": dict(device),
    }
    w = window(run)
    if w is not None:
        out["device"]["busy_s"] = TR.busy_ns(ops(run), *w) * 1e-9
        out["device"]["window_s"] = (w[1] - w[0]) * 1e-9
        out["breakdown"] = TR.breakdown(run.trace, run.plane, *w)
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    return out


def emit(out: Dict[str, Any], checks: Dict[str, Dict]) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    sys.stdout.flush()
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
