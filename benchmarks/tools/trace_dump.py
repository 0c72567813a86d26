#!/usr/bin/env python3
"""Run one cell traced and print what its trace holds: planes, lines and the
names that took most time in each. What one looks at by hand before writing
a per-layer reader against a trace; no part of a benchmark run.

  python3 benchmarks/tools/trace_dump.py --workload ysb_catchup --seed 7 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("PYTHONHASHSEED", "0")


def trace_summary(trace, top: int = 12) -> Dict:
    """Planes, lines and the names that took most time in each: what one
    looks at by hand before writing a reader against a trace."""
    out = {}
    for plane, lines in trace.planes.items():
        for line, evs in lines.items():
            agg: Dict[str, List[float]] = {}
            for name, a, b in evs:
                rec = agg.setdefault(name[:80], [0, 0.0])
                rec[0] += 1
                rec[1] += (b - a) / 1e6
            names = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
            out[f"{plane} | {line}"] = {
                "events": len(evs),
                "top_ms": [[n, c, round(ms, 3)] for n, (c, ms) in names]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10)
    args = ap.parse_args()

    from benchmarks import harness
    from flink_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    out = harness.run_cell(args.workload, args.seed, args.seconds, True)
    print(json.dumps({"trace_summary": trace_summary(out["_detail"]["trace"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
