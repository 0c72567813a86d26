#!/usr/bin/env python3
"""Fold `chiprun_out/runs.jsonl` (written by session.py on the chip) and
`chiprun_out/sweep.jsonl` into `benchmarks/runs/chip_runs.jsonl`: one compact
line per chip run made for this benchmark (cell, seed, seconds, every
end-to-end number or per-layer number it printed). Idempotent."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "benchmarks", "runs", "chip_runs.jsonl")


def main() -> None:
    seen = set()
    lines = []
    if os.path.exists(OUT):
        for ln in open(OUT):
            seen.add(ln.strip())
            lines.append(ln.strip())
    for name in ("runs.jsonl", "sweep.jsonl"):
        path = os.path.join(ROOT, "chiprun_out", name)
        if not os.path.exists(path):
            continue
        for ln in open(path):
            rec = json.loads(ln)
            res = rec.pop("result", None)
            if res:
                rec["correct"] = res.get("correct")
                rec["metrics"] = {k: v["value"]
                                  for k, v in res.get("metrics", {}).items()}
                dev = res.get("device", {})
                rec["device"] = {k: dev.get(k) for k in (
                    "kind", "count", "memory_peak_bytes", "busy_s", "window_s")
                    if k in dev}
                bad = {k: c for k, c in res.get("compared", {}).items()
                       if not res.get("correct")}
                if bad:
                    rec["compared"] = bad
            out = json.dumps(rec, sort_keys=True)
            if out not in seen:
                seen.add(out)
                lines.append(out)
    with open(OUT, "w") as f:
        f.write("\n".join(lines) + ("\n" if lines else ""))
    print(f"{len(lines)} runs recorded in {OUT}")


if __name__ == "__main__":
    main()
