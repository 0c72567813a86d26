#!/usr/bin/env python3
"""Run a list of benchmark runs, each a process of its own, and record them.

  python3 benchmarks/tools/session.py --tag T ysb_catchup:101:10:0 [...]

Each item is `workload:seed:seconds:trace[:extra-flag...]`. The parent never
touches JAX (a chip belongs to one process). Every run's result line goes to
`chiprun_out/runs.jsonl` with its exit code and wall time, and its whole
output to `chiprun_out/<tag>/<n>.log`. This is how the chip runs recorded in
`benchmarks/runs/chip_runs.jsonl` were made.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--command", default="benchmarks/run.py",
                    help="the script to run (sweep.py takes the same items)")
    ap.add_argument("items", nargs="+")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    for n, item in enumerate(args.items):
        workload, seed, seconds, trace, *extra = item.split(":")
        cmd = [sys.executable, os.path.join(ROOT, args.command),
               "--workload", workload, "--seed", seed, "--seconds", seconds,
               "--trace", trace] + [f"--{e}" for e in extra]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        with open(os.path.join(out_dir, f"{n:02d}.log"), "w") as f:
            f.write(" ".join(cmd) + "\n--- stdout\n" + proc.stdout
                    + "\n--- stderr\n" + proc.stderr[-20000:])
        lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        result = None
        if lines:
            try:
                result = json.loads(lines[-1])
            except ValueError:
                result = None
        rec = {"tag": args.tag, "workload": workload, "seed": int(seed),
               "seconds": float(seconds), "trace": int(trace), "extra": extra,
               "rc": proc.returncode, "wall_s": round(wall, 2), "result": result}
        with open(os.path.join(ROOT, "chiprun_out", "runs.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        brief = {}
        if result:
            brief = {k: v["value"] for k, v in result.get("metrics", {}).items()}
            brief["correct"] = result.get("correct")
        print(f"[{args.tag} {n}] {item} rc={proc.returncode} wall={wall:.1f}s "
              f"{json.dumps(brief)}", flush=True)
        if proc.returncode:
            print(proc.stderr[-1500:], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
