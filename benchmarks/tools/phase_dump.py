#!/usr/bin/env python3
"""Run one cell traced and print its window program's phase table: ms per
dispatch under each `jax.named_scope` phase, the nested rows (`prologue/t1.map`,
`ingest/hist`), each phase's longest ops with the `op_name` the compiler kept
for them, and what the time under no scope is made of. What one reads before
guessing a mechanism from a device op's name; no part of a benchmark run.

  python3 benchmarks/tools/phase_dump.py --workload q5_hot_items_catchup --seed 7 --seconds 12
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("PYTHONHASHSEED", "0")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--json", action="store_true",
                    help="one JSON object in place of the text table")
    args = ap.parse_args()

    from benchmarks import harness, layer_lib
    from flink_tpu.metrics import device_phases
    from flink_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    reduce_trace = harness.traced_metrics
    kept = tempfile.mkdtemp(prefix="phase_dump.")
    seen = {}

    def keep_capture(ctx, tracer, per_layer):
        # the harness removes the capture once its readers have run
        shutil.copy(tracer.path, kept)
        seen["ctx"] = ctx
        return reduce_trace(ctx, tracer, per_layer)

    harness.traced_metrics = keep_capture
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, True)
        ctx = seen["ctx"]
        plane = layer_lib.fullest(ctx)
        t0 = time.perf_counter()
        table = device_phases.phase_table(
            kept, programs=ctx["cfg"]["trace_modules"], planes=[plane],
            window=ctx["trace_window"])
        report = {"phase_table_s": time.perf_counter() - t0,
                  "table": device_phases.per_execution(table)}
    finally:
        shutil.rmtree(kept, ignore_errors=True)
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    if args.json:
        print(json.dumps({"workload": args.workload, "correct": out["correct"],
                          "metrics": metrics, **report}))
        return 0
    print(device_phases.render(report["table"], ops=True))
    print(f"superscan_ms {metrics.get('superscan_ms.catchup')}; phase_table "
          f"took {report['phase_table_s']:.2f} s; correct {out['correct']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
