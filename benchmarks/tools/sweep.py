#!/usr/bin/env python3
"""The sweep that finds the knee of a configuration, once.

  python3 benchmarks/tools/sweep.py --config ysb_keys64k --traffic catchup \
      --rates 2000000,3000000,4000000 --seconds 12 --seed 1

One process; for each fixed rate one open-loop run of the configuration (not
a cell of BENCHMARK.json: the traffic file's rate is replaced). A rate is
held when the reader's lateness does not grow: the median lag of the last
third of the window is under `--held-ms` and no more than twice that of the
first third. `--rates 0` runs the configuration closed-loop (catch-up) and
reports what it sustains. Results go to stdout and chiprun_out/sweep.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("PYTHONHASHSEED", "0")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--held-ms", type=float, default=250.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    from benchmarks import harness

    cfg = harness.load_json("configs", args.config + ".json")
    base = harness.load_json("traffic", args.traffic + ".json")
    from flink_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for n, rate in enumerate(int(float(r)) for r in args.rates.split(",")):
        traffic = dict(base)
        if rate:
            traffic.update(loop="open", rate_events_per_s=rate,
                           density_events_per_event_s=rate)
        else:
            traffic.update(loop="closed", rate_events_per_s=None,
                           density_events_per_event_s=1_000_000)
        spec = {"cell": {"name": f"sweep_{args.config}", "config": args.config,
                         "traffic": args.traffic, "chips": 1},
                "cfg": cfg, "traffic": traffic, "end_to_end": [], "per_layer": []}
        out = harness.run_cell(spec["cell"]["name"], args.seed + n, args.seconds,
                               False, rehearse=args.rehearse_cpu, spec=spec)
        d = out["_detail"]
        lag = d["lag_s"]
        third = max(len(lag) // 3, 1)
        rec = {"config": args.config, "rate": rate, "seconds": args.seconds,
               "seed": args.seed + n, "correct": out["correct"],
               "e2e": d["e2e"], "window_s": d["window_s"],
               "drain_s": d["drain_s"],
               "compiles_in_window": d["compiles_in_window"]}
        if lag:
            first = statistics.median(lag[:third]) * 1000
            last = statistics.median(lag[-third:]) * 1000
            rec.update(lag_first_third_ms=first, lag_last_third_ms=last,
                       lag_max_ms=max(lag) * 1000,
                       held=bool(last < args.held_ms
                                 and last <= max(2 * first, 20.0)))
        print(json.dumps(rec), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "sweep.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
