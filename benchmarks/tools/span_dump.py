#!/usr/bin/env python3
"""Run one cell traced and print where the job's thread spent the traced
window: the self time of every `flink_tpu.*` / `benchmark.*` span, the time
under no span, between which spans that dark time lies and which of the
profiler's own events fall into it, and, dispatch by dispatch, how long each
cycle took and which stage the slower half of the cycles spent it in;
beside them the program's own `stages` table and `link` counters. What
one reads before moving a stage site or adding one; no part of a benchmark
run. `--cell-file` runs a test-only cell (`tests/cells/<name>.json`: a
configuration and a traffic mix of the benchmark and what it changes in
them), `--trace-seconds` traces more than the window's last 4 s.

  python3 benchmarks/tools/span_dump.py --workload ysb_catchup --seed 7 --seconds 12
  python3 benchmarks/tools/span_dump.py --seconds 25 --trace-seconds 15 \
      --cell-file benchmarks/tests/cells/keys64k_mesh4_catchup.json
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


def span_summary(trace, top: int = 12) -> Dict:
    from benchmarks import span_lib
    from benchmarks import trace_reduce as tr

    lo, hi = tr.window_of(trace)
    ctx = {"trace": trace, "trace_window": (lo, hi)}
    times = span_lib.self_times(ctx)
    if times is None:
        return {"spans": None}
    thread = tr.job_thread(trace)
    ours = [e for e in thread
            if e[0].startswith((span_lib.PROGRAM, span_lib.BENCHMARK))]
    segs = [e for e in tr.innermost_segments(ours) if e[2] > lo and e[1] < hi]
    between: Dict[str, List[float]] = {}
    for (n1, _a1, b1), (n2, a2, _b2) in zip(segs, segs[1:]):
        if a2 > b1:
            rec = between.setdefault(f"{n1} -> {n2}", [0, 0.0])
            rec[0] += 1
            rec[1] += (a2 - b1) / 1e6
    dark = tr.subtract([(lo, hi)], tr.union((a, b) for _n, a, b in ours))
    theirs = [e for e in thread
              if not e[0].startswith((span_lib.PROGRAM, span_lib.BENCHMARK))]
    in_dark: Dict[str, float] = {}
    j = 0
    for name, a, b in tr.innermost_segments(theirs):
        while j < len(dark) and dark[j][1] <= a:
            j += 1
        k = j
        while k < len(dark) and dark[k][0] < b:
            part = min(b, dark[k][1]) - max(a, dark[k][0])
            if part > 0:
                in_dark[name[:60]] = in_dark.get(name[:60], 0.0) + part / 1e6
            k += 1

    def top_of(d, key):
        return sorted(d.items(), key=key)[:top]

    # dispatch by dispatch: a cycle runs from one dispatch span to the next
    starts = sorted(a for n, a, _b in ours
                    if n == span_lib.PROGRAM + "dispatch" and lo <= a < hi)
    cycles: List[Dict[str, float]] = [{} for _ in starts[:-1]]
    i = 0
    for name, a, b in segs:
        while i + 1 < len(starts) - 1 and a >= starts[i + 1]:
            i += 1
        if cycles and starts[i] <= a < starts[i + 1]:
            cycles[i][name] = cycles[i].get(name, 0.0) + (b - a) / 1e6
    lengths = [(starts[k + 1] - starts[k]) / 1e6 for k in range(len(cycles))]
    halves = {}
    if len(cycles) >= 4:
        cut = sorted(lengths)[len(lengths) // 2]
        for label, pick in (("faster_half", lambda v: v < cut),
                            ("slower_half", lambda v: v >= cut)):
            chosen = [c for c, v in zip(cycles, lengths) if pick(v)]
            names = {n for c in chosen for n in c}
            halves[label] = {
                "cycles": len(chosen),
                "mean_cycle_ms": round(sum(
                    v for v in lengths if pick(v)) / max(len(chosen), 1), 3),
                "mean_self_ms": {n: round(sum(c.get(n, 0.0) for c in chosen)
                                          / len(chosen), 3)
                                 for n in sorted(names)}}

    return {
        "window_ms": (hi - lo) / 1e6,
        "self_pct": {k: round(100.0 * v / (hi - lo), 3) for k, v in
                     sorted(times.items(), key=lambda kv: -kv[1])},
        "dark_pct": round(span_lib.dark_pct(ctx), 3),
        "dark_between_ms": [[k, c, round(ms, 3)] for k, (c, ms) in
                            top_of(between, lambda kv: -kv[1][1])],
        "profiler_events_in_dark_ms": [[k, round(ms, 3)] for k, ms in
                                       top_of(in_dark, lambda kv: -kv[1])],
        "cycle_ms": [round(v, 1) for v in lengths],
        "cycles": halves,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--cell-file")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--trace-seconds", type=float, default=None)
    args = ap.parse_args()
    if bool(args.workload) == bool(args.cell_file):
        ap.error("give --workload or --cell-file")

    from benchmarks import harness
    from flink_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    spec = None
    if args.cell_file:
        with open(args.cell_file) as f:
            cell_file = json.load(f)
        cell = cell_file["cell"]
        spec = {"cell": cell, "end_to_end": [], "per_layer": [],
                "cfg": dict(harness.load_json(
                    "configs", cell["config"] + ".json"),
                    **cell_file["cfg_update"]),
                "traffic": dict(harness.load_json(
                    "traffic", cell["traffic"] + ".json"),
                    **cell_file["traffic_update"])}
    if args.trace_seconds:
        harness.TRACE_SECONDS = args.trace_seconds
    name = args.workload or spec["cell"]["name"]
    # the program's own stage table and link counters: the harness hands on
    # a fixed set of keys, so they are taken where it reads the rest
    program: Dict = {}
    hand_on = harness.program_counters

    def keep(device_metrics: Dict) -> Dict:
        program["stages"] = device_metrics.get("stages")
        program["link"] = {uid: op["link"] for uid, op in
                           device_metrics.get("operators", {}).items()
                           if "link" in op}
        return hand_on(device_metrics)

    harness.program_counters = keep
    out = harness.run_cell(name, args.seed, args.seconds, True, spec=spec)
    detail = out.pop("_detail")
    program["records_in"] = detail["counters"]["records_in"]
    print(json.dumps({"workload": name, "correct": out["correct"],
                      "program": program,
                      "e2e": detail["e2e"], "metrics": out["metrics"],
                      "idle_gaps": out["breakdown"]["idle_gaps"],
                      "device": out["device"],
                      "span_summary": span_summary(detail["trace"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
