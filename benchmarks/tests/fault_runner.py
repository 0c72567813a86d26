#!/usr/bin/env python3
"""Drive the rest of a run with the timed path broken underneath.

  python3 benchmarks/tests/fault_runner.py <fault> <workload | tests/cells/<name>.json>

A workload is a cell of BENCHMARK.json, or a test-only cell: a file under
`tests/cells/` that names a configuration and a traffic mix of the benchmark
and what it changes in them (a mesh, an open loop), so that the parts of the
harness no shipped cell drives today stay proven.

Skips the harness's look for a chip (CPU backend, rehearsal sizes), plants
one fault in the program, runs the cell and prints the result object. The
faults a cell of this benchmark can have:

  none             nothing broken (the run must be correct)
  answer_altered   one emitted count is changed where it is produced
  batch_dropped    the window step never sees one batch of the stream
  exchange_left_out  the keyBy exchange between chips returns what it was given
  control          not a fault of the program: the reference with a batch
                   replayed, put in the program's place
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plant(fault: str) -> None:
    if fault in ("none", "control"):
        return
    if fault == "answer_altered":
        from flink_tpu.runtime import fused_window_operator as fwo

        def altered(orig):
            state = {"done": False}

            def emit(self, window, counts, fields, *rest):
                import numpy as np

                counts = np.array(counts)
                live = np.flatnonzero(counts > 0)
                if live.size and not state["done"]:
                    state["done"] = True
                    counts[live[0]] += 1
                return orig(self, window, counts, fields, *rest)
            return emit

        fwo.FusedWindowOperator._emit_dense_rows = altered(
            fwo.FusedWindowOperator._emit_dense_rows)
        fwo.FusedWindowOperator._emit_keydict_rows = altered(
            fwo.FusedWindowOperator._emit_keydict_rows)
    elif fault == "batch_dropped":
        from flink_tpu.runtime import executor as ex

        def dropping(orig):
            seen = {"n": 0}

            def on_batch(self, values, timestamps):
                seen["n"] += 1
                if seen["n"] == 50:
                    return None
                return orig(self, values, timestamps)
            return on_batch

        ex.DeviceChainRunner.on_batch = dropping(ex.DeviceChainRunner.on_batch)
        ex.WindowStepRunner.on_batch = dropping(ex.WindowStepRunner.on_batch)
    elif fault == "exchange_left_out":
        import jax

        jax.lax.all_to_all = lambda x, *a, **k: x
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def test_cell(path: str):
    from benchmarks import harness

    with open(path) as f:
        spec = json.load(f)
    cell = spec["cell"]
    cfg = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    cfg.update(spec["cfg_update"])
    traffic.update(spec["traffic_update"])
    return {"cell": cell, "cfg": cfg, "traffic": traffic,
            "end_to_end": [], "per_layer": []}


def main() -> int:
    fault, workload = sys.argv[1], sys.argv[2]
    plant(fault)
    from benchmarks import harness

    spec = test_cell(workload) if workload.endswith(".json") else None
    out = harness.run_cell(workload, 20230923, 2.0, False, rehearse=True,
                           control="replay_batch" if fault == "control" else None,
                           spec=spec, log=lambda *_a: None)
    out["e2e"] = out.pop("_detail")["e2e"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
