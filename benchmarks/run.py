#!/usr/bin/env python3
"""Run one cell of the benchmark once.

  python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one touch of JAX: builds the cell's job, warms it, measures for
`--seconds`, compares every result row with the plain reference, and prints
one JSON object as the last line of stdout. Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for, or when
the checkout does not hold the program. `--rehearse-cpu` runs the same path
at tiny sizes on the CPU backend (four virtual devices) to prove the script;
it says so and never prints the result line or a device metric.
"""

from __future__ import annotations

import os
import sys
import time

_T_PROCESS = float(os.environ.get("FLINK_BENCH_T0") or time.time())

if os.environ.get("PYTHONHASHSEED") != "0":
    # a fixed hash seed, set before Python starts: re-exec once
    env = dict(os.environ, PYTHONHASHSEED="0", FLINK_BENCH_T0=repr(_T_PROCESS))
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

import argparse
import json

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", default=None, choices=("replay_batch",),
                    help="put the reference with one guarantee broken in the "
                         "program's place; the run must come out not correct")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "flink_tpu")):
        print("benchmark: this checkout does not hold the program "
              "(flink_tpu/); nothing was run", file=sys.stderr)
        return 3
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    t_import = time.time()
    import jax

    from benchmarks import harness

    spec = harness.load_cell(args.workload)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        run_seconds = json.load(f)["run_seconds"]
    seconds = args.seconds if args.seconds is not None else run_seconds
    t_devices = time.time()
    devs = jax.devices()
    t_ready = time.time()
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU backend at tiny sizes: proves the script "
              "runs, says nothing about the chip")
        seconds = min(seconds, 3.0)
    elif devs[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 4
    if len(devs) < spec["cell"]["chips"]:
        print(f"benchmark: the cell needs {spec['cell']['chips']} chips, JAX "
              f"sees {len(devs)}; nothing was run", file=sys.stderr)
        return 4

    from flink_tpu.utils import native_bridge
    from flink_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    if native_bridge.get_lib() is None:
        print(f"benchmark: the native library did not build: "
              f"{native_bridge.load_error()}", file=sys.stderr)
        return 1
    print(f"device: {devs[0].platform} {devs[0].device_kind!r} x{len(devs)}; "
          f"compile cache {cache_dir}; set-up so far "
          f"{time.time() - _T_PROCESS:.2f} s (python {t_import - _T_PROCESS:.2f}, "
          f"imports {t_devices - t_import:.2f}, jax.devices() "
          f"{t_ready - t_devices:.2f}, native library "
          f"{time.time() - t_ready:.2f})")

    out = harness.run_cell(
        args.workload, args.seed, seconds, bool(args.trace),
        rehearse=args.rehearse_cpu, t_process=_T_PROCESS, control=args.control)
    detail = out.pop("_detail")
    print(json.dumps({"per_second": detail["per_second"],
                      "window_s": detail["window_s"],
                      "drain_s": detail["drain_s"],
                      "events": detail["events"],
                      "rows": detail["rows"],
                      "reference_s": detail["reference_s"],
                      "compiles_in_window": detail["compiles_in_window"],
                      "programs": detail["counters"]["programs"]}))
    harness.print_compared(out)
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "correct": out["correct"],
                          "compared": out["compared"],
                          "numbers_not_of_a_chip": detail["e2e"]}))
        return 0 if out["correct"] or args.control else 1
    compared = out.pop("compared")
    out["compared"] = compared          # comes last in the line
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
