"""One run of one cell: build the job, warm it, measure, check, report.

Driven by data: the cell's entry in `BENCHMARK.json` names a configuration
(`configs/<config>.json`) and a traffic mix (`traffic/<traffic>.json`); the
configuration names its job builder (`jobs/<job>.py`) and its plain reference
(`references/<module>.py`); each per-layer metric the cell lists is read by
`layer_metrics/<metric>.py`. Nothing here knows a
cell, a configuration or a metric by name.

The entry the window drives is `StreamExecutionEnvironment.execute()` at the
`Configuration()` a user gets plus only the options the configuration's file
states. From the program the harness takes the system under test, its
counters (`result.metrics`) and its kernel names.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import reader as rd
from benchmarks import reference as ref
from benchmarks.stream import build_cycle

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0          # the traced part of a --trace 1 window
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell, "cfg": cfg, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


REHEARSAL = {"cycle_ms": 2000, "density_cap": 200_000, "warm_batches": 40,
             "options": {"execution.step.batch-size": 4096}}


def rehearsal_traffic(traffic: Dict) -> Dict:
    """Tiny sizes for the CPU rehearsal: same shape of traffic, a shorter
    cycle and a thinner stream. Says nothing about the chip."""
    t = dict(traffic)
    t["density_events_per_event_s"] = min(
        t["density_events_per_event_s"], REHEARSAL["density_cap"])
    if t.get("rate_events_per_s"):
        t["rate_events_per_s"] = min(t["rate_events_per_s"], 60_000)
        t["density_events_per_event_s"] = t["rate_events_per_s"]
    t["cycle_ms"] = REHEARSAL["cycle_ms"]
    t["warm"] = dict(t["warm"], min_batches=REHEARSAL["warm_batches"])
    t["drain_timeout_s"] = 30
    return t


def build_job(cfg: Dict, state: rd.RunState, tables: Dict, options: Dict):
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.config import Configuration

    config = Configuration()
    for key, value in options.items():
        config.set_string(key, value)
    env = StreamExecutionEnvironment.get_execution_environment(config)
    load_module("jobs", cfg["job"]).build(
        env, rd.make_source(state), rd.make_sink(state), cfg, tables)
    return env


def batch_size_of(options: Dict) -> int:
    from flink_tpu.config import Configuration, ExecutionOptions

    config = Configuration()
    for key, value in options.items():
        config.set_string(key, value)
    return int(config.get(ExecutionOptions.BATCH_SIZE))


class Tracer:
    """The traced part of a `--trace 1` window: its last TRACE_SECONDS and the
    drain that closes it, so that stopping the profiler (seconds of host
    work) falls after the window. Host events at level 2, no Python tracer:
    the trace stays small enough to write and read back inside the run's
    time limit."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.start_after = max(0.0, seconds - TRACE_SECONDS)
        self.wall = [None, None]      # perf_counter of the traced window
        self._window = None
        self._tracing = False
        self.path = None

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def tick(self, since_start: float) -> None:
        if self._tracing or self.wall[0] is not None \
                or since_start < self.start_after:
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._tracing = True
        self._window = jax.profiler.TraceAnnotation("benchmark.traced_window")
        self._window.__enter__()
        self.wall[0] = time.perf_counter()

    def end_window(self) -> None:
        if self._window is not None:
            self.wall[1] = time.perf_counter()
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self) -> None:
        import glob

        import jax

        if not self._tracing:
            return
        self.end_window()
        self._tracing = False
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
        self.path = max(found, key=os.path.getmtime) if found else None


def device_block(devs) -> Dict:
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:   # noqa: BLE001 — a backend without the call
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def compile_events_listener():
    """(wall perf_counter, seconds) of every backend compile, and every load
    from the persistent cache, that JAX makes in this process — the
    benchmark's own count, beside the program's."""
    import jax.monitoring

    events: List[tuple] = []

    def on_duration(name, seconds, **_kw):
        if name.endswith(("backend_compile_duration",
                          "cache_retrieval_time_sec")):
            events.append((time.perf_counter(), float(seconds)))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return events


def program_counters(device_metrics: Dict) -> Dict:
    programs: Dict[str, Dict] = {}
    per_device: List[Dict] = []
    for op in device_metrics.get("operators", {}).values():
        for prog, st in op.get("compile", {}).get("programs", {}).items():
            programs[prog] = {"dispatches": st["dispatches"],
                              "compiles": st["compiles"],
                              "last": st.get("lastSignature")}
        per_device.extend(op.get("keys", {}).get("perDevice", []))
    return {"programs": programs, "per_device": per_device,
            "num_compiles": device_metrics.get("compile", {}).get("numCompiles")}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, t_process: Optional[float] = None,
             control: Optional[str] = None, spec: Optional[Dict] = None,
             log=print) -> Dict:
    """One run. Returns the result object (the last stdout line of `run.py`)
    plus, under `_detail`, what tools and tests read."""
    import jax

    t_process = time.time() if t_process is None else t_process
    spec = spec or load_cell(workload)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    if rehearse:
        traffic = rehearsal_traffic(traffic)
    devs = jax.devices()

    compiles = compile_events_listener()
    tracer = Tracer(trace and not rehearse, seconds)
    options = dict(cfg.get("options", {}))
    if rehearse:
        options.update(REHEARSAL["options"])
    batch = batch_size_of(options)
    refmod = load_module("references", cfg["reference"]["module"])
    tables = refmod.make_tables(cfg["reference"])
    t0 = time.perf_counter()
    cycle = build_cycle(cfg["stream"], traffic, seed, wrap=batch)
    log(f"stream: {cycle.events} events per cycle of {cycle.cycle_ms} ms, "
        f"{cycle.values.nbytes + cycle.ts.nbytes >> 20} MiB, built in "
        f"{time.perf_counter() - t0:.2f} s")

    marks: Dict[str, Any] = {}

    def on_measure_start():
        marks["compiles_before"] = len(compiles)
        marks["setup_s"] = time.time() - t_process

    state = rd.RunState(
        cycle, traffic, cfg["window"], seconds, batch,
        annotate=tracer.annotate if tracer.enabled else None,
        on_measure_start=on_measure_start,
        on_tick=tracer.tick if tracer.enabled else None,
        on_window_end=tracer.end_window if tracer.enabled else None)
    env = build_job(cfg, state, tables, options)
    result = env.execute(workload)
    tracer.stop()
    t_done = time.perf_counter()
    if state.t_end is None:     # the job ended before the reader closed the window
        state.t_end = state.closed_at or t_done
    device = device_block(devs)
    counters = program_counters(result.metrics["device"])
    counters["mesh_devices"] = result.metrics.get("mesh_devices")
    counters["records_in"] = result.records_in
    compiles_in_window = sum(
        1 for t, _s in compiles[marks.get("compiles_before", 0):]
        if state.t_start <= t <= state.t_end)
    rows, arrivals = state.rows, state.arrivals
    del env, result
    gc.unfreeze()
    gc.collect()

    # -- correct: every row the sink received, against the plain reference,
    # once the window has closed and the peak has been read
    t_ref = time.perf_counter()
    expect, j0 = refmod.expected(
        cycle, cfg["reference"], tables, cfg["window"], state.events,
        int(traffic["jitter_ms"]))
    if control is None:
        check_rows = rd.unpack_rows(rows)
    else:
        # the CONTROL: the reference put in the program's place, with one
        # guarantee of the configuration broken (a batch delivered twice)
        broken, _ = refmod.expected(
            cycle, cfg["reference"], tables, cfg["window"], state.events,
            int(traffic["jitter_ms"]), replay=(0, batch))
        check_rows = ref.rows_of(broken, j0, cfg["window"])
    cmp = ref.compare(check_rows, expect, j0, cfg["window"],
                      counters["records_in"], state.events,
                      windows_due_missing=int(state.missing_target))
    numbers, limits = cmp["numbers"], dict(ref.LIMITS)
    want = cfg.get("expect", {})
    if "devices_with_records" in want:
        numbers["devices_with_records"] = sum(
            1 for e in counters["per_device"] if e.get("records", 0) > 0)
        limits["devices_with_records"] = (">=", want["devices_with_records"])
    if "mesh_devices" in want:
        numbers["mesh_devices"] = int(counters["mesh_devices"] or 0)
        limits["mesh_devices"] = (">=", want["mesh_devices"])
    # which kernel ran is a fact of the chip: a CPU rehearsal takes other paths
    for prog in ([] if rehearse else cfg.get("programs", [])):
        numbers[f"dispatches.{prog}"] = int(
            counters["programs"].get(prog, {}).get("dispatches", 0))
        limits[f"dispatches.{prog}"] = (">=", 1)
    correct = ref.verdict(numbers, limits)
    ref_s = time.perf_counter() - t_ref

    window_s = state.t_end - state.t_start
    ctx = {
        "cell": cell, "cfg": cfg, "traffic": traffic, "state": state,
        "window_s": window_s, "counters": counters, "device": device,
        "compiles_in_window": compiles_in_window, "arrivals": arrivals,
        "trace": None, "trace_window": None, "trace_wall": tuple(tracer.wall),
        "peaks": None,
    }
    e2e = end_to_end(ctx, marks)
    out: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(cmp["cells_compared"]),
        "failed": int(numbers["cells_wrong"] + numbers["cells_missing"]
                      + numbers["cells_twice"] + numbers["rows_outside"]),
    }
    if trace and not rehearse:
        out["metrics"], device_extra, breakdown = traced_metrics(
            ctx, tracer, spec["per_layer"])
        device.update(device_extra)
        out["breakdown"] = breakdown
    else:
        out["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"] if e2e.get(m["name"]) is not None}
    out["device"] = device
    out["compared"] = {
        k: {"value": numbers[k], "limit": f"{limits[k][0]}{limits[k][1]}"}
        for k in numbers}
    out["_detail"] = {
        "e2e": e2e, "window_s": window_s, "events": state.events,
        "counted_events": state.counted_events, "reference_s": ref_s,
        "rows": cmp["rows_compared"], "per_second": state.per_second,
        "compiles_in_window": compiles_in_window, "counters": counters,
        "setup_s": marks.get("setup_s"), "lag_s": state.lag_s,
        "drain_s": window_s - state.seconds, "trace": ctx["trace"],
    }
    return out


def end_to_end(ctx: Dict, marks: Dict) -> Dict[str, Optional[float]]:
    """The end-to-end numbers, taken by the benchmark's own clock."""
    st: rd.RunState = ctx["state"]
    out: Dict[str, Optional[float]] = {"setup_s": marks.get("setup_s")}
    if not st.open_loop:
        # all counted events over the whole window: the window ends when the
        # last window a counted event belongs to has reached the sink
        out["events_per_s"] = st.counted_events / ctx["window_s"]
        return out
    lat = st.emission_latencies_ms()
    if len(lat):
        out["emit_p50_ms"] = float(np.percentile(lat, 50))
        out["emit_p95_ms"] = float(np.percentile(lat, 95))
    return out


def traced_metrics(ctx: Dict, tracer: Tracer, per_layer: List[Dict]):
    from benchmarks import trace_reduce as tr

    peaks = load_json("peaks.json")
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json: an "
                         "unlisted device is an error, never a default")
    ctx["peaks"] = peaks[kind]
    if tracer.path is None:
        raise SystemExit("the traced run wrote no .xplane.pb")
    trace = tr.load_xplane(tracer.path)
    lo, hi = tr.window_of(trace)
    ctx["trace"], ctx["trace_window"] = trace, (lo, hi)
    busy = tr.busy_by_device(trace, lo, hi)
    if not busy or max(busy.values()) <= 0:
        raise SystemExit("no operation ran on the device in the traced window")
    metrics = {}
    for m in per_layer:
        value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    fullest = max(busy, key=busy.get)
    by_name, _idle = tr.attribute_gaps(trace, fullest, lo, hi)
    breakdown = {
        "device_ops": tr.top_device_ops(trace, lo, hi),
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
    }
    extra = {"busy_s": statistics.fmean(busy.values()) / 1e9,
             "window_s": (hi - lo) / 1e9}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return metrics, extra, breakdown


def print_compared(out: Dict, stream=sys.stderr) -> None:
    for name, c in out["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})", file=stream)
