"""Device-plane observability: XLA compile/recompile tracking and
per-kernel cost/roofline attribution.

PR 2 instrumented the data path (latency markers, busy/idle ratios,
stage-clock wall times) and PR 4 the control plane (checkpoint/failure
stats); the device itself stayed a black box — the runtime could not say
whether a job is recompile-thrashing, where a laggard kernel's device time
goes, or how far a kernel sits from the HBM/FLOPs roofline. This module is
the third observability plane's core:

- **CompileTracker** wraps a jitted program's dispatch sites: per-program
  compile count and compile wall time, the triggering shape signature, a
  bounded recompile-event ring with *cause attribution* (ring doubling /
  batch-geometry churn / dtype change — inferred by diffing the signature
  that compiled against the program's previous one), and a
  ``recompileStorm`` warning gauge when N recompiles land within a sliding
  window. Detection uses the jitted callable's own executable cache
  (``_cache_size`` growth across a call — the call that grew it is the
  call that compiled), falling back to per-signature bookkeeping for
  callables that do not expose it.
- **Cost & roofline capture** — on each compile the tracker captures
  ``fn.lower(*args).cost_analysis()`` (FLOPs, bytes accessed; one extra
  trace, no compile) and optionally the AOT executable's
  ``memory_analysis()`` (temp/output HBM — costs an extra compile, off by
  default). Per-dispatch costs accumulate into lifetime bytes/FLOPs
  totals, which combined with the stage clock's outer-section wall time give the
  ``hbmUtilizationPct``/``flopsUtilizationPct`` roofline gauges.

Layering: metrics sits below the runtime — this module never imports it.
The jitted callables and their arguments are handed IN by runtime callers;
jax itself is only touched through those objects (duck-typed), so plain
control-plane processes never pay a jax import for importing this module.
All tracker state is lock-protected: dispatch happens on task threads
while heartbeat/REST threads read gauges and payloads.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from flink_tpu.metrics.task_io import tag_dispatch

#: roofline denominators by `jax.devices()[0].device_kind`, used when the
#: observability.device.hbm-gbps / .peak-tflops options are left at 0.
#: Only parts with a published datasheet are listed; a kind that is not
#: here gets NO roofline gauge — never another device's row.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e" system architecture: 16 GB HBM2e
    # at 819 GB/s, 197 bf16 TFLOP/s, 393 int8 TOP/s per chip
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0,
                    "int8_tops": 393.0},
}


def platform_peaks(hbm_gbps: float = 0.0, peak_tflops: float = 0.0,
                   device_kind: Optional[str] = None,
                   ) -> "Optional[tuple[float, float]]":
    """Resolve the roofline denominators (HBM GB/s, peak TFLOP/s):
    configured values win; a value left at 0 comes from the DEVICE_PEAKS
    row of `device_kind` (default: the kind of `jax.devices()[0]`). None
    when a value is needed from the table and the kind is not in it."""
    if hbm_gbps > 0 and peak_tflops > 0:
        return hbm_gbps, peak_tflops
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    row = DEVICE_PEAKS.get(device_kind)
    if row is None:
        return None
    return (hbm_gbps if hbm_gbps > 0 else row["hbm_gbps"],
            peak_tflops if peak_tflops > 0 else row["bf16_tflops"])


def _signature_str(signature: Dict[str, Any]) -> str:
    return ",".join(f"{k}={signature[k]}" for k in sorted(signature))


def attribute_cause(prev: Optional[Dict[str, Any]],
                    new: Dict[str, Any]) -> str:
    """Why did this signature recompile, given the program's previous one?

    Precedence mirrors how disruptive each churn source is: a dtype change
    is a program-semantics change (usually a bug), key-capacity growth is
    the ring-doubling cost model working as designed (amortized, but worth
    seeing), and T/B churn is batch-geometry instability (ragged tails,
    unstable source batching) — the classic silent-recompile thrash."""
    if prev is None:
        return "initial"
    changed = {k for k in set(prev) | set(new) if prev.get(k) != new.get(k)}
    if not changed:
        # same signature compiled again: the executable cache was evicted
        # or a sibling program shares the name — still worth flagging
        return "cache-eviction"
    if any("dtype" in k.lower() for k in changed):
        return "dtype-change"
    if "K" in changed:
        return "ring-doubling"
    if changed & {"T", "B"}:
        return "batch-geometry"
    return "other:" + "+".join(sorted(changed))


class _ProgramStats:
    __slots__ = ("compiles", "compile_ms", "dispatches", "last_signature",
                 "seen_signatures", "bytes_total", "flops_total",
                 "cost_by_signature")

    def __init__(self):
        self.compiles = 0
        self.compile_ms = 0.0
        self.dispatches = 0
        self.last_signature: Optional[Dict[str, Any]] = None
        self.seen_signatures: set = set()
        self.bytes_total = 0.0
        self.flops_total = 0.0
        # sig_str -> {"flops", "bytes_accessed", "temp_bytes"?, ...}
        self.cost_by_signature: Dict[str, Dict[str, float]] = {}


class CompileTracker:
    """Compile/recompile + cost accounting for one job's device programs.

    One tracker per operator (runner) keeps attribution local; job-level
    exposure merges the per-runner payloads (merge_compile_payloads)."""

    def __init__(self, *, history_size: int = 32, storm_threshold: int = 4,
                 storm_window_ms: int = 60_000, cost_analysis: bool = True,
                 memory_analysis: bool = False,
                 on_event: Optional[Callable[[Dict[str, Any]], None]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 wall_clock: Callable[[], float] = time.time):
        self.history_size = max(int(history_size), 1)
        self.storm_threshold = max(int(storm_threshold), 1)
        self.storm_window_ms = max(int(storm_window_ms), 1)
        self.cost_analysis = cost_analysis
        self.memory_analysis = memory_analysis
        self.on_event = on_event
        self._clock = clock
        self._wall = wall_clock
        self._lock = threading.Lock()
        self._programs: Dict[str, _ProgramStats] = {}
        self._events: deque = deque(maxlen=self.history_size)
        self._recompile_times: deque = deque(maxlen=256)  # monotonic stamps
        self.num_compiles = 0
        self.num_recompiles = 0
        self.compile_ms_total = 0.0

    # -- dispatch wrapper --------------------------------------------------
    def call(self, program: str, fn, args: tuple,
             signature: Dict[str, Any]):
        """Invoke ``fn(*args)``, recording a compile event if this call
        compiled. Non-compiling dispatches cost one cache-size probe and a
        dict increment — O(1) host work on the hot path. The single seam
        every window program is dispatched through: it tags the operator's
        open `flink_tpu.dispatch` span with ``program=<program>``."""
        tag_dispatch(program)
        probe = getattr(fn, "_cache_size", None)
        pre = None
        if probe is not None:
            try:
                pre = probe()
            except Exception:  # noqa: BLE001 — observability never fails
                probe = None   # the dispatch
        t0 = self._clock()
        out = fn(*args)
        elapsed_ms = (self._clock() - t0) * 1000.0
        sig_str = _signature_str(signature)
        compiled = False
        if probe is not None and pre is not None:
            try:
                compiled = probe() > pre
            except Exception:  # noqa: BLE001
                compiled = False
        needs_cost = False
        with self._lock:
            stats = self._programs.get(program)
            if stats is None:
                stats = self._programs[program] = _ProgramStats()
            new_signature = sig_str not in stats.seen_signatures
            if probe is None or pre is None:
                # no executable-cache introspection: first sighting of a
                # signature is the compile (an upper bound — a shared jax
                # cache may already hold it, but the signature is new to
                # THIS program's stream of dispatches)
                compiled = new_signature
            stats.seen_signatures.add(sig_str)
            stats.dispatches += 1
            if compiled:
                cause = attribute_cause(stats.last_signature, signature)
                recompile = stats.compiles > 0
                stats.compiles += 1
                stats.compile_ms += elapsed_ms
                self.num_compiles += 1
                self.compile_ms_total += elapsed_ms
                if recompile:
                    self.num_recompiles += 1
                    self._recompile_times.append(self._clock())
                event = {
                    "program": program,
                    "signature": sig_str,
                    "cause": cause,
                    "recompile": recompile,
                    "compile_count": stats.compiles,
                    # wall time of the compiling call: trace + XLA compile
                    # + the first execution (jax offers no finer split at
                    # dispatch time)
                    "duration_ms": round(elapsed_ms, 3),
                    "wall_ts_ms": self._wall() * 1000.0,
                }
                self._events.append(event)
            else:
                event = None
                cost = stats.cost_by_signature.get(sig_str)
                if cost is not None:
                    stats.bytes_total += cost.get("bytes_accessed", 0.0)
                    stats.flops_total += cost.get("flops", 0.0)
                elif new_signature:
                    # the process-wide jit caches already held this shape
                    # (a sibling pipeline or a previous job compiled it):
                    # no compile EVENT for this job, but the roofline
                    # still needs the per-dispatch cost — a warm-cache
                    # job must not read 0% utilization forever
                    needs_cost = True
            stats.last_signature = dict(signature)
        if event is not None or needs_cost:
            # analysis OUTSIDE the lock: lower() re-traces and the
            # optional memory pass compiles — seconds-long work that must
            # not block heartbeat/REST readers of the gauges
            cost = self._analyze(fn, args)
            if cost is not None:
                with self._lock:
                    stats.cost_by_signature[sig_str] = cost
                    stats.bytes_total += cost.get("bytes_accessed", 0.0)
                    stats.flops_total += cost.get("flops", 0.0)
                    if event is not None:
                        event["cost"] = dict(cost)
        if event is not None and self.on_event is not None:
            try:
                self.on_event(event)
            except Exception:  # noqa: BLE001 — a broken span sink
                pass           # must not fail the dispatch
        return out

    def _analyze(self, fn, args) -> Optional[Dict[str, float]]:
        """Best-effort cost/memory analysis of the program that just
        compiled. ``lower()`` re-traces (cheap, no XLA compile); the
        memory pass additionally AOT-compiles — gated separately."""
        if not self.cost_analysis:
            return None
        lower = getattr(fn, "lower", None)
        if lower is None:
            return None
        out: Dict[str, float] = {}
        try:
            lowered = lower(*args)
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):   # some versions wrap per-device
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                if isinstance(ca.get("flops"), (int, float)):
                    out["flops"] = float(ca["flops"])
                if isinstance(ca.get("bytes accessed"), (int, float)):
                    out["bytes_accessed"] = float(ca["bytes accessed"])
        except Exception:  # noqa: BLE001 — backends without cost analysis
            return None
        if self.memory_analysis:
            try:
                mem = lowered.compile().memory_analysis()
                for name, attr in (("temp_bytes", "temp_size_in_bytes"),
                                   ("output_bytes", "output_size_in_bytes"),
                                   ("argument_bytes",
                                    "argument_size_in_bytes"),
                                   ("code_bytes",
                                    "generated_code_size_in_bytes")):
                    v = getattr(mem, attr, None)
                    if isinstance(v, int):
                        out[name] = float(v)
            except Exception:  # noqa: BLE001
                pass
        return out or None

    # -- gauges ------------------------------------------------------------
    def recompile_storm(self) -> int:
        """1 when >= storm_threshold recompiles landed within the sliding
        storm window (a job paying compile latency on the hot path)."""
        with self._lock:
            return self.recompile_storm_unlocked()

    def bytes_accessed_total(self) -> float:
        with self._lock:
            return sum(s.bytes_total for s in self._programs.values())

    def flops_total(self) -> float:
        with self._lock:
            return sum(s.flops_total for s in self._programs.values())

    def dispatches_total(self) -> int:
        with self._lock:
            return sum(s.dispatches for s in self._programs.values())

    def register(self, group) -> None:
        """Register the compile-observability gauges on a metric group."""
        group.gauge("numCompiles", lambda: self.num_compiles,
                    fold="sum", kind="counter")
        group.gauge("numRecompiles", lambda: self.num_recompiles,
                    fold="sum", kind="counter")
        group.gauge("compileTimeMsTotal",
                    lambda: round(self.compile_ms_total, 3),
                    fold="sum", kind="counter")
        group.gauge("recompileStorm", self.recompile_storm, fold="max")

    # -- exposure ----------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._events]

    def payload(self) -> Dict[str, Any]:
        """Plain-data compile block (REST /jobs/:id/device shape)."""
        with self._lock:
            return {
                "numCompiles": self.num_compiles,
                "numRecompiles": self.num_recompiles,
                "compileTimeMsTotal": round(self.compile_ms_total, 3),
                "recompileStorm": self.recompile_storm_unlocked(),
                "programs": {
                    name: {
                        "compiles": s.compiles,
                        "dispatches": s.dispatches,
                        "compileTimeMsTotal": round(s.compile_ms, 3),
                        "lastSignature": (_signature_str(s.last_signature)
                                          if s.last_signature else None),
                    }
                    for name, s in self._programs.items()
                },
                "events": [dict(e) for e in self._events],
            }

    def recompile_storm_unlocked(self) -> int:
        horizon = self._clock() - self.storm_window_ms / 1000.0
        recent = sum(1 for t in self._recompile_times if t >= horizon)
        return 1 if recent >= self.storm_threshold else 0


def roofline_pct(bytes_accessed: float, flops: float, device_time_s: float,
                 hbm_gbps: float, peak_tflops: float) -> Dict[str, float]:
    """Utilization of the memory/compute rooflines over a measured device
    wall-time window: achieved GB/s (or FLOP/s) as a percentage of the
    part's peak. NOTE what callers pass today: `device_roofline()` hands
    in the stage clock's outer sections (`deviceTimeMsTotal`), which is
    HOST time in the dispatch and resolve sections — an asynchronous
    enqueue under-counts the device and a blocking resolve over-counts it.
    The figure still feeds scheduler/signals.py; a later issue re-sources
    it from a device trace or removes it (ROADMAP.md)."""
    if device_time_s <= 0:
        return {"hbmUtilizationPct": 0.0, "flopsUtilizationPct": 0.0}
    hbm = bytes_accessed / (device_time_s * max(hbm_gbps, 1e-9) * 1e9)
    fl = flops / (device_time_s * max(peak_tflops, 1e-9) * 1e12)
    return {
        "hbmUtilizationPct": round(min(hbm, 10.0) * 100.0, 3),
        "flopsUtilizationPct": round(min(fl, 10.0) * 100.0, 3),
    }


def compile_event_span(event: Dict[str, Any]):
    """One compile event as a trace span (scope 'device', name
    'XlaCompile') for the TraceRegistry / TM->JM span shipping. Attribute
    values are OTLP-scalar-safe (str/int/float/bool)."""
    from flink_tpu.metrics.traces import Span

    end = float(event.get("wall_ts_ms", 0.0))
    dur = float(event.get("duration_ms", 0.0))
    attrs: Dict[str, Any] = {
        "program": event.get("program"),
        "signature": event.get("signature"),
        "cause": event.get("cause"),
        "recompile": bool(event.get("recompile", False)),
        "compileCount": int(event.get("compile_count", 1)),
    }
    cost = event.get("cost") or {}
    if "flops" in cost:
        attrs["costFlops"] = float(cost["flops"])
    if "bytes_accessed" in cost:
        attrs["costBytesAccessed"] = float(cost["bytes_accessed"])
    return Span("device", "XlaCompile", end - dur, end, attrs)


def merge_compile_payloads(payloads: List[Dict[str, Any]],
                           history_size: int = 64) -> Dict[str, Any]:
    """Fold per-operator compile payloads into one job-level block: counts
    sum, storm ORs, program tables merge (names are per-program already),
    events interleave by wall timestamp, newest kept within the bound."""
    out: Dict[str, Any] = {
        "numCompiles": 0, "numRecompiles": 0, "compileTimeMsTotal": 0.0,
        "recompileStorm": 0, "programs": {}, "events": [],
    }
    events: List[Dict[str, Any]] = []
    for p in payloads:
        out["numCompiles"] += int(p.get("numCompiles", 0))
        out["numRecompiles"] += int(p.get("numRecompiles", 0))
        out["compileTimeMsTotal"] = round(
            out["compileTimeMsTotal"]
            + float(p.get("compileTimeMsTotal", 0.0)), 3)
        out["recompileStorm"] = max(out["recompileStorm"],
                                    int(p.get("recompileStorm", 0)))
        for name, s in (p.get("programs") or {}).items():
            cur = out["programs"].setdefault(
                name, {"compiles": 0, "dispatches": 0,
                       "compileTimeMsTotal": 0.0, "lastSignature": None})
            cur["compiles"] += int(s.get("compiles", 0))
            cur["dispatches"] += int(s.get("dispatches", 0))
            cur["compileTimeMsTotal"] = round(
                cur["compileTimeMsTotal"]
                + float(s.get("compileTimeMsTotal", 0.0)), 3)
            cur["lastSignature"] = s.get("lastSignature") or cur["lastSignature"]
        events.extend(p.get("events") or ())
    events.sort(key=lambda e: e.get("wall_ts_ms", 0.0))
    out["events"] = events[-history_size:]
    return out


def empty_device_payload() -> Dict[str, Any]:
    """REST /jobs/:id/device body for a job with no device plane (gates
    off, no device operators, or no attempt yet)."""
    return {
        "enabled": False,
        "compile": merge_compile_payloads([]),
        "operators": {},
        "stages": {},
        "profiler": {"enabled": False, "captures": 0,
                     "last_capture_dir": None},
    }
