"""Benchmark: Yahoo-Streaming-Benchmark-style keyed sliding-window count.

Workload (BASELINE.json config 2): events keyed by campaign (dense int
keys), 10s windows sliding by 1s, event-time, watermark advanced per batch.

Device path: the fused PALLAS superscan — the whole T-step window
dispatch (MXU one-hot ingest + fire + purge) as ONE kernel with the
slice-ring state resident in VMEM (flink_tpu/ops/pallas_superscan.py).
The record stream is synthesized ON DEVICE with jax threefry PRNG from a
fixed integer schedule; the host regenerates bit-identical records (threefry
is backend-deterministic) for the single-core numpy baseline and the
window-by-window parity check. Only kilobyte-sized plan arrays cross the
host link per dispatch, so the headline reflects the operator alone — it is
NOT the path a user's `env.execute()` job takes (chip_smoke.py drives that).

CPU baseline: an optimized single-core numpy implementation of the same
slice-decomposed algorithm (np.bincount segment sums) — a deliberately
*stronger* baseline than a per-record port of the reference's JVM
WindowOperator (see BASELINE.md; hot path WindowOperator.java:293).

Processes: this file is a supervisor that stays off jax. A chip belongs to
one process at a time, so the host-side scenario blocks run first, one
CPU-pinned child after another, and then the chip child runs alone. The
headline `ysb_sliding_count_tuples_per_sec` (tuples/s/chip) comes from the
chip child or from nowhere: a run that finds no TPU, whose chip child ends
without a result, or in which any block reports an error, prints what it
has with the failure named and exits non-zero. Children's stderr goes to
this process's stderr.

Progress events are JSON lines; the LAST line is the result object:
{"metric", "value", "unit", "vs_baseline", ...blocks, "failed": [...]}.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import traceback
from typing import Optional

import numpy as np

NUM_KEYS = 8192
WINDOW_MS = 10_000
SLIDE_MS = 1_000
OOO_MS = 500                  # out-of-orderness jitter bound
WM_DELAY_MS = 1_000
STEP_MS = 655                 # event-time span of one step (int schedule)
NSB = 4
SEED = 42

# main (TPU) workload scale
LOG2_BATCH = int(os.environ.get("BENCH_LOG2_BATCH", "20"))
SPAN_STEPS = int(os.environ.get("BENCH_SPAN_STEPS", "48"))   # steps per dispatch
SPANS = int(os.environ.get("BENCH_SPANS", "8"))
PIPE_DEPTH = int(os.environ.get("BENCH_PIPE_DEPTH", "3"))

# wall budget of the chip child
BUDGET_S = int(os.environ.get("BENCH_WATCHDOG_S", "1200"))


def _emit(obj):
    print(json.dumps(obj), flush=True)


def observability_snapshot(stage_time_s: Optional[dict], elapsed_s: float) -> dict:
    """Per-stage device-time attribution + backpressure ratio for the bench
    result JSON, plus a measured overhead check of the metric hot path (one
    histogram update is what a latency marker costs per operator hop)."""
    from flink_tpu.metrics.registry import Histogram

    h = Histogram()
    n = 10_000
    t0 = time.perf_counter()
    for i in range(n):
        h.update(float(i))
    marker_us = (time.perf_counter() - t0) / n * 1e6
    stage_ms = {k: round(v * 1000.0, 1)
                for k, v in (stage_time_s or {}).items()}
    resolve_s = (stage_time_s or {}).get("superscan_resolve_block", 0.0)
    return {
        "per_stage_device_time_ms": stage_ms,
        # host blocked on device readback / wall — the run loop's
        # backPressuredTimeRatio analogue for the bench pipeline
        "backpressure_ratio": round(resolve_s / max(elapsed_s, 1e-9), 4),
        "marker_record_us": round(marker_us, 3),
        "overhead_ok": marker_us < 50.0,
    }


# ---------------------------------------------------------------------------
# deterministic stream schedule (integer math, identical on host and device)
#
#   step t, record b (0-based):
#     base  = t*STEP_MS + ((b+1)*STEP_MS)//B
#     ts    = max(base - jitter, 0),  jitter = bits >> 13 mod (OOO_MS+1)
#     key   = bits & (NUM_KEYS-1)     bits = threefry(fold_in(seed, t))
#   watermark after step t: (t+1)*STEP_MS - WM_DELAY_MS
# ---------------------------------------------------------------------------

def hbm_gbps(events: int, elapsed_s: float, *, batch: int,
             num_keys: int = NUM_KEYS, num_slices: int = 32,
             bytes_per_record: int = 8) -> float:
    """Achieved HBM bandwidth implied by a measured run (roofline seed).

    Pure arithmetic from quantities already in hand (T, B, K, S) — no
    profiler: each ingested record streams its key + slice id through the
    kernel (2 x int32 = 8 B; value aggs pass bytes_per_record=12), and
    every step reads AND writes the [K, S] int32 slice ring
    (2*K*S*4 B, steps = events/batch). Fire/purge readbacks and padding
    are ignored, so this is a LOWER bound on real traffic — paired with
    the chip's HBM spec it answers "how close to the roofline?" for
    BENCH_*.json consumers."""
    steps = events / max(batch, 1)
    bytes_moved = events * bytes_per_record + steps * 2 * num_keys * num_slices * 4
    return bytes_moved / max(elapsed_s, 1e-9) / 1e9


# ---------------------------------------------------------------------------
# zipf key sampling — THE stateless skewed-key sampler, single-sourced:
# every skewed bench leg (multichip, millikey, the skew matrix) draws keys
# through this, so "zipf(1.0)" means the same distribution in every
# scenario and skew numbers are comparable across the whole artifact
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.lru_cache(maxsize=2)
def zipf_bounded_cdf(num_keys: int, s: float = 1.0):
    """Bounded zipf cdf over ranks 1..num_keys: p_k ~ 1/k^s, normalized.
    np.random.zipf is unbounded and undefined at s=1.0, so every skewed
    leg inverse-cdf samples this instead. Cached small: the millikey
    vocabulary's cdf is ~80 MB and two scenarios never need more."""
    ranks = np.arange(1, int(num_keys) + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks ** float(s))
    cdf /= cdf[-1]
    cdf.setflags(write=False)
    return cdf


def zipf_keys(idx: np.ndarray, num_keys: int, s: float = 1.0,
              hot_perm: Optional[np.ndarray] = None) -> np.ndarray:
    """STATELESS bounded-zipf key draw for element indices `idx`.

    - the uniform variate is a splitmix64-style hash of the element index,
      NOT a chunk-seeded rng: host oracles re-generate the stream under
      different chunk boundaries, and a per-chunk seed would diverge;
    - rank -> key id is identity by default (key 0 is the hottest), or
      `hot_perm` (any permutation of [0, num_keys)) to place the hot
      RANKS deliberately — spread them to model independent hot tenants,
      or cluster them into one device's key range to model the adjacent
      hot-key-group shape the skew rebalancer exists to fix."""
    idx = np.asarray(idx)
    z = (idx.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    u = z.astype(np.float64) / 2.0 ** 64
    rank = np.searchsorted(zipf_bounded_cdf(num_keys, s), u)
    if hot_perm is not None:
        rank = np.asarray(hot_perm)[rank]
    return rank.astype(np.int64)


def step_bounds(t: int, B: int, slide_ms: int = SLIDE_MS):
    """Inclusive (smin, smax) slice bounds of step t's records."""
    smin = max((t * STEP_MS + STEP_MS // B - OOO_MS) // slide_ms, 0)
    smax = ((t + 1) * STEP_MS) // slide_ms
    return smin, smax


def host_step(t: int, B: int, bits_fn):
    """Regenerate step t's (keys, ts) on host, bit-identical to the device."""
    bits = bits_fn(t)
    keys = (bits & (NUM_KEYS - 1)).astype(np.int64)
    jitter = ((bits >> 13) % (OOO_MS + 1)).astype(np.int64)
    base = t * STEP_MS + ((np.arange(1, B + 1, dtype=np.int64) * STEP_MS) // B)
    ts = np.maximum(base - jitter, 0)
    return keys, ts


def make_bits_fn(B: int):
    """Host-side threefry bit stream (jitted on the cpu backend)."""
    import jax

    cpu = jax.devices("cpu")[0]
    base = jax.random.PRNGKey(SEED)

    @jax.jit
    def _bits(t):
        return jax.random.bits(jax.random.fold_in(base, t), (B,), "uint32")

    def bits_fn(t: int) -> np.ndarray:
        with jax.default_device(cpu):
            return np.asarray(_bits(t))

    return bits_fn


def make_device_gen(T: int, B: int, slide_ms: int = SLIDE_MS,
                    with_vals: bool = False, flat: bool = True,
                    nsb: int = NSB):
    """Jitted on-device generator: span of T steps -> idx [T*B] (or [T,B])
    int32, optionally with a value column derived from the same bits."""
    import jax
    import jax.numpy as jnp

    base = jax.random.PRNGKey(SEED)
    bb = jnp.arange(1, B + 1, dtype=jnp.int32)

    @jax.jit
    def gen(t0, smin_abs):
        def one(tr):
            t = t0 + tr
            bits = jax.random.bits(jax.random.fold_in(base, t), (B,), "uint32")
            kid = (bits & jnp.uint32(NUM_KEYS - 1)).astype(jnp.int32)
            jit_ = ((bits >> jnp.uint32(13)) % jnp.uint32(OOO_MS + 1)).astype(jnp.int32)
            ts = jnp.maximum(t * STEP_MS + (bb * STEP_MS) // B - jit_, 0)
            srel = ts // slide_ms - smin_abs[tr]
            idx = kid * nsb + srel
            if with_vals:
                val = ((bits >> jnp.uint32(23)) & jnp.uint32(0xFF)).astype(jnp.float32)
                return idx, val
            return idx

        out = jax.vmap(one)(jnp.arange(T, dtype=jnp.int32))
        if with_vals:
            idx, vals = out
            return (idx.reshape(-1), vals.reshape(-1)) if flat else (idx, vals)
        return out.reshape(-1) if flat else out

    return gen


def host_vals(bits: np.ndarray) -> np.ndarray:
    return ((bits >> 23) & 0xFF).astype(np.float32)


# ---------------------------------------------------------------------------
# CPU baseline: same slice-decomposed algorithm, single core, numpy
# ---------------------------------------------------------------------------

class NumpyWindower:
    """Incremental single-core reference; alg_seconds excludes generation."""

    S = 64

    def __init__(self, window_ms: int = WINDOW_MS, slide_ms: int = SLIDE_MS,
                 agg: str = "count"):
        self.window_ms = window_ms
        self.slide_ms = slide_ms
        self.agg = agg
        fill = 0 if agg in ("count", "sum") else -np.inf
        self.counts = np.full((NUM_KEYS, self.S), fill, dtype=np.float64)
        self.fired_upto = None
        self.fired = {}
        self.alg_seconds = 0.0
        self.events = 0

    def step(self, keys, ts, wm, vals=None):
        S, spw = self.S, self.window_ms // self.slide_ms
        t0 = time.perf_counter()
        s_abs = ts // self.slide_ms
        flat = keys * S + (s_abs % S)
        if self.agg == "count":
            self.counts += np.bincount(flat, minlength=NUM_KEYS * S).reshape(
                NUM_KEYS, S)
        elif self.agg == "sum":
            np.add.at(self.counts.reshape(-1), flat, vals)
        else:  # max
            np.maximum.at(self.counts.reshape(-1), flat, vals)
        self.events += len(keys)
        j_hi = (wm + 1 - self.window_ms) // self.slide_ms
        j_lo = self.fired_upto + 1 if self.fired_upto is not None else j_hi
        combine = np.max if self.agg == "max" else np.sum
        fill = 0 if self.agg in ("count", "sum") else -np.inf
        for j in range(j_lo, j_hi + 1):
            # windows with negative start exist for early records, matching
            # the reference's getWindowStartWithOffset arithmetic
            pos = np.arange(j, j + spw) % S
            self.fired[j] = combine(self.counts[:, pos], axis=1)
            self.counts[:, j % S] = fill
        if self.fired_upto is None or j_hi > self.fired_upto:
            self.fired_upto = j_hi
        self.alg_seconds += time.perf_counter() - t0


def _parity(cpu_fired, dev_fired, require_all: bool = True):
    """Window-by-window equality; with require_all=False (partial runs) only
    the windows the device actually fired are compared."""
    mismatches = 0
    checked = 0
    for j, crow in cpu_fired.items():
        drow = dev_fired.get(j)
        if drow is None:
            if require_all and crow.any():
                mismatches += 1
            continue
        checked += 1
        if not np.array_equal(crow.astype(np.int64), np.asarray(drow).astype(np.int64)):
            mismatches += 1
    ok = mismatches == 0 and (checked > 0 or not require_all)
    if require_all:
        nonempty = len([j for j, c in cpu_fired.items() if c.any()])
        ok = ok and len(dev_fired) >= nonempty
    return ok, checked


# ---------------------------------------------------------------------------
# TPU child
# ---------------------------------------------------------------------------

def _new_pipe(chunk: int, backend: str = "auto", window_ms: int = WINDOW_MS,
              slide_ms: int = SLIDE_MS, agg: str = "count",
              num_slices: int = 32, nsb: int = NSB, out_rows: int = 64,
              scope: str = "keyed"):
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedGlobalWindowPipeline,
        FusedWindowPipeline,
    )

    if scope == "global":
        # per-window GLOBAL aggregate (Q7 shape): keyed-partial ->
        # cross-segment fold, [S] state, scalar fire rows — on TPU the
        # whole dispatch is one pallas kernel (build_global_superscan)
        return FusedGlobalWindowPipeline(
            SlidingEventTimeWindows.of(window_ms, slide_ms),
            agg,
            num_slices=num_slices,
            nsb=nsb,
            fires_per_step=4,
            out_rows=out_rows,
            chunk=chunk,
            backend=backend,
        )
    if agg == "max8":
        # bounded-domain max (values are 8-bit here): rides the pallas MXU
        # nibble-histogram path, ~3x the scatter unit
        from flink_tpu.ops.aggregators import max_agg

        agg = max_agg(domain_bits=8)
    return FusedWindowPipeline(
        SlidingEventTimeWindows.of(window_ms, slide_ms),
        agg,
        key_capacity=NUM_KEYS,
        num_slices=num_slices,
        nsb=nsb,
        fires_per_step=4,
        out_rows=out_rows,
        chunk=chunk,
        backend=backend,
    )


def run_tpu_stream(T: int, B: int, spans: int, depth: int, t0_step: int = 0,
                   warmup: bool = True, window_ms: int = WINDOW_MS,
                   slide_ms: int = SLIDE_MS, agg: str = "count",
                   backend: str = "auto", resolve_field: Optional[str] = None,
                   postproc=None, num_slices: int = 32, nsb: int = NSB,
                   out_rows: int = 64, scope: str = "keyed"):
    """Pipelined on-device-generated stream; yields progress per resolve.

    agg 'count' streams only key/slice ids; 'sum'/'max' also stream a value
    column derived from the same threefry bits. `postproc(count_row,
    field_row)` maps a fired window's device rows before banking (e.g. the
    Q5 top-k cut); default keeps the count row (count agg) or field row.
    scope 'global' runs the global-window pipeline (scalar rows per fire)
    over the SAME staged idx streams — the kid part folds out by % NSB.
    """
    import jax
    import jax.numpy as jnp

    with_vals = agg != "count"
    pallas = backend != "xla"
    # count-only pallas dispatches fit CH=32768 int8 one-hots in VMEM
    # (measured ~1.7x the 8192-chunk rate); weighted stays at 8192 bf16;
    # max8's nibble-pass transients cap the chunk at 1024 with S=32/R=64
    chunk = (32768 if not with_vals else 8192) if pallas else 4096
    if agg == "max8":
        chunk = 1024

    def mk():
        return _new_pipe(chunk=chunk, backend=backend,
                         window_ms=window_ms, slide_ms=slide_ms, agg=agg,
                         num_slices=num_slices, nsb=nsb, out_rows=out_rows,
                         scope=scope)

    pipe = mk()
    gen = make_device_gen(T, B, slide_ms=slide_ms, with_vals=with_vals,
                          flat=pallas, nsb=nsb)

    def stage(p, lo):
        bounds = [step_bounds(lo + r, B, slide_ms) for r in range(T)]
        wms = [(lo + r + 1) * STEP_MS - WM_DELAY_MS for r in range(T)]
        staged, smin_abs = p.plan_superbatch(bounds, wms)
        out = gen(jnp.int32(lo), jnp.asarray(smin_abs))
        if with_vals:
            idx, vals = out
        else:
            idx, vals = out, jnp.zeros((T, 1), jnp.float32)
        return staged._replace(xs=(idx, vals))

    if warmup:
        # compile gen + superscan + staging shapes on a throwaway pipe (the
        # compiled executables are shared via module-level caches), so the
        # timed region below measures steady-state streaming only
        wpipe = mk()
        wpipe.dispatch(stage(wpipe, t0_step))
        del wpipe

    # observability: host time split per pipeline stage — plan+generate+
    # enqueue (dispatch) vs blocked in resolve (readback; the host's
    # "backpressured by the device" condition)
    stage_time = {"plan_stage_dispatch": 0.0, "superscan_resolve_block": 0.0}

    def enqueue(i):
        t0 = time.perf_counter()
        d = pipe.dispatch(stage(pipe, t0_step + i * T), defer=True)
        stage_time["plan_stage_dispatch"] += time.perf_counter() - t0
        return d, time.perf_counter()

    fired = {}
    span_lat = []
    t_first = time.perf_counter()
    inflight = []
    for i in range(min(depth, spans)):
        inflight.append(enqueue(i))
    next_i = len(inflight)
    resolved = 0
    while inflight:
        d, t_enq = inflight.pop(0)
        t_res0 = time.perf_counter()
        for window, counts, fields in d.resolve():
            row = fields[resolve_field] if resolve_field else counts
            if postproc is not None:
                row = postproc(counts, row)
            fired[window.start // slide_ms] = row
        stage_time["superscan_resolve_block"] += time.perf_counter() - t_res0
        span_lat.append((time.perf_counter() - t_enq) * 1000.0)
        resolved += 1
        if next_i < spans:
            inflight.append(enqueue(next_i))
            next_i += 1
        yield_partial = resolved < spans
        elapsed = time.perf_counter() - t_first
        yield {
            "events": resolved * T * B,
            "elapsed": elapsed,
            "fired": fired,
            "span_latency_ms": span_lat,
            "stage_time_s": dict(stage_time),
            # the pipeline's ACTUAL kernel decision, not a backend guess:
            # a geometry that trips the pallas support gate must show up
            # in the artifact as the XLA fallback it really ran
            "used_pallas": bool(pipe._use_pallas()),
            "final": not yield_partial,
        }


def child_tpu(T: int, B: int, spans: int) -> None:
    import jax

    from flink_tpu.utils.compile_cache import configure_compile_cache

    _emit({"event": "start", "device": "tpu", "pid": os.getpid()})
    configure_compile_cache()
    t0 = time.perf_counter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _emit({"event": "backend_ready", **device,
           "init_s": round(time.perf_counter() - t0, 1)})
    if device["platform"] != "tpu":
        # the headline is a chip metric: no chip, no number
        raise SystemExit(
            f"bench: the chip child found no TPU (platform "
            f"{device['platform']!r}); nothing measured")

    def result_json(tps, vsb, parity, checked, lat_ms, events, extra,
                    batch_size=B):
        res = {
            "metric": "ysb_sliding_count_tuples_per_sec",
            "value": round(tps, 1),
            "unit": "tuples/s/chip",
            "vs_baseline": round(vsb, 3),
            "hbm_gbps": float(f"{hbm_gbps(events, events / max(tps, 1e-9), batch=batch_size):.3g}"),
            "parity": parity,
            "windows_checked": checked,
            "p99_flush_latency_ms": round(
                float(np.percentile(lat_ms, 99)), 1) if lat_ms else 0.0,
            "events": events,
            "num_keys": NUM_KEYS,
            "window_ms": WINDOW_MS,
            "slide_ms": SLIDE_MS,
            "device": "tpu",
            "device_kind": device["kind"],
            "device_count": device["count"],
            "kernel": "pallas_superscan",
            "data_source": "on_device_threefry_generator",
        }
        res.update(extra)
        return res

    # ---- quick numpy-baseline estimate (for partial-result ratios) ----
    bits_small = make_bits_fn(1 << 18)
    est = NumpyWindower()
    for t in range(8):
        keys, ts = host_step(t, 1 << 18, bits_small)
        est.step(keys, ts, (t + 1) * STEP_MS - WM_DELAY_MS)
    cpu_tps_est = est.events / max(est.alg_seconds, 1e-9)
    _emit({"event": "cpu_baseline_estimate", "tuples_per_sec": round(cpu_tps_est)})

    # ---- tiny first measurement: parity-checked TPU number, banked fast ----
    tiny_T, tiny_B, tiny_spans = 8, 1 << 18, 2
    t0 = time.perf_counter()
    last = None
    for prog in run_tpu_stream(tiny_T, tiny_B, tiny_spans, depth=2):
        last = prog
    ref = NumpyWindower()
    for t in range(tiny_T * tiny_spans):
        keys, ts = host_step(t, tiny_B, bits_small)
        ref.step(keys, ts, (t + 1) * STEP_MS - WM_DELAY_MS)
    ok, checked = _parity(ref.fired, last["fired"], require_all=True)
    tiny_tps = last["events"] / last["elapsed"]
    _emit({"event": "span_done", "phase": "tiny",
           "partial_result": result_json(
               tiny_tps, tiny_tps / cpu_tps_est, bool(ok), checked,
               last["span_latency_ms"], last["events"],
               {"partial": True, "scale": "small",
                "observability": observability_snapshot(
                    last.get("stage_time_s"), last["elapsed"]),
                "wall_from_backend_ready_s": round(time.perf_counter() - t0, 1)},
               batch_size=tiny_B)})

    # ---- main run ----
    t_compile = time.perf_counter()
    last = None
    for prog in run_tpu_stream(T, B, spans, depth=PIPE_DEPTH):
        last = prog
        if not prog["final"]:
            tps = prog["events"] / prog["elapsed"]
            _emit({"event": "span_done", "phase": "main",
                   "partial_result": result_json(
                       tps, tps / cpu_tps_est, "deferred", 0,
                       prog["span_latency_ms"], prog["events"],
                       {"partial": True})})
    tps = last["events"] / last["elapsed"]
    _emit({"event": "main_done", "tuples_per_sec": round(tps),
           "elapsed_s": round(last["elapsed"], 3),
           "incl_warmup_s": round(time.perf_counter() - t_compile, 1)})

    # ---- untimed: full host replay for parity + the real baseline ----
    bits_fn = make_bits_fn(B)
    ref = NumpyWindower()
    for t in range(T * spans):
        keys, ts = host_step(t, B, bits_fn)
        ref.step(keys, ts, (t + 1) * STEP_MS - WM_DELAY_MS)
        if t % 64 == 63:
            _emit({"event": "replay_progress", "steps": t + 1})
    cpu_tps = ref.events / max(ref.alg_seconds, 1e-9)
    ok, checked = _parity(ref.fired, last["fired"], require_all=True)
    res = result_json(
        tps, tps / cpu_tps, bool(ok), checked,
        last["span_latency_ms"], last["events"],
        {"cpu_baseline_tuples_per_sec": round(cpu_tps, 1),
         "span_steps": T, "batch": B, "spans": spans,
         "pipeline_depth": PIPE_DEPTH,
         "late_dropped": 0,
         "observability": observability_snapshot(
             last.get("stage_time_s"), last["elapsed"])},
    )
    _emit({"event": "result", "result": res})

    # secondary BASELINE configs ride the same artifact; the banked headline
    # above survives any failure here
    if os.environ.get("BENCH_SECONDARY", "1") == "1":
        res["secondary"] = run_secondary_configs(headline_ref=ref)
    _emit({"event": "result_final", "result": res})


# ---------------------------------------------------------------------------
# secondary BASELINE configs (1: WordCount tumbling, 3: session reduce,
# 4: Nexmark Q5 top-k, 5: Nexmark Q7 global max) — each guarded so the
# headline result survives any secondary failure
# ---------------------------------------------------------------------------

def roofline_keys(events: int, tps: float, *, batch: int,
                  num_keys: int = NUM_KEYS, num_slices: int = 32,
                  bytes_per_record: int = 8,
                  flops_per_record: float = 2.0) -> dict:
    """Per-scenario roofline attribution for the secondary blocks: the
    same analytic lower-bound traffic model as `hbm_gbps` (records
    streamed + ring read/write per step) over the published peaks of the
    device kind that ran (metrics/device_stats.DEVICE_PEAKS; no keys for a
    kind that is not listed). These keys make a laggard regression
    ATTRIBUTABLE from the artifact alone: a scenario whose throughput
    drops while hbm_utilization_pct holds is compute/overhead-bound, one
    whose utilization drops with it lost memory-level parallelism."""
    from flink_tpu.metrics.device_stats import platform_peaks

    peaks = platform_peaks()
    if peaks is None:
        return {}
    hbm_peak_gbps, peak_tflops = peaks
    elapsed = events / max(tps, 1e-9)
    gbps = hbm_gbps(events, elapsed, batch=batch, num_keys=num_keys,
                    num_slices=num_slices, bytes_per_record=bytes_per_record)
    tflops = events * flops_per_record / max(elapsed, 1e-9) / 1e12
    return {
        "hbm_utilization_pct": round(100.0 * gbps / max(hbm_peak_gbps, 1e-9), 2),
        "flops_utilization_pct": round(100.0 * tflops / max(peak_tflops, 1e-9), 3),
    }

def _replay(window_ms, slide_ms, agg, T, B, bits_fn):
    ref = NumpyWindower(window_ms, slide_ms, agg)
    for t in range(T):
        bits = bits_fn(t)
        keys = (bits & (NUM_KEYS - 1)).astype(np.int64)
        jitter = ((bits >> 13) % (OOO_MS + 1)).astype(np.int64)
        base = t * STEP_MS + ((np.arange(1, B + 1, dtype=np.int64) * STEP_MS) // B)
        ts = np.maximum(base - jitter, 0)
        ref.step(keys, ts, (t + 1) * STEP_MS - WM_DELAY_MS,
                 vals=host_vals(bits))
    return ref


def secondary_wordcount(bits_fn) -> dict:
    """Config 1: WordCount keyBy().sum() over 1s tumbling windows (the
    count of 1s == sum of ones; pallas superscan, tumbling geometry)."""
    T, B, spans = 24, 1 << 20, 2
    last = None
    for prog in run_tpu_stream(T, B, spans, depth=2, t0_step=0,
                               window_ms=1000, slide_ms=1000):
        last = prog
    ref = _replay(1000, 1000, "count", T * spans, B, bits_fn)
    ok, checked = _parity(ref.fired, last["fired"], require_all=True)
    tps = last["events"] / last["elapsed"]
    return {
        "metric": "wordcount_tumbling_count_tuples_per_sec",
        "value": round(tps, 1),
        "vs_baseline": round(tps / (ref.events / max(ref.alg_seconds, 1e-9)), 3),
        "parity": bool(ok),
        "windows_checked": checked,
        "events": last["events"],
        **roofline_keys(last["events"], tps, batch=B, num_slices=32),
    }


def secondary_q5_topk(headline_ref) -> dict:
    """Config 4: Nexmark Q5 hot items — sliding count + top-10 per window.
    The top-k cut runs per fired window; parity compares the sorted top-10
    multiset (tie-insensitive). Reuses the headline replay (same stream
    prefix) instead of re-running minutes of single-core numpy."""
    N = 10
    T, B, spans = SPAN_STEPS, 1 << LOG2_BATCH, 2

    def topk(counts, _row):
        part = np.partition(counts, len(counts) - N)[-N:]
        return np.sort(part)[::-1]

    last = None
    for prog in run_tpu_stream(T, B, spans, depth=2, postproc=topk):
        last = prog
    ref = headline_ref
    mismatch = 0
    for j, row in last["fired"].items():
        expect = np.sort(np.partition(ref.fired[j], NUM_KEYS - N)[-N:])[::-1]
        if not np.array_equal(np.asarray(row, dtype=np.int64),
                              expect.astype(np.int64)):
            mismatch += 1
    tps = last["events"] / last["elapsed"]
    return {
        "metric": "nexmark_q5_topk_tuples_per_sec",
        "value": round(tps, 1),
        "vs_baseline": round(tps / (ref.events / max(ref.alg_seconds, 1e-9)), 3),
        "parity": mismatch == 0 and len(last["fired"]) > 0,
        "windows_checked": len(last["fired"]),
        "top_n": N,
        "events": last["events"],
        **roofline_keys(last["events"], tps, batch=B, num_slices=32),
    }


def secondary_q7_global_max(bits_fn_small) -> dict:
    """Config 5: Nexmark Q7 — global per-window max. ISSUE-14 moved this
    laggard (14.6x at r05) off the dense keyed nibble-histogram reduction
    onto the GLOBAL-window superscan: keyed partials per rel-slice fold
    cross-segment into a [S] ring (the single-chip analogue of the mesh's
    psum/pmax merge), window fires are ONE scalar each, and on TPU the
    whole T-step dispatch is one pallas kernel with the ring resident in
    a single VMEM row (ops/pallas_superscan.build_global_superscan). The
    per-chunk cost drops from two conditional [16*NSB*K/128, CH] nibble
    histograms + a [R, K] readback to NSB masked whole-chunk folds + R
    scalars. Values stay 8-bit for the baseline replay, but the fold is
    elementwise — unbounded max has a device form on this path."""
    T, B, spans = 96, 1 << 18, 5

    def gmax(_counts, row):
        return float(np.max(row))

    last = None
    for prog in run_tpu_stream(T, B, spans, depth=3, window_ms=10_000,
                               slide_ms=10_000, agg="max",
                               resolve_field="max", postproc=gmax,
                               num_slices=8, nsb=2, out_rows=16,
                               backend="auto", scope="global"):
        last = prog
    import jax
    if jax.default_backend() == "tpu" and not last["used_pallas"]:
        # the 25x bar is judged on the pallas kernel; a geometry change
        # that trips supports_global must fail the scenario loudly, not
        # silently bank the XLA fallback's number under the same metric
        raise RuntimeError(
            "q7 global-max ran the XLA scan fallback on TPU — "
            "pallas_superscan.supports_global stopped selecting")
    ref = _replay(10_000, 10_000, "max", T * spans, B, bits_fn_small)
    mismatch = 0
    for j, got in last["fired"].items():
        if abs(float(np.max(ref.fired[j])) - got) > 1e-3:
            mismatch += 1
    tps = last["events"] / last["elapsed"]

    # the global path replaced the keyed nibble-histogram reduction HERE,
    # but the bounded-domain max8 MXU path stays shipped and selectable —
    # keep one bench driver on it (short keyed leg, same stream prefix +
    # replay) so a nibble-kernel regression stays visible in the artifact;
    # backend='pallas' raises rather than silently falling back, as before
    k_last = None
    for prog in run_tpu_stream(24, B, 2, depth=2, window_ms=10_000,
                               slide_ms=10_000, agg="max8",
                               resolve_field="max", postproc=gmax,
                               num_slices=8, nsb=2, out_rows=16,
                               backend="pallas"):
        k_last = prog
    k_mismatch = 0
    for j, got in k_last["fired"].items():
        if abs(float(np.max(ref.fired[j])) - got) > 1e-3:
            k_mismatch += 1
    keyed_parity = k_mismatch == 0 and len(k_last["fired"]) > 0

    return {
        "metric": "nexmark_q7_global_max_tuples_per_sec",
        "value": round(tps, 1),
        "vs_baseline": round(tps / (ref.events / max(ref.alg_seconds, 1e-9)), 3),
        "parity": mismatch == 0 and len(last["fired"]) > 0 and keyed_parity,
        "windows_checked": len(last["fired"]),
        "events": last["events"],
        "kernel": ("pallas_global_superscan" if last["used_pallas"]
                   else "global_superscan_xla"),
        "keyed_max8_tuples_per_sec": round(
            k_last["events"] / k_last["elapsed"], 1),
        "keyed_max8_windows_checked": len(k_last["fired"]),
        # the global scan holds a [S] ring, not [K, S]: the traffic model
        # is the streamed records themselves (num_keys=1 zeroes the ring
        # term, which is bytes-exact here)
        **roofline_keys(last["events"], tps, batch=B, num_keys=1,
                        num_slices=8, bytes_per_record=8),
    }


def _numpy_sessionize(keys, ts, vals, gap):
    """Single-core batch sessionizer: sort by (key, ts), split where the key
    changes or the gap exceeds `gap`, segment-sum the values."""
    order = np.lexsort((ts, keys))
    k, t, v = keys[order], ts[order], vals[order]
    brk = np.empty(len(k), dtype=bool)
    brk[0] = True
    brk[1:] = (k[1:] != k[:-1]) | (t[1:] - t[:-1] > gap)
    starts = np.flatnonzero(brk)
    sums = np.add.reduceat(v, starts)
    ends = np.r_[starts[1:], len(k)] - 1
    return {
        (int(k[s]), int(t[s]), int(t[e]) + gap): float(sv)
        for s, e, sv in zip(starts, ends, sums)
    }


def secondary_sessions() -> dict:
    """Config 3: clickstream sessionization (session windows + sum reduce)
    on the device session operator. ISSUE-14 moved this laggard (9.8x at
    r05) onto the fused session superspan: 16 staged ingest steps AND
    their in-scan gap-merges run as ONE device dispatch with ONE packed
    emission readback (ops/superscan.make_session_superscan) — sessions
    coalesce inside the scan carry and never round-trip to host per merge,
    where the old path paid one ingest dispatch + one merge dispatch + one
    packed D2H per 8 steps. The stream rotates its active key set so
    sessions actually close; records are synthesized ON DEVICE with the
    host replaying identical bits for the single-core baseline + parity,
    like the headline config."""
    from flink_tpu.api.windowing.assigners import EventTimeSessionWindows
    from flink_tpu.runtime.tpu_session_operator import TpuSessionWindowOperator

    import jax
    import jax.numpy as jnp

    gap = 2000
    B, nb = 1 << 20, 16
    SPAN = 8                       # merge cadence (= the key-rotation
    #                                period; worst-case session emission
    #                                lag stays under 3 gaps)
    SUPER = 16                     # steps fused per superspan dispatch
    #                                (the whole 16-step workload: every
    #                                ingest and both merges in ONE program)
    S = 64
    base_key = jax.random.PRNGKey(SEED + 7)
    cpu = jax.devices("cpu")[0]
    bb_i32 = jnp.arange(1, B + 1, dtype=jnp.int32)

    @jax.jit
    def gen_super(t0):
        """SUPER steps generated in one dispatch as [T, B] staged arrays
        for one fused superspan — one generator + one operator dispatch
        per 16 steps instead of per 8."""
        def one(tr):
            t = t0 + tr
            bits = jax.random.bits(jax.random.fold_in(base_key, t), (B,), "uint32")
            active = (t >> 2) & 3
            kid = ((bits & jnp.uint32(4095)) | (active.astype(jnp.uint32) << 12)
                   ).astype(jnp.int32)
            jit_ = ((bits >> jnp.uint32(13)) % jnp.uint32(OOO_MS + 1)).astype(jnp.int32)
            ts = jnp.maximum(t * STEP_MS + (bb_i32 * STEP_MS) // B - jit_, 0)
            s_abs = ts // gap
            return kid, (s_abs % S).astype(jnp.int32), (ts - s_abs * gap), \
                ((bits >> jnp.uint32(23)) & jnp.uint32(0xFF)).astype(jnp.float32)

        return jax.vmap(one)(jnp.arange(SUPER, dtype=jnp.int32))

    def host_batch(t):
        with jax.default_device(cpu):
            bits = np.asarray(jax.random.bits(
                jax.random.fold_in(base_key, jnp.int32(t)), (B,), "uint32"))
        active = (t >> 2) & 3
        keys = ((bits & 4095) | (active << 12)).astype(np.int64)
        jitter = ((bits >> 13) % (OOO_MS + 1)).astype(np.int64)
        bb = np.arange(1, B + 1, dtype=np.int64)
        ts = np.maximum(t * STEP_MS + (bb * STEP_MS) // B - jitter, 0)
        return keys, host_vals(bits), ts

    def bounds(t):
        smin = max((t * STEP_MS + STEP_MS // B - OOO_MS) // gap, 0)
        smax = ((t + 1) * STEP_MS) // gap
        return smin, smax

    def mk():
        return TpuSessionWindowOperator(
            EventTimeSessionWindows.with_gap(gap), "sum",
            key_capacity=1 << 14, num_slices=S,
            defer_emissions=True,    # merge scans enqueue without syncs
        )

    def superspan_args(lo):
        """[T, B] staged arrays + per-step bounds + merge schedule for one
        fused superspan starting at step `lo` (merge every SPAN steps —
        the same watermark cadence the per-span path used, so emissions
        are bit-identical; only the dispatch count changes)."""
        k, sp, rel, v = gen_super(jnp.int32(lo))
        step_bounds = [bounds(lo + r) for r in range(SUPER)]
        merge_wms = [
            ((lo + r + 1) * STEP_MS - WM_DELAY_MS)
            if (r + 1) % SPAN == 0 else None
            for r in range(SUPER)
        ]
        return k, sp, rel, v, step_bounds, merge_wms

    # warmup: replay the WHOLE loop on a throwaway operator so the fused
    # superspan (and generator) shapes are compiled — threefry determinism
    # makes this an exact dry run of the timed region
    warm = mk()
    for lo in range(0, nb, SUPER):
        warm.process_superspan_staged(*superspan_args(lo))
    warm.process_watermark(1 << 60)
    warm.drain_output()
    del warm

    op = mk()
    out = []
    t0 = time.perf_counter()
    for lo in range(0, nb, SUPER):
        op.process_superspan_staged(*superspan_args(lo))
    op.process_watermark(1 << 60)
    out.extend(op.drain_output())   # resolves the deferred packed arrays
    elapsed = time.perf_counter() - t0
    events = nb * B

    data = [host_batch(t) for t in range(nb)]
    all_k = np.concatenate([d[0] for d in data])
    all_v = np.concatenate([d[1] for d in data])
    all_t = np.concatenate([d[2] for d in data])
    t0 = time.perf_counter()
    expect = _numpy_sessionize(all_k, all_t, all_v, gap)
    base_s = time.perf_counter() - t0
    got = {
        (int(k), w.start, w.end): float(r) for (k, w, r, _t) in out
    }
    parity = (
        len(got) > 0
        and got.keys() == expect.keys()
        and all(abs(got[k] - expect[k]) <= 1e-3 * max(1.0, abs(expect[k]))
                for k in got)
    )
    tps = events / elapsed
    return {
        "metric": "session_sum_tuples_per_sec",
        "value": round(tps, 1),
        "vs_baseline": round(tps / (events / max(base_s, 1e-9)), 3),
        "parity": bool(parity),
        "sessions_emitted": len(got),
        "gap_ms": gap,
        "events": events,
        "kernel": "session_superscan",
        "dispatches": -(-nb // SUPER),
        "data_source": "on_device_threefry_generator",
        # session ring: cnt+mn+mx+sum = 4 arrays of [K, S] i32/f32; each
        # record streams (kid, spos, rel, val) = 16 B
        **roofline_keys(events, tps, batch=B, num_keys=4 * (1 << 14),
                        num_slices=S, bytes_per_record=16),
    }


def run_secondary_configs(headline_ref=None) -> dict:
    sec = {}
    bits_big = make_bits_fn(1 << 20)
    bits_small = make_bits_fn(1 << 18)
    if headline_ref is None:
        headline_ref = _replay(WINDOW_MS, SLIDE_MS, "count",
                               SPAN_STEPS * 2, 1 << LOG2_BATCH,
                               make_bits_fn(1 << LOG2_BATCH))
    for name, fn in (
        ("wordcount_tumbling_count", lambda: secondary_wordcount(bits_big)),
        ("nexmark_q5_topk", lambda: secondary_q5_topk(headline_ref)),
        ("nexmark_q7_global_max", lambda: secondary_q7_global_max(bits_small)),
        ("session_sum", secondary_sessions),
    ):
        t0 = time.perf_counter()
        try:
            sec[name] = fn()
            sec[name]["wall_s"] = round(time.perf_counter() - t0, 1)
        except Exception as e:  # noqa: BLE001 — the other configs still run;
            # the parent exits non-zero on any block that carries "error"
            traceback.print_exc()
            sec[name] = {"error": repr(e)[:300]}
        _emit({"event": "secondary_done", "config": name, "result": sec[name]})
    return sec


# ---------------------------------------------------------------------------
# dataplane microbench: localhost exchange, 1 MiB columnar batches
# ---------------------------------------------------------------------------

def dataplane_microbench(batches: int = 24, max_sweeps: int = 12,
                         min_sweeps: int = 6, budget_s: float = 120.0) -> dict:
    """Cross-host exchange throughput over the REAL dataplane stack
    (ExchangeServer + OutputChannel on loopback): 1 MiB columnar batches
    — 64k float64 values + 64k int64 timestamps — on the zero-copy binary
    columnar wire vs the legacy pickle wire, with transport auth on and
    off. Emits exchange_gbps_{pickle,binary}[_noauth] so the serialization
    tax removed by ISSUE-3 stays tracked in the bench trajectory.

    Protocol: configurations are sampled in interleaved sweeps (so a calm
    or noisy scheduling window hits all of them, not just one) and each
    reports the BEST sweep — throughput microbenchmarks on shared or
    sandboxed hosts see multi-x scheduler noise, and max-of-N estimates
    the wire's capability the way min-of-N estimates latency. Ring
    capacity exceeds the batch count so credit flow never throttles the
    measurement. Sweeping stops early only on CONVERGENCE — two
    consecutive sweeps that improve no configuration's best by more than
    3% — never on the value of the ratio itself, so the stop rule cannot
    bias the reported numbers toward any threshold."""
    import threading as _threading

    from flink_tpu.runtime.dataplane import ExchangeServer, OutputChannel
    from flink_tpu.security.transport import SecurityConfig

    vals = np.random.default_rng(0).random(1 << 16)       # 512 KiB float64
    ts = np.arange(1 << 16, dtype=np.int64)               # 512 KiB int64
    payload = ("b", vals, ts)
    nbytes = vals.nbytes + ts.nbytes

    def one_rep(wire_format: str, security) -> float:
        warm = 4
        server = ExchangeServer(capacity=batches + warm + 1,
                                wire_format=wire_format, security=security)
        ch = server.channel("bench")
        out = OutputChannel(server.address, "bench",
                            wire_format=wire_format, security=security)
        done = _threading.Event()

        def consume():
            for _ in range(batches + warm):
                ch.poll(timeout=30)
            done.set()

        t = _threading.Thread(target=consume, daemon=True)
        t.start()
        for _ in range(warm):
            out.send(payload)
        t0 = time.perf_counter()
        for _ in range(batches):
            out.send(payload)
        done.wait(timeout=60)
        dt = time.perf_counter() - t0
        out.end()
        out.close()
        server.stop()
        return batches * nbytes / dt / 1e9

    configs = {
        "exchange_gbps_pickle": ("pickle", None),
        "exchange_gbps_binary": ("binary", None),
        "exchange_gbps_pickle_noauth": ("pickle", SecurityConfig.disabled()),
        "exchange_gbps_binary_noauth": ("binary", SecurityConfig.disabled()),
    }
    seen: dict = {k: 0.0 for k in configs}
    sweeps = 0
    flat_sweeps = 0
    # hard wall-clock cap: the microbench shares the bench's fixed budget
    # with the TPU attempts — a deadlocked exchange (60 s rep timeouts)
    # must not eat the window that produces the headline metric
    bench_deadline = time.perf_counter() + budget_s
    for sweep in range(max_sweeps):
        improved = False
        for key, (fmt, sec) in configs.items():
            if time.perf_counter() > bench_deadline:
                break
            got = one_rep(fmt, sec)
            if got > seen[key] * 1.03:
                improved = True
            seen[key] = max(seen[key], got)
        sweeps = sweep + 1
        flat_sweeps = 0 if improved else flat_sweeps + 1
        if sweeps >= min_sweeps and flat_sweeps >= 2:
            break
        if time.perf_counter() > bench_deadline:
            break

    res: dict = {"batch_bytes": nbytes, "batches": batches, "sweeps": sweeps}
    res.update({k: round(v, 3) for k, v in seen.items()})
    res["binary_vs_pickle_auth"] = round(
        res["exchange_gbps_binary"] / max(res["exchange_gbps_pickle"], 1e-9), 2)
    return res


def checkpoint_microbench(events: int = 100_000, reps: int = 2) -> dict:
    """Checkpoint overhead on the windowed hot path: the keyed tumbling
    pipeline at a FIXED event count, checkpointing off vs on (Fs storage,
    25 ms interval — several snapshots per run), best-of-reps each (wall
    time is latency-like: min-of-N estimates the cost floor). Emits
    checkpoint.{overhead_pct, last_duration_ms, last_size_bytes} so the
    fault-tolerance tax stays tracked in the bench trajectory alongside
    the throughput headline."""
    import shutil
    import tempfile

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.config import (
        CheckpointingOptions,
        Configuration,
        ExecutionOptions,
    )
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.utils.arrays import obj_array

    def gen(idx):
        vals = obj_array([(int(i) & 63, 1.0) for i in idx])
        return Batch(vals, (idx * 10).astype(np.int64))

    def run_once(chk_dir):
        config = Configuration()
        config.set(ExecutionOptions.BATCH_SIZE, 8192)
        if chk_dir is not None:
            config.set(CheckpointingOptions.INTERVAL_MS, 25)
            config.set(CheckpointingOptions.DIRECTORY, chk_dir)
        env = StreamExecutionEnvironment(config)
        stream = env.from_source(
            DataGeneratorSource(gen, count=events),
            watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
        )
        (stream.key_by(lambda x: x[0])
               .window(TumblingEventTimeWindows.of(1000)).count()
               .sink_to(CollectSink()))
        t0 = time.perf_counter()
        client = env.execute_async("checkpoint-bench")
        status = client.wait(240)
        dt = time.perf_counter() - t0
        if status.value != "FINISHED":
            raise RuntimeError(f"bench job ended {status.value}")
        return dt, client

    best_off = best_on = float("inf")
    best_on_client = None
    run_once(None)        # warmup: jit compiles must not bill the OFF config
    for _ in range(reps):
        dt, _c = run_once(None)
        best_off = min(best_off, dt)
        chk = tempfile.mkdtemp(prefix="flink-tpu-cpbench-")
        try:
            dt, client = run_once(chk)
        finally:
            shutil.rmtree(chk, ignore_errors=True)
        if dt < best_on:
            best_on, best_on_client = dt, client
    gauges = best_on_client.checkpoint_stats.gauge_values()
    return {
        "events": events,
        "elapsed_off_s": round(best_off, 3),
        "elapsed_on_s": round(best_on, 3),
        "checkpoints_completed": int(gauges["numberOfCompletedCheckpoints"]),
        "overhead_pct": round((best_on - best_off) / max(best_off, 1e-9) * 100, 2),
        "last_duration_ms": round(float(gauges["lastCheckpointDuration"]), 3),
        "last_size_bytes": int(gauges["lastCheckpointSize"]),
    }


class _ScenarioWindows:
    """Tumbling assigner with an amortized per-record service cost that
    releases the GIL (bulk sleeps), so extra shard threads genuinely add
    capacity: the saturation the autoscaler must detect is real, and the
    recovery it buys is measurable, even inside one bench process."""

    def __init__(self, size_ms, cost_s, bulk=150):
        from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows

        self._inner = TumblingEventTimeWindows.of(size_ms)
        self.cost_s = cost_s
        self.bulk = bulk
        self._n = 0

    def __getattr__(self, name):
        if name.startswith("_"):      # never proxy dunders/privates: the
            raise AttributeError(name)  # unpickle path probes them before
        return getattr(self._inner, name)  # _inner exists

    def assign_windows(self, element, timestamp):
        self._n += 1
        if self._n % self.bulk == 0:
            time.sleep(self.cost_s * self.bulk)
        return self._inner.assign_windows(element, timestamp)


class _ScenarioSource:
    """Arrival-paced 2x load-step source (picklable): profile[s] records in
    step s across shards, sliced per shard; step s blocks until its
    scheduled arrival (re-anchored per attempt, so replay stays paced)."""

    def __init__(self, profile, interval_s):
        self.profile = list(profile)
        self.interval_s = interval_s

    def __call__(self, shard, num_shards):
        outer = self

        class _Paced(list):
            def __init__(self):
                super().__init__(range(len(outer.profile)))
                self._anchor = None

            def __getitem__(self, s):
                now = time.monotonic()
                if self._anchor is None:
                    self._anchor = (now, s)
                due = self._anchor[0] + (s - self._anchor[1]) * outer.interval_s
                if due > now:
                    time.sleep(due - now)
                rng = np.random.default_rng(4000 + s)
                n = outer.profile[s]
                keys = rng.integers(0, 64, n).astype(np.int64)
                vals = np.ones(n, dtype=np.float64)
                ts = (s * 1000 + rng.integers(0, 1000, n)).astype(np.int64)
                sl = slice(shard, None, num_shards)
                return keys[sl], vals[sl], ts[sl], s * 1000 + 500

        return _Paced()


def autoscaler_scenario(pre_steps: int = 30, high_steps: int = 100,
                        interval_s: float = 0.062,
                        cost_s: float = 0.0002) -> dict:
    """Adaptation-speed microbench (ROADMAP item 2 gate): an arrival-paced
    keyed job at ~0.65 utilization takes a 2x load step that saturates
    parallelism 1; the autoscaler must scale up by checkpoint rewind +
    key-group remap. Emits autoscaler.{rescales, time_to_adapt_s,
    throughput_ratio_post_step} so adaptation speed is tracked per PR
    (time_to_adapt = load step crossing the wire -> rescaled attempt
    RUNNING; throughput ratio = the coordinator's settled post-rescale
    rate over the pre-step offered rate)."""
    from flink_tpu.config import AutoscalerOptions, Configuration
    from flink_tpu.runtime.cluster import (
        DistributedJobSpec,
        JobManagerEndpoint,
        TaskExecutorEndpoint,
    )
    from flink_tpu.runtime.rpc import RpcService

    import tempfile

    pre, high = 162, 324
    profile = [pre] * pre_steps + [high] * high_steps
    pre_rate = pre / interval_s
    cfg = (Configuration()
           .set(AutoscalerOptions.ENABLED, True)
           .set(AutoscalerOptions.POLICY, "threshold")
           .set(AutoscalerOptions.MAX_PARALLELISM, 2)
           .set(AutoscalerOptions.INTERVAL_MS, 200)
           .set(AutoscalerOptions.SIGNAL_WINDOW, 6)
           .set(AutoscalerOptions.STABILIZATION_INTERVAL_MS, 1500)
           .set(AutoscalerOptions.SCALE_UP_THRESHOLD, 0.9)
           # up-adaptation only: the e2e suite covers scale-down, and a
           # noisy low reading mid-scenario would pollute the timing
           .set(AutoscalerOptions.SCALE_DOWN_THRESHOLD, 0.05))
    spec = DistributedJobSpec(
        name="autoscaler-scenario",
        source_factory=_ScenarioSource(profile, interval_s),
        assigner=_ScenarioWindows(2000, cost_s),
        aggregate="sum",
        max_parallelism=16,
    )
    chk = tempfile.mkdtemp(prefix="flink-tpu-asbench-")
    svc_jm, svc_tm = RpcService(), RpcService()
    jm = JobManagerEndpoint(
        svc_jm, checkpoint_dir=chk, checkpoint_interval=0.3,
        heartbeat_interval=0.2, heartbeat_timeout=15.0,
        autoscaler_config=cfg,
    )
    te = TaskExecutorEndpoint(svc_tm, slots=2, shipping_interval_ms=200)
    te.connect(svc_jm.address)
    client = svc_jm.gateway(svc_jm.address, "jobmanager")
    try:
        job_id = client.submit_job(spec.to_bytes(), 1)
        # nominal arrival time of the 2x step (the source's pacing anchor
        # is its first batch, within startup jitter of submit)
        t_step = time.monotonic() + pre_steps * interval_s
        t_adapted = None
        deadline = time.monotonic() + 180
        status = {}
        while time.monotonic() < deadline:
            status = client.job_status(job_id)
            if (t_adapted is None and status["rescales"] >= 1
                    and status["status"] == "RUNNING"):
                t_adapted = time.monotonic()
            if status["status"] in ("FINISHED", "FAILED"):
                break
            time.sleep(0.1)
        auto = client.job_autoscaler(job_id)
        settled = [d for d in auto["decisions"]
                   if d["action"] == "scale-up" and d["outcome"] == "executed"
                   and d.get("throughput_after")]
        # decision log is newest-first: [0] is the LATEST settled scale-up
        post_tput = settled[0]["throughput_after"] if settled else 0.0
        return {
            "status": status.get("status"),
            "rescales": int(status.get("rescales", 0)),
            "time_to_adapt_s": (round(max(t_adapted - t_step, 0.0), 3)
                                if t_adapted is not None else None),
            "throughput_ratio_post_step": round(post_tput / pre_rate, 3),
            "last_rescale_duration_ms": round(
                float(auto.get("last_rescale_duration_ms") or 0.0), 3),
            "pre_rate_records_per_s": pre_rate,
        }
    finally:
        te.stop()
        jm.heartbeats.stop()
        svc_jm.stop()
        svc_tm.stop()
        import shutil

        shutil.rmtree(chk, ignore_errors=True)


def child_autoscaler() -> None:
    """Autoscaler-scenario child: CPU-pinned like child_checkpoint (the
    oracle path never needs a device, and a chip belongs to one process:
    the chip child)."""
    _emit({"event": "start", "device": "cpu-autoscaler", "pid": os.getpid()})
    _emit({"event": "result", "result": autoscaler_scenario()})


def run_autoscaler_scenario_child(timeout_s: float = 240.0) -> dict:
    """Autoscaler load-step scenario in a CPU-pinned child."""
    return _run_cpu_child('autoscaler', timeout_s)


def api_path_microbench(events: Optional[int] = None,
                        batch: int = 8192,
                        span_event_ms: int = 64_000) -> dict:
    """The api_vs_fused scenario (BENCH_r02), permanent: a FULL DataStream
    program — from_source().filter().key_by().window().aggregate().sink()
    — on the YSB sliding-count workload, run through BOTH execution paths
    in the same process on the same data:

      - whole-graph fusion (execution.chain.device-fusion true, the
        default): traceable filter + key extraction + window aggregate
        compile into one jitted multi-step device program
        (DeviceChainRunner, docs/fusion.md);
      - the legacy path (device-fusion false): host ChainRunner transforms
        + WindowStepRunner with per-batch host key/value extraction.

    Emits api_path_tuples_per_sec (fused) and chain_runner_tuples_per_sec
    (legacy) so the API-vs-kernel gap is tracked in every BENCH_*.json —
    it silently disappeared after r02. `parity` is exact result equality
    between the two paths; `fused_selected` pins that the fused runner was
    actually chosen (a silent reroute back to the slow runner would
    otherwise still report parity true)."""
    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import Configuration, ExecutionOptions
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import build_runners

    events = events or int(os.environ.get("BENCH_API_EVENTS", str(1 << 21)))

    def source(n):
        def gen(idx):
            # deterministic YSB-ish columns: (campaign, event_type); the
            # filter keeps event_type 0 ("view"), 1/3 of the stream
            camp = (idx * 2654435761) % NUM_KEYS
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            ts = 10_000 + idx * span_event_ms // n
            return Batch(col, ts.astype(np.int64))

        return DataGeneratorSource(gen, n)

    # one set of UDF OBJECTS shared by warmup and measured runs: compiled
    # chain executables are memoized on the fn identities, so the warmup
    # pays compilation and the measured runs bill steady-state throughput
    # (exactly a long-running job's economics)
    t_filter = lambda col: col[:, 1] < 0.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype(jnp.int32)           # noqa: E731
    s_filter = lambda r: r[1] < 0.5                           # noqa: E731
    s_key = lambda r: int(r[0])                               # noqa: E731

    def build(n, mode, columnar=True):
        cfg = Configuration()
        cfg.set(ExecutionOptions.CHAIN_FUSION, mode == "fused")
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, NUM_KEYS)
        # columnar sinks for the TIMED runs: the measurement targets the
        # execution paths, not the per-row Python expansion tax a naive
        # sink adds equally to every path; parity runs in row mode below,
        # where every operator emits raw keys
        cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, columnar)
        env = StreamExecutionEnvironment.get_execution_environment(cfg)
        ds = env.from_source(
            source(n),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        if mode == "scalar":
            # the r02 api_vs_fused program: per-record UDFs through host
            # Python loops — what a user writes first, and the gap the
            # whole-graph fusion refactor exists to close
            ds = ds.filter(s_filter)
            keyed = ds.key_by(s_key)
        else:
            ds = ds.filter(t_filter, traceable=True)
            keyed = ds.key_by(t_key, traceable=True)
        win = (
            keyed.window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
            .aggregate("count")
        )
        sink = win.collect()
        return env, sink

    def run(n, mode, columnar=True):
        env, sink = build(n, mode, columnar)
        t0 = time.perf_counter()
        env.execute()
        return sink.results, n / max(time.perf_counter() - t0, 1e-9)

    env_probe, _ = build(batch, "fused")
    runners, _ = build_runners(plan(env_probe._sinks), env_probe.config)
    fused_selected = any(
        type(r).__name__ == "DeviceChainRunner" for r in runners)

    # ---- parity gate: row mode (every operator emits raw keys there),
    # THREE-way exact equality — fused vs today's chain path vs the
    # per-record scalar program; counts are ints, comparison is exact
    n_parity = max(events // 8, batch)
    rows = {
        mode: sorted((int(k), int(v)) for k, v in
                     run(n_parity, mode, columnar=False)[0])
        for mode in ("fused", "chain", "scalar")
    }
    parity = (
        len(rows["fused"]) > 0
        and rows["fused"] == rows["chain"] == rows["scalar"]
    )

    # ---- timed runs: interleaved max-of-N sweeps, the PR-3 dataplane
    # protocol — the sandboxed 2-vCPU host sees multi-x scheduler noise,
    # and interleaving means a calm window benefits every configuration
    # (max-of-N estimates capability the way min-of-N estimates latency).
    # The parity pass above compiled the small shapes; one warmup per
    # jitted mode covers the full-size shapes. The slow paths run fewer
    # events (their per-event rate is flat; they are the gap being
    # measured, not re-validated).
    run(batch * 12, "fused")
    run(batch * 12, "chain")
    tps_fused = tps_chain = tps_scalar = 0.0
    res_fused = []
    for _sweep in range(3):
        res_fused, t = run(events, "fused")
        tps_fused = max(tps_fused, t)
        _r, t = run(max(events // 4, batch), "chain")
        tps_chain = max(tps_chain, t)
        _r, t = run(max(events // 8, batch), "scalar")
        tps_scalar = max(tps_scalar, t)
    return {
        "api_path_tuples_per_sec": round(tps_fused, 1),
        "chain_runner_tuples_per_sec": round(tps_chain, 1),
        "scalar_api_tuples_per_sec": round(tps_scalar, 1),
        "speedup_vs_chain_runner": round(tps_fused / max(tps_chain, 1e-9), 2),
        "speedup_vs_scalar_api": round(tps_fused / max(tps_scalar, 1e-9), 2),
        "parity": bool(parity),
        "fused_selected": bool(fused_selected),
        "windows_emitted": len(res_fused),
        "events": events,
        "num_keys": NUM_KEYS,
        "window_ms": WINDOW_MS,
        "slide_ms": SLIDE_MS,
        "columnar_output": True,
        "workload": "ysb_sliding_count_datastream_api",
    }


def correlated_windows_microbench(events: Optional[int] = None,
                                  batch: int = 65536,
                                  sweeps: int = 3) -> dict:
    """Shared-partials scenario (ISSUE-14, Factor Windows): ONE keyed
    stream aggregated into THREE correlated tumbling windows — 1m, 5m,
    1h — through two execution shapes on the same data:

      - shared (execution.window.shared-partials true, the default): the
        sharing optimizer (graph/window_sharing.py) collapses the three
        window() siblings into ONE shared-partial device program — slices
        ingest once at the gcd granule (1m) and every member window
        derives its result from the shared ring at fire time;
      - independent (shared-partials false): three separate fused device
        programs, each re-scanning the stream — exactly what the job paid
        before the optimizer existed.

    `parity` is exact per-window result equality between the two shapes;
    `shared_selected` pins that translation actually built ONE
    SharedWindowRunner (the reroute gate). A mesh leg re-runs both shapes
    sharded over the visible device mesh (the virtual 8-device CPU mesh
    in the gate; real chips on hardware), so the sharing speedup is
    tracked on BOTH the single-chip and mesh paths."""
    import jax
    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.config import Configuration, ExecutionOptions, ParallelOptions
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.fusion import plan_device_chains
    from flink_tpu.graph.transformation import plan
    from flink_tpu.graph.window_sharing import plan_shared_windows
    from flink_tpu.runtime.executor import build_runners

    # default batch 65536 (the executor default): the sharing win is the
    # (N-1) saved ingest scans, a PER-RECORD cost — small batches leave the
    # per-step ring traffic dominant and bury it
    events = events or int(
        os.environ.get("BENCH_CORRELATED_EVENTS", str(1 << 22)))
    span_event_ms = 2 * 3_600_000       # 2h of event time: two 1h windows
    window_sizes_ms = (60_000, 300_000, 3_600_000)

    def source(n):
        def gen(idx):
            camp = (idx * 2654435761) % NUM_KEYS
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            ts = 10_000 + idx * span_event_ms // n
            return Batch(col, ts.astype(np.int64))

        return DataGeneratorSource(gen, n)

    t_filter = lambda col: col[:, 1] < 0.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype(jnp.int32)           # noqa: E731

    def build(n, shared: bool, mesh: bool, columnar: bool = True):
        cfg = Configuration()
        cfg.set(ExecutionOptions.SHARED_PARTIALS, shared)
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, NUM_KEYS)
        cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, columnar)
        if mesh:
            cfg.set(ParallelOptions.MESH_ENABLED, True)
        env = StreamExecutionEnvironment.get_execution_environment(cfg)
        ds = env.from_source(
            source(n),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        ds = ds.filter(t_filter, traceable=True)
        keyed = ds.key_by(t_key, traceable=True)
        sinks = [
            keyed.window(TumblingEventTimeWindows.of(sz)).aggregate("count")
            .collect()
            for sz in window_sizes_ms
        ]
        return env, sinks

    def run(n, shared, mesh, columnar=True):
        env, sinks = build(n, shared, mesh, columnar)
        t0 = time.perf_counter()
        env.execute()
        return ([s.results for s in sinks],
                n / max(time.perf_counter() - t0, 1e-9))

    # planner probe: the optimizer must classify ONE group of 3 and the
    # executor must build ONE SharedWindowRunner (the reroute gate)
    env_probe, _ = build(batch, shared=True, mesh=False)
    graph = plan(env_probe._sinks)
    chain_plans, _abs = plan_device_chains(graph)
    sw_plans = plan_shared_windows(graph, chain_plans)
    runners, _ = build_runners(graph, env_probe.config)
    shared_selected = any(
        type(r).__name__ == "SharedWindowRunner" for r in runners)
    est_factor = (sw_plans[0].estimated_sharing_factor if sw_plans else 0.0)

    def leg(mesh: bool) -> dict:
        # parity: row mode, exact per-window equality shared vs independent
        n_parity = max(events // 8, batch)
        rows_s = [sorted((int(k), int(v)) for k, v in r)
                  for r in run(n_parity, True, mesh, columnar=False)[0]]
        rows_i = [sorted((int(k), int(v)) for k, v in r)
                  for r in run(n_parity, False, mesh, columnar=False)[0]]
        parity = all(len(a) > 0 and a == b for a, b in zip(rows_s, rows_i))
        # timed: interleaved max-of-3 sweeps (the PR-3 protocol — a calm
        # scheduler window benefits both shapes)
        run(batch * 12, True, mesh)
        run(batch * 12, False, mesh)
        tps_s = tps_i = 0.0
        for _sweep in range(sweeps):
            _r, t = run(events, True, mesh)
            tps_s = max(tps_s, t)
            _r, t = run(events, False, mesh)
            tps_i = max(tps_i, t)
        return {
            "shared_tuples_per_sec": round(tps_s, 1),
            "independent_tuples_per_sec": round(tps_i, 1),
            "speedup_vs_independent": round(tps_s / max(tps_i, 1e-9), 2),
            "parity": bool(parity),
            "windows_emitted": [len(r) for r in rows_s],
        }

    result = {
        **leg(mesh=False),
        "shared_selected": bool(shared_selected),
        "groups_planned": len(sw_plans),
        "sharing_factor_estimate": round(est_factor, 2),
        "granule_ms": sw_plans[0].granule_ms if sw_plans else None,
        "events": events,
        "num_keys": NUM_KEYS,
        "window_sizes_ms": list(window_sizes_ms),
        "workload": "correlated_1m_5m_1h_tumbling_count",
    }
    n_dev = len(jax.devices())
    if n_dev >= 2 and NUM_KEYS % n_dev == 0:
        mesh_leg = leg(mesh=True)
        mesh_leg["devices"] = n_dev
        result["mesh"] = mesh_leg
    else:
        result["mesh"] = {"skipped": f"{n_dev} device(s) visible"}
    return result


def child_correlated() -> None:
    """Correlated-windows child: CPU-pinned with the 8-device virtual mesh
    forced, so the mesh leg of the sharing scenario exercises a real
    sharded shared-partial program."""
    _emit({"event": "start", "device": "cpu-correlated", "pid": os.getpid()})
    _emit({"event": "result", "result": correlated_windows_microbench()})


def run_correlated_child(timeout_s: float = 420.0) -> dict:
    """Correlated-windows microbench in a CPU-pinned child on the forced
    8-device virtual mesh (single-chip leg + mesh leg in one child)."""
    return _run_cpu_child('correlated', timeout_s, force_mesh=True)


def sql_path_microbench(events: Optional[int] = None,
                        batch: int = 8192,
                        span_event_ms: int = 64_000) -> dict:
    """SQL front-door scenario (ISSUE-13): the YSB sliding count written
    as SQL — `SELECT campaign, COUNT(*) ... GROUP BY campaign, HOP(...)`
    over a columnar table — through THREE paths in one process on the
    same data:

      - SQL-fused (table.device-fusion true, the default): the planner
        (flink_tpu/planner) lowers the statement onto the same
        whole-graph-fusion StepGraph a hand-built DataStream job takes —
        DeviceChainRunner runs filter + key/value extraction + window as
        ONE compiled superscan;
      - interpreted table path (table.device-fusion false): the legacy
        TableEnvironment translation — per-record row view, host keying,
        per-batch device window — what every SQL statement paid before;
      - hand-built DataStream-fused: the SAME program written against the
        fluent API with traceable UDFs, with the SAME SQL-shaped output
        row assembly, so `ratio_vs_datastream_fused` isolates what the
        SQL front door costs over hand fusion (the ~1.2x acceptance bar)
        rather than re-measuring the row-materialization tax both pay.

    `parity` is exact three-way row equality; `fused_selected` pins that
    graph translation actually chose DeviceChainRunner for the SQL job
    (the reroute gate) AND the planner reported the fused path. A session
    -window statement additionally runs through the same TableEnvironment
    to pin the fallback contract: it must EXECUTE on the interpreted path
    with its catalogued reason attributed, not fail."""
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import Configuration, ExecutionOptions, TableOptions
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import build_runners
    from flink_tpu.table import TableEnvironment, TableSchema

    events = events or int(os.environ.get("BENCH_SQL_EVENTS", str(1 << 21)))

    def source(n):
        def gen(idx):
            camp = (idx * 2654435761) % NUM_KEYS
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            ts = 10_000 + idx * span_event_ms // n
            return Batch(col, ts.astype(np.int64))

        return DataGeneratorSource(gen, n)

    SQL = (
        "SELECT campaign, COUNT(*) AS views, WINDOW_END AS wend FROM ysb "
        "WHERE event_type < 0.5 GROUP BY campaign, "
        f"HOP(rowtime, INTERVAL '{SLIDE_MS}' MILLISECOND, "
        f"INTERVAL '{WINDOW_MS}' MILLISECOND)"
    )

    def config(fused: bool) -> Configuration:
        cfg = Configuration()
        cfg.set(TableOptions.DEVICE_FUSION, fused)
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, NUM_KEYS)
        return cfg

    def build_sql(n, fused):
        env = StreamExecutionEnvironment.get_execution_environment(config(fused))
        tenv = TableEnvironment(env)
        stream = env.from_source(
            source(n),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        tenv.register_table(
            "ysb", stream,
            TableSchema(["campaign", "event_type", "rowtime"],
                        rowtime="rowtime",
                        field_types=["int", "float", "int"]),
            columnar=True,
        )
        sink = tenv.sql_query(SQL).collect()
        return env, tenv, sink

    # shared UDF objects across runs: compiled chain executables memoize on
    # fn identity, so warmup pays compilation once (api_path economics)
    t_filter = lambda col: col[:, 1] < 0.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype("int32")             # noqa: E731

    def ds_to_row(rec, ts):
        # the SQL statement's output shape, hand-written: what a user
        # replacing SQL with the fluent API would still have to emit
        return {"campaign": rec[0], "views": rec[1], "wend": ts + 1}

    def build_ds(n):
        env = StreamExecutionEnvironment.get_execution_environment(config(True))
        ds = env.from_source(
            source(n),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        win = (
            ds.filter(t_filter, traceable=True)
            .key_by(t_key, traceable=True)
            .window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
            .aggregate("count")
        )
        sink = win.map_with_timestamp(ds_to_row, name="sql_shape_output").collect()
        return env, sink

    def norm(rows):
        return sorted((int(r["campaign"]), int(r["wend"]), int(r["views"]))
                      for r in rows)

    def run_sql(n, fused):
        env, _tenv, sink = build_sql(n, fused)
        t0 = time.perf_counter()
        env.execute()
        return sink.results, n / max(time.perf_counter() - t0, 1e-9)

    def run_ds(n):
        env, sink = build_ds(n)
        t0 = time.perf_counter()
        env.execute()
        return sink.results, n / max(time.perf_counter() - t0, 1e-9)

    # ---- reroute gate: the SQL program's own graph must translate to
    # DeviceChainRunner AND the planner must report the fused path
    env_probe, tenv_probe, _ = build_sql(batch, True)
    probe_runners, _ = build_runners(plan(env_probe._sinks), env_probe.config)
    report = tenv_probe.last_plan_report
    fused_selected = bool(
        any(type(r).__name__ == "DeviceChainRunner" for r in probe_runners)
        and report is not None and report.fused
    )

    # ---- fallback contract: an unsupported statement EXECUTES on the
    # interpreted path with its reason attributed (never fails)
    env_fb = StreamExecutionEnvironment.get_execution_environment(config(True))
    tenv_fb = TableEnvironment(env_fb)
    tenv_fb.from_rows(
        "pay",
        [{"user": i % 5, "amount": float(i % 3), "rowtime": i * 100}
         for i in range(512)],
        TableSchema(["user", "amount", "rowtime"], rowtime="rowtime",
                    field_types=["int", "float", "int"]),
    )
    fb_rows = tenv_fb.execute_sql_to_list(
        "SELECT user, COUNT(*) AS n FROM pay "
        "GROUP BY user, SESSION(rowtime, INTERVAL '1' SECOND)")
    fb_report = tenv_fb.last_plan_report
    fallback_attributed = bool(
        fb_rows and fb_report is not None
        and fb_report.path == "interpreted"
        and fb_report.reason == "session-window")

    # ---- parity gate: exact three-way row equality. The interpreted path
    # is per-record host work; a reduced slice keeps the gate O(seconds)
    # while still covering every window shape the others see.
    n_parity = max(events // 16, batch)
    rows_fused = norm(run_sql(n_parity, True)[0])
    rows_interp = norm(run_sql(n_parity, False)[0])
    rows_ds = norm(run_ds(n_parity)[0])
    parity = bool(len(rows_fused) > 0
                  and rows_fused == rows_interp == rows_ds)

    # ---- timed runs: interleaved max-of-N sweeps (PR-3 protocol); the
    # interpreted path runs fewer events — its per-event rate is flat and
    # it IS the gap being measured
    run_sql(batch * 12, True)
    run_ds(batch * 12)
    tps_sql = tps_interp = tps_ds = 0.0
    res_sql = []
    for _sweep in range(3):
        res_sql, t = run_sql(events, True)
        tps_sql = max(tps_sql, t)
        _r, t = run_sql(max(events // 16, batch), False)
        tps_interp = max(tps_interp, t)
        _r, t = run_ds(events)
        tps_ds = max(tps_ds, t)
    return {
        "sql_tuples_per_sec": round(tps_sql, 1),
        "interpreted_tuples_per_sec": round(tps_interp, 1),
        "datastream_fused_tuples_per_sec": round(tps_ds, 1),
        "speedup_vs_interpreted": round(tps_sql / max(tps_interp, 1e-9), 2),
        "ratio_vs_datastream_fused": round(tps_ds / max(tps_sql, 1e-9), 3),
        "parity": parity,
        "fused_selected": fused_selected,
        "fallback_attributed": fallback_attributed,
        "fallback_reason_demo": getattr(fb_report, "reason", None),
        "windows_emitted": len(res_sql),
        "events": events,
        "num_keys": NUM_KEYS,
        "window_ms": WINDOW_MS,
        "slide_ms": SLIDE_MS,
        "statement": SQL,
        "workload": "ysb_sliding_count_sql",
    }


def device_plane_microbench(events: Optional[int] = None,
                            batch: int = 8192,
                            num_keys: Optional[int] = None,
                            span_event_ms: int = 64_000,
                            sweeps: int = 3) -> dict:
    """Device-plane observability scenario (ISSUE-8): the YSB sliding-count
    DataStream program on the fused device chain, run with the device
    plane ON and OFF in interleaved max-of-N sweeps.

    Emits the `device` block every BENCH_*.json now tracks:

      - compile observability: nonzero compile count, the recompile-event
        ring with cause attribution (the tail dispatch's power-of-two
        shape is a REAL batch-geometry recompile; a secondary small-key
        classic-path run grows its key dictionary past the initial
        capacity to induce a ring-doubling recompile),
      - per-operator roofline utilization (hbm/flops pct from XLA cost
        analysis over the DeviceTimer wall time),
      - per-phase ingest/fire/purge step counters from the superscan
        carry,
      - key-skew telemetry (uniform YSB keys read skew ~1; a hot-key
        regression shows up as the coefficient rising toward the
        key-group count),
      - measured overhead of the enabled plane vs gates-off (the <= 2%
        acceptance bar). The overhead RATIO uses median-of-N on both
        sides: max-of-N estimates capability for absolute throughput, but
        for an A/B ratio a single lucky scheduler draw on one side skews
        the quotient by tens of percent on the sandboxed 2-vCPU host —
        the median is the unbiased comparator (absolute tuples/s are
        still reported max-of-N for continuity with the other
        scenarios)."""
    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ObservabilityOptions,
    )
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import JobRuntime

    events = events or int(os.environ.get("BENCH_DEVICE_EVENTS", str(1 << 20)))
    num_keys = num_keys or NUM_KEYS

    def source(n):
        def gen(idx):
            camp = (idx * 2654435761) % num_keys
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            ts = 10_000 + idx * span_event_ms // n
            return Batch(col, ts.astype(np.int64))

        return DataGeneratorSource(gen, n)

    # fresh UDF objects per call: the chained executable cache keys on fn
    # identity, so the first stats-on run always observes its own compiles
    t_filter = lambda col: col[:, 1] < 0.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype(jnp.int32)           # noqa: E731

    def build_runtime(n, stats_on):
        cfg = Configuration()
        cfg.set(ExecutionOptions.CHAIN_FUSION, True)
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, num_keys)
        cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, True)
        # dispatch every 8 steps so the key-stats fold sees resident
        # device state mid-stream even at smoke scale (both sides of the
        # overhead A/B run the same geometry, so the ratio is unaffected)
        cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 8)
        cfg.set(ObservabilityOptions.DEVICE_STATS_ENABLED, stats_on)
        env = StreamExecutionEnvironment(cfg)
        ds = env.from_source(
            source(n),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        (ds.filter(t_filter, traceable=True)
           .key_by(t_key, traceable=True)
           .window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
           .aggregate("count")
           .collect())
        return JobRuntime(plan(env._sinks), cfg)

    # warmup both configurations AT FULL SCALE (the phase-counter flag is
    # part of the executable cache key, so each side owns its compiles and
    # an asymmetric warmup would bill one side's jit to its measured run),
    # banking the FIRST stats-on runtime's snapshot — it observed the
    # compiles
    rt_on = build_runtime(events, True)
    rt_on.run()
    snap = rt_on.device_snapshot()
    build_runtime(events, False).run()

    samples: dict = {True: [], False: []}
    for sweep in range(sweeps):
        # alternate the within-sweep order so a drifting machine biases
        # neither side
        order = (True, False) if sweep % 2 == 0 else (False, True)
        for stats_on in order:
            rt = build_runtime(events, stats_on)
            t0 = time.perf_counter()
            rt.run()
            samples[stats_on].append(
                events / max(time.perf_counter() - t0, 1e-9))
    tps_on, tps_off = max(samples[True]), max(samples[False])
    med = lambda xs: sorted(xs)[len(xs) // 2]               # noqa: E731

    # ring-doubling induction: the CLASSIC fused path starts its key
    # capacity at min(1024, configured) and doubles with the key
    # dictionary — a >1024-key stream recompiles with cause attribution
    ring_causes: list = []
    try:
        from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
        from flink_tpu.metrics.device_stats import CompileTracker
        from flink_tpu.runtime.fused_window_operator import FusedWindowOperator

        op = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "count",
                                 key_capacity=1 << 10, superbatch_steps=4,
                                 chunk=256)
        tracker = CompileTracker()
        op.attach_device_stats(tracker)
        rng = np.random.default_rng(7)
        for s in range(12):
            # narrow key range first so dispatches run at the initial
            # capacity, THEN widen past it — the dictionary growth doubles
            # the ring and the next dispatch recompiles with cause
            # attribution
            hi = 512 if s < 6 else 1536
            keys = rng.integers(0, hi, 512)
            op.process_batch(keys, np.ones(512, np.float32),
                             np.full(512, s * 300, np.int64))
            op.process_watermark(s * 300)
        from flink_tpu.core.time import MAX_WATERMARK

        op.process_watermark(MAX_WATERMARK)
        ring_causes = [e["cause"] for e in tracker.events()
                       if e.get("recompile")]
    except Exception as e:  # noqa: BLE001 — the block must survive
        ring_causes = [f"error: {e!r}"[:120]]

    comp = snap["compile"]
    op_entries = [e for e in snap["operators"].values() if "compile" in e]
    roof = op_entries[0] if op_entries else {}
    keys_blk = (op_entries[0].get("keys", {}) if op_entries else {})
    med_on, med_off = med(samples[True]), med(samples[False])
    overhead = ((med_off - med_on) / max(med_off, 1e-9)) * 100.0
    return {
        "tuples_per_sec_on": round(tps_on, 1),
        "tuples_per_sec_off": round(tps_off, 1),
        "overhead_pct": round(overhead, 2),
        "numCompiles": int(comp["numCompiles"]),
        "numRecompiles": int(comp["numRecompiles"]),
        "compileTimeMsTotal": comp["compileTimeMsTotal"],
        "recompileStorm": int(comp["recompileStorm"]),
        "recompile_causes": sorted({e["cause"] for e in comp["events"]
                                    if e.get("recompile")} | set(ring_causes)),
        "hbmUtilizationPct": roof.get("hbmUtilizationPct", 0.0),
        "flopsUtilizationPct": roof.get("flopsUtilizationPct", 0.0),
        "phases": roof.get("phases", {}),
        "keySkew": keys_blk.get("keySkew"),
        "activeKeys": keys_blk.get("activeKeys", 0),
        "hotKeys": (keys_blk.get("hotKeys") or [])[:3],
        "events": events,
        "num_keys": num_keys,
        "workload": "ysb_sliding_count_datastream_api",
    }


def child_device_plane() -> None:
    """Device-plane child: CPU-pinned like child_api_path (same-backend
    overhead comparison; the chip belongs to the chip child)."""
    _emit({"event": "start", "device": "cpu-device-plane", "pid": os.getpid()})
    _emit({"event": "result", "result": device_plane_microbench()})


def _run_cpu_child(label: str, timeout_s: float, *,
                   force_mesh: bool = False) -> dict:
    """Run `bench.py --child <label>` CPU-pinned and return its result
    event, or an error dict the parent turns into a non-zero exit. This is
    THE child protocol (env merge + reversed-stdout scan for the result
    event), single-sourced: the scenarios ride it and a per-scenario copy
    must never drift. The child's stderr is this process's stderr.
    `force_mesh` forces an 8-device virtual CPU mesh via XLA_FLAGS — the
    multichip scenario and the chaos chip-loss scenario need devices to
    lose."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if force_mesh:
        env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8"
                            ).strip()
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             label, "0", "0", "0"],
            stdout=subprocess.PIPE, text=True, timeout=timeout_s, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"{label} child exceeded {timeout_s:.0f} s"}
    for line in reversed(r.stdout.splitlines()):
        if line.startswith("{"):
            obj = json.loads(line)
            if obj.get("event") == "result":
                return obj["result"]
    return {"error": f"no result event from {label} child "
                     f"(exit code {r.returncode})"}


def run_chip_child(timeout_s: float) -> Optional[dict]:
    """Run the chip child alone, echo its progress events, and return its
    newest result (`result_final` carries the secondary configs, `result`
    the headline only) — None when it ended without one. The child is
    killed at `timeout_s`; its stderr is this process's stderr."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", "tpu",
         str(SPAN_STEPS), str(LOG2_BATCH), str(SPANS)],
        stdout=subprocess.PIPE, text=True,
    )
    killer = threading.Timer(timeout_s, proc.kill)
    killer.start()
    result = None
    try:
        for line in proc.stdout:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if obj.get("event") in ("result", "result_final"):
                result = obj["result"]
            else:
                print(line, flush=True)
        proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0 and result is not None:
        result["error"] = f"chip child exit code {proc.returncode}"
    return result


def run_device_plane_child(timeout_s: float = 300.0) -> dict:
    """Device-plane microbench in a CPU-pinned child."""
    return _run_cpu_child('device-plane', timeout_s)


def child_api_path() -> None:
    """API-path child: CPU-pinned — the comparison is CPU-jit vs CPU-jit
    (same backend both paths), and the chip belongs to the chip child."""
    _emit({"event": "start", "device": "cpu-api-path", "pid": os.getpid()})
    _emit({"event": "result", "result": api_path_microbench()})


def run_api_path_microbench_child(timeout_s: float = 300.0) -> dict:
    """API-path microbench in a CPU-pinned child (same backend both paths)."""
    return _run_cpu_child('api-path', timeout_s)


def latency_frontier_microbench(events: Optional[int] = None,
                                batch: int = 8192) -> dict:
    """The latency x throughput frontier of the flagship fused YSB job.

    Throughput numbers alone hide the quantity a serving user feels: how
    long after a window's event-time close its result is host-visible.
    This scenario drives the fused filter→key_by→sliding-count program
    through an OPEN-LOOP, arrival-paced generator — event timestamps
    follow a fixed wall-clock arrival schedule (t0 + i/rate), so when the
    pipeline falls behind, the backlog shows up as emission latency
    instead of being absorbed by the source slowing down (closed-loop
    sources measure the pipeline's speed; open-loop measures its lag).

    Legs: measured peak (unpaced, plane on vs off — the <2% overhead
    budget of the emission-latency plane), then 25/50/100% of that peak,
    each reporting p50/p99/p999 emission latency from the job's own
    log-bucket histograms (client.latency_report(), the /jobs/:id/latency
    payload) plus the stall-attribution counts (checkpointing runs during
    the paced legs so tail outliers have control spans to land on).

    Parity: every paced leg's (key, count) multiset must EXACTLY equal a
    host-side numpy oracle computed from the same deterministic arrival
    schedule — pacing must never change results, only their timing.
    """
    import shutil
    import tempfile

    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import (
        CheckpointingOptions,
        Configuration,
        ExecutionOptions,
        ObservabilityOptions,
    )
    from flink_tpu.connectors.source import (
        Batch,
        Source,
        SourceReader,
        SourceSplit,
        SplitEnumerator,
    )
    from flink_tpu.core.watermarks import WatermarkStrategy

    events = events or int(
        os.environ.get("BENCH_LATENCY_EVENTS", str(1 << 20)))
    leg_s = float(os.environ.get("BENCH_LATENCY_LEG_S", "2.5"))
    sweeps = int(os.environ.get("BENCH_LATENCY_SWEEPS", "3"))
    # distinct geometry from the api-path scenario (the bench-gate rule:
    # never share another family's cached superscan shapes); windows turn
    # over every FR_SLIDE ms of WALL time here, so even a short paced leg
    # fires hundreds of windows to sample
    FR_KEYS, FR_WINDOW, FR_SLIDE = 512, 2_000, 500

    class _FrontierReader(SourceReader):
        """YSB columns on a wall-anchored arrival schedule. Paced mode
        stamps ts from the SCHEDULE (t0 + i/rate) and sleeps only when
        ahead of it — never when behind (open loop); unpaced mode stamps
        the current wall clock and never sleeps (the peak probe)."""

        def __init__(self, rate: Optional[float]):
            self._rate = rate
            self._next = 0
            self._end = 0
            self.t0_ms: Optional[float] = None

        def add_split(self, split: SourceSplit) -> None:
            self._next = split.payload["start"]
            self._end = split.payload["end"]

        def poll_batch(self, max_records: int) -> Optional[Batch]:
            if self._next >= self._end:
                return None
            n = min(max_records, self._end - self._next)
            idx = np.arange(self._next, self._next + n, dtype=np.int64)
            self._next += n
            now = time.time() * 1000.0
            if self.t0_ms is None:
                self.t0_ms = now
            if self._rate is None:
                ts = np.full(n, int(now), dtype=np.int64)
            else:
                ts = (self.t0_ms + idx * (1000.0 / self._rate)
                      ).astype(np.int64)
                due = self.t0_ms + (self._next / self._rate) * 1000.0
                wait_s = (due - now) / 1000.0
                if wait_s > 0:
                    time.sleep(wait_s)
            camp = (idx * 2654435761) % FR_KEYS
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            return Batch(col, ts)

        def snapshot_position(self) -> dict:
            return {"next": self._next, "end": self._end}

        def restore_position(self, state: dict) -> None:
            self._next = state["next"]
            self._end = state["end"]

    class _FrontierSource(Source):
        def __init__(self, n: int, rate: Optional[float]):
            self.n = n
            self.rate = rate
            self.reader: Optional[_FrontierReader] = None

        def create_enumerator(self) -> SplitEnumerator:
            return SplitEnumerator(
                [SourceSplit("frontier-0", {"start": 0, "end": self.n})])

        def create_reader(self) -> SourceReader:
            self.reader = _FrontierReader(self.rate)
            return self.reader

    # one set of UDF objects for every leg: compiled chain executables
    # memoize on fn identity, so the warmup leg pays compilation once
    t_filter = lambda col: col[:, 1] < 0.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype(jnp.int32)           # noqa: E731

    lat_target_ms = int(os.environ.get("BENCH_LATENCY_TARGET_MS", "10"))

    def run_leg(n, rate, *, plane_on=True, chk_dir=None, latency=False,
                name="frontier"):
        cfg = Configuration()
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, FR_KEYS)
        cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, False)
        if latency:
            # latency-mode leg: same program, execution.latency.* on —
            # the controller shrinks the superbatch at light load and the
            # in-flight ring overlaps host prep with device dispatch
            from flink_tpu.config import LatencyOptions
            cfg.set(LatencyOptions.TARGET_MS, lat_target_ms)
            cfg.set(LatencyOptions.MAX_INFLIGHT, 2)
            # smoke legs last ~1 s; the production default half-second
            # dwell would pin the rung near the full span for most of a
            # short leg, measuring the warm-up hold instead of the mode
            cfg.set(LatencyOptions.MIN_DWELL_MS, 100)
        if not plane_on:
            cfg.set(ObservabilityOptions.EMISSION_LATENCY_ENABLED, False)
        if chk_dir is not None:
            cfg.set(CheckpointingOptions.INTERVAL_MS, 250)
            cfg.set(CheckpointingOptions.DIRECTORY, chk_dir)
        env = StreamExecutionEnvironment(cfg)
        src = _FrontierSource(n, rate)
        ds = env.from_source(
            src,
            watermark_strategy=WatermarkStrategy
            .for_bounded_out_of_orderness(0),
        )
        ds = ds.filter(t_filter, traceable=True)
        keyed = ds.key_by(t_key, traceable=True)
        win = (keyed.window(SlidingEventTimeWindows.of(FR_WINDOW, FR_SLIDE))
               .aggregate("count"))
        sink = win.collect()
        t0 = time.perf_counter()
        client = env.execute_async(name)
        client.wait(240.0)
        wall = time.perf_counter() - t0
        return sink.results, wall, client, src

    def oracle(n, t0_ms, rate):
        """Host numpy oracle over the SAME deterministic schedule: the
        (key, count) multiset of every sliding window with content (the
        terminal watermark flushes them all)."""
        idx = np.arange(n, dtype=np.int64)
        kept = (idx % 3) == 0
        key = ((idx * 2654435761) % FR_KEYS)[kept]
        ts = (t0_ms + idx * (1000.0 / rate)).astype(np.int64)[kept]
        nwin = FR_WINDOW // FR_SLIDE
        last_start = (ts // FR_SLIDE) * FR_SLIDE
        kk = np.tile(key, nwin)
        starts = np.concatenate(
            [last_start - j * FR_SLIDE for j in range(nwin)])
        sid = starts // FR_SLIDE
        codes = kk * np.int64(1 << 40) + (sid - sid.min())
        uniq, counts = np.unique(codes, return_counts=True)
        return sorted(zip((uniq >> 40).tolist(), counts.tolist()))

    # ---- peak probe: unpaced, plane on vs off, interleaved max-of-N
    # (max-of-N estimates capability under scheduler noise — the PR-3
    # dataplane protocol); the plane's throughput budget is <2% here
    # warm up at the MEASURED size: superscan executables specialize on
    # the superbatch group shape, so a smaller warmup would leave the
    # first measured leg paying the compile (and bias the on/off delta)
    run_leg(events, None)
    run_leg(events, None, plane_on=False)
    tps_on = tps_off = 0.0
    for _sweep in range(sweeps):
        _r, wall, _c, _s = run_leg(events, None, plane_on=True)
        tps_on = max(tps_on, events / max(wall, 1e-9))
        _r, wall, _c, _s = run_leg(events, None, plane_on=False)
        tps_off = max(tps_off, events / max(wall, 1e-9))
    peak = tps_on
    overhead_pct = (100.0 * (tps_off - tps_on) / tps_off
                    if tps_off > 0 else 0.0)

    # ---- the frontier: 25/50/100% of measured peak, open-loop
    points = {}
    all_parity = True
    samples_total = 0
    p99_at_full = 0.0
    for frac in (0.25, 0.5, 1.0):
        rate = max(peak * frac, batch * 2.0)
        n = int(min(max(rate * leg_s, batch * 4), events * 4))
        n = max(batch, n - n % batch)               # whole batches
        chk = tempfile.mkdtemp(prefix="flink-tpu-frontier-")
        try:
            results, wall, client, src = run_leg(
                n, rate, chk_dir=chk, name=f"frontier-{int(frac * 100)}")
        finally:
            shutil.rmtree(chk, ignore_errors=True)
        rep = client.latency_report()
        got = sorted((int(k), int(v)) for k, v in results)
        exp = oracle(n, src.reader.t0_ms, rate)
        parity = len(got) > 0 and got == exp
        all_parity = all_parity and parity
        att = rep.get("attribution") or {}
        samples_total += int(rep.get("samples", 0))
        points[str(int(frac * 100))] = {
            "target_rate_tuples_per_sec": round(rate, 1),
            "achieved_rate_tuples_per_sec": round(n / max(wall, 1e-9), 1),
            "events": n,
            "p50_emission_ms": rep.get("p50_ms", 0.0),
            "p99_emission_ms": rep.get("p99_ms", 0.0),
            "p999_emission_ms": rep.get("p999_ms", 0.0),
            "samples": int(rep.get("samples", 0)),
            "watermark_lag_ms": rep.get("watermarkLagMs", 0.0),
            "parity": bool(parity),
            "stall_outliers": int(att.get("outliers", 0)),
            "stall_attributed": {k: int(v.get("count", 0)) for k, v in
                                 (att.get("attributed") or {}).items()},
            "stall_unattributed": int(att.get("unattributed", 0)),
        }
        if frac == 1.0:
            p99_at_full = rep.get("p99_ms", 0.0)

    # ---- latency mode: the SAME program with execution.latency.* on.
    # Peak probe first (unpaced = 100% load): the controller must read
    # the saturated arrival rate, escalate to the full span, and keep
    # throughput within budget of throughput mode (peak_fraction) — the
    # mode's cost when the fleet is busy. The donated executables live in
    # separate cache entries, so warm up at the measured size first, then
    # a short paced warm leg pre-compiles the small-rung geometries the
    # 25% leg will pick (bounded by the pow2 ladder — never a storm).
    run_leg(events, None, latency=True)
    lat_peak = 0.0
    for _sweep in range(sweeps):
        _r, wall, _c, _s = run_leg(events, None, latency=True)
        lat_peak = max(lat_peak, events / max(wall, 1e-9))
    # warm leg with the SAME n, rate, and checkpointing as the measured
    # 25% point: the controller walks the same rung descent, periodic
    # checkpoints flush the same mid-stream tails, and the end-of-stream
    # flush pads the same pow2 tails, so every donated geometry the
    # measured leg dispatches is already compiled (compile stalls would
    # otherwise land on the few windows a smoke leg fires and swamp its
    # p99)
    warm_rate = max(peak * 0.25, batch * 2.0)
    warm_n = int(min(max(warm_rate * leg_s, batch * 4), events * 4))
    warm_n = max(batch, warm_n - warm_n % batch)
    warm_chk = tempfile.mkdtemp(prefix="flink-tpu-frontier-lat-warm-")
    try:
        run_leg(warm_n, warm_rate, chk_dir=warm_chk, latency=True,
                name="frontier-lat-warm")
    finally:
        shutil.rmtree(warm_chk, ignore_errors=True)

    lat_points = {}
    lat_parity = True
    lat_p99_at_25 = 0.0
    lat_ach_at_100 = 0.0
    for frac in (0.25, 0.5, 1.0):
        rate = max(peak * frac, batch * 2.0)
        n = int(min(max(rate * leg_s, batch * 4), events * 4))
        n = max(batch, n - n % batch)               # whole batches
        # the 100% point is judged as a fraction of the throughput-mode
        # peak — itself the best of `sweeps` unpaced legs — so it gets
        # the same best-of-sweeps treatment: a one-off stall (e.g. a
        # checkpoint flush landing on a tail pad the warm leg never
        # compiled) must not masquerade as a throughput regression.
        # Parity still folds over EVERY repetition.
        best_ach = -1.0
        best_entry = None
        best_rep = None
        for _rep in range(sweeps if frac == 1.0 else 1):
            chk = tempfile.mkdtemp(prefix="flink-tpu-frontier-lat-")
            try:
                results, wall, client, src = run_leg(
                    n, rate, chk_dir=chk, latency=True,
                    name=f"frontier-lat-{int(frac * 100)}")
            finally:
                shutil.rmtree(chk, ignore_errors=True)
            rep = client.latency_report()
            got = sorted((int(k), int(v)) for k, v in results)
            exp = oracle(n, src.reader.t0_ms, rate)
            parity = len(got) > 0 and got == exp
            lat_parity = lat_parity and parity
            ach = n / max(wall, 1e-9)
            entry = {
                "target_rate_tuples_per_sec": round(rate, 1),
                "achieved_rate_tuples_per_sec": round(ach, 1),
                "events": n,
                "p50_emission_ms": rep.get("p50_ms", 0.0),
                "p99_emission_ms": rep.get("p99_ms", 0.0),
                "p999_emission_ms": rep.get("p999_ms", 0.0),
                "samples": int(rep.get("samples", 0)),
                "parity": bool(parity),
                # the /jobs/:id/latency controller block: rung, ring
                # depth, distinct compiled geometries (ladder-bounded)
                "controller": rep.get("latency_mode") or {},
            }
            if ach > best_ach:
                best_ach, best_entry, best_rep = ach, entry, rep
        lat_points[str(int(frac * 100))] = best_entry
        if frac == 0.25:
            lat_p99_at_25 = best_rep.get("p99_ms", 0.0)
        if frac == 1.0:
            lat_ach_at_100 = best_ach
    # the tracked peak fraction is the PACED comparison the acceptance bar
    # names: latency-mode throughput at the 100% load point over the
    # throughput-mode peak (the unpaced probe's wall clock folds in job
    # setup and is scheduler-noise-bound on a shared host; the paced
    # point is the apples-to-apples sustained-rate question)
    peak_fraction = lat_ach_at_100 / max(peak, 1e-9)

    return {
        "latency_frontier": {
            "peak_tuples_per_sec": round(peak, 1),
            "plane_on_tuples_per_sec": round(tps_on, 1),
            "plane_off_tuples_per_sec": round(tps_off, 1),
            "plane_overhead_pct": round(overhead_pct, 2),
            "load_points": points,
            "parity": bool(all_parity),
            "samples": samples_total,
            "window_ms": FR_WINDOW,
            "slide_ms": FR_SLIDE,
            "num_keys": FR_KEYS,
            "pacing": "open-loop-arrival",
            "workload": "ysb_sliding_count_paced_wall_clock",
            "latency_mode": {
                "target_ms": lat_target_ms,
                "max_inflight": 2,
                "peak_tuples_per_sec": round(lat_peak, 1),
                "peak_fraction": round(peak_fraction, 4),
                "load_points": lat_points,
                "parity": bool(lat_parity),
            },
        },
        "p99_emission_latency_ms": p99_at_full,
        "latency_mode_p99_ms": lat_p99_at_25,
        "latency_mode_peak_fraction": round(peak_fraction, 4),
    }


def child_latency_frontier() -> None:
    """Latency-frontier child: CPU-pinned like child_api_path (pacing is
    wall-clock-sensitive; the chip belongs to the chip child)."""
    _emit({"event": "start", "device": "cpu-latency-frontier",
           "pid": os.getpid()})
    _emit({"event": "result", "result": latency_frontier_microbench()})


def run_latency_frontier_child(timeout_s: float = 420.0) -> dict:
    """Latency-frontier microbench in a CPU-pinned child."""
    return _run_cpu_child('latency-frontier', timeout_s)


def health_microbench(events: Optional[int] = None,
                      batch: int = 8192,
                      num_keys: Optional[int] = None,
                      interval_ms: int = 50) -> dict:
    """History/doctor plane scenario (ISSUE-19): the flagship YSB-shaped
    keyed tumbling count through the MiniCluster with the metric-history
    sampler ticking at an aggressive `interval_ms` (20x the default rate
    — a conservative overestimate of steady-state sampler cost), then
    read back the two new planes the way a user would:

      - ``GET /jobs/:id/history`` (via client.history_report): the rings
        must be non-empty — counters recorded as rates, the emission
        histogram as per-sample p50/p99 sub-series;
      - ``GET /jobs/:id/doctor`` (via client.doctor_report): an
        undisturbed healthy run must produce a verdict (not "unknown" —
        that means the sampler never ticked);
      - sampler overhead measured from the history's own perf_counter
        self-timing (`sample_time_ms` / job wall time) — the <= 2%
        acceptance bar is judged on this number, measured not claimed.
    """
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ObservabilityOptions,
    )
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy

    events = events or int(os.environ.get("BENCH_HEALTH_EVENTS",
                                          str(1 << 19)))
    num_keys = num_keys or NUM_KEYS

    def source(n):
        def gen(idx):
            keys = ((idx * 2654435761) % num_keys).astype(np.int64)
            ts = 10_000 + idx * 64_000 // n
            return Batch(keys, ts.astype(np.int64))

        return DataGeneratorSource(gen, n)

    config = Configuration()
    config.set(ExecutionOptions.BATCH_SIZE, batch)
    config.set(ExecutionOptions.KEY_CAPACITY, num_keys)
    config.set(ObservabilityOptions.HISTORY_INTERVAL_MS, interval_ms)
    env = StreamExecutionEnvironment(config)
    stream = env.from_source(
        source(events),
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
    )
    sink = CollectSink()
    (stream.key_by(lambda col: col, vectorized=True)
           .window(TumblingEventTimeWindows.of(1000)).count()
           .sink_to(sink))
    t0 = time.perf_counter()
    client = env.execute_async("bench-health")
    client.wait(240)
    wall_s = max(time.perf_counter() - t0, 1e-9)

    hist = client.history_report()
    doc = client.doctor_report()
    series = hist.get("series", {})
    points = sum(len(s.get("points", ())) for s in series.values())
    rate_series = sum(1 for s in series.values()
                      if s.get("kind") == "counter-rate")
    overhead = (hist.get("sample_time_ms", 0.0) / (wall_s * 1000.0)) * 100.0
    return {
        "verdict": doc.get("verdict"),
        "verdict_score": doc.get("score"),
        "diagnoses": [{k: d.get(k) for k in ("family", "score")}
                      for d in doc.get("diagnoses", [])[:3]],
        "watchdog_events": doc.get("watchdog_events", 0),
        "sampler_overhead_pct": round(overhead, 4),
        "sample_count": hist.get("sample_count", 0),
        "sample_time_ms": hist.get("sample_time_ms", 0.0),
        "history_series": len(series),
        "history_points": points,
        "rate_series": rate_series,
        "interval_ms": interval_ms,
        "tuples_per_sec": round(events / wall_s, 1),
        "events": events,
        "num_keys": num_keys,
        "workload": "ysb_tumbling_count_minicluster",
    }


def child_health() -> None:
    """Health-plane child: CPU-pinned like child_api_path (sampler
    overhead is a same-backend wall-clock ratio; the chip belongs to the
    chip child)."""
    _emit({"event": "start", "device": "cpu-health", "pid": os.getpid()})
    _emit({"event": "result", "result": health_microbench()})


def run_health_child(timeout_s: float = 300.0) -> dict:
    """History/doctor microbench in a CPU-pinned child."""
    return _run_cpu_child('health', timeout_s)


def lint_summary() -> dict:
    """Full-registry lint over the installed package, timed — the
    `lint: {modules, rules, violations, analysis_ms}` block stamped into
    every BENCH_*.json next to `health`. Runs in-process (pure AST, no
    device), against the checked-in baseline so `violations` counts
    ACTIVE findings, not justified debt."""
    t0 = time.perf_counter()
    try:
        import pathlib

        import flink_tpu
        from flink_tpu.lint import Baseline, run_lint

        pkg = pathlib.Path(flink_tpu.__file__).parent
        bl_path = pkg.parent / "lint_baseline.json"
        baseline = Baseline.load(bl_path) if bl_path.exists() else None
        report = run_lint(pkg, baseline=baseline)
        return {
            "modules": report.modules_scanned,
            "rules": len(report.rules),
            "violations": len(report.violations),
            "analysis_ms": round((time.perf_counter() - t0) * 1e3, 1),
        }
    except Exception as e:  # noqa: BLE001 — the stamp must never sink a run
        return {"error": f"{type(e).__name__}: {e}",
                "analysis_ms": round((time.perf_counter() - t0) * 1e3, 1)}


def child_sql_path() -> None:
    """SQL-path child: CPU-pinned like child_api_path — the three-way
    comparison is CPU-jit vs CPU-jit (same backend all paths), and the
    chip belongs to the chip child."""
    _emit({"event": "start", "device": "cpu-sql-path", "pid": os.getpid()})
    _emit({"event": "result", "result": sql_path_microbench()})


def run_sql_path_microbench_child(timeout_s: float = 300.0) -> dict:
    """SQL-path microbench in a CPU-pinned child (same backend all paths)."""
    return _run_cpu_child('sql-path', timeout_s)


def child_checkpoint() -> None:
    """Checkpoint-microbench child: CPU-pinned (a parent that touched jax
    would hold the chip and starve the chip child), and the control-plane
    cost being measured is host-side."""
    _emit({"event": "start", "device": "cpu-checkpoint", "pid": os.getpid()})
    _emit({"event": "result", "result": checkpoint_microbench()})


def run_checkpoint_microbench_child(timeout_s: float = 300.0) -> dict:
    """Checkpoint microbench in a CPU-pinned child."""
    return _run_cpu_child('checkpoint', timeout_s)


def multichip_microbench(events: Optional[int] = None,
                         batch: int = 8192,
                         num_keys: Optional[int] = None,
                         span_event_ms: int = 64_000,
                         sweeps: int = 2,
                         devices: int = 0,
                         zipf_s: float = 1.0) -> dict:
    """Multichip SPMD scenario (ISSUE-11): the SAME fused DataStream YSB
    program — from_source().filter().key_by().window().count() with
    traceable UDFs — run single-chip and sharded over the device mesh
    (parallel.mesh.enabled), same backend, same data:

      - `fused_selected` pins that graph translation chose the
        DeviceChainRunner (the user-facing path, not a hand-built kernel),
        and `sharded_selected` that the runner's operator actually targets
        the mesh (mesh_devices > 1) — a silent fall-back to single-chip
        would otherwise still read as perfect parity;
      - `parity` is exact row-mode result equality mesh vs single-chip
        (the single-chip fused path is itself oracle-gated by the api_path
        scenario, so the chain of custody reaches the host oracle);
      - `scaling_efficiency` = mesh tuples/s / (single-chip tuples/s x
        devices). On a real n-chip mesh the acceptance bar is >= 0.8x
        linear; on the virtual CPU mesh (this child, and CI) every "chip"
        timeshares one host, so the ratio only gates against catastrophic
        regressions — the structural keys are the contract;
      - the zipf(`zipf_s`) SKEWED variant re-runs both sides with a
        power-law key distribution and reports
        `skewed_scaling_efficiency` plus the per-device telemetry it
        exercises (meshLoadSkew, per-device records) — an imbalanced mesh
        must be measurable, not inferred (ROADMAP item 4a's first step).
    """
    import jax
    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ObservabilityOptions,
        ParallelOptions,
    )
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import JobRuntime, build_runners

    events = events or int(
        os.environ.get("BENCH_MULTICHIP_EVENTS", str(1 << 20)))
    num_keys = num_keys or NUM_KEYS
    from flink_tpu.parallel.mesh import usable_mesh_size

    avail = len(jax.devices())
    n = usable_mesh_size(devices, avail, num_keys)
    if n < 2:
        return {"error": f"no usable mesh ({avail} device(s), "
                         f"{num_keys} keys)", "devices": int(n)}

    # zipf keys via the single-sourced stateless sampler (zipf_keys), hot
    # ranks spread over the key-id space so the hot key-GROUPS (and with
    # contiguous ranges, the hot DEVICES) are deterministic
    perm = np.random.default_rng(11).permutation(num_keys)

    def source(count, skewed: bool):
        def gen(idx):
            if skewed:
                camp = zipf_keys(idx, num_keys, zipf_s, hot_perm=perm)
            else:
                camp = (idx * 2654435761) % num_keys
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            ts = 10_000 + idx * span_event_ms // count
            return Batch(col, ts.astype(np.int64))

        return DataGeneratorSource(gen, count)

    t_filter = lambda col: col[:, 1] < 0.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype(jnp.int32)           # noqa: E731

    def build(count, mesh_on, *, skewed=False, columnar=True, stats=False):
        cfg = Configuration()
        cfg.set(ExecutionOptions.CHAIN_FUSION, True)
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, num_keys)
        cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, columnar)
        cfg.set(ParallelOptions.MESH_ENABLED, mesh_on)
        if mesh_on:
            cfg.set(ParallelOptions.MESH_DEVICES, n)
        cfg.set(ObservabilityOptions.DEVICE_STATS_ENABLED, stats)
        if stats:
            # collect on every due tick so the smoke-scale run still folds
            cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 8)
            cfg.set(ObservabilityOptions.DEVICE_KEY_STATS_INTERVAL_MS, 0)
        env = StreamExecutionEnvironment(cfg)
        ds = env.from_source(
            source(count, skewed),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        sink = (ds.filter(t_filter, traceable=True)
                  .key_by(t_key, traceable=True)
                  .window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
                  .aggregate("count")
                  .collect())
        return env, sink

    # ---- reroute gate: translation must pick the fused runner AND the
    # runner must actually target the sharded pipeline
    env_probe, _ = build(batch, True)
    runners, _ = build_runners(plan(env_probe._sinks), env_probe.config)
    fused = [r for r in runners if type(r).__name__ == "DeviceChainRunner"]
    fused_selected = bool(fused)
    mesh_devices = fused[0].op.mesh_devices() if fused else 1
    sharded_selected = mesh_devices > 1

    def run(count, mesh_on, *, skewed=False, columnar=True):
        env, sink = build(count, mesh_on, skewed=skewed, columnar=columnar)
        t0 = time.perf_counter()
        env.execute()
        return sink.results, count / max(time.perf_counter() - t0, 1e-9)

    # ---- parity gates in row mode (raw keys), exact equality
    n_parity = max(events // 8, batch)
    parity = {}
    for skewed in (False, True):
        rows = {
            mesh_on: sorted((int(k), int(v)) for k, v in
                            run(n_parity, mesh_on, skewed=skewed,
                                columnar=False)[0])
            for mesh_on in (True, False)
        }
        parity[skewed] = (len(rows[True]) > 0 and rows[True] == rows[False])

    # ---- timed runs: interleaved max-of-N sweeps (the PR-3 protocol)
    run(batch * 12, True)
    run(batch * 12, False)
    tps = {(m, s): 0.0 for m in (True, False) for s in (True, False)}
    for _sweep in range(sweeps):
        for skewed in (False, True):
            for mesh_on in (True, False):
                _r, t = run(events, mesh_on, skewed=skewed)
                tps[(mesh_on, skewed)] = max(tps[(mesh_on, skewed)], t)

    # ---- per-device telemetry under imbalance: one skewed mesh run with
    # the device plane on; the [n, K_local] fold must SEE the hot devices
    mesh_load_skew = None
    per_device = []
    key_skew = None
    try:
        env_t, _sink = build(max(events // 4, batch * 8), True,
                             skewed=True, stats=True)
        rt = JobRuntime(plan(env_t._sinks), env_t.config)
        rt.run()
        snap = rt.device_snapshot()
        for entry in snap["operators"].values():
            keys_blk = entry.get("keys") or {}
            if keys_blk.get("perDevice"):
                mesh_load_skew = keys_blk.get("meshLoadSkew")
                per_device = [e["records"] for e in keys_blk["perDevice"]]
                key_skew = keys_blk.get("keySkew")
                break
    except Exception as e:  # noqa: BLE001 — the block must survive
        per_device = [f"error: {e!r}"[:120]]

    eff = tps[(True, False)] / max(tps[(False, False)] * n, 1e-9)
    eff_skewed = tps[(True, True)] / max(tps[(False, True)] * n, 1e-9)
    return {
        "devices": int(n),
        "tuples_per_sec": round(tps[(True, False)], 1),
        "single_chip_tuples_per_sec": round(tps[(False, False)], 1),
        "scaling_efficiency": round(eff, 4),
        "skewed_tuples_per_sec": round(tps[(True, True)], 1),
        "skewed_single_chip_tuples_per_sec": round(tps[(False, True)], 1),
        "skewed_scaling_efficiency": round(eff_skewed, 4),
        "parity": bool(parity[False]),
        "skewed_parity": bool(parity[True]),
        "fused_selected": bool(fused_selected),
        "sharded_selected": bool(sharded_selected),
        "mesh_load_skew": mesh_load_skew,
        "per_device_records": per_device[:16],
        "key_skew": key_skew,
        "zipf_s": zipf_s,
        "events": events,
        "num_keys": num_keys,
        "window_ms": WINDOW_MS,
        "slide_ms": SLIDE_MS,
        "workload": "ysb_sliding_count_datastream_api_spmd",
    }


def child_multichip() -> None:
    """Multichip child: CPU-pinned with a FORCED 8-device virtual mesh —
    the mesh promotion is exercised on host devices (on a four-chip host
    the same program rides ICI: chip_smoke.py leg 3)."""
    _emit({"event": "start", "device": "cpu-multichip", "pid": os.getpid()})
    _emit({"event": "result", "result": multichip_microbench()})


def run_multichip_child(timeout_s: float = 420.0) -> dict:
    """Multichip microbench in a CPU-pinned child on the 8-device virtual
    mesh (the same program rides ICI on real multi-chip hardware)."""
    return _run_cpu_child('multichip', timeout_s, force_mesh=True)


def millikey_microbench(events: Optional[int] = None,
                        batch: int = 8192,
                        num_keys: Optional[int] = None,
                        hot_capacity: int = 4096,
                        parity_keys: int = 1 << 15,
                        span_event_ms: int = 64_000,
                        zipf_s: float = 1.0,
                        admission_min_count: int = 2,
                        mesh: bool = True) -> dict:
    """Million-key state plane scenario (ISSUE-12, ROADMAP item 2): the
    YSB sliding-count DataStream job over a key vocabulary three orders
    of magnitude larger than the resident HBM capacity
    (state.tier.enabled): at most `hot_capacity` keys own device ring
    rows, the rest aggregate in the cold tier, and checkpoints are
    incremental (state.changelog.enabled).

    Gates, per variant (uniform + zipf(`zipf_s`)):

      - `parity`: exact row-mode equality of the TIERED run against the
        UNTIRED fused run at `parity_keys` cardinality (the untired
        operator materializes every key as an HBM row, so the oracle
        cannot hold the full vocabulary — that impossibility is the
        feature's premise) AND of the full-cardinality tiered run
        against a numpy host oracle over the identical record stream;
      - `resident_keys <= hot_capacity` with `evictions > 0`: the
        vocabulary actually bounds HBM instead of growing;
      - `incremental_ratio`: median per-checkpoint-interval changelog
        bytes / the materialized full-state base size — the < 0.25
        acceptance bar for delta-scaled snapshot cost;
      - `sharded_parity`: the same tiered job over the device mesh
        (parallel.mesh.enabled) when >= 2 devices are visible.
    """
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import (
        CheckpointingOptions,
        Configuration,
        ExecutionOptions,
        ParallelOptions,
        StateTierOptions,
    )
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    import statistics as _stats
    import tempfile as _tempfile

    events = events or int(
        os.environ.get("BENCH_MILLIKEY_EVENTS", str(1 << 18)))
    num_keys = num_keys or int(
        os.environ.get("BENCH_MILLIKEY_KEYS", str(10_000_000)))

    def keys_of(idx: np.ndarray, n_keys: int, skewed: bool) -> np.ndarray:
        if skewed:
            # the single-sourced STATELESS sampler (zipf_keys): the host
            # oracle re-generates the stream under different chunk
            # boundaries, so a chunk-seeded rng would diverge
            return zipf_keys(idx, n_keys, zipf_s)
        return ((idx * 2654435761) % n_keys).astype(np.int64)

    def ts_of(idx: np.ndarray, count: int) -> np.ndarray:
        return (10_000 + idx * span_event_ms // count).astype(np.int64)

    def source(count, n_keys, skewed):
        def gen(idx):
            return Batch(keys_of(idx, n_keys, skewed), ts_of(idx, count))

        return DataGeneratorSource(gen, count)

    def build(count, n_keys, skewed, *, tiered, cap, mesh_on=False,
              chk=None, admission=None):
        cfg = Configuration()
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, max(n_keys, 1024))
        if tiered:
            cfg.set(StateTierOptions.TIER_ENABLED, True)
            cfg.set(StateTierOptions.HOT_KEY_CAPACITY, cap)
            cfg.set(StateTierOptions.CHANGELOG_ENABLED, True)
            # the tiny-LFU doorkeeper: one-touch keys of the heavy tail
            # aggregate cold instead of churning hot rows — the realistic
            # operating point at key cardinality >> capacity
            cfg.set(StateTierOptions.ADMISSION_MIN_COUNT,
                    admission_min_count if admission is None else admission)
            if chk is not None:
                cfg.set(StateTierOptions.CHANGELOG_DIR,
                        os.path.join(chk, "changelog"))
                cfg.set(StateTierOptions.COLD_DIR, os.path.join(chk, "cold"))
        if chk is not None:
            cfg.set(CheckpointingOptions.INTERVAL_MS, 1)
            cfg.set(CheckpointingOptions.DIRECTORY, os.path.join(chk, "chk"))
        if mesh_on:
            cfg.set(ParallelOptions.MESH_ENABLED, True)
        env = StreamExecutionEnvironment(cfg)
        ds = env.from_source(
            source(count, n_keys, skewed),
            watermark_strategy=WatermarkStrategy
            .for_bounded_out_of_orderness(0),
        )
        sink = CollectSink()
        (ds.key_by(lambda col: col, vectorized=True)
           .window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
           .count()
           .sink_to(sink))
        return env, sink

    def run(count, n_keys, skewed, *, tiered, cap, mesh_on=False,
            chk=None, admission=None):
        env, sink = build(count, n_keys, skewed, tiered=tiered, cap=cap,
                          mesh_on=mesh_on, chk=chk, admission=admission)
        t0 = time.perf_counter()
        client = env.execute_async("millikey")
        client.wait(600)
        dt = max(time.perf_counter() - t0, 1e-9)
        rows = sorted((int(k), int(n)) for k, n in sink.results)
        return client, rows, count / dt

    def host_oracle(count, n_keys, skewed):
        """Expected (key, count) rows over ALL fired windows: every
        record lands in spw sliding windows — a pure numpy fold that
        holds the full vocabulary where the untired operator cannot."""
        out: dict = {}
        spw = WINDOW_MS // SLIDE_MS
        for lo in range(0, count, 1 << 18):
            idx = np.arange(lo, min(lo + (1 << 18), count), dtype=np.int64)
            k = keys_of(idx, n_keys, skewed)
            s = ts_of(idx, count) // SLIDE_MS
            for shift in range(spw):
                # window j = s - shift contains every record whose slide
                # granule is s, for shift in [0, spw)
                pairs, cnts = np.unique(
                    np.stack([k, s - shift], axis=1), axis=0,
                    return_counts=True)
                for (kk, jj), c in zip(pairs.tolist(), cnts.tolist()):
                    out[(kk, jj)] = out.get((kk, jj), 0) + c
        return sorted((kk, c) for (kk, _jj), c in out.items())

    result: dict = {"events": events, "num_keys": num_keys,
                    "hot_key_capacity": hot_capacity,
                    "parity_keys": parity_keys, "zipf_s": zipf_s,
                    "window_ms": WINDOW_MS, "slide_ms": SLIDE_MS,
                    "workload": "ysb_sliding_count_datastream_tiered"}

    for skewed, label in ((False, "uniform"), (True, "zipf")):
        blk: dict = {}
        # ---- reduced-cardinality exact parity: tiered vs untired fused
        n_par = min(events, max(batch * 8, 1 << 16))
        p_keys = min(parity_keys, num_keys)
        _c, rows_ref, _t = run(n_par, p_keys, skewed, tiered=False,
                               cap=hot_capacity)
        chk = _tempfile.mkdtemp(prefix="flink-tpu-millikey-")
        try:
            c_t, rows_t, _t2 = run(n_par, p_keys, skewed, tiered=True,
                                   cap=min(hot_capacity, p_keys // 8),
                                   chk=chk)
            blk["parity_vs_untired"] = (len(rows_t) > 0
                                        and rows_t == rows_ref)
            tier_par = _tier_payload(c_t)
            blk["parity_run_evictions"] = (tier_par or {}).get("evictions")
        finally:
            import shutil as _sh

            _sh.rmtree(chk, ignore_errors=True)

        # ---- full-cardinality tiered run: host-oracle parity, bounded
        # residency, throughput, incremental checkpoint ratio
        chk = _tempfile.mkdtemp(prefix="flink-tpu-millikey-")
        try:
            client, rows, tps = run(events, num_keys, skewed, tiered=True,
                                    cap=hot_capacity, chk=chk)
            expected = host_oracle(events, num_keys, skewed)
            blk["parity"] = len(rows) > 0 and rows == expected
            blk["tuples_per_sec"] = round(tps, 1)
            tier = _tier_payload(client)
            if tier is not None:
                blk.update(
                    vocab_size=tier["vocabSize"],
                    resident_keys=tier["residentKeys"],
                    evictions=tier["evictions"],
                    promotions=tier["promotions"],
                    spilled_bytes=tier["spilledBytes"],
                    cold_records=tier["coldRecords"],
                )
                blk["resident_bounded"] = \
                    tier["residentKeys"] <= hot_capacity
            mgr = _tier_manager(client)
            if mgr is not None and mgr.interval_bytes_history \
                    and mgr.last_base_bytes() > 0:
                med = _stats.median(mgr.interval_bytes_history)
                blk["changelog_interval_bytes_p50"] = int(med)
                blk["full_snapshot_bytes"] = mgr.last_base_bytes()
                blk["incremental_ratio"] = round(
                    med / mgr.last_base_bytes(), 6)
                blk["checkpoints"] = len(mgr.interval_bytes_history)
        finally:
            import shutil as _sh

            _sh.rmtree(chk, ignore_errors=True)
        result[label] = blk

    # ---- sharded variant: the same tiered job over the mesh
    import jax as _jax

    from flink_tpu.parallel.mesh import usable_mesh_size

    n_mesh = usable_mesh_size(0, len(_jax.devices()), hot_capacity) \
        if mesh else 1
    if n_mesh >= 2:
        n_par = min(events, max(batch * 4, 1 << 15))
        p_keys = min(parity_keys, num_keys)
        _c, rows_ref, _t = run(n_par, p_keys, False, tiered=False,
                               cap=hot_capacity)
        # admission doorkeeper off for this leg: the point is the
        # demote/promote machinery ON the mesh, so force churn. chk dir
        # given so the changelog/cold temp dirs are cleaned up with it.
        chk = _tempfile.mkdtemp(prefix="flink-tpu-millikey-")
        try:
            c_m, rows_m, _t2 = run(n_par, p_keys, False, tiered=True,
                                   cap=min(hot_capacity, p_keys // 8),
                                   mesh_on=True, admission=1, chk=chk)
            tier_m = _tier_payload(c_m)
        finally:
            import shutil as _sh

            _sh.rmtree(chk, ignore_errors=True)
        result["sharded"] = {
            "devices": int(n_mesh),
            "parity": len(rows_m) > 0 and rows_m == rows_ref,
            "evictions": (tier_m or {}).get("evictions"),
            "mesh_selected": bool(
                c_m._runtime is not None
                and c_m._runtime.mesh_devices() > 1),
        }
    else:
        result["sharded"] = {"devices": int(n_mesh), "skipped": True}

    # headline continuity keys
    result["parity"] = bool(result["uniform"].get("parity")
                            and result["zipf"].get("parity")
                            and result["uniform"].get("parity_vs_untired")
                            and result["zipf"].get("parity_vs_untired"))
    result["tuples_per_sec"] = result["uniform"].get("tuples_per_sec", 0.0)
    result["incremental_ratio"] = result["uniform"].get("incremental_ratio")
    return result


def _tier_payload(client) -> Optional[dict]:
    """The tier block of the job's device snapshot (MiniCluster path)."""
    try:
        snap = client._runtime.device_snapshot()
        for entry in snap["operators"].values():
            if entry.get("tier"):
                return entry["tier"]
    except Exception:  # noqa: BLE001 — the bench must survive
        return None
    return None


def _tier_manager(client):
    """The live TieredStateManager of the job's window runner."""
    try:
        for r in client._runtime.runners:
            t = getattr(getattr(r, "op", None), "tier", None)
            if t is not None:
                return t
    except Exception:  # noqa: BLE001
        return None
    return None


def child_millikey() -> None:
    """Millikey child: CPU-pinned with the 8-device virtual mesh forced,
    so the sharded tiered variant exercises a real mesh."""
    _emit({"event": "start", "device": "cpu-millikey", "pid": os.getpid()})
    _emit({"event": "result", "result": millikey_microbench()})


def run_millikey_child(timeout_s: float = 600.0) -> dict:
    """Millikey microbench in a CPU-pinned child on the virtual mesh."""
    return _run_cpu_child('millikey', timeout_s, force_mesh=True)


def skew_matrix_microbench(events: Optional[int] = None,
                           batch: int = 2048,
                           num_keys: Optional[int] = None,
                           span_event_ms: int = 64_000,
                           zipf_s: float = 1.0,
                           sweeps: int = 1) -> dict:
    """Skew scenario matrix (ISSUE-15, ROADMAP 4c): the PDSP-Bench
    parallelism x workload x skew reporting grid over the fused
    DataStream chain, plus the skew-ADAPTIVE flagship leg.

      - `cells`: every (workload, parallelism, skew) combination —
        workloads ysb_count (filter+keyBy+sliding count) and ysb_sum
        (same chain, value aggregation), parallelism 1 and the mesh,
        keys uniform and zipf(`zipf_s`) via the single-sourced stateless
        sampler (`zipf_keys`) — tuples/s per cell, with EXACT mesh vs
        single-chip row parity per (workload, skew);
      - the zipf leg's hot ranks are deliberately CLUSTERED into device
        0's key-groups (one hot key per group, so the placement is
        pathological but splittable) — the adjacent-hot-keys shape the
        static owner function cannot fix and the rebalancer exists to;
      - `combine_parity`: parallel.mesh.local-combine on vs off, byte
        parity (the perf-switch-not-semantics-switch proof at bench
        scale), plus `local_combine_active` pinning the combiner
        actually engaged;
      - the ADAPTIVE leg (`adaptive` block): the mesh zipf job with
        local-combine + skew-rebalance enabled on the in-process job
        master — `rebalances` (must be > 0 under this traffic),
        `post_rebalance_mesh_load_skew` vs `static_mesh_load_skew`, and
        `skewed_uniform_ratio` = adaptive zipf tput / uniform tput (the
        >= 0.8 acceptance bar is judged on real TPU hardware; the CPU
        mesh gates only catastrophic regressions).
    """
    import jax
    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ObservabilityOptions,
        ParallelOptions,
    )
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.transformation import plan
    from flink_tpu.parallel.mesh import usable_mesh_size
    from flink_tpu.parallel.routing import choose_key_groups
    from flink_tpu.runtime.executor import build_runners

    # half the multichip scale by default: the matrix runs 8 timed cells
    # + 2 adaptive legs + 14 parity runs, and the child must leave the
    # parent's budget room for the TPU attempt
    events = events or int(
        os.environ.get("BENCH_SKEW_EVENTS", str(1 << 19)))
    num_keys = num_keys or NUM_KEYS
    avail = len(jax.devices())
    n = usable_mesh_size(0, avail, num_keys)
    if n < 2:
        return {"error": f"no usable mesh ({avail} device(s), "
                         f"{num_keys} keys)", "devices": int(n)}

    # adversarial hot placement: the top G/n zipf ranks land one per
    # key-group of DEVICE 0's contiguous range (kids 0, Kg, 2*Kg, ...) —
    # maximally imbalanced under static routing, fully splittable by a
    # key-group rebalance; the tail fills the rest of the id space
    G = choose_key_groups(num_keys, n)
    kg = num_keys // G
    hot_ids = np.arange(G // n, dtype=np.int64) * kg
    rest = np.setdiff1d(np.arange(num_keys, dtype=np.int64), hot_ids)
    perm = np.concatenate(
        [hot_ids, np.random.default_rng(7).permutation(rest)])

    def keys_of(idx, skewed: bool):
        if skewed:
            return zipf_keys(idx, num_keys, zipf_s, hot_perm=perm)
        return ((idx * 2654435761) % num_keys).astype(np.int64)

    def source(count, skewed: bool):
        def gen(idx):
            camp = keys_of(idx, skewed)
            etype = idx % 3
            col = np.stack([camp, etype], axis=1).astype(np.float32)
            ts = 10_000 + idx * span_event_ms // count
            return Batch(col, ts.astype(np.int64))

        return DataGeneratorSource(gen, count)

    t_filter = lambda col: col[:, 1] < 2.5                    # noqa: E731
    t_key = lambda col: col[:, 0].astype(jnp.int32)           # noqa: E731
    t_val = lambda col: col[:, 1]                             # noqa: E731
    WORKLOADS = ("ysb_count", "ysb_sum")

    def build(count, mesh_on, *, skewed, workload, combine=False,
              rebalance=False, columnar=True, stats=False):
        cfg = Configuration()
        cfg.set(ExecutionOptions.CHAIN_FUSION, True)
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, num_keys)
        cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, columnar)
        # dispatch every 8 steps: the rebalancer (and the key-stats fold
        # it reads) needs device-resident state EARLY in the run, and
        # every leg shares the geometry so the ratio isolates traffic
        # shape, not dispatch cadence
        cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 8)
        cfg.set(ParallelOptions.MESH_ENABLED, mesh_on)
        if mesh_on:
            cfg.set(ParallelOptions.MESH_DEVICES, n)
        cfg.set(ParallelOptions.MESH_LOCAL_COMBINE, combine)
        cfg.set(ParallelOptions.MESH_SKEW_REBALANCE, rebalance)
        cfg.set(ParallelOptions.MESH_REBALANCE_SKEW_THRESHOLD, 1.2)
        cfg.set(ParallelOptions.MESH_REBALANCE_INTERVAL_MS, 0)
        cfg.set(ObservabilityOptions.DEVICE_STATS_ENABLED, stats)
        if stats:
            cfg.set(ObservabilityOptions.DEVICE_KEY_STATS_INTERVAL_MS, 0)
        env = StreamExecutionEnvironment(cfg)
        ds = env.from_source(
            source(count, skewed),
            watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
        )
        chain = (ds.filter(t_filter, traceable=True)
                   .key_by(t_key, traceable=True)
                   .window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS)))
        if workload == "ysb_sum":
            sink = chain.aggregate("sum", t_val,
                                   value_traceable=True).collect()
        else:
            sink = chain.aggregate("count").collect()
        return env, sink

    # ---- reroute gate: the fused runner must target the mesh, and with
    # the combiner flag on, the decomposable count/sum aggregates must
    # actually engage the pre-exchange combine
    env_probe, _ = build(batch, True, skewed=False, workload="ysb_count",
                         combine=True)
    runners, _ = build_runners(plan(env_probe._sinks), env_probe.config)
    fused = [r for r in runners if type(r).__name__ == "DeviceChainRunner"]
    fused_selected = bool(fused)
    mesh_devices = fused[0].op.mesh_devices() if fused else 1
    sharded_selected = mesh_devices > 1
    local_combine_active = bool(
        fused and getattr(fused[0].op.pipe, "local_combine", False))

    def run(count, mesh_on, *, skewed, workload, combine=False,
            columnar=True):
        env, sink = build(count, mesh_on, skewed=skewed, workload=workload,
                          combine=combine, columnar=columnar)
        t0 = time.perf_counter()
        env.execute()
        return sink.results, count / max(time.perf_counter() - t0, 1e-9)

    def rows_of(results):
        return sorted((int(k), float(v)) for k, v in results)

    # ---- parity gates, row mode: single-chip vs mesh vs mesh+combine
    n_parity = max(events // 8, batch)
    parity: dict = {}
    combine_parity = True
    for workload in WORKLOADS:
        for skewed, label in ((False, "uniform"), (True, "zipf")):
            ref = rows_of(run(n_parity, False, skewed=skewed,
                              workload=workload, columnar=False)[0])
            mesh_rows = rows_of(run(n_parity, True, skewed=skewed,
                                    workload=workload, columnar=False)[0])
            comb_rows = rows_of(run(n_parity, True, skewed=skewed,
                                    workload=workload, combine=True,
                                    columnar=False)[0])
            parity[f"{workload}/{label}"] = (len(ref) > 0
                                             and mesh_rows == ref)
            combine_parity = combine_parity and comb_rows == ref

    # ---- the matrix cells: interleaved max-of-N sweeps
    tps: dict = {}
    for _sweep in range(sweeps):
        for workload in WORKLOADS:
            for skewed, label in ((False, "uniform"), (True, "zipf")):
                for par in (1, n):
                    _r, t = run(events, par > 1, skewed=skewed,
                                workload=workload)
                    cell = (workload, par, label)
                    tps[cell] = max(tps.get(cell, 0.0), t)
    cells = [
        {"workload": w, "parallelism": p, "skew": s,
         "tuples_per_sec": round(t, 1)}
        for (w, p, s), t in sorted(tps.items())
    ]

    # ---- static-routing skew telemetry under the adversarial zipf leg
    static_skew = None
    try:
        from flink_tpu.runtime.executor import JobRuntime

        env_t, _ = build(max(events // 4, batch * 8), True, skewed=True,
                         workload="ysb_count", stats=True)
        rt = JobRuntime(plan(env_t._sinks), env_t.config)
        rt.run()
        for entry in rt.device_snapshot()["operators"].values():
            blk = entry.get("keys") or {}
            if blk.get("meshLoadSkew") is not None:
                static_skew = blk["meshLoadSkew"]
                break
    except Exception as e:  # noqa: BLE001 — the block must survive
        static_skew = f"error: {e!r}"[:120]

    # ---- the adaptive leg: local-combine + skew-rebalance on the
    # in-process job master (the rebalancer lives there), uniform AND
    # zipf, telemetry from the final attempt's device snapshot
    adaptive: dict = {}
    post_skew = None
    rebalances = 0
    try:
        def run_adaptive(skewed: bool):
            # stats on for BOTH legs: the ratio must isolate the traffic
            # shape, not the observability plane's cost
            env, _sink = build(events, True, skewed=skewed,
                               workload="ysb_count", combine=True,
                               rebalance=True, stats=True)
            t0 = time.perf_counter()
            client = env.execute_async(
                "skew-adaptive" if skewed else "uniform-adaptive")
            client.wait(600)
            dt = max(time.perf_counter() - t0, 1e-9)
            return client, events / dt

        ref_rows = rows_of(run(n_parity, False, skewed=True,
                               workload="ysb_count", columnar=False)[0])
        client_u, tps_u = run_adaptive(False)
        client_z, tps_z = run_adaptive(True)
        rebalances = int(client_z.mesh_rebalances)
        for entry in client_z._runtime.device_snapshot()[
                "operators"].values():
            blk = entry.get("keys") or {}
            if blk.get("meshLoadSkew") is not None:
                post_skew = blk["meshLoadSkew"]
                break
        # adaptive parity at reduced scale: the rebalanced job's rows
        # must equal the single-chip reference's
        env_p, sink_p = build(n_parity, True, skewed=True,
                              workload="ysb_count", combine=True,
                              rebalance=True, columnar=False)
        client_p = env_p.execute_async("skew-adaptive-parity")
        client_p.wait(600)
        adaptive = {
            "uniform_tuples_per_sec": round(tps_u, 1),
            "zipf_tuples_per_sec": round(tps_z, 1),
            "skewed_uniform_ratio": round(tps_z / max(tps_u, 1e-9), 4),
            "rebalances": rebalances,
            "routing_version":
                client_z._runtime.mesh_routing_version(),
            "parity": rows_of(sink_p.results) == ref_rows
                and len(ref_rows) > 0,
        }
    except Exception as e:  # noqa: BLE001 — the block must survive
        adaptive = {"error": repr(e)[:300]}

    matrix_parity = all(parity.values())
    return {
        "devices": int(n),
        "zipf_s": zipf_s,
        "num_keys": num_keys,
        "events": events,
        "workloads": list(WORKLOADS),
        "cells": cells,
        "cell_parity": parity,
        "parity": bool(matrix_parity),
        "combine_parity": bool(combine_parity),
        "fused_selected": bool(fused_selected),
        "sharded_selected": bool(sharded_selected),
        "local_combine_active": bool(local_combine_active),
        "static_mesh_load_skew": static_skew,
        "post_rebalance_mesh_load_skew": post_skew,
        "rebalances": rebalances,
        "adaptive": adaptive,
        "skewed_uniform_ratio": adaptive.get("skewed_uniform_ratio"),
        "workload": "ysb_skew_matrix_datastream_spmd",
    }


def child_skew_matrix() -> None:
    """Skew-matrix child: CPU-pinned with the FORCED 8-device virtual mesh
    (the same programs ride ICI on real multi-chip hardware)."""
    _emit({"event": "start", "device": "cpu-skew-matrix", "pid": os.getpid()})
    _emit({"event": "result", "result": skew_matrix_microbench()})


def run_skew_matrix_child(timeout_s: float = 600.0) -> dict:
    """Skew matrix in a CPU-pinned child on the forced 8-device virtual
    mesh."""
    return _run_cpu_child('skew-matrix', timeout_s, force_mesh=True)


def join_microbench(events: Optional[int] = None,
                    batch: int = 1024,
                    num_keys: int = 2048,
                    span_event_ms: int = 64_000,
                    zipf_s: float = 1.0) -> dict:
    """NEXMark-derived streaming-join scenarios (ISSUE-16): the two-input
    keyed join on the device bucket ring vs the host join oracle.

      - `nexmark_q3` (local item): persons JOIN auctions ON seller with a
        category filter on the auction side, SLIDING window — the
        filter+join shape;
      - `nexmark_q8` (monitor new users): persons JOIN auctions ON seller
        over a TUMBLING window — the pure windowed equi-join;
      - both scenarios run UNIFORM and ZIPF(`zipf_s`) key legs (the zipf
        leg concentrates records per (key, bucket), forcing the adaptive
        bucket-capacity growth path), each at EXACT row parity against
        the same job with execution.join.device-enabled off — the host
        `WindowJoinRunner` oracle;
      - `join_tuples_per_sec` / `host_join_tuples_per_sec` /
        `speedup_vs_host_join` per scenario — the >= 20x bar is judged on
        real TPU hardware (the CPU child gates parity and selection, not
        the ratio);
      - `sql` block: the q8 shape as SQL through the planner's JOIN
        lowering — `sql_fused_selected` (the fused runner actually
        chosen), the explain describing the device path, row parity vs
        the interpreted leg, and `fallback_attributed` pinning that a
        FULL OUTER query refuses with the catalogued reason instead of a
        bare error;
      - `sharded` block: the q8 job on the forced 8-device mesh (the
        sharded ring pipeline), parity vs single-chip.
    """
    import jax

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import (
        SlidingEventTimeWindows,
        TumblingEventTimeWindows,
    )
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ParallelOptions,
    )
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import build_runners
    from flink_tpu.utils.arrays import obj_array

    events = events or int(
        os.environ.get("BENCH_JOIN_EVENTS", str(1 << 14)))
    devices = len(jax.devices())

    def keys_of(idx, skewed: bool):
        if skewed:
            return zipf_keys(idx, num_keys, zipf_s)
        return ((idx * 2654435761) % num_keys).astype(np.int64)

    def source(count, side: str, skewed: bool):
        """Person/auction record stream: (key, payload, category)."""
        def gen(idx):
            ks = keys_of(idx, skewed)
            cat = idx % 3
            rows = obj_array([(int(k), f"{side}{int(i)}", int(c))
                              for k, i, c in zip(ks, idx, cat)])
            ts = 10_000 + idx * span_event_ms // count
            return Batch(rows, ts.astype(np.int64))

        return DataGeneratorSource(gen, count)

    def build(count, scenario: str, *, device, skewed, mesh_on=False):
        cfg = Configuration()
        cfg.set(ExecutionOptions.BATCH_SIZE, batch)
        cfg.set(ExecutionOptions.KEY_CAPACITY, num_keys)
        cfg.set(ExecutionOptions.DEVICE_JOINS, device)
        cfg.set(ParallelOptions.MESH_ENABLED, mesh_on)
        env = StreamExecutionEnvironment(cfg)
        wm = WatermarkStrategy.for_bounded_out_of_orderness(0)
        persons = env.from_source(source(count, "p", skewed),
                                  watermark_strategy=wm)
        auctions = env.from_source(source(count, "a", skewed),
                                   watermark_strategy=wm)
        if scenario == "nexmark_q3":
            auctions = auctions.filter(lambda r: r[2] == 0)
            window = SlidingEventTimeWindows.of(2000, 1000)
        else:
            window = TumblingEventTimeWindows.of(1000)
        sink = (persons.join(auctions)
                .where(lambda r: r[0]).equal_to(lambda r: r[0])
                .window(window)
                .apply(lambda p, a: (p[0], p[1], a[1]))
                .collect())
        return env, sink

    # ---- reroute gate: the factory must pick the device runner
    env_probe, _ = build(batch, "nexmark_q8", device=True, skewed=False)
    runners, _ = build_runners(plan(env_probe._sinks), env_probe.config)
    fused_selected = any(
        type(r).__name__ == "DeviceJoinRunner" for r in runners)

    def run(count, scenario, *, device, skewed, mesh_on=False):
        env, sink = build(count, scenario, device=device, skewed=skewed,
                          mesh_on=mesh_on)
        t0 = time.perf_counter()
        env.execute()
        dt = max(time.perf_counter() - t0, 1e-9)
        return sorted(sink.results), 2 * count / dt

    scenarios: dict = {}
    all_parity = True
    n_parity = max(events // 4, batch)
    for scenario in ("nexmark_q3", "nexmark_q8"):
        blk: dict = {"window": ("sliding(2000,1000)"
                                if scenario == "nexmark_q3"
                                else "tumble(1000)")}
        for skewed, label in ((False, "uniform"), (True, "zipf")):
            ref, _ = run(n_parity, scenario, device=False, skewed=skewed)
            dev, _ = run(n_parity, scenario, device=True, skewed=skewed)
            blk[f"parity_{label}"] = (len(ref) > 0 and dev == ref)
            all_parity = all_parity and blk[f"parity_{label}"]
        rows_d, tps_d = run(events, scenario, device=True, skewed=True)
        rows_h, tps_h = run(events, scenario, device=False, skewed=True)
        blk["matches"] = len(rows_d)
        blk["join_tuples_per_sec"] = round(tps_d, 1)
        blk["host_join_tuples_per_sec"] = round(tps_h, 1)
        blk["speedup_vs_host_join"] = round(tps_d / max(tps_h, 1e-9), 4)
        scenarios[scenario] = blk

    # ---- sharded leg: q8 on the forced mesh vs the single-chip rows
    sharded: dict = {}
    try:
        ref, _ = run(n_parity, "nexmark_q8", device=True, skewed=True)
        env_m, sink_m = build(n_parity, "nexmark_q8", device=True,
                              skewed=True, mesh_on=True)
        runners_m, _ = build_runners(plan(env_m._sinks), env_m.config)
        djr = [r for r in runners_m
               if type(r).__name__ == "DeviceJoinRunner"]
        env_m2, sink_m2 = build(n_parity, "nexmark_q8", device=True,
                                skewed=True, mesh_on=True)
        env_m2.execute()
        sharded = {
            "sharded_selected": bool(djr and djr[0].sharded),
            "parity": sorted(sink_m2.results) == ref and len(ref) > 0,
            "devices": devices,
        }
    except Exception as e:  # noqa: BLE001 — the block must survive
        sharded = {"error": repr(e)[:300]}

    # ---- SQL front door: q8 as SQL through the planner's JOIN lowering
    sql: dict = {}
    try:
        from flink_tpu.table.table_env import TableEnvironment, TableSchema

        def sql_env(device: bool):
            cfg = Configuration()
            cfg.set(ExecutionOptions.BATCH_SIZE, batch)
            cfg.set(ExecutionOptions.DEVICE_JOINS, device)
            env = StreamExecutionEnvironment(cfg)
            tenv = TableEnvironment(env)
            n = min(n_parity, 4096)
            idx = np.arange(n)
            pk, ak = keys_of(idx, True), keys_of(idx + n, True)
            ts = (10_000 + idx * span_event_ms // n).astype(np.int64)
            tenv.from_rows("person", [
                {"id": int(k), "name": f"p{i}", "ptime": int(t)}
                for i, (k, t) in enumerate(zip(pk, ts))],
                TableSchema(["id", "name", "ptime"], rowtime="ptime"))
            tenv.from_rows("auction", [
                {"seller": int(k), "itemid": f"a{i}", "atime": int(t)}
                for i, (k, t) in enumerate(zip(ak, ts))],
                TableSchema(["seller", "itemid", "atime"],
                            rowtime="atime"))
            return env, tenv

        q8_sql = ("SELECT p.id, p.name, a.itemid FROM person AS p "
                  "JOIN auction AS a ON p.id = a.seller "
                  "WINDOW TUMBLE(INTERVAL '1' SECOND)")
        env_s, tenv_s = sql_env(True)
        report = tenv_s.explain_sql(q8_sql)
        sink_s = tenv_s.sql_query(q8_sql).collect()
        runners_s, _ = build_runners(plan(env_s._sinks), env_s.config)
        sql_fused = [r for r in runners_s
                     if type(r).__name__ == "DeviceJoinRunner"]
        t0 = time.perf_counter()
        env_s.execute()
        sql_dt = max(time.perf_counter() - t0, 1e-9)

        env_i, tenv_i = sql_env(False)
        sink_i = tenv_i.sql_query(q8_sql).collect()
        env_i.execute()

        def norm(rows):
            return sorted(tuple(sorted(r.items())) for r in rows)

        full_report = tenv_s.explain_sql(
            "SELECT p.id, a.itemid FROM person AS p FULL OUTER JOIN "
            "auction AS a ON p.id = a.seller")
        sql = {
            "sql_fused_selected": bool(
                report.fused and sql_fused and sql_fused[0].sql_origin),
            "explain": report.describe()[:400],
            "parity": (norm(sink_s.results) == norm(sink_i.results)
                       and len(sink_s.results) > 0),
            "sql_join_tuples_per_sec": round(
                2 * min(n_parity, 4096) / sql_dt, 1),
            "fallback_attributed":
                full_report.reason == "join-full-outer",
        }
    except Exception as e:  # noqa: BLE001 — the block must survive
        sql = {"error": repr(e)[:300]}

    q8 = scenarios["nexmark_q8"]
    return {
        "devices": devices,
        "events": events,
        "num_keys": num_keys,
        "zipf_s": zipf_s,
        "scenarios": scenarios,
        "parity": bool(all_parity),
        "fused_selected": bool(fused_selected),
        "join_tuples_per_sec": q8["join_tuples_per_sec"],
        "host_join_tuples_per_sec": q8["host_join_tuples_per_sec"],
        "speedup_vs_host_join": q8["speedup_vs_host_join"],
        "sharded": sharded,
        "sql": sql,
        "workload": "nexmark_join_device_ring",
    }


def child_join() -> None:
    """Join child: CPU-pinned on the forced 8-device virtual mesh (the
    sharded leg needs devices; real multi-chip rides ICI)."""
    _emit({"event": "start", "device": "cpu-join", "pid": os.getpid()})
    _emit({"event": "result", "result": join_microbench()})


def run_join_child(timeout_s: float = 600.0) -> dict:
    """Join scenarios in a CPU-pinned child on the forced 8-device
    virtual mesh."""
    return _run_cpu_child('join', timeout_s, force_mesh=True)


def chaos_microbench(names: Optional[list] = None) -> dict:
    """Resilience gate (ISSUE-10): run the chaos scenario matrix
    (flink_tpu/chaos/scenarios.py — injected rpc flaps, dataplane blips,
    torn checkpoints, storage brownouts, device dispatch errors, TM crash
    mid-rescale, heartbeat partitions) and emit
    chaos.{scenarios_passed, scenarios_total, parity, recovery_time_ms_p50}
    so recovery behavior is tracked per PR exactly like throughput. Every
    scenario asserts exactly-once parity vs an undisturbed oracle run and
    the expected ExceptionHistory/recovery-timeline shape (injected
    attribution included)."""
    from flink_tpu.chaos import scenarios

    result = scenarios.run_matrix(names)
    # compact per-scenario view for the artifact (full detail on failure)
    result["scenarios"] = [
        {k: r.get(k) for k in ("name", "path", "passed", "parity",
                               "restarts", "recovery_ms", "injected_fired",
                               "attributed", "skipped", "detail")}
        for r in result["scenarios"]
    ]
    return result


def child_chaos() -> None:
    """Chaos-matrix child: CPU-pinned like child_checkpoint (scenarios run
    in-process mini/distributed clusters; the chip belongs to the chip
    child)."""
    _emit({"event": "start", "device": "cpu-chaos", "pid": os.getpid()})
    _emit({"event": "result", "result": chaos_microbench()})


def run_chaos_microbench_child(timeout_s: float = 420.0) -> dict:
    """Chaos matrix in a CPU-pinned child with a FORCED 8-device virtual
    mesh, so the chip-loss-sharded scenario exercises a real reduced-mesh
    recovery, not a skip."""
    return _run_cpu_child('chaos', timeout_s, force_mesh=True)


def parent_main() -> int:
    # host-only, a few seconds: the exchange microbench never touches jax
    try:
        dataplane = dataplane_microbench()
    except Exception as e:  # noqa: BLE001 — the other blocks still run;
        traceback.print_exc()        # an "error" block fails the run below
        dataplane = {"error": repr(e)[:300]}
    _emit({"event": "dataplane_microbench", "result": dataplane})

    # checkpoint-overhead microbench: also host-only, but it builds window
    # operators — run it in a CPU-pinned child so the parent never imports
    # a jax backend and holds the chip the chip child needs
    checkpoint = run_checkpoint_microbench_child()
    _emit({"event": "checkpoint_microbench", "result": checkpoint})

    # elastic-autoscaler adaptation speed: host-only 2x load-step scenario
    # in its own CPU-pinned child, so the trajectory tracks how fast the
    # scheduler turns a saturation signal into a completed rescale
    autoscaler = run_autoscaler_scenario_child()
    _emit({"event": "autoscaler_scenario", "result": autoscaler})

    # API-vs-kernel gap: the full DataStream program through the fused
    # device path vs the legacy ChainRunner path, CPU-pinned child (same
    # backend both sides — the ratio is the refactor's, not the chip's)
    api_path = run_api_path_microbench_child()
    _emit({"event": "api_path_microbench", "result": api_path})

    # SQL front door: the YSB sliding count as SQL through the planner's
    # fused lowering vs the interpreted table path vs the hand-built
    # DataStream-fused yardstick — three-way parity + the reroute gate,
    # CPU-pinned child like the api-path scenario
    sql_path = run_sql_path_microbench_child()
    _emit({"event": "sql_path_microbench", "result": sql_path})

    # device-plane observability: compile/recompile tracking, roofline +
    # phase attribution, key skew, and the measured overhead of the
    # enabled plane — CPU-pinned child like the api-path scenario
    device_plane = run_device_plane_child()
    _emit({"event": "device_plane_microbench", "result": device_plane})

    # chaos scenario matrix: injected compound faults against both
    # execution paths, exactly-once parity vs undisturbed oracles —
    # resilience tracked per-PR like throughput (CPU-pinned child)
    chaos = run_chaos_microbench_child()
    _emit({"event": "chaos_microbench", "result": chaos})

    # multichip SPMD: the fused DataStream YSB program sharded over the
    # (virtual 8-device) mesh vs single-chip — scaling efficiency, zipf
    # skewed variant, per-device telemetry, reroute + parity gates
    multichip = run_multichip_child()
    _emit({"event": "multichip_microbench", "result": multichip})

    # million-key state plane: YSB at a key cardinality orders of
    # magnitude past the resident HBM capacity — bounded residency,
    # cold-tier churn, incremental checkpoint ratio, host-oracle parity
    millikey = run_millikey_child()
    _emit({"event": "millikey_microbench", "result": millikey})

    # shared partials (Factor Windows): the 1m/5m/1h correlated-window job
    # through ONE shared-partial program vs three independent fused runs,
    # single-chip + mesh legs, parity + reroute gates (CPU-pinned child)
    correlated = run_correlated_child()
    _emit({"event": "correlated_windows_microbench", "result": correlated})

    # skew matrix (PDSP-Bench grid): parallelism x workload x skew cells
    # with exact parity, plus the skew-adaptive leg (local-combine +
    # key-group rebalance) — skewed/uniform ratio and post-rebalance
    # meshLoadSkew tracked per PR like throughput
    skew_matrix = run_skew_matrix_child()
    _emit({"event": "skew_matrix_microbench", "result": skew_matrix})

    # streaming joins (NEXMark q3/q8): the device bucket-ring join vs the
    # host join oracle — exact parity on uniform AND zipf legs, the SQL
    # JOIN lowering's reroute gate, and the sharded-mesh leg
    join_bench = run_join_child()
    _emit({"event": "join_microbench", "result": join_bench})

    # latency x throughput frontier: the fused YSB job under open-loop
    # arrival pacing at 25/50/100% of measured peak — p50/p99/p999
    # emission latency (event-time close -> host-visible) per load point,
    # stall attribution, and the emission plane's on/off overhead
    latency_frontier = run_latency_frontier_child()
    _emit({"event": "latency_frontier_microbench",
           "result": latency_frontier})

    # history/doctor plane: ring non-emptiness over the REST read path,
    # the doctor's verdict on an undisturbed run, and the sampler's
    # measured overhead — the health block every artifact now carries
    health = run_health_child()
    _emit({"event": "health_microbench", "result": health})

    # static-analysis plane (ISSUE-20 acceptance): the full 16-rule lint
    # run rides every artifact next to health — a PR that regresses the
    # analyzer's coverage or leaves active violations shows up in the
    # trajectory, not just in CI
    lint_info = lint_summary()
    _emit({"event": "lint_summary", "result": lint_info})

    # the chip child, alone: every CPU child above has exited
    best = run_chip_child(BUDGET_S)
    if best is None:
        best = {
            "metric": "ysb_sliding_count_tuples_per_sec",
            "unit": "tuples/s/chip",
            "error": "the chip child ended without a result",
        }
    best["dataplane"] = dataplane
    best["checkpoint"] = checkpoint
    best["autoscaler"] = autoscaler
    best["api_path"] = api_path
    best["sql_path"] = sql_path
    # top-level continuity key for the trajectory table: the SQL
    # front door's fused throughput, tracked per PR like the
    # api-path number
    sql_tps = sql_path.get("sql_tuples_per_sec")
    if sql_tps:
        best["sql_path_tuples_per_sec"] = sql_tps
    best["chaos"] = chaos
    best["multichip"] = multichip
    best["state_tier"] = millikey
    best["correlated_windows"] = correlated
    # top-level continuity keys: the shared-partial throughput and
    # the sharing speedup, tracked per PR like the api-path number
    if correlated.get("shared_tuples_per_sec"):
        best["correlated_windows_tuples_per_sec"] = \
            correlated["shared_tuples_per_sec"]
        best["correlated_sharing_speedup"] = \
            correlated.get("speedup_vs_independent")
    if millikey.get("tuples_per_sec"):
        best["millikey_tuples_per_sec"] = \
            millikey["tuples_per_sec"]
        best["millikey_incremental_ratio"] = \
            millikey.get("incremental_ratio")
    best["skew_matrix"] = skew_matrix
    best["join"] = join_bench
    # emission-latency frontier (ISSUE-17 acceptance): the block
    # with per-load-point tail latencies rides every artifact,
    # and the 100%-load p99 is a first-class trajectory key
    best["latency_frontier"] = latency_frontier.get(
        "latency_frontier", latency_frontier)
    if latency_frontier.get("p99_emission_latency_ms") is not None:
        best["p99_emission_latency_ms"] = \
            latency_frontier["p99_emission_latency_ms"]
    # health block (ISSUE-19 acceptance): the doctor's verdict and
    # the sampler's measured overhead ride every artifact
    best["health"] = health
    # lint block (ISSUE-20 acceptance): the exactly-once contract
    # analyzer's verdict on the tree, timed
    best["lint"] = lint_info
    # first-class join keys (ISSUE-16 acceptance): the q8 device
    # throughput and its ratio to the host join oracle — the
    # >= 20x bar is judged where this lands on real TPU hardware
    if join_bench.get("join_tuples_per_sec"):
        best["join_tuples_per_sec"] = \
            join_bench["join_tuples_per_sec"]
        best["join_speedup_vs_host"] = \
            join_bench.get("speedup_vs_host_join")
    # first-class skew keys (ISSUE-15 acceptance): the adaptive
    # zipf/uniform throughput ratio and the post-rebalance device
    # skew, tracked per PR next to the static value they improve
    if skew_matrix.get("skewed_uniform_ratio") is not None:
        best["skewed_uniform_ratio"] = \
            skew_matrix["skewed_uniform_ratio"]
    if skew_matrix.get("post_rebalance_mesh_load_skew") is not None:
        best["post_rebalance_mesh_load_skew"] = \
            skew_matrix["post_rebalance_mesh_load_skew"]
    # top-level continuity keys for the trajectory table
    if multichip.get("tuples_per_sec"):
        best["multichip_tuples_per_sec"] = \
            multichip["tuples_per_sec"]
        best["multichip_scaling_efficiency"] = \
            multichip.get("scaling_efficiency")
    # device_plane, NOT "device": the top-level "device" key is the
    # backend marker the bench driver parses — clobbering it would
    # misclassify the whole artifact
    best["device_plane"] = device_plane
    # top-level continuity keys (the r02 shape): the API-path
    # number and its ratio to the headline kernel, tracked per PR
    tps = api_path.get("api_path_tuples_per_sec")
    if tps:
        best["api_path_tuples_per_sec"] = tps
        if best.get("value"):
            best["api_vs_fused"] = round(tps / best["value"], 4)
    failed = sorted(
        name for name, block in
        [("headline", best), *best.items(),
         *(best.get("secondary") or {}).items()]
        if isinstance(block, dict) and "error" in block)
    if failed:
        best["failed"] = failed
    print(json.dumps(best), flush=True)
    return 1 if failed else 0


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        label = sys.argv[2]
        T = int(sys.argv[3])
        spans = int(sys.argv[5])
        if label == "tpu":
            child_tpu(T, 1 << int(sys.argv[4]), spans)
        elif label == "checkpoint":
            child_checkpoint()
        elif label == "autoscaler":
            child_autoscaler()
        elif label == "api-path":
            child_api_path()
        elif label == "sql-path":
            child_sql_path()
        elif label == "device-plane":
            child_device_plane()
        elif label == "chaos":
            child_chaos()
        elif label == "multichip":
            child_multichip()
        elif label == "millikey":
            child_millikey()
        elif label == "skew-matrix":
            child_skew_matrix()
        elif label == "join":
            child_join()
        elif label == "correlated":
            child_correlated()
        elif label == "latency-frontier":
            child_latency_frontier()
        elif label == "health":
            child_health()
        else:
            raise SystemExit(f"bench: unknown child {label!r}")
        return 0
    return parent_main()


if __name__ == "__main__":
    sys.exit(main())
