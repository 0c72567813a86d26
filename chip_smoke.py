#!/usr/bin/env python3
"""Chip smoke: the served window path, once, on the TPU — and nowhere else.

One process, one touch of JAX. Drives `StreamExecutionEnvironment ...
env.execute()` at the defaults a user gets and compares every fired window
with a plain numpy reference (per-slice `bincount` over the same
`--seed`-generated stream) — exact equality, no tolerance.

  leg 1  traced chain: from_source -> filter -> key_by -> 10 s / 1 s sliding
         count, 2^24 events over 65 536 keys (fills the default key
         capacity), the chained XLA superscan;
  leg 1b a 16 384-row int32 table whose values span int32's whole range,
         read by `jnp.take` in a traced map over 2^22 lanes: the prologue
         does the gather as a one-hot contraction over four byte planes
         (ops/table_lookup.py), compared with numpy's take;
  leg 2  host-keyed count (8192 keys) and sum (4096 keys), 2^22 events
         each: the Pallas kernel as the operator selects it, Mosaic-compiled;
  leg 3  leg 1 sharded over a 4-device mesh — only where 4 devices are
         visible; fails if a dispatch reads back more fire rows than its
         fires used, and prints the link's bytes back per dispatch and event.

Exits non-zero when JAX finds no TPU, when a leg raises, or when a result
differs from the reference. Wall and compile times are printed as set-up
facts of this run, never under a metric's name. The last line of stdout on
success is `{"ok": true, "device": {...}}` as JAX reports the device.

`--rehearse-cpu` runs the same legs at tiny sizes on the CPU backend to
debug the script before spending chip time; its output says so and it
never prints the chip result line.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

WINDOW_MS, SLIDE_MS = 10_000, 1_000
T0_MS = 100_000            # first event time; keeps every window start >= 0
JITTER_MS, OOO_MS = 200, 250


def _mix(idx: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 of (index, seed): the stream is a pure function of both."""
    with np.errstate(over="ignore"):
        x = idx.astype(np.uint64) + np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class Stream:
    """`events` records of (key, aux) f32 columns + an i64 timestamp.

    aux is `x mod aux_mod` (leg 1: an event type the filter reads; leg 2:
    a small integer value, so f32 sums are exact). With `growing`, the
    first half of the stream draws from half the key space, so a host key
    dictionary grows mid-stream and the operator doubles its capacity."""

    def __init__(self, seed, events, keys, span_ms, aux_mod, growing=False):
        self.seed, self.events, self.keys = seed, events, keys
        self.span_ms, self.aux_mod, self.growing = span_ms, aux_mod, growing

    def columns(self, idx: np.ndarray):
        x = _mix(idx, self.seed)
        nk = np.uint64(self.keys)
        if self.growing:
            nk = np.where(idx < self.events // 2, nk // np.uint64(2), nk)
        key = (x % nk).astype(np.int64)
        aux = ((x >> np.uint64(32)) % np.uint64(self.aux_mod)).astype(np.int64)
        jitter = ((x >> np.uint64(40)) % np.uint64(JITTER_MS + 1)).astype(np.int64)
        ts = T0_MS + idx * self.span_ms // self.events - jitter
        return key, aux, ts

    def source(self):
        from flink_tpu.connectors.source import Batch, DataGeneratorSource

        def gen(idx):
            key, aux, ts = self.columns(idx)
            return Batch(np.stack([key, aux], axis=1).astype(np.float32), ts)

        return DataGeneratorSource(gen, self.events)

    def reference(self, keep_aux_below=None, weighted=False):
        """(counts, sums) as [windows, keys] matrices plus the index of the
        first window: slice histograms by bincount, windows as runs of
        WINDOW/SLIDE slices. Independent of the code under test."""
        nsl = WINDOW_MS // SLIDE_MS
        s_lo = (T0_MS - JITTER_MS) // SLIDE_MS
        s_hi = (T0_MS + self.span_ms) // SLIDE_MS
        n_slices = s_hi - s_lo + 1
        cnt = np.zeros(n_slices * self.keys, np.int64)
        sm = np.zeros(n_slices * self.keys, np.float64)
        for lo in range(0, self.events, 1 << 22):
            idx = np.arange(lo, min(lo + (1 << 22), self.events), dtype=np.int64)
            key, aux, ts = self.columns(idx)
            if keep_aux_below is not None:
                keep = aux < keep_aux_below
                key, aux, ts = key[keep], aux[keep], ts[keep]
            cell = (ts // SLIDE_MS - s_lo) * self.keys + key
            cnt += np.bincount(cell, minlength=cnt.size)
            if weighted:
                sm += np.bincount(cell, weights=aux, minlength=cnt.size)
        cnt = cnt.reshape(n_slices, self.keys)
        sm = sm.reshape(n_slices, self.keys)
        # window j covers slices [j, j + nsl); the first one that can hold
        # a record starts nsl - 1 slices before the first slice
        j0 = s_lo - (nsl - 1)
        n_win = n_slices + nsl - 1
        pad = np.zeros((nsl - 1, self.keys))
        cnt_p = np.concatenate([pad, cnt, pad]).astype(np.int64)
        sm_p = np.concatenate([pad, sm, pad])
        wcnt = sum(cnt_p[w:w + n_win] for w in range(nsl))
        wsum = sum(sm_p[w:w + n_win] for w in range(nsl))
        return wcnt, wsum, j0


def window_sink():
    """A sink that keeps each row's timestamp: a window result is emitted
    at `window.end - 1`, which is what identifies the window."""
    from flink_tpu.connectors.sink import Sink, SinkWriter

    class Writer(SinkWriter):
        def __init__(self, store):
            self.store = store

        def write_batch(self, values, timestamps=None):
            self.store.append((values, np.asarray(timestamps, np.int64)))

    class WindowSink(Sink):
        def __init__(self):
            self.batches = []

        def create_writer(self):
            return Writer(self.batches)

    return WindowSink()


def fired_matrix(sink, n_win, keys, j0, dtype):
    """Emitted (key, value) rows -> [windows, keys]; refuses a window or
    key the reference has no cell for, and a cell emitted twice."""
    got = np.zeros((n_win, keys), dtype)
    seen = np.zeros((n_win, keys), bool)
    rows = 0
    for values, ts in sink.batches:
        kv = np.asarray(values.tolist())
        k = kv[:, 0].astype(np.int64)
        j = (ts + 1 - WINDOW_MS) // SLIDE_MS - j0
        if (j < 0).any() or (j >= n_win).any() or (k < 0).any() or (k >= keys).any():
            raise AssertionError("emitted a window or key outside the stream")
        if seen[j, k].any() or len(np.unique(j * keys + k)) != len(k):
            raise AssertionError("a (window, key) cell was emitted twice")
        seen[j, k] = True
        got[j, k] = kv[:, 1]
        rows += len(k)
    return got, seen, rows


def build_job(stream, config, *, traced, aggregate):
    import jax.numpy as jnp

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.core.watermarks import WatermarkStrategy

    env = StreamExecutionEnvironment.get_execution_environment(config)
    ds = env.from_source(
        stream.source(),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(OOO_MS))
    if traced:
        ds = ds.filter(lambda col: col[:, 1] < 0.5, traceable=True)
        keyed = ds.key_by(lambda col: col[:, 0].astype(jnp.int32),
                          traceable=True)
    else:
        keyed = ds.key_by(lambda col: col[:, 0].astype(np.int64),
                          vectorized=True)
    windowed = keyed.window(SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS))
    if aggregate == "sum":
        out = windowed.aggregate("sum", value_fn=lambda col: col[:, 1],
                                 value_vectorized=True)
    else:
        out = windowed.aggregate(aggregate)
    sink = window_sink()
    out.sink_to(sink)
    return env, sink


def window_runner(env):
    """The runner the executor builds for this job's window step — a probe
    built from the same plan, as a job would build it."""
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import build_runners

    runners, _ = build_runners(plan(env._sinks), env.config)
    (runner,) = [r for r in runners if hasattr(r, "op")]
    return runner


def run_job(name, stream, config, *, traced, aggregate, ref_kwargs):
    """Execute, compare with the reference, return the leg's facts."""
    env, sink = build_job(stream, config, traced=traced, aggregate=aggregate)
    t0 = time.perf_counter()
    result = env.execute(name)
    wall_s = time.perf_counter() - t0
    if result.records_in != stream.events:
        raise AssertionError(
            f"{name}: {result.records_in} records in, {stream.events} sent")

    wcnt, wsum, j0 = stream.reference(**ref_kwargs)
    weighted = ref_kwargs.get("weighted", False)
    got, seen, rows = fired_matrix(
        sink, wcnt.shape[0], stream.keys, j0,
        np.float64 if weighted else np.int64)
    expect = wsum if weighted else wcnt
    # a cell is emitted iff records fell into it, and holds their aggregate
    if not np.array_equal(seen, wcnt > 0):
        raise AssertionError(
            f"{name}: emitted cells differ from the reference "
            f"({int(seen.sum())} vs {int((wcnt > 0).sum())})")
    if not np.array_equal(got, np.where(wcnt > 0, expect, 0)):
        bad = np.argwhere(got != np.where(wcnt > 0, expect, 0))
        raise AssertionError(
            f"{name}: {len(bad)} cells differ from the reference, first "
            f"(window, key) {bad[0].tolist()}")

    dev = result.metrics["device"]
    programs = {}
    for op in dev["operators"].values():
        for prog, st in op.get("compile", {}).get("programs", {}).items():
            programs[prog] = {"dispatches": st["dispatches"],
                              "compiles": st["compiles"],
                              "last": st["lastSignature"]}
    windows = int((wcnt > 0).any(axis=1).sum())
    full = int(max(0, stream.span_ms // SLIDE_MS - WINDOW_MS // SLIDE_MS + 1))
    facts = {
        "leg": name, "events": stream.events, "keys": stream.keys,
        "windows_fired": windows, "full_windows": full, "rows": rows,
        "wall_s": round(wall_s, 2),
        "compile_s": round(dev["compile"]["compileTimeMsTotal"] / 1000.0, 2),
        "compiles": dev["compile"]["numCompiles"],
        "programs": programs,
        "mesh_devices": result.metrics["mesh_devices"],
    }
    return facts, got, dev


def leg1(seed, sizes):
    from flink_tpu.config import Configuration

    stream = Stream(seed, sizes["events1"], sizes["keys1"], sizes["span_ms"], 3)
    config = Configuration()
    env, _ = build_job(stream, config, traced=True, aggregate="count")
    runner = window_runner(env)
    if type(runner).__name__ != "DeviceChainRunner":
        raise AssertionError(
            f"leg 1: the executor chose {type(runner).__name__}, not the "
            "fused device chain")
    facts, got, _dev = run_job("leg1-served-chain", stream, config, traced=True,
                               aggregate="count",
                               ref_kwargs={"keep_aux_below": 1})
    chained = facts["programs"].get("fused_chained_superscan")
    if not chained or chained["dispatches"] < 1:
        raise AssertionError(f"leg 1: no chained-superscan dispatch: {facts}")
    return facts, got, stream


def leg_lookup(seed, sizes):
    """The traced prologue's table lookup at its largest: 16 384 rows, four
    byte planes, int32's two extremes among the values, indices in range,
    negative (wrapped by `jnp.take`), >= N and < -N (read as its fill).
    Only a chip run shows a precision trap (PERF.md section 6, PR 37)."""
    import jax
    import jax.numpy as jnp

    from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

    rng = np.random.default_rng(seed)
    N, lanes, B = 1 << 14, sizes["lookup_lanes"], sizes["lookup_batch"]
    i32 = np.iinfo(np.int32)
    table = rng.integers(i32.min, i32.max, N, endpoint=True,
                         dtype=np.int64).astype(np.int32)
    table[:2] = i32.min, i32.max
    idx = rng.integers(-N - 4096, N + 4096, lanes).astype(np.int32)
    dev_table = jnp.asarray(table)
    pro = TracedPrologue(
        transforms=(("map", lambda col: jnp.take(dev_table, col[:, 0])[:, None]),),
        key_fn=lambda col: col[:, 0])
    kb = jnp.asarray([-1, 0], jnp.int32)

    def step(_, raw):
        srel = jnp.zeros(raw.shape[:1], jnp.int32)
        return None, pro.apply(raw, srel, None, kb, K=1, NSB=1,
                               needs_vals=False)[1]

    run = jax.jit(lambda xs: jax.lax.scan(step, None, xs)[1])
    xs = idx.reshape(-1, B, 1)
    compiled = run.lower(xs).compile()
    lowered, kept = pro.gathers()
    if (lowered, kept) != (1, ()):
        raise AssertionError(f"leg 1b: gathers lowered / kept {lowered} / {kept}")
    if " gather(" in compiled.as_text():
        raise AssertionError("leg 1b: the compiled program still gathers")
    t0 = time.perf_counter()
    got = np.asarray(compiled(xs)).reshape(-1)
    wall = time.perf_counter() - t0
    wrapped = np.where(idx < 0, idx + N, idx)
    inside = (wrapped >= 0) & (wrapped < N)
    expect = np.where(inside, np.take(table, np.clip(wrapped, 0, N - 1)),
                      i32.min)
    wrong = int(np.count_nonzero(got != expect))
    if wrong:
        raise AssertionError(f"leg 1b: {wrong} of {lanes} lookups differ "
                             "from numpy's take")
    return {"leg": "leg1b-table-lookup", "rows": N, "lanes": lanes,
            "byte_planes": 4, "outside": int((~inside).sum()),
            "wall_s": round(wall, 3)}


def leg2(seed, sizes, on_chip):
    from flink_tpu.config import Configuration

    out = []
    for aggregate, keys in (("count", sizes["keys2_count"]),
                            ("sum", sizes["keys2_sum"])):
        stream = Stream(seed + 1, sizes["events2"], keys, sizes["span_ms"], 8,
                        growing=True)
        config = Configuration()
        env, _ = build_job(stream, config, traced=False, aggregate=aggregate)
        pipe = window_runner(env).op.pipe
        # the pipeline's own decision at every capacity the doubling visits
        decisions = {}
        K = pipe.K
        while True:
            pipe.ensure_key_capacity(K)
            decisions[pipe.K] = bool(pipe._use_pallas())
            if pipe.K >= keys:
                break
            K = pipe.K * 2
        if on_chip and not (all(decisions.values())
                            and pipe.pallas_interpret is False):
            raise AssertionError(
                f"leg 2 {aggregate}: pallas not selected at every capacity "
                f"{decisions} (interpret={pipe.pallas_interpret})")
        facts, _got, _dev = run_job(
            f"leg2-host-keyed-{aggregate}", stream, config, traced=False,
            aggregate=aggregate,
            ref_kwargs={"weighted": aggregate == "sum"})
        facts["pallas_selected_at"] = decisions
        facts["pallas_interpret"] = pipe.pallas_interpret
        ran = facts["programs"]
        if on_chip:
            pallas = ran.get("pallas_superscan")
            if not pallas or pallas["dispatches"] < 2 \
                    or f"K={keys}," not in pallas["last"] + ",":
                raise AssertionError(
                    f"leg 2 {aggregate}: the job did not run the Mosaic "
                    f"kernel up to K={keys}: {ran}")
            if "fused_superscan" in ran:
                raise AssertionError(
                    f"leg 2 {aggregate}: XLA superscan dispatched: {ran}")
        out.append(facts)
    return out


def leg3(leg1_got, leg1_stream):
    from flink_tpu.config import Configuration, ParallelOptions

    config = Configuration()
    config.set(ParallelOptions.MESH_ENABLED, True)
    config.set(ParallelOptions.MESH_DEVICES, 4)
    env, _ = build_job(leg1_stream, config, traced=True, aggregate="count")
    op = window_runner(env).op
    if op.mesh_devices() != 4:
        raise AssertionError(
            f"leg 3: asked for a 4-device mesh, got {op.mesh_devices()}")
    shards = op.pipe._count.addressable_shards
    owners = {s.device for s in shards}
    if len(owners) != 4 or any(s.data.size == 0 for s in shards):
        raise AssertionError(
            f"leg 3: ring shards {[(s.device, s.data.shape) for s in shards]}")
    # what each dispatch hands to the deferred readback: (fires, rows, bytes)
    from flink_tpu.runtime import fused_window_pipeline as fwp

    readbacks = []
    init = fwp.DeferredEmissions.__init__

    def probe(self, pipe, fires, count_out, outs, **kw):
        init(self, pipe, fires, count_out, outs, **kw)
        readbacks.append((len(fires), int(count_out.shape[0]), self.nbytes))

    fwp.DeferredEmissions.__init__ = probe
    try:
        facts, got, dev = run_job("leg3-mesh-4", leg1_stream, config,
                                  traced=True, aggregate="count",
                                  ref_kwargs={"keep_aux_below": 1})
    finally:
        fwp.DeferredEmissions.__init__ = init
    if facts["mesh_devices"] != 4:
        raise AssertionError(f"leg 3: job reports mesh {facts['mesh_devices']}")
    # only the fire rows a dispatch used come back from the four shards
    K = op.pipe.K
    n = op.mesh_devices()
    for fires, rows, nbytes in readbacks:
        used = -(-max(fires, 1) // 16) * 16
        # beside the rows: the key bounds (8 B) and, per shard, the
        # exchange's two counters and the three phase counters (20 B)
        if rows > used or nbytes > used * K * 4 + 8 + n * 20:
            raise AssertionError(
                f"leg 3: a dispatch with {fires} fires reads back {rows} "
                f"rows, {nbytes} B: more than {used} rows of {K} keys")
    (link,) = [o["link"] for o in dev["operators"].values() if "link" in o]
    if link["d2hBytes"] != sum(r[2] for r in readbacks):
        raise AssertionError(
            f"leg 3: the stage clock counts {link['d2hBytes']} B read back, "
            f"the dispatches handed over {sum(r[2] for r in readbacks)}")
    facts["d2h_bytes_per_dispatch"] = round(
        link["d2hBytes"] / max(link["dispatches"], 1))
    facts["d2h_bytes_per_event"] = round(
        link["d2hBytes"] / max(link["eventsStaged"], 1), 3)
    facts["readback_rows_per_dispatch"] = [r[1] for r in readbacks]
    per_dev = [e for o in dev["operators"].values()
               for e in o.get("keys", {}).get("perDevice", [])]
    facts["per_device_records"] = [e.get("records") for e in per_dev]
    if len(per_dev) != 4 or not all(e.get("records", 0) > 0 for e in per_dev):
        raise AssertionError(
            f"leg 3: a device held no records mid-run: {per_dev}")
    if not np.array_equal(got, leg1_got):
        raise AssertionError("leg 3: mesh result differs from leg 1")
    facts["shard_shapes"] = [list(s.data.shape) for s in shards]
    facts["devices"] = sorted(str(d) for d in owners)
    return facts


CHIP_SIZES = dict(events1=1 << 24, keys1=1 << 16, events2=1 << 22,
                  keys2_count=8192, keys2_sum=4096, span_ms=40_000,
                  lookup_lanes=1 << 22, lookup_batch=1 << 16)
REHEARSAL_SIZES = dict(events1=1 << 22, keys1=1 << 16, events2=1 << 22,
                       keys2_count=2048, keys2_sum=2048, span_ms=14_000,
                       lookup_lanes=1 << 16, lookup_batch=1 << 12)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU backend; proves the script, "
                         "not the chip")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']}")
    print(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {libtpu}")
    on_chip = device["platform"] == "tpu"
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU backend at tiny sizes: proves the "
              "script runs, says nothing about the chip")
    elif not on_chip:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 4

    from flink_tpu.utils import native_bridge
    from flink_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)")
    t0 = time.perf_counter()
    if native_bridge.get_lib() is None:
        print(f"chip_smoke: native library did not build: "
              f"{native_bridge.load_error()}", file=sys.stderr)
        return 1
    print(f"native library: built from native/*.cpp and loaded in "
          f"{time.perf_counter() - t0:.1f} s; the host key dictionary of "
          f"leg 2 runs on it")

    sizes = REHEARSAL_SIZES if args.rehearse_cpu else CHIP_SIZES
    t_all = time.perf_counter()
    leg = "leg 1"
    try:
        facts1, got1, stream1 = leg1(args.seed, sizes)
        print("leg 1 ok:", json.dumps(facts1))
        leg = "leg 1b"
        print("leg 1b ok:", json.dumps(leg_lookup(args.seed, sizes)))
        leg = "leg 2"
        for facts in leg2(args.seed, sizes, on_chip):
            print("leg 2 ok:", json.dumps(facts))
        leg = "leg 3"
        if len(devs) >= 4:
            print("leg 3 ok:", json.dumps(leg3(got1, stream1)))
        else:
            print(f"leg 3 did not run: it needs 4 devices, JAX sees "
                  f"{len(devs)}")
    except BaseException:
        print(f"chip_smoke: {leg} FAILED", file=sys.stderr)
        raise
    n_after = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"all legs: {time.perf_counter() - t_all:.1f} s wall; compile "
          f"cache now holds {n_after} entries (+{n_after - n_cached})")
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "legs_ok": True,
                          "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
