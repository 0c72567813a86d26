"""Device-plane observability (ISSUE-8): compile/recompile tracking with
cause attribution, per-kernel cost & roofline capture, superscan phase
counters, key-skew telemetry, and the /jobs/:id/device exposure.

Layers:

1. **CompileTracker units** — compile detection off the jit executable
   cache, recompile cause attribution by signature diff (ring doubling /
   batch geometry / dtype change), ring bounding, storm gauge, cost
   capture, payload/merge shapes.
2. **Key-stats units** — the device fold against a numpy oracle: per-group
   histogram, top-K hot keys, skew coefficient, readiness gating.
3. **Operator integration** — phase counters match the stream's ground
   truth; device stats change NO results (parity on vs off); ring
   doubling recompiles with the right cause.
4. **End-to-end** — a MiniCluster job serves /jobs/:id/device with a
   nonzero compile count, an induced geometry-churn recompile in the
   event ring, roofline/phase/key blocks, compile spans on the trace
   registry, and the profiler capture surface (satellite: the per-attempt
   jax.profiler capture used to be write-only).
"""

import json
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from flink_tpu.core.time import MAX_WATERMARK
from flink_tpu.metrics.device_stats import (
    DEVICE_PEAKS,
    CompileTracker,
    attribute_cause,
    compile_event_span,
    empty_device_payload,
    merge_compile_payloads,
    platform_peaks,
    roofline_pct,
)
from flink_tpu.metrics.key_stats import KeyStatsCollector


# ---------------------------------------------------------------------------
# 1. CompileTracker units
# ---------------------------------------------------------------------------

def _jit_add(scale):
    import jax

    return jax.jit(lambda x: x * scale + 1)


def test_tracker_counts_compiles_and_dispatches():
    t = CompileTracker()
    fn = _jit_add(2)
    x = jnp.ones((8,))
    t.call("p", fn, (x,), {"T": 1, "B": 8})
    t.call("p", fn, (x,), {"T": 1, "B": 8})
    t.call("p", fn, (x,), {"T": 1, "B": 8})
    assert t.num_compiles == 1
    assert t.num_recompiles == 0
    assert t.dispatches_total() == 3
    assert t.compile_ms_total > 0
    p = t.payload()
    assert p["programs"]["p"]["compiles"] == 1
    assert p["programs"]["p"]["dispatches"] == 3
    assert p["events"][0]["cause"] == "initial"


def test_tracker_detects_recompile_with_batch_geometry_cause():
    t = CompileTracker()
    fn = _jit_add(3)
    t.call("p", fn, (jnp.ones((8,)),), {"T": 1, "B": 8})
    t.call("p", fn, (jnp.ones((16,)),), {"T": 1, "B": 16})
    assert t.num_compiles == 2
    assert t.num_recompiles == 1
    ev = t.events()[-1]
    assert ev["recompile"] is True
    assert ev["cause"] == "batch-geometry"
    assert "B=16" in ev["signature"]


def test_cause_attribution_priorities():
    assert attribute_cause(None, {"K": 1}) == "initial"
    assert attribute_cause({"K": 1, "T": 2}, {"K": 2, "T": 2}) == "ring-doubling"
    assert attribute_cause({"K": 1, "T": 2}, {"K": 1, "T": 4}) == "batch-geometry"
    assert attribute_cause({"K": 1, "B": 2}, {"K": 1, "B": 4}) == "batch-geometry"
    # dtype outranks everything: it is a program-semantics change
    assert attribute_cause(
        {"K": 1, "dtype": "f32"}, {"K": 2, "dtype": "f64"}) == "dtype-change"
    assert attribute_cause({"K": 1}, {"K": 1}) == "cache-eviction"
    assert attribute_cause({"S": 8, "K": 1}, {"S": 16, "K": 1}) == "other:S"


def test_tracker_cost_capture_feeds_roofline():
    t = CompileTracker()
    fn = _jit_add(5)
    x = jnp.ones((128,))
    t.call("p", fn, (x,), {"B": 128})
    assert t.bytes_accessed_total() > 0
    assert t.flops_total() > 0
    ev = t.events()[0]
    assert ev["cost"]["bytes_accessed"] > 0
    # non-compiling dispatches keep accumulating the cached per-signature
    # cost — the roofline numerator grows with every dispatch
    before = t.bytes_accessed_total()
    t.call("p", fn, (x,), {"B": 128})
    assert t.bytes_accessed_total() == pytest.approx(2 * before)


def test_tracker_event_ring_is_bounded_and_storm_gauge_trips():
    clock = [0.0]
    t = CompileTracker(history_size=4, storm_threshold=3,
                       storm_window_ms=10_000, cost_analysis=False,
                       clock=lambda: clock[0])
    for i in range(8):
        t.call("p", _jit_add(100 + i), (jnp.ones((4,)),), {"B": 4, "v": i})
    assert len(t.events()) == 4
    assert t.num_compiles == 8
    assert t.num_recompiles == 7
    assert t.recompile_storm() == 1
    clock[0] += 100.0   # storm window slides past
    assert t.recompile_storm() == 0


def test_warm_cache_job_still_captures_cost_for_roofline():
    """A second job whose program geometry is already warm in the
    process-wide jit caches observes NO compile (truthful — it paid
    none), but the roofline must still get the per-dispatch cost: a
    warm-cache job reading 0% utilization forever would be a lie."""
    fn = _jit_add(42)
    x = jnp.ones((32,))
    warm = CompileTracker()
    warm.call("p", fn, (x,), {"B": 32})     # pays the compile
    fresh = CompileTracker()                 # same fn, already compiled
    fresh.call("p", fn, (x,), {"B": 32})
    fresh.call("p", fn, (x,), {"B": 32})
    assert fresh.num_compiles == 0           # no compile event: none happened
    assert fresh.events() == []
    assert fresh.bytes_accessed_total() > 0  # ...but the cost is captured
    assert fresh.bytes_accessed_total() == pytest.approx(
        warm.bytes_accessed_total() * 2)     # accumulated per dispatch


def test_tracker_falls_back_to_signature_tracking():
    calls = []

    def plain_fn(x):   # no _cache_size, no lower: a non-jit callable
        calls.append(1)
        return x

    t = CompileTracker()
    t.call("p", plain_fn, (1,), {"B": 1})
    t.call("p", plain_fn, (1,), {"B": 1})
    t.call("p", plain_fn, (2,), {"B": 2})
    assert len(calls) == 3
    assert t.num_compiles == 2          # one per distinct signature
    assert t.num_recompiles == 1


def test_memory_analysis_capture_when_enabled():
    t = CompileTracker(memory_analysis=True)
    fn = _jit_add(7)
    t.call("p", fn, (jnp.ones((64,)),), {"B": 64})
    cost = t.events()[0]["cost"]
    assert "output_bytes" in cost and cost["output_bytes"] > 0


def test_merge_compile_payloads_and_empty_payload_shape():
    t1, t2 = CompileTracker(), CompileTracker()
    t1.call("a", _jit_add(11), (jnp.ones((4,)),), {"B": 4})
    t2.call("b", _jit_add(12), (jnp.ones((4,)),), {"B": 4})
    merged = merge_compile_payloads([t1.payload(), t2.payload()])
    assert merged["numCompiles"] == 2
    assert set(merged["programs"]) == {"a", "b"}
    assert len(merged["events"]) == 2
    empty = empty_device_payload()
    assert empty["enabled"] is False
    assert empty["compile"]["numCompiles"] == 0
    assert empty["profiler"] == {"enabled": False, "captures": 0,
                                 "last_capture_dir": None}


def test_roofline_pct_math_and_platform_peaks():
    r = roofline_pct(bytes_accessed=50e9, flops=137.5e12,
                     device_time_s=1.0, hbm_gbps=100.0, peak_tflops=275.0)
    assert r["hbmUtilizationPct"] == pytest.approx(50.0)
    assert r["flopsUtilizationPct"] == pytest.approx(50.0)
    assert roofline_pct(1e9, 1e9, 0.0, 100.0, 1.0) == {
        "hbmUtilizationPct": 0.0, "flopsUtilizationPct": 0.0}
    # configured values win; zeros take the device kind's published row
    assert platform_peaks(123.0, 4.5) == (123.0, 4.5)
    assert platform_peaks(device_kind="TPU v5 lite") == (819.0, 197.0)
    assert platform_peaks(0.0, 4.5, device_kind="TPU v5 lite") == (819.0, 4.5)
    assert DEVICE_PEAKS["TPU v5 lite"]["int8_tops"] == 393.0
    # an unlisted kind (this CPU backend too) never borrows a row
    assert platform_peaks(device_kind="TPU v9 imaginary") is None
    assert platform_peaks(0.0, 4.5, device_kind="TPU v9 imaginary") is None
    assert platform_peaks() is None


def test_compile_event_span_attribute_mapping():
    t = CompileTracker()
    t.call("prog", _jit_add(13), (jnp.ones((8,)),), {"T": 2, "B": 8})
    span = compile_event_span(t.events()[0])
    assert span.scope == "device" and span.name == "XlaCompile"
    assert span.attributes["program"] == "prog"
    assert span.attributes["cause"] == "initial"
    assert span.attributes["recompile"] is False
    assert span.attributes["compileCount"] == 1
    assert span.attributes["costBytesAccessed"] > 0
    # end - (end - dur) at epoch-ms magnitude loses ~1e-4 ms to float
    # cancellation; the span is for humans, not for timing arithmetic
    assert span.duration_ms == pytest.approx(
        t.events()[0]["duration_ms"], abs=1e-2)


# ---------------------------------------------------------------------------
# 2. key-stats units
# ---------------------------------------------------------------------------

def test_key_stats_fold_matches_numpy_oracle():
    K, G = 64, 8
    loads_np = np.zeros(K, np.int32)
    loads_np[3] = 100      # hot key in group 0
    loads_np[17] = 40      # group 2
    loads_np[40] = 20      # group 5
    loads = jnp.asarray(loads_np)
    c = KeyStatsCollector(lambda: loads, num_key_groups=G, top_k=3,
                          row_bytes_fn=lambda: 128, interval_ms=0)
    assert c.collect()
    p = c.payload()
    gids = (np.arange(K, dtype=np.int64) * G) // K
    per_group = np.bincount(gids, weights=loads_np, minlength=G)
    assert p["totalRecordsResident"] == 160
    assert p["maxKeyLoad"] == 100
    assert p["activeKeys"] == 3
    assert p["hotKeys"] == [[3, 100], [17, 40], [40, 20]]
    # skew = max group load / mean group load
    assert p["keySkew"] == pytest.approx(
        per_group.max() / per_group.mean(), rel=1e-4)
    assert p["keyGroupLoad"]["max"] == per_group.max()
    assert p["keyGroupLoad"]["count"] == G
    # state bytes histogram: active keys per group x row bytes
    assert p["keyGroupStateBytes"]["max"] == 128.0


def test_key_stats_skew_even_vs_hot():
    K, G = 128, 16
    even = KeyStatsCollector(lambda: jnp.ones((K,), jnp.int32),
                             num_key_groups=G, interval_ms=0)
    even.collect()
    assert even.payload()["keySkew"] == pytest.approx(1.0)
    hot_np = np.zeros(K, np.int32)
    hot_np[0] = 1000
    hot = KeyStatsCollector(lambda: jnp.asarray(hot_np),
                            num_key_groups=G, interval_ms=0)
    hot.collect()
    assert hot.payload()["keySkew"] == pytest.approx(G)   # one group owns all


def test_key_stats_empty_state_reads_none_not_zero():
    c = KeyStatsCollector(lambda: jnp.zeros((16,), jnp.int32),
                          num_key_groups=4, interval_ms=0)
    c.collect()
    assert c.skew() is None          # absent measurement, never "0 skew"
    assert c.payload()["keySkew"] is None


def test_key_stats_ready_gate_defers_interval():
    ready = [False]
    folds = []

    def loads():
        folds.append(1)
        return jnp.ones((8,), jnp.int32)

    clock = [0.0]
    c = KeyStatsCollector(loads, num_key_groups=4, interval_ms=1000,
                          ready_fn=lambda: ready[0],
                          clock=lambda: clock[0])
    assert not c.maybe_collect()     # not ready: no fold, interval intact
    assert not folds
    ready[0] = True
    assert c.maybe_collect()         # first ready tick folds immediately
    assert len(folds) == 1
    clock[0] += 0.5
    assert not c.maybe_collect()     # throttled
    clock[0] += 0.6
    assert c.maybe_collect()
    assert len(folds) == 2


# ---------------------------------------------------------------------------
# 3. operator integration
# ---------------------------------------------------------------------------

def _fused_count_op(key_capacity=64, superbatch_steps=4, prologue=None):
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.runtime.fused_window_operator import FusedWindowOperator

    return FusedWindowOperator(
        TumblingEventTimeWindows.of(1000), "count",
        key_capacity=key_capacity, superbatch_steps=superbatch_steps,
        chunk=256, prologue=prologue,
    )


def _drive(op, steps=8, n=256, keys_hi=16):
    rng = np.random.default_rng(3)
    for s in range(steps):
        keys = rng.integers(0, keys_hi, n)
        op.process_batch(keys, np.ones(n, np.float32),
                         np.full(n, s * 300, np.int64))
        op.process_watermark(s * 300)
    op.process_watermark(MAX_WATERMARK)
    return op.drain_output()


def test_phase_counters_match_stream_ground_truth():
    op = _fused_count_op()
    op.attach_device_stats(CompileTracker())
    _drive(op, steps=8, n=256)
    phases = op.phase_totals()
    # every on-time record ingests exactly once
    assert phases["ingestRecords"] == 8 * 256
    # tumbling 1000ms over 8 steps of 300ms: windows 0..2400 fire
    assert phases["fireSteps"] >= 2
    assert phases["purgeSteps"] >= 1


def test_device_stats_do_not_change_results():
    plain = _fused_count_op()
    out_plain = _drive(plain)
    tracked = _fused_count_op()
    tracked.attach_device_stats(CompileTracker())
    out_tracked = _drive(tracked)
    assert sorted((k, w.start, r) for k, w, r, _t in out_plain) == \
        sorted((k, w.start, r) for k, w, r, _t in out_tracked)


def test_ring_doubling_recompile_cause_on_key_growth():
    op = _fused_count_op(key_capacity=32, superbatch_steps=2)
    t = CompileTracker()
    op.attach_device_stats(t)
    rng = np.random.default_rng(5)
    for s in range(10):
        hi = 24 if s < 5 else 120   # dispatch small first, then outgrow K
        keys = rng.integers(0, hi, 128)
        op.process_batch(keys, np.ones(128, np.float32),
                         np.full(128, s * 300, np.int64))
        op.process_watermark(s * 300)
    op.process_watermark(MAX_WATERMARK)
    causes = {e["cause"] for e in t.events() if e["recompile"]}
    assert "ring-doubling" in causes
    assert t.num_recompiles >= 1


def test_fused_operator_key_loads_and_ready_probe():
    op = _fused_count_op(superbatch_steps=2)
    assert op.key_stats_ready() is False
    _drive(op, steps=4, n=64, keys_hi=8)
    # after MAX watermark everything purged; drive again mid-stream
    op2 = _fused_count_op(superbatch_steps=2)
    rng = np.random.default_rng(1)
    for s in range(4):
        op2.process_batch(rng.integers(0, 8, 64),
                          np.ones(64, np.float32),
                          np.full(64, s * 300, np.int64))
        op2.process_watermark(s * 300)
    assert op2.key_stats_ready() is True
    loads = np.asarray(op2.key_loads())
    assert loads.shape[0] == op2.pipe.K
    assert loads.sum() > 0
    assert op2.state_row_bytes() > 0


def test_tpu_window_operator_key_loads():
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.runtime.tpu_window_operator import TpuWindowOperator

    op = TpuWindowOperator(TumblingEventTimeWindows.of(1000), "sum",
                           key_capacity=32)
    assert op.key_stats_ready() is False
    op.process_batch(np.arange(8), np.ones(8, np.float32),
                     np.full(8, 100, np.int64))
    assert op.key_stats_ready() is True
    assert int(np.asarray(op.key_loads()).sum()) == 8
    assert op.state_row_bytes() > 0


# ---------------------------------------------------------------------------
# 4. end to end: MiniCluster -> REST /jobs/:id/device
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def device_job():
    """Two jobs on one cluster: a fused-chain job sized to dispatch
    mid-stream (key stats see resident state, phases count the traced
    filter's survivors) and a classic fused job whose key dictionary
    outgrows the initial ring capacity mid-stream — a REAL induced
    geometry-churn recompile for the event ring to attribute."""
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
    from flink_tpu.runtime.minicluster import MiniCluster
    from flink_tpu.runtime.rest import RestServer
    from flink_tpu.utils.arrays import obj_array

    # compile observability is detected through the jitted callables'
    # cache growth, and superscan executables are cached MODULE-LEVEL by
    # geometry — any earlier test whose wall-clock-dependent dispatch
    # pattern (checkpoint flushes truncate superbatches at arbitrary T)
    # happens to hit this fixture's geometry would pre-compile it and
    # silently hide the compile/recompile events asserted below. Start
    # from a clean executable cache so the events are THIS fixture's own.
    from flink_tpu.runtime import fused_window_pipeline as _fwp

    _fwp._build_superscan.cache_clear()
    _fwp._CHAINED_CACHE.clear()

    def gen(idx):
        col = np.stack([(idx * 31) % 23, idx % 3], axis=1).astype(np.float32)
        return Batch(col, (idx * 5).astype(np.int64))

    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, 128)
    cfg.set(ExecutionOptions.KEY_CAPACITY, 23)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    cfg.set(ObservabilityOptions.DEVICE_KEY_STATS_INTERVAL_MS, 0)
    # the CPU backend has no DEVICE_PEAKS row: state the peaks, as a user
    # of an unlisted part would, so the roofline gauges exist here
    cfg.set(ObservabilityOptions.DEVICE_HBM_GBPS, 50.0)
    cfg.set(ObservabilityOptions.DEVICE_PEAK_TFLOPS, 0.2)
    env = StreamExecutionEnvironment(cfg)
    ds = env.from_source(
        DataGeneratorSource(gen, count=3264),
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
    )
    (ds.filter(lambda c: c[:, 1] < 1.5, traceable=True)
       .key_by(lambda c: c[:, 0].astype(jnp.int32), traceable=True)
       .window(TumblingEventTimeWindows.of(1000)).count()
       .sink_to(CollectSink()))
    client = env.execute_async("device-plane-e2e")
    assert client.wait(180).value == "FINISHED"

    # classic fused path with a growing key dictionary: dense ring starts
    # at min(1024, capacity) and doubles when the dictionary outgrows it,
    # recompiling the superscan with cause 'ring-doubling'
    def gen_grow(idx):
        # first ~10 batches stay under 1024 distinct keys (dispatches run
        # at the initial capacity), later ones outgrow it
        lo = idx % np.where(idx < 1280, 700, 1500)
        return Batch(obj_array([(int(k), 1.0) for k in lo]),
                     (idx * 5).astype(np.int64))

    cfg2 = Configuration()
    cfg2.set(ExecutionOptions.BATCH_SIZE, 128)
    cfg2.set(ExecutionOptions.KEY_CAPACITY, 2048)
    cfg2.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    env2 = StreamExecutionEnvironment(cfg2)
    (env2.from_source(
        DataGeneratorSource(gen_grow, count=3000),
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps())
        .key_by(lambda r: r[0])
        .window(TumblingEventTimeWindows.of(1000)).count()
        .sink_to(CollectSink()))
    grow_client = env2.execute_async("device-plane-ring-doubling")
    assert grow_client.wait(180).value == "FINISHED"

    cluster = MiniCluster.get_shared()
    cluster.jobs.setdefault(client.job_id, client)
    cluster.jobs.setdefault(grow_client.job_id, grow_client)
    server = RestServer(cluster).start()
    yield server, client, grow_client
    server.stop()


def _get(server, path):
    with urllib.request.urlopen(f"{server.url}{path}", timeout=10) as r:
        return json.loads(r.read())


def test_rest_device_payload_has_compile_and_recompile_ring(device_job):
    server, client, grow = device_job
    body = _get(server, f"/jobs/{client.job_id}/device")
    assert body["enabled"] is True
    comp = body["compile"]
    assert comp["numCompiles"] >= 1
    assert all(e["duration_ms"] > 0 for e in comp["events"])
    # induced geometry churn: the classic job's key dictionary outgrew the
    # initial dense ring mid-stream — the recompile appears in the event
    # ring with its cause attributed
    grow_body = _get(server, f"/jobs/{grow.job_id}/device")
    gcomp = grow_body["compile"]
    assert gcomp["numRecompiles"] >= 1
    recompiles = [e for e in gcomp["events"] if e["recompile"]]
    assert recompiles
    assert any(e["cause"] == "ring-doubling" for e in recompiles)


def test_rest_device_payload_operator_blocks(device_job):
    server, client, _grow = device_job
    body = _get(server, f"/jobs/{client.job_id}/device")
    ops = body["operators"]
    assert ops, "no operator entries in the device payload"
    (entry,) = [e for e in ops.values() if "compile" in e]
    assert entry["deviceDispatches"] > 0
    assert "hbmUtilizationPct" in entry and "flopsUtilizationPct" in entry
    phases = entry["phases"]
    # the chained program masks the filter INSIDE the scan: the ingest
    # counter sees exactly the records that survive it (etype < 1.5 keeps
    # 2/3 of 3264)
    assert phases["ingestRecords"] == 2176
    assert phases["fireSteps"] >= 1
    keys = entry["keys"]
    assert keys["keySkew"] is not None and keys["keySkew"] >= 1.0
    assert keys["activeKeys"] > 0
    assert keys["hotKeys"]


def test_rest_device_payload_profiler_block_default_off(device_job):
    server, client, _grow = device_job
    body = _get(server, f"/jobs/{client.job_id}/device")
    assert body["profiler"] == {"enabled": False, "captures": 0,
                                "last_capture_dir": None}


def test_compile_events_ride_the_trace_registry(device_job):
    _server, client, _grow = device_job
    spans = client.otel.payload()["resourceSpans"][0]["scopeSpans"][0]["spans"]
    device_spans = [s for s in spans if s["name"] == "device.XlaCompile"]
    assert device_spans
    attrs = {a["key"]: list(a["value"].values())[0]
             for a in device_spans[0]["attributes"]}
    assert attrs["program"].startswith("fused")
    assert "cause" in attrs and "signature" in attrs
    # the job's deterministic trace id correlates compile spans with the
    # rest of the job's trace
    assert device_spans[0]["traceId"] == client.trace_id


def test_job_level_gauges_ship_in_metric_snapshot(device_job):
    _server, client, grow = device_job
    from flink_tpu.metrics.registry import metrics_snapshot

    snap = metrics_snapshot(client.metrics.all_metrics())
    assert snap["job.device.numCompiles"] >= 1
    assert "job.device.hbmUtilizationPct" in snap
    assert snap["job.keySkew"] >= 1.0
    # operator-scope families for Prometheus
    op_keys = [k for k in snap if ".numCompiles" in k and "operator" in k]
    assert op_keys
    # the induced-recompile job's counter rides the same key space
    grow_snap = metrics_snapshot(grow.metrics.all_metrics())
    assert grow_snap["job.device.numRecompiles"] >= 1


def test_signals_pick_up_device_gauges(device_job):
    _server, client, _grow = device_job
    from flink_tpu.metrics.registry import metrics_snapshot
    from flink_tpu.scheduler.signals import extract_signals

    s = extract_signals(metrics_snapshot(client.metrics.all_metrics()))
    assert s.key_skew is not None and s.key_skew >= 1.0
    assert s.device_utilization is not None


def test_device_payload_empty_for_unknown_runtime(device_job):
    server, _client, _grow = device_job
    from flink_tpu.runtime.minicluster import JobClient, MiniCluster

    stub = JobClient("stubjob", "stub")   # no _runtime attribute
    MiniCluster.get_shared().jobs["stubjob"] = stub
    try:
        body = _get(server, "/jobs/stubjob/device")
        assert body["enabled"] is False
        assert body["compile"]["numCompiles"] == 0
    finally:
        MiniCluster.get_shared().jobs.pop("stubjob", None)


# ---------------------------------------------------------------------------
# 5. OTLP/JSON export of compile-event spans (satellite c: OtlpJsonTrace-
#    Reporter coverage — attribute mapping + payload golden)
# ---------------------------------------------------------------------------

def _compile_event(**over):
    ev = {
        "program": "fused_superscan",
        "signature": "B=8192,K=8192,S=32,T=32,dtype=float32",
        "cause": "ring-doubling",
        "recompile": True,
        "compile_count": 2,
        "duration_ms": 1500.0,
        "wall_ts_ms": 1_700_000_001_500.0,
        "cost": {"flops": 2.5e9, "bytes_accessed": 4.0e9},
    }
    ev.update(over)
    return ev


def test_otlp_reporter_encodes_compile_span_attributes():
    from flink_tpu.metrics.otel import OtlpJsonTraceReporter

    rep = OtlpJsonTraceReporter(service_name="flink-tpu-test")
    rep.report_span(compile_event_span(_compile_event()))
    payload = rep.payload()
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert len(spans) == 1
    s = spans[0]
    assert s["name"] == "device.XlaCompile"
    # nanosecond timestamps bracket the compile wall time
    dur_ns = int(s["endTimeUnixNano"]) - int(s["startTimeUnixNano"])
    assert dur_ns == pytest.approx(1500.0 * 1e6, rel=1e-3)
    attrs = {a["key"]: a["value"] for a in s["attributes"]}
    # OTLP typed-value mapping: strings stay strings, ints encode as
    # STRING intValue (the OTLP/JSON int64 rule), bools as boolValue,
    # floats as doubleValue
    assert attrs["program"] == {"stringValue": "fused_superscan"}
    assert attrs["signature"]["stringValue"].startswith("B=8192,K=8192")
    assert attrs["cause"] == {"stringValue": "ring-doubling"}
    assert attrs["recompile"] == {"boolValue": True}
    assert attrs["compileCount"] == {"intValue": "2"}
    assert attrs["costFlops"] == {"doubleValue": 2.5e9}
    assert attrs["costBytesAccessed"] == {"doubleValue": 4.0e9}


def test_otlp_payload_golden_shape():
    from flink_tpu.metrics.otel import OtlpJsonTraceReporter

    rep = OtlpJsonTraceReporter(service_name="flink-tpu-test")
    span = compile_event_span(_compile_event(cost=None, recompile=False,
                                             compile_count=1,
                                             cause="initial"))
    span.trace_id = "ab" * 16
    rep.report_span(span)
    payload = rep.payload()
    # golden envelope: resourceSpans -> resource.attributes(service.name)
    # -> scopeSpans -> scope(name/version) -> spans
    assert list(payload) == ["resourceSpans"]
    rs = payload["resourceSpans"][0]
    assert rs["resource"]["attributes"] == [
        {"key": "service.name", "value": {"stringValue": "flink-tpu-test"}}]
    sc = rs["scopeSpans"][0]
    assert sc["scope"] == {"name": "flink_tpu", "version": "1"}
    s = sc["spans"][0]
    assert set(s) == {"traceId", "spanId", "name", "kind",
                      "startTimeUnixNano", "endTimeUnixNano", "attributes",
                      "status"}
    assert s["traceId"] == "ab" * 16          # correlation id propagates
    assert s["kind"] == 1                     # SPAN_KIND_INTERNAL
    assert json.dumps(payload)                # strictly JSON-serializable


def test_otlp_reporter_bounds_buffer_and_flushes_file(tmp_path):
    from flink_tpu.metrics.otel import OtlpJsonTraceReporter

    path = tmp_path / "spans.jsonl"
    rep = OtlpJsonTraceReporter(path=str(path), max_spans=4)
    for i in range(6):
        rep.report_span(compile_event_span(_compile_event(compile_count=i + 1)))
    spans = rep.payload()["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert len(spans) == 4                    # bounded buffer keeps newest
    counts = [
        {a["key"]: a["value"] for a in s["attributes"]}["compileCount"]
        for s in spans
    ]
    assert counts == [{"intValue": str(i)} for i in (3, 4, 5, 6)]
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 6                    # the file keeps every export
    for line in lines:
        doc = json.loads(line)
        assert doc["resourceSpans"][0]["scopeSpans"][0]["spans"]


# ---------------------------------------------------------------------------
# 6. distributed plane: shard folding + TM->JM shipping
# ---------------------------------------------------------------------------

def test_shard_combine_rules_for_device_gauges():
    """Skew/storm take the WORST shard, roofline percentages average per
    chip, compile counters sum — summing a skew ratio across shards would
    be meaningless and averaging would hide one hot shard."""
    from flink_tpu.runtime.cluster import aggregate_shard_metrics

    agg = aggregate_shard_metrics({
        0: {"job.keySkew": 1.2, "job.device.numCompiles": 2,
            "job.device.hbmUtilizationPct": 10.0,
            "job.device.recompileStorm": 0,
            "job.operator.keyed-window.hotKeyLoad": 5},
        1: {"job.keySkew": 4.0, "job.device.numCompiles": 3,
            "job.device.hbmUtilizationPct": 30.0,
            "job.device.recompileStorm": 1,
            "job.operator.keyed-window.hotKeyLoad": 9},
    })
    assert agg["job.keySkew"] == 4.0
    assert agg["job.device.numCompiles"] == 5
    assert agg["job.device.hbmUtilizationPct"] == pytest.approx(20.0)
    assert agg["job.device.recompileStorm"] == 1
    assert agg["job.operator.keyed-window.hotKeyLoad"] == 9


def test_distributed_keyed_job_ships_key_skew(tmp_path):
    """The keyed distributed path folds per-key loads on device, ships
    keySkew on the heartbeat snapshots, and the JM serves it through
    job_device — the signal the autoscaler's learning policy lacked."""
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.runtime.cluster import (
        DistributedJobSpec,
        JobManagerEndpoint,
        TaskExecutorEndpoint,
    )
    from flink_tpu.runtime.rpc import RpcService
    import time as _time

    def source_factory(shard, num_shards):
        rng = np.random.default_rng(23 + shard)
        steps = []
        for s in range(20):
            # heavy skew: ~3/4 of records on key 0
            keys = np.where(rng.random(64) < 0.75, 0,
                            rng.integers(1, 16, 64)).astype(np.int64)
            steps.append((keys, np.ones(64, np.float64),
                          (s * 1000 + rng.integers(0, 1000, 64)).astype(
                              np.int64),
                          s * 1000 + 500))
        return steps

    spec = DistributedJobSpec(
        name="keyed-device-skew", source_factory=source_factory,
        assigner=TumblingEventTimeWindows.of(60_000), aggregate="sum",
        max_parallelism=16, operator="device",
    )
    svc_jm, svc_tm = RpcService(), RpcService()
    jm = JobManagerEndpoint(svc_jm, checkpoint_dir=str(tmp_path / "chk"))
    te = TaskExecutorEndpoint(svc_tm, slots=1, shipping_interval_ms=100)
    te.connect(svc_jm.address)
    client = svc_jm.gateway(svc_jm.address, "jobmanager")
    try:
        job_id = client.submit_job(spec.to_bytes(), 1)
        deadline = _time.time() + 60
        while _time.time() < deadline:
            if client.job_status(job_id)["status"] in ("FINISHED", "FAILED"):
                break
            _time.sleep(0.05)
        assert client.job_status(job_id)["status"] == "FINISHED"
        # the heartbeat keeps shipping snapshots for the finished task:
        # poll until the skew gauge lands
        body = None
        while _time.time() < deadline:
            body = client.job_device(job_id)
            if any("keySkew" in k for k in body["metrics"]):
                break
            _time.sleep(0.1)
        skews = [v for k, v in body["metrics"].items() if "keySkew" in k
                 and isinstance(v, (int, float))]
        assert skews, f"no keySkew shipped: {sorted(body['metrics'])}"
        # ~3/4 of the load on one key of 16 key-groups: strong skew
        assert max(skews) > 2.0
        assert body["enabled"] is True
    finally:
        te.stop()
        jm.heartbeats.stop()
        svc_jm.stop()
        svc_tm.stop()


def test_profiler_capture_surface(tmp_path):
    """Satellite (a): observability.profiler.enabled captures are no
    longer write-only — the device payload reports count + location."""
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.config import Configuration, ObservabilityOptions
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.utils.arrays import obj_array

    def gen(idx):
        return Batch(obj_array([int(i) for i in idx]),
                     (idx * 10).astype(np.int64))

    prof_dir = str(tmp_path / "prof")
    cfg = Configuration()
    cfg.set(ObservabilityOptions.PROFILER_ENABLED, True)
    cfg.set(ObservabilityOptions.PROFILER_DIR, prof_dir)
    env = StreamExecutionEnvironment(cfg)
    env.from_source(
        DataGeneratorSource(gen, count=64),
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
    ).map(lambda x: x).sink_to(CollectSink())
    client = env.execute_async("profiler-surface")
    assert client.wait(120).value == "FINISHED"
    snap = client._runtime.device_snapshot()
    assert snap["profiler"]["enabled"] is True
    assert snap["profiler"]["captures"] == 1
    assert snap["profiler"]["last_capture_dir"] == prof_dir
