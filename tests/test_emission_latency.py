"""Emission-latency plane tests: log-bucket histogram geometry, bucket-wise
merges, sentinel clamps, fire-to-resolve stamping, stall attribution, the
shard-fold + payload-filter registration regression, and the uniform
Prometheus summary export (observability.emission-latency.*)."""

import math

import numpy as np
import pytest

from flink_tpu.metrics.emission_latency import (
    LATENCY_SPAN_NAME,
    LATENCY_SPAN_SCOPE,
    NUM_BUCKETS,
    SUBBUCKETS,
    EmissionHistogram,
    EmissionLatencyTracker,
    bucket_index,
    bucket_upper,
    build_latency_report,
    is_emission_snapshot,
    merge_snapshots,
    stall_attribution,
    watermark_lag_ms,
)

MAX_WATERMARK = (1 << 63) - 1
MIN_WATERMARK = -(1 << 63)


# -- histogram geometry ---------------------------------------------------

def test_bucket_boundaries():
    # <=1ms (and degenerate inputs) collapse into bucket 0
    for v in (0.0, 0.5, 1.0, -3.0, float("nan")):
        assert bucket_index(v) == 0
    assert bucket_upper(0) == 1.0
    # each octave splits into SUBBUCKETS; exact powers of two open a new
    # octave's first sub-bucket
    assert bucket_index(1.0001) == 1
    assert bucket_index(2.0) == 1 + SUBBUCKETS    # octave 1, first sub
    # bucket_upper is the inclusive upper bound: a value never lands in a
    # bucket whose upper bound is below it, and the relative error of
    # reporting the upper bound is <= 1/SUBBUCKETS
    rng = np.random.default_rng(7)
    for v in rng.uniform(1.001, 1e9, size=500):
        idx = bucket_index(v)
        up = bucket_upper(idx)
        assert up >= v * (1.0 - 1e-9)
        assert up <= v * (1.0 + 1.0 / SUBBUCKETS) * (1.0 + 1e-9)
    # monotone: larger values never map to smaller buckets
    vals = np.sort(rng.uniform(0.0, 1e12, size=1000))
    idxs = [bucket_index(v) for v in vals]
    assert idxs == sorted(idxs)
    # the top bucket absorbs everything beyond the covered range
    assert bucket_index(float(1 << 60)) == NUM_BUCKETS - 1


def test_histogram_percentiles_basic():
    h = EmissionHistogram()
    for v in range(1, 101):           # 1..100 ms
        h.record(float(v))
    assert h.count == 100
    s = h.snapshot()
    assert s["min"] == 1.0 and s["max"] == 100.0
    # log-bucket percentiles carry <=12.5% relative error upward, and are
    # clamped to the observed max
    assert 50.0 <= s["p50"] <= 50.0 * 1.125
    assert 99.0 <= s["p99"] <= 100.0
    assert s["p999"] <= s["max"]


def test_p999_adversarial_tail():
    # 990 fast fires + 10 catastrophic stalls: p999 (rank 999 of 1000)
    # must surface a stall — a reservoir histogram routinely misses the
    # tail; the log buckets cannot
    h = EmissionHistogram()
    h.record(1.0, n=990)
    h.record(10_000.0, n=10)
    assert h.value_at(99.9) >= 10_000.0 * (1.0 - 1.0 / SUBBUCKETS)
    assert h.value_at(99.9) <= h.max
    # all-identical distribution: every percentile is that value's bucket
    h2 = EmissionHistogram()
    h2.record(42.0, n=10_000)
    for pct in (50.0, 95.0, 99.0, 99.9):
        assert h2.value_at(pct) == 42.0  # clamped to observed max


def test_merge_associativity_and_commutativity():
    rng = np.random.default_rng(11)
    chunks = [rng.lognormal(3.0, 2.0, size=200) for _ in range(3)]
    hs = []
    for c in chunks:
        h = EmissionHistogram()
        for v in c:
            h.record(float(v))
        hs.append(h.snapshot())
    direct = EmissionHistogram()
    for c in chunks:
        for v in c:
            direct.record(float(v))
    # (a + b) + c == a + (b + c) == direct recording, bucket-exactly
    ab_c = merge_snapshots([merge_snapshots(hs[:2]), hs[2]])
    a_bc = merge_snapshots([hs[0], merge_snapshots(hs[1:])])
    cba = merge_snapshots(list(reversed(hs)))
    want = direct.snapshot()
    assert ab_c == a_bc == cba == want
    assert ab_c["count"] == 600


def test_snapshot_roundtrip_flat_numeric():
    h = EmissionHistogram()
    for v in (2.0, 30.0, 400.0):
        h.record(v)
    s = h.snapshot()
    # flat numeric dict: survives metrics_snapshot's numeric-only filter
    assert all(isinstance(v, (int, float)) for v in s.values())
    assert is_emission_snapshot(s)
    assert not is_emission_snapshot({"count": 3})     # no buckets
    back = EmissionHistogram.from_snapshot(s)
    assert back.snapshot() == s


def test_int64_sentinel_clamps():
    t = EmissionLatencyTracker("op", clock=lambda: 1000.0)
    # watermark sentinels carry no event-time close: counted, not recorded
    assert t.record_fire(MAX_WATERMARK - 1) is None
    assert t.record_fire(MIN_WATERMARK + 1) is None
    assert t.record_fire(0) is None
    assert t.record_fire("not-a-number") is None
    assert t.sentinel == 3            # the non-numeric fire isn't a fire
    assert t.histogram.count == 0
    assert t.snapshot()["sentinel"] == 3
    # a plausible event time records without overflow even at huge lag
    lat = t.record_fire(1.0)
    assert lat is not None and lat > 0
    assert math.isfinite(t.histogram.max)


def test_watermark_lag_sentinels():
    now = 1_700_000_000_000.0
    assert watermark_lag_ms(MIN_WATERMARK, now) == 0.0
    assert watermark_lag_ms(MAX_WATERMARK, now) == 0.0
    assert watermark_lag_ms(0, now) == 0.0
    assert watermark_lag_ms(None, now) == 0.0
    assert watermark_lag_ms(now - 250.0, now) == 250.0
    assert watermark_lag_ms(now + 10_000.0, now) == 0.0   # never negative


# -- fire-to-resolve stamping --------------------------------------------

def test_record_fire_measures_resolve_not_dispatch():
    clock = [100.0]                   # seconds
    t = EmissionLatencyTracker("w", clock=lambda: clock[0])
    # window closed at event-time 99_000ms with 500ms lateness; the host
    # resolves it at wall 100_000ms -> 500ms emission latency
    lat = t.record_fire(99_000, lateness_ms=500)
    assert lat == 500.0
    # resolving later (deferred readback drained on a later step) grows
    # the measured latency — the stamp is at RESOLVE time
    clock[0] = 101.0
    assert t.record_fire(99_000, lateness_ms=500) == 1500.0


def test_outlier_capture_ring_and_spans():
    clock = [0.0]
    spans = []

    def sink(scope, name, start, end, attrs):
        spans.append((scope, name, start, end, attrs))

    t = EmissionLatencyTracker(
        "w", outlier_pct=99.0, outlier_floor_ms=5.0, ring_size=4,
        min_samples=1, span_sink=sink, span_min_gap_ms=0.0,
        clock=lambda: clock[0])
    # sub-floor latencies never capture
    for i in range(20):
        clock[0] = i * 10.0 + 0.001
        t.record_fire(clock[0] * 1000.0 - 1.0)
    assert t.outliers == [] and spans == []
    # a 50ms stall beats the floor and the p99 threshold
    clock[0] = 300.0
    t.record_fire(clock[0] * 1000.0 - 50.0)
    assert len(t.outliers) == 1
    assert t.outliers[0]["latencyMs"] == 50.0
    [(scope, name, start, end, attrs)] = spans
    assert (scope, name) == (LATENCY_SPAN_SCOPE, LATENCY_SPAN_NAME)
    assert attrs["operator"] == "w" and attrs["latencyMs"] == 50.0
    assert end == 300_000.0
    # the ring stays bounded
    for i in range(10):
        clock[0] = 400.0 + i
        t.record_fire(clock[0] * 1000.0 - 60.0)
    assert len(t.outliers) == 4


def test_outlier_min_samples_gate():
    clock = [10.0]
    t = EmissionLatencyTracker("w", min_samples=16, outlier_floor_ms=5.0,
                               clock=lambda: clock[0])
    for _ in range(15):
        t.record_fire(clock[0] * 1000.0 - 100.0)
    assert t.outliers == []           # still warming up
    t.record_fire(clock[0] * 1000.0 - 100.0)
    assert len(t.outliers) == 1       # 16th fire may capture


def test_outlier_span_liveness_bound():
    # synthetic-epoch job (event time near 1970): the stall span must
    # start no earlier than the tracker's birth / previous resolve, never
    # at the 1970 window close — otherwise attribution degenerates to
    # "whichever control span is longest"
    clock = [500.0]
    spans = []
    t = EmissionLatencyTracker(
        "w", min_samples=1, span_min_gap_ms=0.0, clock=lambda: clock[0],
        span_sink=lambda *a: spans.append(a))
    clock[0] = 500.2
    t.record_fire(12_000)             # window end = 12s after 1970
    [(_s, _n, start, end, _a)] = spans
    assert start >= 500_000.0         # tracker birth wall, not 12_000
    assert end == 500_200.0


# -- stall attribution ----------------------------------------------------

def _span(scope, name, start, end, **attrs):
    return {"scope": scope, "name": name, "start_ts_ms": start,
            "end_ts_ms": end, "attributes": attrs}


def test_stall_attribution_largest_overlap_wins():
    spans = [
        _span("checkpointing", "Checkpoint", 1000.0, 1010.0),
        _span("recovery", "JobRestart", 1005.0, 1095.0),
        _span(LATENCY_SPAN_SCOPE, LATENCY_SPAN_NAME, 1000.0, 1100.0,
              latencyMs=100.0),
    ]
    rep = stall_attribution(spans, slack_ms=0.0)
    assert rep["outliers"] == 1 and rep["unattributed"] == 0
    assert set(rep["attributed"]) == {"recovery.JobRestart"}
    blk = rep["attributed"]["recovery.JobRestart"]
    assert blk["count"] == 1 and blk["maxLatencyMs"] == 100.0


@pytest.mark.parametrize("checkpoint_ms", [5.0, 12.0, 40.0])
def test_a_stall_that_overlaps_a_restart_is_the_restarts(checkpoint_ms):
    """The join-restore race (chaos/scenarios.py): the post-restore stall
    opens mid-restart and also spans the restored job's first checkpoint;
    whichever of the two spans lasted longer used to own it."""
    spans = [
        _span("recovery", "JobRestart", 1000.0, 1013.0),
        _span("checkpointing", "Checkpoint", 1013.5, 1013.5 + checkpoint_ms),
        _span(LATENCY_SPAN_SCOPE, LATENCY_SPAN_NAME, 1004.0, 1060.0,
              latencyMs=56.0),
    ]
    rep = stall_attribution(spans)
    assert set(rep["attributed"]) == {"recovery.JobRestart"}
    # a restart the stall only comes near (inside the slack) has no such
    # claim: there the larger overlap still decides
    spans[0] = _span("recovery", "JobRestart", 990.0, 1003.0)
    owner = "checkpointing.Checkpoint" if checkpoint_ms > 13.0 \
        else "recovery.JobRestart"
    assert set(stall_attribution(spans)["attributed"]) == {owner}


def test_stall_attribution_unattributed_and_slack():
    stall = _span(LATENCY_SPAN_SCOPE, LATENCY_SPAN_NAME, 2000.0, 2100.0)
    far = _span("checkpointing", "Checkpoint", 2140.0, 2150.0)
    assert stall_attribution([stall, far],
                             slack_ms=0.0)["unattributed"] == 1
    # the same control span within the slack window attributes
    assert stall_attribution([stall, far], slack_ms=50.0)["attributed"]


def test_build_latency_report_shape():
    snap = EmissionHistogram()
    snap.record(10.0, n=98)
    snap.record(500.0, n=2)
    metrics = {
        "job.op.win-1.emissionLatencyMs": snap.snapshot(),
        "job.op.win-1.watermarkLagMs": 25.0,
        "job.op.src-0.watermarkLagMs": 75.0,
        "job.op.win-1.numRecordsIn": 100,
    }
    rep = build_latency_report(metrics, [])
    assert rep["samples"] == 100
    assert rep["p99_ms"] >= 500.0 * (1.0 - 1.0 / SUBBUCKETS)
    assert rep["watermarkLagMs"] == 75.0          # MAX across operators
    assert rep["operators"]["win-1"]["watermarkLagMs"] == 25.0
    assert "emissionLatencyMs" in rep["operators"]["win-1"]
    assert "attribution" in rep and rep["attribution"]["outliers"] == 0
    # the job-level emission block is bucket-free (payload hygiene)
    assert not any(k.startswith("b") for k in rep["emission"])


# -- shard-fold + payload-filter registration (the _TIER_GAUGES lesson) ---

def test_latency_gauges_registered_in_fold_and_filters():
    """Every emission-plane leaf the executors register must sit in the
    ONE shared tuple that feeds both the aggregate_shard_metrics fold rule
    and the /jobs/:id/device payload filters — a family missing from
    either silently reads 0/absent at the job level."""
    from flink_tpu.runtime.cluster import (
        _LATENCY_GAUGES,
        _LATENCY_HISTOGRAMS,
        _LATENCY_MAX_GAUGES,
        _shard_combine,
    )

    # the leaves register_metrics/JobRuntime actually register
    assert "emissionLatencyMs" in _LATENCY_HISTOGRAMS
    assert "watermarkLagMs" in _LATENCY_MAX_GAUGES
    assert "p99EmissionLatencyMs" in _LATENCY_MAX_GAUGES
    assert set(_LATENCY_GAUGES) == (
        set(_LATENCY_MAX_GAUGES) | set(_LATENCY_HISTOGRAMS))
    # lag/percentile scalars fold MAX (worst shard), never sum
    for leaf in _LATENCY_MAX_GAUGES:
        assert _shard_combine(f"op.win-1.{leaf}") == "max"
    # ISSUE-18: the latency-mode controller gauges ride the same tuple —
    # omitting any from the fold rule OR either payload filter silently
    # hides a shard's rung/ring state at the job level (the exact
    # _TIER_GAUGES failure class this test exists to pin)
    from flink_tpu.runtime.cluster import _LATENCY_CONTROLLER_GAUGES

    assert set(_LATENCY_CONTROLLER_GAUGES) == {
        "latencyModeActive", "currentBatchRung",
        "inflightDepth", "ladderRecompiles"}
    for leaf in _LATENCY_CONTROLLER_GAUGES:
        assert leaf in _LATENCY_MAX_GAUGES, \
            f"{leaf} missing from the MAX fold family"
        assert leaf in _LATENCY_GAUGES, \
            f"{leaf} missing from the payload-filter family"
        assert _shard_combine(f"op.win-1.{leaf}") == "max"


def test_aggregate_shard_metrics_folds_emission_bucketwise():
    from flink_tpu.runtime.cluster import aggregate_shard_metrics

    h1, h2 = EmissionHistogram(), EmissionHistogram()
    h1.record(4.0, n=50)
    h2.record(900.0, n=50)
    per_shard = {
        0: {"op.win-1.emissionLatencyMs": h1.snapshot(),
            "op.win-1.watermarkLagMs": 10.0,
            "job.p99EmissionLatencyMs": 4.5},
        1: {"op.win-1.emissionLatencyMs": h2.snapshot(),
            "op.win-1.watermarkLagMs": 90.0,
            "job.p99EmissionLatencyMs": 1012.0},
    }
    agg = aggregate_shard_metrics(per_shard)
    merged = agg["op.win-1.emissionLatencyMs"]
    direct = EmissionHistogram()
    direct.record(4.0, n=50)
    direct.record(900.0, n=50)
    # EXACT bucket-wise fold: identical to recording on one shard — the
    # generic dict envelope (sum counts, max percentiles) would report
    # p50 == 900 for this split
    assert merged == direct.snapshot()
    assert merged["count"] == 100
    assert merged["p50"] <= 4.5
    assert agg["op.win-1.watermarkLagMs"] == 90.0
    assert agg["job.p99EmissionLatencyMs"] == 1012.0


# -- end-to-end: deferred-path stamping + the /latency report -------------

def test_windowed_job_records_emission_latency_end_to_end():
    """A windowed job on the MiniCluster path stamps every fired window at
    its host-resolve point and serves the aggregate through
    client.latency_report() (the /jobs/:id/latency payload)."""
    import time as _time

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.core.watermarks import WatermarkStrategy

    t0 = _time.time() * 1000.0 - 10_000.0     # wall-anchored event time
    env = StreamExecutionEnvironment.get_execution_environment()
    data = [(f"k{i % 4}", 1.0, int(t0 + i * 10)) for i in range(400)]
    stream = env.from_collection(
        data,
        timestamp_fn=lambda x: x[2],
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
    )
    sink = (stream.key_by(lambda x: x[0])
            .window(TumblingEventTimeWindows.of(1000))
            .aggregate("count")
            .collect())
    client = env.execute_async("emission-e2e")
    client.wait(60.0)
    assert sum(n for _, n in sink.results) == 400
    rep = client.latency_report()
    # every window that closed inside the run was stamped at resolve; the
    # terminal-watermark flush fires count as sentinel, not latency
    assert rep["samples"] > 0
    assert rep["p99_ms"] >= rep["p50_ms"] > 0
    ops = [op for op in rep["operators"].values()
           if "emissionLatencyMs" in op]
    assert ops and any(op["emissionLatencyMs"]["count"] > 0 for op in ops)


# -- uniform histogram export (Prometheus text) ---------------------------

def test_prometheus_text_renders_emission_snapshot_as_summary():
    from flink_tpu.metrics.registry import MetricRegistry, prometheus_text

    h = EmissionHistogram()
    h.record(10.0, n=999)
    h.record(5000.0)
    reg = MetricRegistry()
    g = reg.group("job", "op", "win-1")
    g.gauge("emissionLatencyMs", h.snapshot)
    g.gauge("watermarkLagMs", lambda: 12.5)
    text = prometheus_text(reg.all_metrics())
    # a dict-valued gauge with a `count` key exports as a summary family —
    # same quantile set as reservoir Histograms, p999 included
    assert "# TYPE job_op_win_1_emissionLatencyMs summary" in text
    assert 'job_op_win_1_emissionLatencyMs{quantile="0.999"}' in text
    assert "job_op_win_1_emissionLatencyMs_count 1000" in text
    assert "job_op_win_1_watermarkLagMs 12.5" in text
