"""End-to-end streaming observability plane (ISSUE 2).

Covers: latency markers feeding per-operator histograms (in-process and
across stage boundaries), busy/idle/backpressure ratios, TPU cost
attribution gauges, Prometheus exposition hygiene (# TYPE, escaping),
registry collision behavior, authenticated REST exposure, and TM -> JM
metric/span shipping with matching trace ids."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
from flink_tpu.config import Configuration, ExecutionOptions, SecurityOptions
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.graph.transformation import plan
from flink_tpu.metrics.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    metrics_snapshot,
    prometheus_text,
    prometheus_text_from_snapshot,
)
from flink_tpu.metrics.task_io import StageClock, TaskIOMetrics
from flink_tpu.metrics.traces import Span, TraceRegistry, job_trace_id
from flink_tpu.runtime.minicluster import JobStatus, MiniCluster
from flink_tpu.runtime.rest import RestServer


# ---------------------------------------------------------------------------
# registry + prometheus satellites
# ---------------------------------------------------------------------------

def test_registry_type_collision_keeps_first_and_warns(caplog):
    import logging

    reg = MetricRegistry()
    g = reg.group("job", "op")
    c = g.counter("m")
    c.inc(3)
    with caplog.at_level(logging.WARNING, logger="flink_tpu.metrics"):
        h = g.histogram("m")   # same key, different type
    # first registration wins; the conflicting caller gets a usable
    # (detached) instance of the type it asked for, not a Counter that
    # would crash on update()
    assert isinstance(h, Histogram)
    h.update(1.5)              # safe no-op on the registry's view
    assert reg.all_metrics()["job.op.m"] is c
    assert any("already registered" in r.message for r in caplog.records)
    # same-type re-registration stays idempotent and silent
    assert g.counter("m") is c


def test_prometheus_text_type_lines_and_escaping():
    reg = MetricRegistry()
    g = reg.group("job")
    g.counter("numRecordsIn").inc(5)
    g.gauge("ratio", lambda: 0.25)
    h = g.histogram("latencyMs")
    for i in range(100):
        h.update(i)
    # metric-name edge case: leading digit + exotic characters
    reg.group("0weird", "a-b").counter("x:y").inc(1)
    text = prometheus_text(reg.all_metrics())
    assert "# TYPE job_numRecordsIn counter" in text
    assert "job_numRecordsIn 5" in text
    assert "# TYPE job_ratio gauge" in text
    assert "# TYPE job_latencyMs summary" in text
    assert 'job_latencyMs{quantile="0.99"}' in text
    assert "job_latencyMs_count 100" in text
    # leading digit sanitized to a valid prometheus name
    assert "\n_0weird_a_b_x_y 1" in text
    for line in text.splitlines():
        assert line.startswith("#") or line[0].isalpha() or line[0] == "_"


def test_prometheus_snapshot_exposition_with_labels():
    snap = {"job.numRecordsIn": 42,
            "job.latencyMs": {"count": 7, "p50": 1.5, "p99": 9.0}}
    text = prometheus_text_from_snapshot(snap, labels={"job": 'a"b\\c', "shard": 1})
    assert '# TYPE job_numRecordsIn gauge' in text
    assert 'job="a\\"b\\\\c"' in text        # label value escaping
    assert 'shard="1"' in text
    assert 'job_latencyMs_count' in text and 'quantile="0.99"' in text


def test_merge_prometheus_text_one_type_line_per_family():
    from flink_tpu.metrics.registry import merge_prometheus_text

    a = prometheus_text_from_snapshot(
        {"job.n": 1, "job.h": {"count": 1, "p50": 2.0}}, labels={"shard": 0})
    b = prometheus_text_from_snapshot(
        {"job.n": 2, "job.h": {"count": 3, "p50": 4.0}}, labels={"shard": 1})
    text = merge_prometheus_text([a, b])
    # exactly one TYPE declaration per family, all samples retained
    assert text.count("# TYPE job_n gauge") == 1
    assert text.count("# TYPE job_h summary") == 1
    assert 'job_n{shard="0"} 1' in text and 'job_n{shard="1"} 2' in text
    assert text.count("job_h_count") == 2
    # samples grouped contiguously under their family's TYPE line
    lines = [l for l in text.splitlines() if l]
    fam_of = []
    for l in lines:
        if l.startswith("# TYPE "):
            fam_of.append(l.split(" ")[2])
        else:
            fam_of.append("job_h" if l.startswith("job_h") else "job_n")
    assert fam_of == sorted(fam_of, key=fam_of.index)   # no interleaving


def test_aggregate_shard_metrics_sums_throughput_averages_ratios():
    from flink_tpu.runtime.cluster import aggregate_shard_metrics

    agg = aggregate_shard_metrics({
        0: {"job.numRecordsIn": 100, "job.numRecordsInPerSecond": 1000.0,
            "job.busyTimeRatio": 0.5, "job.busyTimeMsPerSecond": 400.0,
            "job.operator.w.currentWatermark": 1000},
        1: {"job.numRecordsIn": 50, "job.numRecordsInPerSecond": 500.0,
            "job.busyTimeRatio": 0.7, "job.busyTimeMsPerSecond": 600.0,
            "job.operator.w.currentWatermark": 5000},
    })
    assert agg["job.numRecordsIn"] == 150
    # throughput is work done: sums across subtasks
    assert agg["job.numRecordsInPerSecond"] == 1500.0
    # per-task fractions average
    assert abs(agg["job.busyTimeRatio"] - 0.6) < 1e-9
    assert abs(agg["job.busyTimeMsPerSecond"] - 500.0) < 1e-9
    # the job-level watermark is what EVERY shard has reached
    assert agg["job.operator.w.currentWatermark"] == 1000
    # per-channel pool occupancy is a fraction (numeric leaf): averages,
    # never sums past 1.0
    agg2 = aggregate_shard_metrics({
        0: {"job.exchange.inPoolUsage.0": 0.75},
        1: {"job.exchange.inPoolUsage.0": 0.25},
    })
    assert abs(agg2["job.exchange.inPoolUsage.0"] - 0.5) < 1e-9


def test_metrics_snapshot_plain_data():
    reg = MetricRegistry()
    g = reg.group("job")
    g.counter("c").inc(2)
    g.gauge("g", lambda: np.float32(1.5))
    g.gauge("broken", lambda: 1 / 0)     # must not poison the snapshot
    h = g.histogram("h")
    h.update(3.0)
    snap = metrics_snapshot(reg.all_metrics())
    assert snap["job.c"] == 2
    assert snap["job.g"] == 1.5 and isinstance(snap["job.g"], float)
    assert snap["job.h"]["count"] == 1
    assert "job.broken" not in snap
    json.dumps(snap)   # fully JSON-serializable


# ---------------------------------------------------------------------------
# TaskIOMetrics + the stage clock's outer sections (DeviceTimer's case, ported)
# ---------------------------------------------------------------------------

def test_task_io_ratios_and_windowed_sampling():
    io = TaskIOMetrics()
    bp = [0.0]
    io.add_backpressure_source(lambda: bp[0])
    io.record_step(busy_dt=0.6, loop_dt=1.0)
    bp[0] = 0.2     # 0.2s of that busy time was really blocked on credits
    r = io.ratios()
    assert abs(r["busyRatio"] - 0.4) < 1e-6
    assert abs(r["backPressuredRatio"] - 0.2) < 1e-6
    assert abs(r["idleRatio"] - 0.4) < 1e-6
    assert abs(sum(r.values()) - 1.0) < 1e-6
    # windowed sample: rates are per wall-second, clamped to 1000ms/s
    io.maybe_sample(interval_ms=0, now=io._last_sample_t + 1.0)
    assert 0.0 <= io.ms_per_second("busy") <= 1000.0
    assert 0.0 <= io.ms_per_second("backPressured") <= 1000.0

    reg = MetricRegistry()
    io.register(reg.group("job"))
    keys = set(reg.all_metrics())
    assert {"job.busyTimeRatio", "job.idleTimeRatio",
            "job.backPressuredTimeRatio", "job.busyTimeMsPerSecond",
            "job.idleTimeMsPerSecond",
            "job.backPressuredTimeMsPerSecond"} <= keys


def test_stage_clock_outer_sections_accumulate():
    h = Histogram()
    t = StageClock(histogram=h)
    for _ in range(3):
        with t.section():
            time.sleep(0.002)
    assert t.dispatches == 3
    assert t.total_s >= 0.006
    assert h.stats()["count"] == 3


# ---------------------------------------------------------------------------
# markers across stage boundaries (dataplane "m" frames)
# ---------------------------------------------------------------------------

def test_marker_crosses_stage_boundary_via_exchange_protocol():
    import threading

    from flink_tpu.graph.transformation import Transformation, Step
    from flink_tpu.runtime.stages import StageOutputRunner, _StageReader, _WmBox

    sent = []

    class _FakeSender:
        backpressured_s = 0.0

        def send(self, msg, timeout=None):
            sent.append(msg)

        def end(self):
            sent.append(("eos",))

        def available_credits(self):
            return 8

    t = Transformation("stage_output", "out", [],
                       {"sender": _FakeSender(),
                        "cancelled": threading.Event()})
    t.uid = "stage-out-x0"
    runner = StageOutputRunner(Step(chain=[], terminal=t, partitioning="forward",
                                    inputs=[]))
    runner.on_batch(np.asarray([1, 2], dtype=object),
                    np.asarray([10, 20], dtype=np.int64))
    runner.on_marker(1234.5)
    assert ("m", 1234.5) in sent

    class _FakeChannel:
        def __init__(self, msgs):
            self.msgs = list(msgs)

        def poll(self, timeout=None):
            if not self.msgs:
                raise TimeoutError()
            return self.msgs.pop(0)

    reader = _StageReader(_FakeChannel([("m", 1234.5), ("b", sent[0][1], [10, 20])]),
                          threading.Event(), _WmBox())
    batch = reader.poll_batch(16)       # consumes the marker frame
    assert len(batch.timestamps) == 0
    assert reader.take_marker() == 1234.5
    assert reader.take_marker() is None     # cleared on read
    batch = reader.poll_batch(16)
    assert len(batch.timestamps) == 2


# ---------------------------------------------------------------------------
# MiniCluster job: per-operator latency histograms + ratios via REST +
# Prometheus (acceptance criterion)
# ---------------------------------------------------------------------------

def _window_job(cluster, records=256):
    conf = Configuration()
    conf.set(ExecutionOptions.BATCH_SIZE, 32)
    env = StreamExecutionEnvironment(conf)
    (
        env.from_collection(
            [(f"k{i % 4}", i * 100) for i in range(records)],
            timestamp_fn=lambda x: x[1],
            watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
        )
        .key_by(lambda x: x[0])
        .window(TumblingEventTimeWindows.of(1000))
        .count()
        .collect()
    )
    client = cluster.submit(plan(env._sinks), conf, "obs-job")
    assert client.wait(60) == JobStatus.FINISHED
    return client


def _get(url, token=None):
    req = urllib.request.Request(url)
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    with urllib.request.urlopen(req, timeout=10) as r:
        body = r.read()
    return body


def test_minicluster_observability_over_rest_and_prometheus():
    cluster = MiniCluster()
    client = _window_job(cluster)
    server = RestServer(cluster).start()
    try:
        jid = client.job_id
        detail = json.loads(_get(f"{server.url}/jobs/{jid}"))
        assert detail["trace_id"] == job_trace_id(jid)

        metrics = json.loads(_get(f"{server.url}/jobs/{jid}/metrics"))
        # busy/idle/backpressure ratios
        assert 0 < metrics["job.busyTimeRatio"] <= 1.0
        assert 0 <= metrics["job.idleTimeRatio"] <= 1.0
        assert 0 <= metrics["job.backPressuredTimeRatio"] <= 1.0
        # non-empty per-operator latency histograms from the markers
        op_latency = {k: v for k, v in metrics.items()
                      if k.startswith("job.operator.") and k.endswith(".latencyMs")}
        assert op_latency and any(v.get("count", 0) > 0
                                  for v in op_latency.values())
        # device-time + state gauges on the window operator
        assert any(k.endswith("deviceTimeMsTotal") for k in metrics)
        sb = [v for k, v in metrics.items() if k.endswith(".stateBytes")]
        assert sb and sb[0] > 0

        # vertex backpressure endpoint
        uid = next(k for k in metrics if k.endswith(".stateBytes")).split(".")[2]
        bp = json.loads(_get(f"{server.url}/jobs/{jid}/vertices/{uid}/backpressure"))
        assert bp["status"] == "ok"
        assert bp["backpressureLevel"] in ("ok", "low", "high")
        assert bp["subtasks"][0]["busyRatio"] > 0

        # prometheus text carries the same plane with # TYPE metadata
        text = _get(f"{server.url}/metrics").decode()
        assert "# TYPE job_busyTimeRatio gauge" in text
        assert "job_backPressuredTimeRatio" in text
        assert "latencyMs_count" in text
    finally:
        server.stop()


def test_rest_observability_routes_require_bearer_when_auth_enabled():
    """Satellite: /metrics and /jobs/:id/metrics under
    security.rest.auth.enabled — 401 without the bearer, 200 with the
    token derived from the cluster secret."""
    from flink_tpu.security import SecurityConfig, rest_bearer_token

    cfg = Configuration()
    cfg.set(SecurityOptions.TRANSPORT_SECRET, "obs-secret")
    cfg.set(SecurityOptions.REST_AUTH_ENABLED, True)
    cluster = MiniCluster()
    client = _window_job(cluster)
    server = RestServer(cluster, config=cfg).start()
    token = rest_bearer_token(SecurityConfig.with_secret("obs-secret"))
    try:
        for path in ("/metrics", f"/jobs/{client.job_id}/metrics",
                     f"/jobs/{client.job_id}/vertices/x/backpressure"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(f"{server.url}{path}")
            assert exc.value.code == 401
        metrics = json.loads(_get(f"{server.url}/jobs/{client.job_id}/metrics",
                                  token=token))
        assert metrics["job.numRecordsIn"] == 256
        text = _get(f"{server.url}/metrics", token=token).decode()
        assert "# TYPE" in text and "job_numRecordsIn" in text
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# trace-id propagation + spans
# ---------------------------------------------------------------------------

def test_trace_registry_stamps_default_trace_id_and_otlp_uses_it():
    from flink_tpu.metrics.otel import span_to_otlp
    from flink_tpu.metrics.traces import InMemoryTraceReporter

    tid = job_trace_id("abc123")
    assert len(tid) == 32 and tid == job_trace_id("abc123")
    assert tid != job_trace_id("abc124")
    reg = TraceRegistry(trace_id=tid)
    rep = InMemoryTraceReporter()
    reg.add_reporter(rep)
    reg.report(reg.span("checkpointing", "Checkpoint").end())
    assert rep.spans[0].trace_id == tid
    assert span_to_otlp(rep.spans[0])["traceId"] == tid
    # round trip through the RPC shipping form
    d = rep.spans[0].to_dict()
    assert Span.from_dict(d).trace_id == tid


def test_minicluster_job_spans_carry_job_trace_id():
    from flink_tpu.metrics.traces import InMemoryTraceReporter
    from flink_tpu.config import CheckpointingOptions

    conf = Configuration()
    conf.set(ExecutionOptions.BATCH_SIZE, 16)
    conf.set(CheckpointingOptions.INTERVAL_MS, 1)
    env = StreamExecutionEnvironment(conf)
    (
        env.from_collection(
            [(i % 3, i * 50) for i in range(400)],
            timestamp_fn=lambda x: x[1],
            watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
        )
        .key_by(lambda x: x[0])
        .window(TumblingEventTimeWindows.of(500))
        .count()
        .collect()
    )
    cluster = MiniCluster()
    client = cluster.submit(plan(env._sinks), conf, "span-job")
    rep = InMemoryTraceReporter()
    deadline = time.time() + 10
    while not hasattr(client, "traces") and time.time() < deadline:
        time.sleep(0.005)
    client.traces.add_reporter(rep)
    assert client.wait(60) == JobStatus.FINISHED
    cp = [s for s in rep.spans if s.name == "Checkpoint"]
    assert cp and all(s.trace_id == client.trace_id for s in cp)


def test_rpc_trace_context_propagates_in_frame():
    """The traceparent-lite header: a trace id attached on the caller's
    thread rides the invocation frame and is visible via current_trace_id()
    inside the remote handler — and ONLY there."""
    from flink_tpu.runtime.rpc import (
        RpcEndpoint,
        RpcService,
        current_trace_id,
        trace_context,
    )

    class _Probe(RpcEndpoint):
        def __init__(self):
            super().__init__(name="probe")

        def observed_trace(self):
            return current_trace_id()

    svc = RpcService()
    svc.register(_Probe())
    gw = svc.gateway(svc.address, "probe")
    try:
        assert gw.observed_trace() is None          # no context: legacy frame
        with trace_context("feedfacefeedfacefeedfacefeedface"):
            assert gw.observed_trace() == "feedfacefeedfacefeedfacefeedface"
        assert gw.observed_trace() is None          # context scoped to block
    finally:
        gw.close()
        svc.stop()


# ---------------------------------------------------------------------------
# distributed: TM -> JM metric/span shipping over the RPC plane
# (acceptance criterion: trace ids match across JM and TM span reports)
# ---------------------------------------------------------------------------

def test_tm_ships_metrics_and_spans_to_jm_with_matching_trace_ids(tmp_path):
    from flink_tpu.runtime.cluster import (
        DistributedJobSpec,
        JobManagerEndpoint,
        TaskExecutorEndpoint,
    )
    from flink_tpu.runtime.rpc import RpcService

    def source_factory(shard, num_shards):
        rng = np.random.default_rng(7 + shard)
        batches = []
        for s in range(2500):
            keys = rng.integers(0, 8, 16).astype(np.int64)
            vals = np.ones(16, dtype=np.float64)
            ts = (s * 100 + rng.integers(0, 100, 16)).astype(np.int64)
            batches.append((keys, vals, ts, s * 100))
        return batches

    spec = DistributedJobSpec(
        name="obs-dist", source_factory=source_factory,
        assigner=TumblingEventTimeWindows.of(1000), aggregate="sum",
        max_parallelism=16, operator="device",
    )
    svc_jm, svc_tm = RpcService(), RpcService()
    jm = JobManagerEndpoint(
        svc_jm, checkpoint_dir=str(tmp_path / "chk"),
        checkpoint_interval=0.0, heartbeat_interval=0.2,
        heartbeat_timeout=15.0,
    )
    te = TaskExecutorEndpoint(svc_tm, slots=1, shipping_interval_ms=100)
    te.connect(svc_jm.address)
    client = svc_jm.gateway(svc_jm.address, "jobmanager")
    job_id = client.submit_job(spec.to_bytes(), 1)
    expected_tid = job_trace_id(job_id)
    try:
        # drive one cut through the savepoint machinery: its decline path
        # re-triggers with a doubled margin until the common step lands, so
        # a fast job under suite load cannot outrun it the way a one-shot
        # trigger_checkpoint can
        sp_requested = False
        deadline = time.time() + 90
        status = None
        while time.time() < deadline:
            status = client.job_status(job_id)
            if not sp_requested and status["status"] == "RUNNING":
                sp_requested = client.trigger_savepoint(
                    job_id, str(tmp_path / "sp")) is not None
            if status["status"] in ("FINISHED", "FAILED"):
                break
            time.sleep(0.05)
        assert status["status"] == "FINISHED", status
        assert status["trace_id"] == expected_tid
        assert status["checkpoints"], (
            f"no checkpoint completed mid-run (savepoint requested: "
            f"{sp_requested}, failed: {status['savepoints_failed']})")

        # TM-shipped metric snapshots reach the JM (last heartbeat may lag).
        # snap and agg must come from ONE job_metrics response: the JM folds
        # the aggregate from the same snapshot store at serve time, but a
        # final post-FINISH ship landing between two separate calls makes
        # them disagree.
        deadline = time.time() + 10
        metrics = {"per_shard": {}}
        spans = []
        while time.time() < deadline:
            metrics = client.job_metrics(job_id)
            spans = client.job_spans(job_id)
            if metrics["per_shard"] and any(
                    s["name"] == "CheckpointAck" for s in spans):
                break
            time.sleep(0.2)
        per_shard = metrics["per_shard"]
        assert per_shard, "TM never shipped a metric snapshot"
        snap = per_shard[0]
        assert snap["job.numRecordsIn"] > 0
        assert any(k.endswith("stateKeyCount") for k in snap)
        # the keyed hot path carries real task IO ratios, so the
        # backpressure view below isn't trivially zero
        assert 0 < snap["job.busyTimeRatio"] <= 1.0
        agg = metrics["job"]
        assert agg["job.numRecordsIn"] == snap["job.numRecordsIn"]

        # spans from BOTH processes, all on the derived trace id
        names = {s["name"] for s in spans}
        assert "CheckpointTrigger" in names          # JM-side
        assert "CheckpointAck" in names              # TM-side, shipped on RPC
        assert all(s["trace_id"] == expected_tid for s in spans)

        # backpressure view classifies from the shipped ratios
        bp = client.job_backpressure(job_id)
        assert bp["subtasks"] and bp["backpressureLevel"] in ("ok", "low", "high")
        assert bp["subtasks"][0]["busyRatio"] > 0
    finally:
        te.stop()
        jm.heartbeats.stop()
        svc_jm.stop()
        svc_tm.stop()
