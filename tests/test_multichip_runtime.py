"""Multichip SPMD keyed execution through the USER-FACING runtime (ISSUE-11).

The sharded superscan has kernel-level parity coverage in
tests/test_sharded_superscan.py; this file gates the PROMOTION — fused
DataStream jobs (graph/fusion.py -> DeviceChainRunner -> FusedWindowOperator
-> ShardedFusedPipeline) running SPMD over the virtual 8-device CPU mesh
with the keyBy shuffle as an in-scan all-to-all:

- byte-identical results vs the single-chip fused path AND a numpy host
  oracle, across tumbling + sliding windows and ragged batches;
- the classic (host key dictionary) fused window path on the mesh,
  including mid-stream key-capacity growth re-sharding;
- a live mesh-size rescale mid-stream (checkpoint rewind + key-group
  re-shard across device counts) at exactly-once parity, down AND up;
- per-device key telemetry (KeyStatsCollector mesh fold) and the
  aggregate_shard_metrics per-device MAX rule (the device-0-view bugfix).
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.config import (
    Configuration,
    ExecutionOptions,
    ParallelOptions,
    RestartOptions,
)
from flink_tpu.connectors.sink import CollectSink
from flink_tpu.connectors.source import Batch, DataGeneratorSource
from flink_tpu.core.watermarks import WatermarkStrategy

N_KEYS = 192          # divides the 8-device mesh; distinctive geometry
SPAN_MS = 40_000


def _columns(idx: np.ndarray, n: int):
    camp = (idx * 2654435761) % N_KEYS
    etype = idx % 3
    col = np.stack([camp, etype], axis=1).astype(np.float32)
    ts = 10_000 + idx * SPAN_MS // n
    return col, ts.astype(np.int64)


def _make_env(assigner, *, mesh_on, n=40_000, batch=1536, devices=0,
              extra=None, sink=None):
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, batch)
    cfg.set(ExecutionOptions.KEY_CAPACITY, N_KEYS)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 8)
    cfg.set(ParallelOptions.MESH_ENABLED, mesh_on)
    if devices:
        cfg.set(ParallelOptions.MESH_DEVICES, devices)
    for opt, val in (extra or {}).items():
        cfg.set(opt, val)

    def gen(idx):
        col, ts = _columns(idx, n)
        return Batch(col, ts)

    env = StreamExecutionEnvironment(cfg)
    # num_splits=7 with a non-multiple count: ragged partial batches on
    # every split tail, exercising the power-of-two staging widths
    ds = env.from_source(
        DataGeneratorSource(gen, n, num_splits=7),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
    )
    out = sink if sink is not None else CollectSink()
    (ds.filter(lambda col: col[:, 1] < 0.5, traceable=True)
       .key_by(lambda col: col[:, 0].astype(jnp.int32), traceable=True)
       .window(assigner).count().sink_to(out))
    return env, out


def _rows(sink):
    return sorted((int(k), int(v)) for k, v in sink.results)


def _numpy_oracle(assigner, n):
    """Host oracle: per-(key, window) counts of the filtered stream as the
    same sorted (key, count) multiset the sink collects."""
    idx = np.arange(n)
    col, ts = _columns(idx, n)
    keep = col[:, 1] < 0.5
    keys = col[keep, 0].astype(np.int64)
    tss = ts[keep]
    # derive (size, slide) from the assigner's slice geometry
    size = assigner.slices_per_window * assigner.slice_ms
    slide = assigner.slide_slices * assigner.slice_ms
    counts = {}
    for k, t in zip(keys, tss):
        last_start = t - (t % slide)
        start = last_start
        while start > t - size:
            counts[(int(k), int(start))] = counts.get(
                (int(k), int(start)), 0) + 1
            start -= slide
    return sorted((k, v) for (k, _s), v in counts.items())


@pytest.mark.parametrize("assigner_fn", [
    lambda: TumblingEventTimeWindows.of(5000),
    lambda: SlidingEventTimeWindows.of(8000, 2000),
], ids=["tumbling", "sliding"])
def test_fused_mesh_job_matches_single_chip_and_host_oracle(assigner_fn):
    n = 40_000
    env_m, sink_m = _make_env(assigner_fn(), mesh_on=True, n=n)

    # the reroute gate: translation chose the fused runner AND it targets
    # the sharded pipeline (a silent single-chip fallback would still show
    # perfect parity below)
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import build_runners

    runners, _ = build_runners(plan(env_m._sinks), env_m.config)
    fused = [r for r in runners if type(r).__name__ == "DeviceChainRunner"]
    assert fused, "fusion planner no longer selects the device chain"
    assert fused[0].op.mesh_devices() == 8

    env_m.execute()
    env_s, sink_s = _make_env(assigner_fn(), mesh_on=False, n=n)
    env_s.execute()

    rows_m, rows_s = _rows(sink_m), _rows(sink_s)
    assert len(rows_m) > 0
    assert rows_m == rows_s, "mesh vs single-chip fused parity broken"
    assert rows_m == _numpy_oracle(assigner_fn(), n), \
        "mesh path diverged from the host oracle"


def test_classic_keydict_fused_path_on_mesh_with_capacity_growth():
    """The non-traceable (host key dictionary) fused window path also goes
    multi-chip, and mid-stream dictionary growth re-shards the global
    [K, S] state without losing a row. >1024 distinct keys forces
    ensure_key_capacity past the fused operator's 1024-row starting
    capacity while sharded."""
    n, n_keys = 30_000, 1600

    def build(mesh_on):
        cfg = Configuration()
        cfg.set(ExecutionOptions.BATCH_SIZE, 1024)
        cfg.set(ExecutionOptions.KEY_CAPACITY, 4096)
        cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 8)
        cfg.set(ParallelOptions.MESH_ENABLED, mesh_on)

        def gen(idx):
            # narrow key range first, then the full vocabulary: growth
            # happens mid-stream, not at first dispatch
            hi = np.where(idx < n // 2, 512, n_keys)
            keys = (idx * 48271) % hi
            vals = [(int(k), 1.0, int(t)) for k, t in
                    zip(keys, 10_000 + idx * 3)]
            from flink_tpu.utils.arrays import obj_array

            return Batch(obj_array(vals), (10_000 + idx * 3).astype(np.int64))

        env = StreamExecutionEnvironment(cfg)
        ds = env.from_source(
            DataGeneratorSource(gen, n, num_splits=5),
            watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
        )
        sink = CollectSink()
        (ds.key_by(lambda x: x[0])
           .window(TumblingEventTimeWindows.of(4000)).count().sink_to(sink))
        return env, sink

    env_m, sink_m = build(True)
    env_m.execute()
    env_s, sink_s = build(False)
    env_s.execute()
    rows_m, rows_s = _rows(sink_m), _rows(sink_s)
    assert len(rows_m) > 0
    assert rows_m == rows_s


def _run_async(assigner, *, n, rescale_to=None, rescale_after=None,
               batch=1024):
    extra = {RestartOptions.INITIAL_BACKOFF_MS: 1}
    env, sink = _make_env(assigner, mesh_on=True, n=n, batch=batch,
                          extra=extra)
    client = env.execute_async("multichip-e2e")
    if rescale_to is not None:
        deadline = time.monotonic() + 60
        while (client.records_in < rescale_after
               and time.monotonic() < deadline):
            time.sleep(0.01)
        client.rescale_mesh(rescale_to)
    client.wait(180)
    return client, sink


def test_live_mesh_rescale_mid_stream_is_exactly_once():
    """A running fused mesh job rescales its device count (8 -> 4) at a
    step boundary (checkpoint rewind + key-group re-shard) and finishes
    with results byte-identical to an undisturbed single-chip run — the
    'rescale across device counts' acceptance of ISSUE-11."""
    assigner = SlidingEventTimeWindows.of(8000, 2000)
    n = 60_000
    env_ref, sink_ref = _make_env(assigner, mesh_on=False, n=n)
    env_ref.execute()

    client, sink = _run_async(assigner, n=n, rescale_to=4,
                              rescale_after=n // 4)
    assert client.status().value == "FINISHED"
    assert client.mesh_rescales >= 1
    assert client._runtime.mesh_devices() == 4
    assert client.num_restarts == 0
    kinds = [r["kind"] for r in client.exceptions.payload()["recoveries"]]
    assert kinds == ["rescale"] * len(kinds) and kinds
    assert _rows(sink) == _rows(sink_ref)
    assert client.last_mesh_rescale_duration_ms > 0


def test_manual_rescale_to_same_effective_size_is_a_no_op():
    """rescale_mesh with a target that clamps back to the current size
    (here: 9 on an 8-device mesh with 8 visible devices) must not cost a
    stop-the-world rebuild — no rescale counted, no recovery record."""
    assigner = TumblingEventTimeWindows.of(5000)
    n = 30_000
    client, sink = _run_async(assigner, n=n, rescale_to=9,
                              rescale_after=n // 4)
    assert client.status().value == "FINISHED"
    assert client.mesh_rescales == 0
    assert client._runtime.mesh_devices() == 8
    assert client.exceptions.payload()["recoveries"] == []


def test_mesh_rescale_up_mid_stream():
    """Scale UP across device counts too: 2 -> 8 mid-stream, exact."""
    assigner = TumblingEventTimeWindows.of(5000)
    n = 60_000
    env_ref, sink_ref = _make_env(assigner, mesh_on=False, n=n)
    env_ref.execute()

    extra = {RestartOptions.INITIAL_BACKOFF_MS: 1}
    env, sink = _make_env(assigner, mesh_on=True, n=n, batch=1024,
                          devices=2, extra=extra)
    client = env.execute_async("multichip-upscale")
    deadline = time.monotonic() + 60
    while client.records_in < n // 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    client.rescale_mesh(8)
    client.wait(180)
    assert client.status().value == "FINISHED"
    assert client.mesh_rescales == 1
    assert client._runtime.mesh_devices() == 8
    assert _rows(sink) == _rows(sink_ref)


def test_autoscaler_executes_mesh_rescales_as_the_parallelism_axis():
    """With autoscaler.enabled on a mesh job, the coordinator holds a REAL
    rescale executor (not observe-only): a decision for a new device count
    parks a live-rescale request the run loop executes, a same-size or
    unreachable target is rejected (no no-op churn), and the completed
    rescale stamps the job's rescale gauges."""
    from flink_tpu.config import AutoscalerOptions

    assigner = TumblingEventTimeWindows.of(5000)
    n = 60_000
    env_ref, sink_ref = _make_env(assigner, mesh_on=False, n=n)
    env_ref.execute()

    extra = {
        AutoscalerOptions.ENABLED: True,
        RestartOptions.INITIAL_BACKOFF_MS: 1,
    }
    env, sink = _make_env(assigner, mesh_on=True, n=n, batch=1024,
                          extra=extra)
    client = env.execute_async("multichip-autoscale")
    deadline = time.monotonic() + 60
    while client.records_in < n // 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    auto = client.autoscaler
    assert auto.rescale_executor is not None, \
        "mesh job's autoscaler is still observe-only"
    # same-size target: rejected, never parked (no no-op rescale churn)
    accepted, detail = auto.rescale_executor(client.job_id, 8, "drill")
    assert not accepted and "already at 8" in detail
    # real decision: executes as a live rescale at the next step boundary
    accepted, _detail = auto.rescale_executor(client.job_id, 4, "drill")
    assert accepted
    client.wait(180)
    assert client.status().value == "FINISHED"
    assert client.mesh_rescales == 1
    assert client._runtime.mesh_devices() == 4
    assert client.last_mesh_rescale_duration_ms > 0
    assert _rows(sink) == _rows(sink_ref)


def test_grown_snapshot_restores_onto_a_mesh_its_k_does_not_divide():
    """A classic keyed job grows K past construction capacity (pow2 rounded
    to the OLD mesh's multiple); restoring that checkpoint onto a mesh size
    the grown K does not divide must identity-pad and proceed — failing
    would wedge the job in a restart loop against the same checkpoint."""
    from flink_tpu.parallel.mesh import build_mesh
    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
    from flink_tpu.runtime.fused_window_pipeline import FusedWindowPipeline

    kw = dict(num_slices=16, nsb=4, fires_per_step=4, out_rows=16, chunk=256)
    a = ShardedFusedPipeline(
        build_mesh(8), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=768, **kw)
    a.ensure_key_capacity(1000)          # -> K=1024 (pow2, multiple of 8)
    assert a.K == 1024
    from flink_tpu.testing.harness import keyed_window_stream

    batches, wms = keyed_window_stream(5, 8, 400, 768)
    half = 4
    a.process_superbatch(batches[:half], wms[:half])
    snap = a.snapshot()
    assert snap["count"].shape[0] == 1024

    # 1024 % 6 != 0: restore must pad to 1026, not raise
    b = ShardedFusedPipeline(
        build_mesh(6), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=768, **kw)
    b.restore(snap)
    assert b.K % 6 == 0 and b.K >= 1024
    out_b = b.process_superbatch(batches[half:], wms[half:])

    single = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=768, backend="xla", **kw)
    single.restore(snap)
    out_s = single.process_superbatch(batches[half:], wms[half:])
    assert len(out_b) == len(out_s) > 0
    for (rw, rc, _), (gw, gc, _) in zip(out_s, out_b):
        assert rw == gw
        assert np.array_equal(np.asarray(rc),
                              np.asarray(gc)[: np.asarray(rc).shape[0]])


def test_snapshot_interchange_single_chip_to_mesh_operator():
    """A FusedWindowOperator snapshot taken single-chip restores into a
    mesh operator (and back): the canonical [K, S] layout is the rescale
    contract the runtime path relies on."""
    from flink_tpu.parallel.mesh import build_mesh
    from flink_tpu.runtime.fused_window_operator import FusedWindowOperator

    def mk(mesh):
        return FusedWindowOperator(
            TumblingEventTimeWindows.of(2000), "count",
            key_capacity=128, superbatch_steps=4, chunk=256, mesh=mesh)

    rng = np.random.default_rng(5)
    a = mk(None)
    for s in range(6):
        keys = rng.integers(0, 96, 300)
        a.process_batch(keys, np.ones(300, np.float32),
                        np.full(300, s * 400, np.int64))
        a.process_watermark(s * 400)
    snap = a.snapshot()

    b = mk(build_mesh(8))
    b.restore(snap)
    a2 = mk(None)
    a2.restore(snap)
    for s in range(6, 12):
        keys = rng.integers(0, 96, 300)
        for op in (b, a2):
            op.process_batch(keys.copy(), np.ones(300, np.float32),
                             np.full(300, s * 400, np.int64))
            op.process_watermark(s * 400)
    from flink_tpu.core.time import MAX_WATERMARK

    for op in (b, a2):
        op.process_watermark(MAX_WATERMARK)
    got = sorted((k, int(r)) for k, _w, r, _t in b.drain_output())
    ref = sorted((k, int(r)) for k, _w, r, _t in a2.drain_output())
    assert got == ref and len(got) > 0


# ---------------------------------------------------------------------------
# per-device telemetry + the aggregate fold bugfix
# ---------------------------------------------------------------------------

def test_key_stats_mesh_fold_sees_the_hot_device_not_device_zero():
    from flink_tpu.metrics.key_stats import KeyStatsCollector

    # device 0 perfectly even, device 3 owns a hot key — the per-device
    # fold must surface device 3's load, and the scalar mesh gauges must
    # be the MAX across devices
    loads = np.zeros((4, 32), np.int32)
    loads[0, :] = 10
    loads[1, :] = 10
    loads[2, :] = 10
    loads[3, 0] = 900
    flat = loads.reshape(-1)
    ks = KeyStatsCollector(lambda: flat, num_key_groups=16, interval_ms=0,
                           mesh_loads_fn=lambda: loads)
    assert ks.collect()
    p = ks.payload()
    per = {e["device"]: e for e in p["perDevice"]}
    assert per[3]["records"] == 900
    assert p["meshLoadSkew"] == pytest.approx(
        900 / (flat.sum() / 4), rel=1e-3)
    assert ks.mesh_load_skew() > 1.0
    # the hot key-group sits on device 3; its per-device skew dominates
    assert per[3]["keySkew"] == max(
        e["keySkew"] for e in p["perDevice"] if e["keySkew"] is not None)


def test_key_stats_per_device_skew_matches_global_when_groups_straddle():
    """A key group straddling a device boundary (non-pow2 K_local) must
    attribute its FULL global load to every device it touches — otherwise
    max-over-devices understates the global skew and the per-device gauges
    hide the hot device they exist to expose."""
    from flink_tpu.metrics.key_stats import KeyStatsCollector

    n_dev, kl, g = 4, 33, 16          # k_total=132: groups straddle devices
    loads = np.zeros((n_dev, kl), np.int32)
    # key 32 and 33 share a group but live on devices 0 and 1
    loads[0, 32] = 400
    loads[1, 0] = 400
    loads[2, :] = 3
    flat = loads.reshape(-1)
    ks = KeyStatsCollector(lambda: flat, num_key_groups=g, interval_ms=0,
                           mesh_loads_fn=lambda: loads)
    assert ks.collect()
    p = ks.payload()
    global_skew = ks.skew()
    per_dev_max = max(e["keySkew"] for e in p["perDevice"]
                      if e["keySkew"] is not None)
    assert per_dev_max == pytest.approx(global_skew, rel=1e-3)


def test_key_stats_without_mesh_reports_no_per_device_block():
    from flink_tpu.metrics.key_stats import KeyStatsCollector

    ks = KeyStatsCollector(lambda: np.ones(64, np.int32), interval_ms=0)
    assert ks.collect()
    p = ks.payload()
    assert p["perDevice"] == []
    assert p["meshLoadSkew"] is None


def test_aggregate_shard_metrics_folds_per_device_maps_with_max():
    """The ISSUE-11 bugfix: a {device: value} map under a MAX-rule gauge
    family must fold max ACROSS THE SHARD'S DEVICES first — the generic
    dict merge keyed on device indexes collides across shards and the
    job-level scalar silently became device 0's view."""
    from flink_tpu.runtime.cluster import aggregate_shard_metrics

    agg = aggregate_shard_metrics({
        0: {"job.operator.w.keySkewPerDevice": {"0": 1.0, "3": 7.5},
            "job.operator.w.meshDeviceLoad": {"0": 10, "3": 900},
            "job.operator.w.meshLoadSkew": 3.2,
            "job.meshDevices": 4},
        1: {"job.operator.w.keySkewPerDevice": {"0": 2.0},
            "job.operator.w.meshDeviceLoad": {"0": 40},
            "job.operator.w.meshLoadSkew": 1.0,
            "job.meshDevices": 1},
    })
    # worst device anywhere, not device 0's view and not a sum
    assert agg["job.operator.w.keySkewPerDevice"] == 7.5
    assert agg["job.operator.w.meshDeviceLoad"] == 900
    assert agg["job.operator.w.meshLoadSkew"] == 3.2
    # each shard reports ITS mesh size; summing would read a plain
    # 2-shard job as a 2-device mesh
    assert agg["job.meshDevices"] == 4


def test_sharded_job_exposes_per_device_telemetry_in_device_snapshot():
    from flink_tpu.config import ObservabilityOptions
    from flink_tpu.graph.transformation import plan
    from flink_tpu.runtime.executor import JobRuntime

    cfg_extra = {
        ObservabilityOptions.DEVICE_STATS_ENABLED: True,
        ObservabilityOptions.DEVICE_KEY_STATS_INTERVAL_MS: 0,
    }
    env, _sink = _make_env(SlidingEventTimeWindows.of(8000, 2000),
                           mesh_on=True, n=20_000, extra=cfg_extra)
    rt = JobRuntime(plan(env._sinks), env.config)
    rt.run()
    assert rt.mesh_devices() == 8
    snap = rt.device_snapshot()
    blocks = [e.get("keys") for e in snap["operators"].values()
              if e.get("keys")]
    assert blocks, "no key telemetry block on the sharded job"
    keys_blk = blocks[0]
    assert len(keys_blk["perDevice"]) == 8
    assert keys_blk["meshLoadSkew"] is not None
    assert sum(e["records"] for e in keys_blk["perDevice"]) > 0
