"""Skew-aware key-group routing (ISSUE-15): the routing table's layout
algebra (parallel/routing.py), the rebalancer policy
(scheduler/rebalancer.py), the sharded pipeline's table surface, and the
end-to-end MiniCluster rebalance — exactly-once, with checkpoints staying
canonical [K, S] across tables and mesh sizes."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.parallel.routing import (
    KeyGroupRouting,
    choose_key_groups,
    plan_balanced_assignment,
    predicted_skew,
)
from flink_tpu.scheduler.rebalancer import SkewRebalancer


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("shards",))


# ---------------------------------------------------------------------------
# routing table algebra
# ---------------------------------------------------------------------------

def test_choose_key_groups_divides_both_ways():
    for k, n in ((8192, 8), (768, 8), (640, 8), (512, 4), (384, 8),
                 (1 << 20, 8), (24, 8)):
        g = choose_key_groups(k, n)
        assert g % n == 0 and k % g == 0, (k, n, g)
        assert g <= max(128, n)
    # explicit request honored when well-formed, clamped otherwise
    assert choose_key_groups(8192, 8, 64) == 64
    assert choose_key_groups(8, 8, 128) == 8


def test_identity_routing_is_the_contiguous_layout():
    r = KeyGroupRouting(512, 8)
    assert r.is_identity
    np.testing.assert_array_equal(r.perm, np.arange(512))
    assert r.version == 0


def test_layout_round_trip_under_permuted_table():
    r = KeyGroupRouting(512, 8)
    assign = np.repeat(np.arange(8)[::-1], r.G // 8)
    r2 = r.with_assignment(assign)
    assert r2.version == 1 and not r2.is_identity
    canon = np.random.default_rng(0).integers(0, 99, (512, 16))
    np.testing.assert_array_equal(
        r2.to_canonical(r2.to_device_layout(canon)), canon)
    # device-major layout really places group g's rows on assign[g]
    flat = r2.to_device_layout(canon)
    kl = 512 // 8
    g0_dev = int(assign[0])
    np.testing.assert_array_equal(
        flat[g0_dev * kl: g0_dev * kl + r2.Kg], canon[:r2.Kg])


def test_unbalanced_assignment_rejected():
    r = KeyGroupRouting(512, 8)
    bad = np.zeros(r.G, np.int64)   # every group on device 0
    with pytest.raises(ValueError, match="exactly"):
        r.with_assignment(bad)


def test_balanced_lpt_spreads_hot_groups_and_keeps_ownership_counts():
    g, n = 128, 8
    loads = np.ones(g)
    hot = np.arange(16)             # device 0's groups under identity
    loads[hot] = 100.0
    assign = plan_balanced_assignment(loads, n)
    counts = np.bincount(assign, minlength=n)
    assert np.all(counts == g // n), "ownership must stay exactly G/n"
    # the 16 hot groups spread two per device
    assert np.all(np.bincount(assign[hot], minlength=n) == 2)
    assert predicted_skew(loads, assign, n) < 1.1
    ident = (np.arange(g, dtype=np.int64) * n) // g
    assert predicted_skew(loads, ident, n) > 4.0


def test_lpt_tie_prefers_current_owner():
    loads = np.ones(128)
    ident = (np.arange(128, dtype=np.int64) * 8) // 128
    assign = plan_balanced_assignment(loads, 8, ident)
    np.testing.assert_array_equal(assign, ident)


# ---------------------------------------------------------------------------
# rebalancer policy
# ---------------------------------------------------------------------------

def _fake_clock(start=0.0):
    state = {"t": start}

    def clock():
        return state["t"]

    return clock, state


def test_rebalancer_below_threshold_holds():
    clock, _ = _fake_clock()
    reb = SkewRebalancer(skew_threshold=1.5, interval_ms=0, min_samples=1,
                         clock=clock)
    loads = np.ones(128)
    ident = (np.arange(128, dtype=np.int64) * 8) // 128
    assert reb.maybe_decide(loads, ident, 8) is None
    assert reb.decisions[-1].action == "hold"


def test_rebalancer_fires_on_splittable_skew_then_settles():
    clock, _ = _fake_clock()
    reb = SkewRebalancer(skew_threshold=1.25, interval_ms=0, min_samples=1,
                         clock=clock)
    loads = np.ones(128)
    loads[:16] = 100.0
    ident = (np.arange(128, dtype=np.int64) * 8) // 128
    assign = reb.maybe_decide(loads, ident, 8)
    assert assign is not None
    reb.rebalance_completed()
    # same traffic under the NEW placement: balanced, policy holds
    assert reb.maybe_decide(loads, assign, 8) is None
    assert reb.num_rebalances == 1


def test_rebalancer_refuses_unsplittable_hot_group():
    """One group carrying everything: the replan cannot improve, so the
    policy holds forever instead of churning stop-the-world rebuilds."""
    clock, _ = _fake_clock()
    reb = SkewRebalancer(skew_threshold=1.25, interval_ms=0, min_samples=1,
                         clock=clock)
    loads = np.zeros(128)
    loads[0] = 1000.0
    ident = (np.arange(128, dtype=np.int64) * 8) // 128
    assert reb.maybe_decide(loads, ident, 8) is None
    assert "does not improve" in reb.decisions[-1].reason


def test_rebalancer_interval_throttles():
    clock, state = _fake_clock()
    reb = SkewRebalancer(skew_threshold=1.25, interval_ms=1000,
                         min_samples=1, clock=clock)
    loads = np.ones(128)
    loads[:16] = 100.0
    ident = (np.arange(128, dtype=np.int64) * 8) // 128
    assert reb.due()
    assert reb.maybe_decide(loads, ident, 8) is not None
    assert not reb.due()
    assert reb.maybe_decide(loads, ident, 8) is None   # throttled
    state["t"] += 1.5
    assert reb.due()
    assert reb.maybe_decide(loads, ident, 8) is not None


def test_rebalancer_windows_out_single_snapshot_spikes():
    """The decision runs on the windowed SUM of load snapshots: a
    one-snapshot spike in a different group each tick (the
    freshest-dense-id group right after a purge — a moving target no
    placement can balance) must NOT fire, while a PERSISTENT hot set
    accumulating across the window must."""
    clock, _ = _fake_clock()
    reb = SkewRebalancer(skew_threshold=1.25, interval_ms=0,
                         window=8, min_samples=4, clock=clock)
    ident = (np.arange(128, dtype=np.int64) * 8) // 128
    # warm-up: nothing decides before min_samples accumulate
    spike = np.ones(128)
    spike[60] = 60.0
    assert reb.maybe_decide(spike, ident, 8) is None
    assert not reb.decisions, "decided during warm-up"
    for g in (77, 90, 105):   # the spike marches; integrated view is flat
        loads = np.ones(128)
        loads[g] = 60.0
        decision = reb.maybe_decide(loads, ident, 8)
    assert decision is None, "moving one-snapshot spike caused a rebalance"
    # a persistent hot set dominates the same window: fires
    for _ in range(4):
        loads = np.ones(128)
        loads[:16] = 60.0
        decision = reb.maybe_decide(loads, ident, 8)
    assert decision is not None
    # a completed rebalance clears the evidence window
    reb.rebalance_completed()
    assert reb.maybe_decide(loads, decision, 8) is None
    assert len(reb._window) == 1


# ---------------------------------------------------------------------------
# pipeline surface
# ---------------------------------------------------------------------------

def test_pipeline_key_loads_stay_canonical_across_rebalance():
    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
    from flink_tpu.testing.harness import keyed_window_stream

    pipe = ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=256, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, skew_routing=True)
    batches, wms = keyed_window_stream(9, 4, 400, 256)
    pipe.process_superbatch(batches, wms)
    before = np.asarray(pipe.key_loads())
    groups_before = pipe.mesh_group_loads()
    assign = np.repeat(np.arange(8)[::-1], pipe.routing.G // 8)
    pipe.set_routing_assignment(assign)
    np.testing.assert_array_equal(np.asarray(pipe.key_loads()), before)
    np.testing.assert_array_equal(pipe.mesh_group_loads(), groups_before)


def test_capacity_growth_resets_routing_to_identity():
    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline

    pipe = ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=256, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, skew_routing=True)
    v0 = pipe.set_routing_assignment(
        np.repeat(np.arange(8)[::-1], pipe.routing.G // 8))
    pipe.ensure_key_capacity(300)
    assert pipe.K == 512
    assert pipe.routing.K == 512 and pipe.routing.is_identity
    assert pipe.routing.version > v0, "growth must bump the table version"


def test_snapshot_is_routing_independent():
    """A snapshot under a permuted table restores into any (mesh size,
    table) combination — checkpoints are canonical [K, S] throughout."""
    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
    from flink_tpu.testing.harness import keyed_window_stream

    batches, wms = keyed_window_stream(4, 4, 400, 256, True)
    src = ShardedFusedPipeline(
        _mesh(8), SlidingEventTimeWindows.of(2000, 500), "sum",
        key_capacity=256, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, skew_routing=True)
    src.process_superbatch(batches, wms)
    src.set_routing_assignment(
        np.repeat(np.arange(8)[::-1], src.routing.G // 8))
    snap = src.snapshot()

    dst = ShardedFusedPipeline(
        _mesh(4), SlidingEventTimeWindows.of(2000, 500), "sum",
        key_capacity=256, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, skew_routing=True)
    dst.set_routing_assignment(
        np.repeat(np.arange(4), dst.routing.G // 4)[::-1].copy())
    dst.restore(snap)
    count, state = dst._canonical_arrays()
    np.testing.assert_array_equal(count, snap["count"])
    for name, arr in snap["state"].items():
        np.testing.assert_array_equal(state[name], arr)


# ---------------------------------------------------------------------------
# end-to-end: MiniCluster rebalance, exactly-once
# ---------------------------------------------------------------------------

def _run_skewed_job(rebalance: bool, combine: bool = True):
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ParallelOptions,
    )
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy

    NUM_KEYS = 256

    def keys_of(idx):
        # 70% of mass on 32 hot keys: dense ids cluster low (arrival
        # order) = device 0's contiguous range under the identity table
        u = ((idx * 2654435761) % 1000) / 1000.0
        hot = (idx % 32) * 8
        cold = (idx * 40503) % NUM_KEYS
        return np.where(u < 0.7, hot, cold).astype(np.int64)

    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, 512)
    cfg.set(ExecutionOptions.KEY_CAPACITY, NUM_KEYS)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    cfg.set(ParallelOptions.MESH_ENABLED, rebalance or combine)
    cfg.set(ParallelOptions.MESH_LOCAL_COMBINE, combine)
    cfg.set(ParallelOptions.MESH_SKEW_REBALANCE, rebalance)
    cfg.set(ParallelOptions.MESH_REBALANCE_SKEW_THRESHOLD, 1.2)
    cfg.set(ParallelOptions.MESH_REBALANCE_INTERVAL_MS, 0)
    env = StreamExecutionEnvironment(cfg)
    count = 16 * 512

    def gen(idx):
        return Batch(keys_of(idx), (idx * 2).astype(np.int64))

    ds = env.from_source(
        DataGeneratorSource(gen, count),
        watermark_strategy=WatermarkStrategy.for_monotonous_timestamps())
    sink = CollectSink()
    (ds.key_by(lambda col: col, vectorized=True)
       .window(TumblingEventTimeWindows.of(1000)).count().sink_to(sink))
    client = env.execute_async("skew-routing-e2e")
    client.wait(180)
    return client, sorted((int(k), int(n)) for k, n in sink.results)


def test_minicluster_rebalance_exactly_once():
    from flink_tpu.metrics.registry import metrics_snapshot

    _c0, expected = _run_skewed_job(rebalance=False, combine=False)
    client, rows = _run_skewed_job(rebalance=True)
    assert rows == expected and len(rows) > 0, "rebalance changed results"
    assert client.mesh_rebalances >= 1, "no rebalance under forced skew"
    assert client.num_restarts == 0, "a rebalance must not count a restart"
    assert client._runtime.mesh_routing_version() >= 1
    # the recovery timeline attributes the rebuild as kind=rebalance
    kinds = {r["kind"] for r in client.exceptions.payload()["recoveries"]}
    assert "rebalance" in kinds
    # gauges registered + live (the _TIER_GAUGES-omission class)
    snap = metrics_snapshot(client.metrics.all_metrics())
    assert snap["job.meshRebalances"] >= 1
    assert snap["job.routingTableVersion"] >= 1
    assert snap["job.lastRebalanceDurationMs"] > 0
    # /jobs/:id/device carries the routing block
    blocks = [e["routing"]
              for e in client._runtime.device_snapshot()["operators"].values()
              if e.get("routing")]
    assert blocks and blocks[0]["version"] >= 1
    assert blocks[0]["movedGroups"] > 0


def test_rebalance_survives_capacity_growth():
    """Classic keyed mesh path with a key dictionary that OUTGROWS the
    initial 1024-row capacity: restore on the rebuilt rebalance attempt
    ADOPTS the grown snapshot K and rebuilds the routing table for it —
    the planned assignment must be applied onto THAT table (after
    restore), not silently reset to identity. The pre-fix behavior:
    every rebalance counted as completed while the table stayed
    identity, and the rebalancer re-decided the identical move forever
    (stop-the-world rebuild churn with meshLoadSkew never improving)."""
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        ParallelOptions,
    )
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy

    NUM_KEYS = 2048   # > the 1024-row starting capacity: forces growth

    def keys_of(idx):
        u = ((idx * 2654435761) % 1000) / 1000.0
        hot = (idx % 64) * 8
        cold = (idx * 40503) % NUM_KEYS
        return np.where(u < 0.6, hot, cold).astype(np.int64)

    def run(rebalance):
        cfg = Configuration()
        cfg.set(ExecutionOptions.BATCH_SIZE, 512)
        cfg.set(ExecutionOptions.KEY_CAPACITY, NUM_KEYS)
        cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
        cfg.set(ParallelOptions.MESH_ENABLED, True)
        cfg.set(ParallelOptions.MESH_SKEW_REBALANCE, rebalance)
        cfg.set(ParallelOptions.MESH_REBALANCE_SKEW_THRESHOLD, 1.2)
        cfg.set(ParallelOptions.MESH_REBALANCE_INTERVAL_MS, 0)
        env = StreamExecutionEnvironment(cfg)
        count = 24 * 512

        def gen(idx):
            return Batch(keys_of(idx), (idx * 2).astype(np.int64))

        ds = env.from_source(
            DataGeneratorSource(gen, count),
            watermark_strategy=WatermarkStrategy
            .for_monotonous_timestamps())
        sink = CollectSink()
        (ds.key_by(lambda col: col, vectorized=True)
           .window(TumblingEventTimeWindows.of(1000)).count()
           .sink_to(sink))
        client = env.execute_async("skew-grown")
        client.wait(180)
        return client, sorted((int(k), int(n)) for k, n in sink.results)

    _c0, expected = run(False)
    client, rows = run(True)
    assert rows == expected and len(rows) > 0
    assert client.num_restarts == 0
    assert client.mesh_rebalances >= 1, "no rebalance under forced skew"
    # the applied assignment must have SURVIVED the K-adopting restore:
    # the live table is non-identity, and the policy settled instead of
    # re-deciding the same (discarded) move on every step boundary
    blocks = [e["routing"]
              for e in client._runtime.device_snapshot()["operators"].values()
              if e.get("routing")]
    assert blocks and blocks[0]["movedGroups"] > 0, (
        "rebalanced assignment was discarded by the grown-K restore")
    # the vocabulary fill legitimately shifts integrated load for a few
    # windows at the interval-0 test cadence (a handful of re-decisions);
    # the pre-fix discarded-move loop fired on EVERY step boundary
    # (~steps-many rebalances), which this cap clearly separates
    assert client.mesh_rebalances <= 8, (
        f"{client.mesh_rebalances} rebalances — the rebalancer is "
        "re-deciding a discarded move forever")


def test_set_mesh_routing_skips_mismatched_group_count():
    """A decision sized for a different G (the geometry changed between
    decision and application) is skipped, never a crash — the rebalancer
    re-decides from live skew under the new table."""
    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline

    pipe = ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=256, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, skew_routing=True)

    class _Op:
        def __init__(self, pipe):
            self.pipe = pipe

        def routing_version(self):
            return self.pipe.routing_version()

        def set_routing_assignment(self, assign):
            return self.pipe.set_routing_assignment(assign)

    class _Runner:
        op = _Op(pipe)

    from flink_tpu.runtime.executor import JobRuntime

    rt = JobRuntime.__new__(JobRuntime)
    rt.runners = [_Runner()]
    rt.set_mesh_routing(np.zeros(7, np.int64))    # wrong G: no-op
    assert pipe.routing.is_identity and pipe.routing.version == 0
    good = np.repeat(np.arange(8)[::-1], pipe.routing.G // 8)
    rt.set_mesh_routing(good)
    assert pipe.routing.version == 1 and not pipe.routing.is_identity


def test_rebalance_gauges_fold_max_across_shards():
    from flink_tpu.runtime.cluster import aggregate_shard_metrics

    agg = aggregate_shard_metrics({
        0: {"job.meshRebalances": 3, "job.routingTableVersion": 3,
            "job.lastRebalanceDurationMs": 12.5},
        1: {"job.meshRebalances": 3, "job.routingTableVersion": 3,
            "job.lastRebalanceDurationMs": 9.0},
    })
    # per-mesh facts reported by every shard: MAX, never the x2 sum
    assert agg["job.meshRebalances"] == 3
    assert agg["job.routingTableVersion"] == 3
    assert agg["job.lastRebalanceDurationMs"] == 12.5
