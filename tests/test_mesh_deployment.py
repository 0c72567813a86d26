"""The keyed job at parallelism n: the deployments `ysb_keys64k_mesh4` and
`ysb_keys64k_zipf_mesh4` of the benchmark (`benchmarks/configs/`) at small
sizes on the virtual CPU mesh.

- the configuration's job through `env.execute()` with the two mesh options,
  rows equal to the configuration's plain reference cell for cell, on uniform
  and on zipf keys (rank = id: the first key range owns most of the records),
  and under zipf with each skew switch on;
- what the exchange delivered to each device (`perDevice[i].routed`) and what
  that device's ingest read for it (`.lanes`), against a numpy count;
- the fire rows a mesh dispatch hands to the deferred readback: only the rows
  its fires used, on both dispatch paths, with and without a routing table;
- the mesh's enqueue: every argument of a warm dispatch sits where the program
  reads it (no device-to-device copy, the plan replicated over the mesh), and
  one program per fire shape cuts, concatenates and permutes the fire slabs;
- the stage clock on the mesh: the deal's own stage, the link's bytes back;
- the lane deal over shards that do not divide the batch.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import harness
from benchmarks.stream import build_cycle
from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.metrics.key_stats import KeyStatsCollector
from flink_tpu.metrics.task_io import StageClock
from flink_tpu.metrics.device_stats import CompileTracker
from flink_tpu.parallel.sharded_superscan import (
    _MESH_CHAINED,
    _MESH_CLASSIC,
    ShardedFusedPipeline,
    _fire_shaper,
)
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    TracedPrologue,
)

#: the key draw -> the shipped configuration that has it
CONFIGS = {"uniform": "ysb_keys64k_mesh4", "zipf": "ysb_keys64k_zipf_mesh4"}
KEYS = 4096


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("shards",))


# ---------------------------------------------------------------------------
# the configuration's job through env.execute(), against its plain reference
# ---------------------------------------------------------------------------

def _small_spec(n: int, keys: str = "uniform", **options):
    """The shipped configuration with the key space and the key capacity cut
    to test size and the mesh over `n` devices; the job, the record, the key
    draw, the window, the jitter and the reference are the file's."""
    cfg = harness.load_json("configs", CONFIGS[keys] + ".json")
    (key_col,) = [c for c in cfg["stream"]["columns"]
                  if c["name"] == "campaign_id"]
    key_col["mod"] = KEYS
    assert key_col.get("dist") == (
        {"kind": "zipf", "s": 1.0} if keys == "zipf" else None)
    cfg["reference"]["keys"] = KEYS
    cfg["options"] = dict(cfg["options"], **{
        "parallel.mesh.devices": n, "execution.state.key-capacity": KEYS},
        **options)
    cfg["expect"] = ({"mesh_devices": n, "devices_with_records": n}
                     if n > 1 else {})
    return {"cell": {"name": f"test_keys4k_{keys}_mesh{n}",
                     "config": CONFIGS[keys], "traffic": "catchup",
                     "chips": n},
            "cfg": cfg, "traffic": harness.load_json("traffic", "catchup.json"),
            "end_to_end": [], "per_layer": []}


def _run(spec, seed):
    return harness.run_cell(spec["cell"]["name"], seed, 1.0, False,
                            rehearse=True, spec=spec, log=lambda _m: None)


def _views_per_key_range(spec, seed, events: int, n: int) -> np.ndarray:
    """numpy's count of the records the exchange has to deliver to each of n
    contiguous key ranges: of the first `events` events of the run's stream
    (the cycle, lap after lap), those that pass the view filter."""
    cfg = spec["cfg"]
    cycle = build_cycle(cfg["stream"], harness.rehearsal_traffic(
        spec["traffic"]), seed, wrap=harness.batch_size_of(cfg["options"]))
    keys = cycle.column("campaign_id").astype(np.int64)
    view = cycle.column("event_type") < cfg["reference"]["filter"]["keep_below"]
    laps, rest = divmod(events, cycle.events)
    per_key = laps * np.bincount(keys[view], minlength=KEYS) + np.bincount(
        keys[:rest][view[:rest]], minlength=KEYS)
    return per_key.reshape(n, KEYS // n).sum(axis=1)


@pytest.mark.parametrize("keys", ["uniform", "zipf"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_job_on_n_devices_equals_the_plain_reference(n, keys):
    spec = _small_spec(n, keys)
    assert spec["cfg"]["reference"]["module"] == "keyed_window_count"
    seed = 2600000000 + n
    out = _run(spec, seed)
    assert out["correct"] is True, out["compared"]
    # > 2 windows; under zipf the coldest keys see no view in some windows
    assert out["attempted"] > (2 * KEYS if keys == "uniform" else KEYS)
    assert out["failed"] == 0
    compared = {k: c["value"] for k, c in out["compared"].items()}
    counters = out["_detail"]["counters"]
    if n == 1:  # one device: no mesh applies, the one-chip program runs
        assert counters["mesh_devices"] == 1
        assert counters["programs"]["fused_chained_superscan"][
            "dispatches"] >= 1
        assert counters["per_device"] == []
        return
    assert compared["mesh_devices"] == n
    assert compared["devices_with_records"] == n
    program = counters["programs"]["sharded_chained_superscan"]
    assert program["dispatches"] >= 1
    # the exchange's counters: every view reached the owner of its key range
    # once, and every device's ingest read the same lanes for them
    per_device = counters["per_device"]
    assert [e["device"] for e in per_device] == list(range(n))
    want = _views_per_key_range(spec, seed, out["_detail"]["events"], n)
    np.testing.assert_array_equal([e["routed"] for e in per_device], want)
    assert want.sum() > out["_detail"]["events"] // 4
    assert len({e["lanes"] for e in per_device}) == 1
    assert per_device[0]["lanes"] >= out["_detail"]["events"]
    share = want / want.sum()
    if keys == "zipf":      # rank = id: the first range owns the hot keys
        assert share[0] > (0.9 if n == 2 else 0.8) and list(
            np.argsort(-share)) == list(range(n))
    else:
        assert abs(share - 1 / n).max() < 0.01


@pytest.mark.parametrize("switch", [None, "parallel.mesh.local-combine",
                                    "parallel.mesh.skew-rebalance"])
def test_under_zipf_each_skew_switch_gives_the_reference_s_rows(switch):
    """Neither switch is on in a shipped configuration; each is a choice of
    placement or of exchange, never of result. (Through `env.execute()` the
    second turns the owner function into a routing table and nothing more:
    the rebalancer that would remap it is the MiniCluster's.)"""
    spec = _small_spec(4, "zipf", **({switch: True} if switch else {}))
    seed = 2600000014
    out = _run(spec, seed)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > KEYS and out["failed"] == 0
    per_device = out["_detail"]["counters"]["per_device"]
    want = _views_per_key_range(spec, seed, out["_detail"]["events"], 4)
    np.testing.assert_array_equal([e["routed"] for e in per_device], want)
    lanes = per_device[0]["lanes"]
    if switch == "parallel.mesh.local-combine":
        # the combiner's ingest reads partial cells, n * K_local * NSB a step
        assert lanes % (4 * (KEYS // 4) * 4) == 0
    else:
        assert lanes >= out["_detail"]["events"]


# ---------------------------------------------------------------------------
# only the fire rows a dispatch used are handed to the deferred readback
# ---------------------------------------------------------------------------

#: four dispatches of 8 steps x 500 ms of event time; the watermarks hold the
#: fires back and release them: 0, 1, 16 and 17 fires (4 a step at most)
STEPS, STEP_MS, BATCH, K, R = 8, 500, 300, 256, 32
FIRES = (0, 1, 16, 17)
WATERMARKS = (
    [0] * 8,
    [249] * 8,
    [1249, 2249, 3249, 4249] + [4249] * 4,
    [5249, 6249, 7249, 8249, 8499] + [8499] * 3,
)
ASSIGNER = SlidingEventTimeWindows.of(1000, 250)
GEOM = dict(key_capacity=K, num_slices=128, nsb=4, fires_per_step=4,
            out_rows=R, chunk=512)


def _dispatches(seed=3, count=len(FIRES)):
    """[(records [BATCH, 3] f32: key, value, flag; None; ts)] per step, per
    dispatch; every record lies ahead of every watermark."""
    rng = np.random.default_rng(seed)
    out = []
    for d in range(count):
        steps = []
        for s in range(STEPS):
            rec = np.stack([rng.integers(0, K, BATCH),
                            rng.integers(1, 9, BATCH),
                            rng.integers(0, 2, BATCH)], axis=1)
            t0 = (d * STEPS + s) * STEP_MS
            ts = (t0 + rng.integers(0, STEP_MS, BATCH)).astype(np.int64)
            steps.append((rec.astype(np.float32), None, ts))
        out.append(steps)
    return out


def _prologue(aggregate):
    return TracedPrologue(
        transforms=(("filter", lambda col: col[:, 2] < 0.5),),
        key_fn=lambda col: col[:, 0].astype(jnp.int32),
        value_fn=(lambda col: col[:, 1]) if aggregate == "sum" else None)


def _steps_for(steps, raw: bool):
    """The steps as the path under test takes them: records, or key ids
    and values."""
    if raw:
        return steps
    return [(rec[:, 0].astype(np.int32), rec[:, 1], ts)
            for rec, _none, ts in steps]


def _reversed_ranges(pipe):
    """A routing table that is no identity: the key ranges in reverse."""
    return np.repeat(np.arange(pipe.n)[::-1], pipe.routing.G // pipe.n)


def _feed(pipe, steps, wms, raw: bool):
    """One deferred dispatch through the path under test."""
    return pipe.process_superbatch(_steps_for(steps, raw), wms, defer=True)


def _ceil16(fires):
    return -(-max(fires, 1) // 16) * 16


@pytest.mark.parametrize("raw", [False, True], ids=["keyed", "traced_chain"])
@pytest.mark.parametrize("routed", [False, True], ids=["static", "table"])
@pytest.mark.parametrize("aggregate", ["count", "sum"])
@pytest.mark.parametrize("n", [2, 4])
def test_mesh_dispatch_reads_back_only_the_fire_rows_it_used(
        n, aggregate, routed, raw):
    geom = dict(GEOM, prologue=_prologue(aggregate)) if raw else GEOM
    single = FusedWindowPipeline(ASSIGNER, aggregate, backend="xla", **geom)
    sharded = ShardedFusedPipeline(_mesh(n), ASSIGNER, aggregate,
                                   skew_routing=routed, **geom)
    if routed:      # the columns are permuted
        sharded.set_routing_assignment(_reversed_ranges(sharded))
    fields = 1 + (aggregate == "sum")
    for steps, wms, fires in zip(_dispatches(), WATERMARKS, FIRES):
        want, got = (_feed(p, steps, wms, raw) for p in (single, sharded))
        assert len(got._fires) == len(want._fires) == fires
        rows = _ceil16(fires)
        handed = [got._count_out, *got._outs.values()]
        assert len(handed) == fields
        assert [a.shape for a in handed] == [(rows, K)] * fields
        # what resolve() reads back: the used rows (+ the key bounds, i32[2],
        # and on the mesh the exchange's two counters of each shard behind them)
        assert want.nbytes == rows * K * 4 * fields + (8 if raw else 0)
        assert got.nbytes == want.nbytes + (8 * n if raw else 0)
        want, got = want.resolve(), got.resolve()
        assert len(got) == len(want) == fires
        for (ww, wc, wf), (gw, gc, gf) in zip(want, got):
            assert (gw.start, gw.end) == (ww.start, ww.end)
            np.testing.assert_array_equal(gc, wc)
            live = np.asarray(wc) > 0
            for name in wf:
                np.testing.assert_array_equal(
                    np.asarray(gf[name])[live], np.asarray(wf[name])[live])
        if fires:
            assert sum(int(np.asarray(c).sum()) for _w, c, _f in got) > 0


# ---------------------------------------------------------------------------
# the mesh's enqueue: where a dispatch's arguments sit, and the one program
# that shapes its fire rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("aggregate", ["count", "sum"])
@pytest.mark.parametrize("routed", [False, True], ids=["static", "table"])
@pytest.mark.parametrize("raw", [False, True], ids=["keyed", "traced_chain"])
def test_a_warm_mesh_dispatch_copies_nothing_from_device_to_device(
        raw, routed, aggregate):
    """An explicit `device_put` passes the guard; what it catches is the
    implicit reshard of an argument that was put somewhere else than the
    program's `in_specs` say (the plan on device 0, as it was, or a routing
    table)."""
    n = 4
    mesh = _mesh(n)
    geom = dict(GEOM, prologue=_prologue(aggregate)) if raw else GEOM
    single = FusedWindowPipeline(ASSIGNER, aggregate, backend="xla", **geom)
    pipe = ShardedFusedPipeline(mesh, ASSIGNER, aggregate,
                                skew_routing=routed, **geom)
    if routed:
        pipe.set_routing_assignment(_reversed_ranges(pipe))
    replicated = NamedSharding(mesh, P())
    (warm, warm_wms), *rest = zip(_dispatches(), WATERMARKS)
    for p in (single, pipe):
        _feed(p, warm, warm_wms, raw).resolve()
    for steps, wms in rest:
        with jax.transfer_guard_device_to_device("disallow"):
            staged = pipe.stage(_steps_for(steps, raw), wms)
            got = pipe.dispatch(staged, defer=True)
        assert pipe._program(staged.payload) is (
            _MESH_CHAINED if raw else _MESH_CLASSIC)
        # the five plan arrays side by side, one replicated array
        (plan,) = staged.plan
        assert plan.shape == (STEPS, 1 + 3 * pipe.F + pipe.S)
        assert plan.committed
        assert plan.sharding.is_equivalent_to(replicated, plan.ndim)
        assert len(plan.addressable_shards) == n
        # the lanes: dealt over the source shards; a count's [T, 1]
        # placeholder of values has none and is replicated with the plan
        lanes = len(staged.xs) if raw or aggregate == "sum" else 1
        for i, a in enumerate(staged.xs):
            assert a.committed
            want = NamedSharding(mesh, P("shards")) if i < lanes else replicated
            assert a.sharding.is_equivalent_to(want, a.ndim)
        want = _feed(single, steps, wms, raw).resolve()
        got = got.resolve()
        assert len(got) == len(want)
        for (_ww, wc, wf), (_gw, gc, gf) in zip(want, got):
            np.testing.assert_array_equal(gc, wc)
            live = np.asarray(wc) > 0
            for name in wf:
                np.testing.assert_array_equal(
                    np.asarray(gf[name])[live], np.asarray(wf[name])[live])


@pytest.mark.parametrize("routed", [False, True], ids=["static", "table"])
@pytest.mark.parametrize("field", [False, True], ids=["count", "sum"])
@pytest.mark.parametrize("fired", FIRES)
@pytest.mark.parametrize("n", [2, 4])
def test_the_fire_shape_program_equals_numpy_on_the_per_shard_slabs(
        n, fired, field, routed):
    pipe = ShardedFusedPipeline(_mesh(n), ASSIGNER,
                                "sum" if field else "count",
                                skew_routing=routed, **GEOM)
    if routed:
        pipe.set_routing_assignment(_reversed_ranges(pipe))
    rng = np.random.default_rng(31 + fired)
    names = [f.name for f in pipe._value_fields]
    assert len(names) == int(field)
    slabs = [rng.integers(0, 1 << 20, (n, R, K // n)).astype(np.int32)] + [
        rng.normal(size=(n, R, K // n)).astype(np.float32) for _ in names]
    on_mesh = jax.device_put(slabs, pipe._shard_spec(None, None))
    with jax.transfer_guard_device_to_device("disallow"):
        count_rows, out_rows = pipe._canonical_fire_rows(
            on_mesh[0], dict(zip(names, on_mesh[1:])), fired)
    assert list(out_rows) == names
    used = _ceil16(fired)
    for got, slab in zip([count_rows, *out_rows.values()], slabs):
        want = np.transpose(slab[:, :used], (1, 0, 2)).reshape(used, K)
        if routed:
            want = np.take(want, pipe.routing.perm, axis=1)
        got = np.asarray(got)
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("routed", [False, True], ids=["static", "table"])
def test_one_fire_shape_program_per_used_rows_over_a_steady_run(routed):
    """The first four dispatches fire 0, 1, 16 and 17 windows (16 and 32 rows
    read back), every later one 16: nothing is built after them, neither a
    fire-shape program nor a window program."""
    pipe = ShardedFusedPipeline(_mesh(4), ASSIGNER, "count",
                                skew_routing=routed,
                                **dict(GEOM, prologue=_prologue("count")))
    tracker = CompileTracker()
    pipe.attach_device_stats(tracker)
    dispatches = _dispatches(count=12)
    watermarks = list(WATERMARKS)
    while len(watermarks) < len(dispatches):
        top = watermarks[-1][-1]
        watermarks.append([top + 1000 * min(s + 1, 4) for s in range(STEPS)])
    fires = []
    for d, (steps, wms) in enumerate(zip(dispatches, watermarks)):
        got = pipe.process_superbatch(steps, wms, defer=True)
        fires.append(len(got._fires))
        got.resolve()
        if d == len(FIRES) - 1:
            built = _fire_shaper.cache_info().misses
            compiles = tracker.num_compiles
            sizes = [_fire_shaper(u)._cache_size() for u in (16, 32)]
    assert tuple(fires) == FIRES + (16,) * (len(dispatches) - len(FIRES))
    assert _fire_shaper.cache_info().misses == built
    assert tracker.num_compiles == compiles
    # jit's own cache under each `used`: one entry per slab shape, with a
    # table or without
    assert [_fire_shaper(u)._cache_size() for u in (16, 32)] == sizes
    assert min(sizes) >= 1
    assert pipe.phase_totals[0] > 0     # folded over the shards at resolve


# ---------------------------------------------------------------------------
# what the exchange delivered to each device, and what its ingest read
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("raw", [False, True], ids=["keyed", "traced_chain"])
@pytest.mark.parametrize("routed", [False, True], ids=["static", "table"])
@pytest.mark.parametrize("n", [2, 4])
def test_per_device_routed_and_lanes_equal_a_numpy_count(n, routed, raw):
    """`perDevice[i].routed`: the records whose key device i owns (its key
    range, or the groups the routing table gives it) that passed the chain's
    filter, summed over every resolved dispatch; `.lanes`: n source shards x
    their lanes x steps, the same on every device. Both ride the key-bounds
    vector, which only the traced-chain program reads back: the key-id program
    (`sharded_superscan`) reports neither, and `perDevice` then lacks them."""
    geom = dict(GEOM, prologue=_prologue("count")) if raw else GEOM
    pipe = ShardedFusedPipeline(_mesh(n), ASSIGNER, "count",
                                skew_routing=routed, **geom)
    owner = np.arange(K) // (K // n)                # static: contiguous ranges
    if routed:
        assign = _reversed_ranges(pipe)
        pipe.set_routing_assignment(assign)
        owner = assign[np.arange(K) // pipe.routing.Kg]
    stats = KeyStatsCollector(
        pipe.key_loads, mesh_loads_fn=pipe.per_device_key_loads,
        mesh_exchange_fn=pipe.per_device_exchange, interval_ms=0)
    want = np.zeros(n, np.int64)
    assert pipe.per_device_exchange() is None       # nothing resolved yet
    for steps, wms in zip(_dispatches(), WATERMARKS):
        deferred = _feed(pipe, steps, wms, raw)
        before = pipe.exchange_totals.copy()
        deferred.resolve()                          # counted at resolve, once
        for rec, _none, _ts in steps:
            keep = rec[:, 2] < 0.5
            want += np.bincount(owner[rec[keep, 0].astype(np.int64)],
                                minlength=n)
        if raw:
            assert (pipe.exchange_totals > before).all()
    assert stats.collect()
    per_device = stats.payload()["perDevice"]
    assert [e["device"] for e in per_device] == list(range(n))
    assert all(e["records"] > 0 for e in per_device)
    if not raw:
        assert pipe.per_device_exchange() is None
        assert not any("routed" in e or "lanes" in e for e in per_device)
        return
    assert [e["routed"] for e in per_device] == list(want)
    # the staging loop pads a step of 300 lanes to its bucket of 512, dealt
    # over the n source shards; every device ingests all n shards' lanes
    lanes = n * (512 // n) * STEPS * len(FIRES)
    assert [e["lanes"] for e in per_device] == [lanes] * n
    assert 0 < want.sum() < lanes


# ---------------------------------------------------------------------------
# the stage clock on the mesh
# ---------------------------------------------------------------------------

def test_the_deal_has_a_stage_of_its_own_and_the_link_counts_used_rows():
    """The spans themselves (`stage.shard` inside `stage.fill`, one `seq`)
    are read from a capture in tests/test_stage_clock.py's mesh4 job."""
    sharded = ShardedFusedPipeline(_mesh(4), ASSIGNER, "count",
                                   prologue=_prologue("count"), **GEOM)
    clock = StageClock()
    sharded.attach_stage_clock(clock)
    for steps, wms in zip(_dispatches(), WATERMARKS):
        d = sharded.process_superbatch(steps, wms, defer=True)
        d.resolve()
        clock.d2h_bytes += d.nbytes         # as `_resolve_oldest` counts it
    n = len(FIRES)
    table = clock.stage_table()
    assert table["stage.shard"]["count"] == table["stage.fill"]["count"] == n
    # the deal is a view of the staged arrays: its copy is `stage.put`'s
    assert table["stage.shard"]["ms"] < table["stage.fill"]["ms"]
    link = clock.link()
    assert link["eventsStaged"] == n * STEPS * BATCH
    assert link["d2hBytes"] == sum(
        _ceil16(f) * K * 4 + 8 + 8 * 4 for f in FIRES)
    # at the cell's shape, 2^21 events and at most 16 rows of 65 536 keys a
    # dispatch: under 4 B per event where all R = 256 rows read 32
    assert _ceil16(1) * 65_536 * 4 / 2 ** 21 < 4 < 256 * 65_536 * 4 / 2 ** 21


# ---------------------------------------------------------------------------
# the lane deal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,B,tail", [(4, 1024, ()), (3, 1024, ()),
                                      (3, 512, (7,)), (2, 6, ())])
def test_lane_deal_drops_and_doubles_no_record(n, B, tail):
    pipe = ShardedFusedPipeline(_mesh(n), ASSIGNER, "count",
                                **dict(GEOM, key_capacity=6 * 64))
    T = 4
    a = np.arange(T * B * int(np.prod(tail, dtype=int)),
                  dtype=np.int32).reshape((T, B) + tail)
    dealt = pipe._deal_lanes(a, -1)
    Bs = -(-B // n)
    assert dealt.shape == (n, T, Bs) + tail
    # shard i holds lanes [i*Bs, (i+1)*Bs) of every step, pads at the end
    flat = np.concatenate(list(dealt), axis=1)          # [T, n*Bs, ...]
    np.testing.assert_array_equal(flat[:, :B], a)
    assert (flat[:, B:] == -1).all() and flat.shape[1] - B == n * Bs - B < n
