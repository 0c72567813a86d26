"""ShardedFusedPipeline parity vs the single-chip superscan (8-dev CPU mesh)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
from flink_tpu.runtime.fused_window_pipeline import FusedWindowPipeline


def _mesh(n=8):
    devs = np.array(jax.devices()[:n])
    return Mesh(devs, ("shards",))


from flink_tpu.testing.harness import keyed_window_stream as _stream
from flink_tpu.testing.harness import seven_field_stream


def _drain(pipe, batches, wms, chunksize=4):
    out = []
    for lo in range(0, len(batches), chunksize):
        out.extend(pipe.process_superbatch(
            batches[lo:lo + chunksize], wms[lo:lo + chunksize]))
    return out


def _norm(out):
    rows = []
    for (w, counts, fields) in out:
        rows.append((w.start, np.asarray(counts).astype(np.int64),
                     {k: np.asarray(v) for k, v in fields.items()}))
    rows.sort(key=lambda r: r[0])
    return rows


@pytest.mark.parametrize("aggregate", ["count", "sum", "max"])
def test_sharded_matches_single_shard(aggregate):
    steps, batch, num_keys = 8, 600, 256
    batches, wms = _stream(3, steps, batch, num_keys, aggregate != "count")

    single = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), aggregate,
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024, backend="xla",
    )
    sharded = ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(2000, 500), aggregate,
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024,
    )
    ref = _norm(_drain(single, batches, wms))
    got = _norm(_drain(sharded, batches, wms))
    assert len(ref) == len(got) > 0
    for (rs, rc, rf), (gs, gc, gf) in zip(ref, got):
        assert rs == gs
        mask = rc > 0
        assert np.array_equal(rc, gc)
        for name in rf:
            np.testing.assert_allclose(rf[name][mask], gf[name][mask],
                                       rtol=1e-6)


def test_sharded_snapshot_rescales_to_single_and_back():
    steps, batch, num_keys = 8, 500, 128
    batches, wms = _stream(7, steps, batch, num_keys, False)
    half = steps // 2

    sharded = ShardedFusedPipeline(
        _mesh(8), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024,
    )
    out1 = _drain(sharded, batches[:half], wms[:half])
    snap = sharded.snapshot()
    assert snap["count"].shape == (num_keys, 16)

    # restore into a single-chip pipeline (8 -> 1 rescale)...
    single = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024, backend="xla",
    )
    single.restore(snap)
    out_single = _drain(single, batches[half:], wms[half:])

    # ...and into a 4-shard mesh (8 -> 4 rescale)
    resharded = ShardedFusedPipeline(
        _mesh(4), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024,
    )
    resharded.restore(snap)
    out_4 = _drain(resharded, batches[half:], wms[half:])

    ref = _norm(out_single)
    got = _norm(out_4)
    assert len(ref) == len(got) > 0
    for (rs, rc, _), (gs, gc, _) in zip(ref, got):
        assert rs == gs and np.array_equal(rc, gc)


def test_sharded_deferred_pipelining():
    steps, batch, num_keys = 8, 400, 128
    batches, wms = _stream(9, steps, batch, num_keys, False)
    sharded = ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024,
    )
    d1 = sharded.process_superbatch(batches[:4], wms[:4], defer=True)
    d2 = sharded.process_superbatch(batches[4:], wms[4:], defer=True)
    out = d1.resolve() + d2.resolve()

    single = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024, backend="xla",
    )
    ref = _drain(single, batches, wms)
    assert len(ref) == len(out) > 0
    for (rw, rc, _), (gw, gc, _) in zip(_norm(ref), _norm(out)):
        assert rw == gw and np.array_equal(rc, gc)


def test_sustained_sharded_stream_with_midstream_checkpoint():
    """VERDICT scale ask: a sustained sharded stream (>=1e5 records, >=1e3
    keys, many steps) with a checkpoint + restore mid-stream, at parity with
    an uninterrupted single-chip run."""
    steps, batch, num_keys = 40, 4096, 1024   # 163,840 records
    batches, wms = _stream(17, steps, batch, num_keys, False)

    def mk_sharded(n):
        return ShardedFusedPipeline(
            _mesh(n), SlidingEventTimeWindows.of(2000, 500), "count",
            key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
            out_rows=32, chunk=1024,
        )

    single = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=32, chunk=1024, backend="xla",
    )
    ref = _norm(_drain(single, batches, wms, chunksize=8))

    # sharded run, killed at step 24 and restored onto a FRESH mesh pipeline
    a = mk_sharded(8)
    out = []
    for lo in range(0, 24, 8):
        out.extend(a.process_superbatch(batches[lo:lo + 8], wms[lo:lo + 8]))
    snap = a.snapshot()
    b = mk_sharded(8)
    b.restore(snap)
    for lo in range(24, steps, 8):
        out.extend(b.process_superbatch(batches[lo:lo + 8], wms[lo:lo + 8]))
    got = _norm(out)

    assert len(got) == len(ref) > 20
    total = 0
    for (rs, rc, _), (gs, gc, _) in zip(ref, got):
        assert rs == gs and np.array_equal(rc, gc)
        total += int(rc.sum())
    assert total > 100_000  # sustained volume actually flowed


def test_sharded_device_stats_attach_parity_and_telemetry():
    """Device-plane observability on the mesh path: an attached
    CompileTracker observes the sharded dispatch, the phase counters fold
    across shards, key loads read back globally — and none of it changes
    results (parity vs the untracked sharded run)."""
    from flink_tpu.metrics.device_stats import CompileTracker
    from flink_tpu.metrics.key_stats import KeyStatsCollector

    steps, batch, num_keys = 8, 600, 256
    batches, wms = _stream(7, steps, batch, num_keys, False)

    def mk():
        return ShardedFusedPipeline(
            _mesh(), SlidingEventTimeWindows.of(2000, 500), "count",
            key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
            out_rows=16, chunk=1024,
        )

    plain = mk()
    ref = _norm(_drain(plain, batches, wms))

    tracked = mk()
    tracker = CompileTracker()
    tracked.attach_device_stats(tracker)
    assert tracked.key_stats_ready() is False
    got = _norm(_drain(tracked, batches, wms))

    # byte-identical output with the plane on
    assert len(ref) == len(got) > 0
    for (rs, rc, _), (gs, gc, _) in zip(ref, got):
        assert rs == gs and np.array_equal(rc, gc)

    # compile observability saw the sharded program
    assert tracker.num_compiles >= 1
    assert "sharded_superscan" in tracker.payload()["programs"]
    sig = tracker.payload()["programs"]["sharded_superscan"]["lastSignature"]
    assert f"K={num_keys}" in sig and "n=8" in sig

    # phase counters: every record of every step ingested exactly once,
    # summed across the 8 shards' lanes
    assert tracked.phase_totals[0] == steps * batch
    assert tracked.phase_totals[1] > 0        # windows fired

    # key telemetry over the sharded [n, Kl, S] state
    assert tracked.key_stats_ready() is True
    ks = KeyStatsCollector(tracked.key_loads, num_key_groups=16,
                           row_bytes_fn=tracked.state_row_bytes,
                           interval_ms=0)
    assert ks.collect()
    p = ks.payload()
    assert p["keySkew"] is not None
    assert p["activeKeys"] > 0


# ---------------------------------------------------------------------------
# the traced chain over the mesh: each staged field dealt over the shards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev,num_keys,aggregate,combine", [
    (8, 256, "count", False),
    (3, 192, "sum", False),     # B / n uneven: 1024 lanes pad to 3 x 342
    (4, 256, "sum", True),      # the map-side combiner's exchange
], ids=["mesh8_count", "mesh3_uneven_sum", "mesh4_combine_sum"])
def test_sharded_traced_chain_stages_per_field(n_dev, num_keys, aggregate,
                                               combine):
    import jax.numpy as jnp

    from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

    pro = TracedPrologue(
        transforms=(("filter", lambda col: col[:, 2] < 0.5),),
        key_fn=lambda col: col[:, 5].astype(jnp.int32),
        value_fn=lambda col: col[:, 1])
    geom = dict(key_capacity=num_keys, num_slices=16, nsb=4,
                fires_per_step=4, out_rows=16, chunk=1024, prologue=pro)
    assigner = SlidingEventTimeWindows.of(2000, 500)
    single = FusedWindowPipeline(assigner, aggregate, backend="xla", **geom)
    sharded = ShardedFusedPipeline(_mesh(n_dev), assigner, aggregate,
                                   local_combine=combine, **geom)
    steps, wms = seven_field_stream(8, 600, num_keys, seed=5)
    steps = [(rec, None, ts) for rec, ts in steps]
    read = (1, 2, 5) if aggregate == "sum" else (2, 5)

    staged = sharded.stage(steps[:4], wms[:4])
    (fields_d, srel_d), _signature = staged.scan_xs()
    Bs = -(-1024 // n_dev)
    assert sharded._planner._layout().columns == read
    assert [f.shape for f in fields_d] == [(n_dev, 4, Bs)] * len(read)
    assert srel_d.shape == (n_dev, 4, Bs)
    # lanes are dealt contiguously: shard i holds lanes [i*Bs, (i+1)*Bs)
    lanes = np.concatenate(list(np.asarray(fields_d[-1])), axis=1)
    np.testing.assert_array_equal(lanes[0, :600], steps[0][0][:, 5])
    assert (np.asarray(srel_d).transpose(1, 0, 2).reshape(4, -1)[:, 600:]
            == -1).all()

    got = sharded.dispatch(staged)
    got += sharded.process_superbatch(steps[4:], wms[4:])
    ref = single.process_superbatch(steps[:4], wms[:4])
    ref += single.process_superbatch(steps[4:], wms[4:])
    ref, got = _norm(ref), _norm(got)
    assert len(ref) == len(got) > 0
    for (rs, rc, rf), (gs, gc, gf) in zip(ref, got):
        assert rs == gs
        assert np.array_equal(rc, gc) and rc.sum() > 0
        for name in rf:
            np.testing.assert_array_equal(rf[name][rc > 0], gf[name][rc > 0])
