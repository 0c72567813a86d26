"""Multi-shard execution tests on the virtual 8-device CPU mesh: key-group
sharding parity, rescale-on-restore, and the on-device keyBy all-to-all."""

import numpy as np
import pytest

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows, TumblingEventTimeWindows
from flink_tpu.core.keygroups import assign_to_key_group, operator_index_for_key_group
from flink_tpu.core.time import TimeWindow
from flink_tpu.ops import segment_ops
from flink_tpu.parallel.mesh import build_mesh, shard_ranges
from flink_tpu.parallel.sharded_window import ShardedTpuWindowOperator
from flink_tpu.runtime.tpu_window_operator import TpuWindowOperator

MAX_PAR = 128


def test_mesh_and_ranges():
    import jax

    assert len(jax.devices()) == 8
    mesh = build_mesh(8)
    ranges = shard_ranges(mesh, MAX_PAR)
    assert sum(len(r) for r in ranges) == MAX_PAR
    # contiguous partition
    assert ranges[0].start == 0 and ranges[-1].end == MAX_PAR - 1


def _run(op, records, wm_every=50):
    max_ts = 0
    chunk_keys, chunk_vals, chunk_ts = [], [], []

    def flush():
        if chunk_keys:
            from flink_tpu.utils.arrays import obj_array

            op.process_batch(
                obj_array(chunk_keys),
                np.asarray(chunk_vals, dtype=np.float32),
                np.asarray(chunk_ts, dtype=np.int64),
            )
            chunk_keys.clear(), chunk_vals.clear(), chunk_ts.clear()

    for i, (k, v, ts) in enumerate(records):
        chunk_keys.append(k)
        chunk_vals.append(v)
        chunk_ts.append(ts)
        max_ts = max(max_ts, ts)
        if (i + 1) % wm_every == 0:
            flush()
            op.process_watermark(max_ts - 300)
    flush()
    op.process_watermark(max_ts + 10**7)
    return sorted((k, w, round(float(r), 3), t) for k, w, r, t in op.drain_output())


def _random_records(n=500, keys=20, span=20_000, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (f"user-{rng.integers(0, keys)}", float(rng.integers(1, 10)), int(rng.integers(0, span)))
        for _ in range(n)
    ]


@pytest.mark.parametrize("n_shards", [2, 8])
def test_sharded_matches_single_shard(n_shards):
    records = _random_records()
    single = TpuWindowOperator(TumblingEventTimeWindows.of(1000), "sum", num_slices=64)
    sharded = ShardedTpuWindowOperator(
        TumblingEventTimeWindows.of(1000),
        "sum",
        build_mesh(n_shards),
        max_parallelism=MAX_PAR,
        num_slices=64,
    )
    assert _run(single, records) == _run(sharded, records)


def test_sharded_sliding_with_lateness():
    records = _random_records(400, keys=10, seed=3)
    single = TpuWindowOperator(
        SlidingEventTimeWindows.of(3000, 1000), "count", num_slices=64, allowed_lateness=500
    )
    sharded = ShardedTpuWindowOperator(
        SlidingEventTimeWindows.of(3000, 1000),
        "count",
        build_mesh(4),
        max_parallelism=MAX_PAR,
        num_slices=64,
        allowed_lateness=500,
    )
    assert _run(single, records) == _run(sharded, records)


def test_rescale_snapshot_restore():
    """Snapshot at 4 shards, restore at 8 and at 2: same final results
    (key-group re-sharding semantics of the reference's rescale restore)."""
    records = _random_records(300, keys=16, span=10_000, seed=7)
    mid = len(records) // 2

    def run_split(n_before, n_after):
        op1 = ShardedTpuWindowOperator(
            TumblingEventTimeWindows.of(1000), "sum", build_mesh(n_before),
            max_parallelism=MAX_PAR, num_slices=64,
        )
        from flink_tpu.utils.arrays import obj_array

        ks = obj_array([r[0] for r in records[:mid]])
        vs = np.asarray([r[1] for r in records[:mid]], dtype=np.float32)
        ts = np.asarray([r[2] for r in records[:mid]], dtype=np.int64)
        op1.process_batch(ks, vs, ts)
        snap = op1.snapshot()

        op2 = ShardedTpuWindowOperator(
            TumblingEventTimeWindows.of(1000), "sum", build_mesh(n_after),
            max_parallelism=MAX_PAR, num_slices=64,
        )
        op2.restore(snap)
        ks = obj_array([r[0] for r in records[mid:]])
        vs = np.asarray([r[1] for r in records[mid:]], dtype=np.float32)
        ts = np.asarray([r[2] for r in records[mid:]], dtype=np.int64)
        op2.process_batch(ks, vs, ts)
        op2.process_watermark(10**7)
        return sorted((k, w, round(float(r), 3)) for k, w, r, _ in op2.drain_output())

    base = run_split(4, 4)
    assert run_split(4, 8) == base
    assert run_split(4, 2) == base


def test_keyby_exchange_routes_by_key_group():
    import jax
    from flink_tpu.ops.exchange import make_keyby_exchange
    from flink_tpu.parallel.mesh import build_mesh

    n, B = 4, 16
    mesh = build_mesh(n)
    exchange = make_keyby_exchange(mesh, MAX_PAR)

    rng = np.random.default_rng(5)
    kg = rng.integers(0, MAX_PAR, size=(n, B)).astype(np.int32)
    payload = rng.integers(0, 1000, size=(n, B)).astype(np.int32)
    # mark some lanes invalid
    kg[:, -2:] = segment_ops.INVALID_INDEX

    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P("shards", None))
    kg_d = jax.device_put(kg, sh)
    pl_d = jax.device_put(payload, sh)
    kg_out, cols = exchange(kg_d, {"payload": pl_d})
    kg_out = np.asarray(kg_out)
    pl_out = np.asarray(cols["payload"])

    # every valid received lane must belong to the receiving shard
    for d in range(n):
        lanes = kg_out[d]
        valid = lanes != segment_ops.INVALID_INDEX
        owners = (lanes[valid].astype(np.int64) * n) // MAX_PAR
        assert (owners == d).all()
    # conservation: every valid (kg, payload) pair shows up exactly once
    sent = sorted(
        (int(k), int(p))
        for k, p in zip(kg.ravel(), payload.ravel())
        if k != segment_ops.INVALID_INDEX
    )
    received = sorted(
        (int(k), int(p))
        for k, p in zip(kg_out.ravel(), pl_out.ravel())
        if k != segment_ops.INVALID_INDEX
    )
    assert sent == received
