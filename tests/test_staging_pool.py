"""Host staging sets reused across dispatches (`fused_window_pipeline._StagingPool`).

A dispatch's lane arrays come from the pipeline's pool and go back when the
dispatch resolves. Held here: a pooled run equals a run that allocates a
fresh set every dispatch, byte for byte, with stale lanes in the reused
buffers and geometry changes (a ragged width, a `T=1` flush); no set is
handed out while the dispatch that staged it is unresolved (an in-flight
ring of 1 and 3, streaming readback); a `device_put` that aliases the host
memory keeps fresh sets; the mesh's dealt views; the two `link` counters.

The CPU backend's `device_put` aliases a host array that starts on a
64-byte boundary and copies any other, and numpy's allocator gives either:
`_at` fixes where a fresh set starts, so each test knows which it gets.
"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.core.time import MAX_WATERMARK
from flink_tpu.metrics.task_io import StageClock
from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
from flink_tpu.runtime import fused_window_pipeline as fwp
from flink_tpu.runtime.fused_window_operator import FusedWindowOperator
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    TracedPrologue,
)
from flink_tpu.scheduler.latency_controller import LatencySpec

K, CHUNK, SLIDE = 64, 256, 250
ASSIGNER = SlidingEventTimeWindows.of(1000, SLIDE)
GEOM = dict(key_capacity=K, num_slices=16, nsb=4, fires_per_step=4,
            out_rows=16, chunk=CHUNK)
#: key in field 0, value in field 1, field 2 filters; field 3 never read
PROLOGUE = TracedPrologue(
    transforms=(("filter", lambda col: col[:, 2] < 0.75),),
    key_fn=lambda col: col[:, 0].astype(jnp.int32),
    value_fn=lambda col: col[:, 1])


def _at(offset):
    """A `_fresh` whose arrays start `offset` bytes past a 64-byte
    boundary: 0 lets the CPU backend's `device_put` alias them, 16 not."""
    def fresh(geometry):
        out = []
        for shape, dtype, fill in geometry:
            dtype = np.dtype(dtype)
            n = int(np.prod(shape)) * dtype.itemsize
            raw = np.empty(n + 128, np.uint8)
            lo = (-raw.ctypes.data) % 64 + offset
            a = raw[lo:lo + n].view(dtype).reshape(shape)
            if fill is not None:
                a[...] = fill
            out.append(a)
        return tuple(out)
    return fresh


@pytest.fixture
def copied(monkeypatch):
    """Fresh sets that the CPU backend's `device_put` copies."""
    monkeypatch.setattr(fwp, "_fresh", _at(16))


class _FreshPool(fwp._StagingPool):
    """Staging without the pool: a fresh set every dispatch."""

    def take(self, geometry):
        return fwp._StagingLease(None, geometry, fwp._fresh(geometry), False)


def _groups(seed, sizes):
    """One group of `T` steps per entry of `sizes` ((T, records a step)):
    records [n, 4] f32 (key, value, filter field, unread field), event
    time advancing 250 ms a step, the watermark 100 ms behind."""
    rng = np.random.default_rng(seed)
    groups, t = [], 0
    for T, n in sizes:
        steps, wms = [], []
        for _s in range(T):
            ts = t * SLIDE + rng.integers(0, SLIDE, n)
            rec = np.stack([rng.integers(0, K, n), rng.integers(1, 99, n),
                            rng.random(n), rng.random(n) * 1e9],
                           axis=1).astype(np.float32)
            steps.append((rec, ts.astype(np.int64)))
            wms.append((t + 1) * SLIDE - 100)
            t += 1
        groups.append((steps, wms))
    return groups


def _key_ids(steps):
    return [(rec[:, 0].astype(np.int32), rec[:, 1], ts) for rec, ts in steps]


def _records(steps):
    return [(rec, None, ts) for rec, ts in steps]


def _run(pipe, groups, as_steps, depth=1):
    """Stage and dispatch each group as the operator does (group N+1 is
    staged before N resolves, `depth` dispatches in flight): every fire
    as (window start, count row, sum row)."""
    clock = StageClock()
    pipe.attach_stage_clock(clock)
    ring, fired = collections.deque(), []
    for steps, wms in groups:
        ring.append(pipe.dispatch(pipe.stage(as_steps(steps), wms),
                                  defer=True))
        while len(ring) > depth:
            fired.extend(ring.popleft().resolve())
    while ring:
        fired.extend(ring.popleft().resolve())
    rows = [(w.start, np.asarray(c), np.asarray(f["sum"]))
            for w, c, f in fired]
    return rows, clock.link()


def _same(a, b):
    assert len(a) == len(b) and a
    for (wa, ca, fa), (wb, cb, fb) in zip(a, b):
        assert wa == wb
        np.testing.assert_array_equal(ca, cb)
        np.testing.assert_array_equal(fa.view(np.uint32), fb.view(np.uint32))


#: fewer live lanes than the group before at the same width (stale lanes
#: stay in the reused buffer), a ragged width, the full width again, a T=1
#: flush: 780-1000 records stage 1024 lanes, 300 stage 512, for key ids
#: (a chunk multiple) and records (a power of two of chunks) alike
SIZES = [(8, 900), (8, 800), (8, 900), (8, 780), (8, 300), (8, 900),
         (8, 1000), (1, 40)]


@pytest.mark.parametrize("payload", ["record", "key_ids"])
def test_pooled_runs_match_fresh_allocation_byte_for_byte(copied, payload):
    groups = _groups(42, SIZES)
    prologue, as_steps = ((PROLOGUE, _records) if payload == "record"
                          else (None, _key_ids))
    pooled = FusedWindowPipeline(ASSIGNER, "sum", prologue=prologue,
                                 backend="xla", **GEOM)
    fresh = FusedWindowPipeline(ASSIGNER, "sum", prologue=prologue,
                                backend="xla", **GEOM)
    fresh._staging = _FreshPool()
    got, link = _run(pooled, groups, as_steps)
    want, fresh_link = _run(fresh, groups, as_steps)
    _same(got, want)
    assert fresh_link["stagingSetsReused"] == 0
    # two sets of the full width carry the run: N+1 stages while N is out
    assert (link["stagingSetsAllocated"], link["stagingSetsReused"]) == (4, 4)
    assert (link["stagingSetsAllocated"] + link["stagingSetsReused"]
            == len(SIZES))


def test_a_reused_set_keeps_the_stale_lanes_it_had_dead(copied):
    """The reused buffer still holds the wider group's records past the
    new group's live lanes: `pad` marks them dead (srel -1) and leaves
    the fields as they were, as it leaves `np.empty`'s garbage."""
    groups = _groups(7, [(2, 900), (2, 900), (2, 520)])
    pipe = FusedWindowPipeline(ASSIGNER, "sum", prologue=PROLOGUE,
                               backend="xla", **GEOM)
    for steps, wms in groups[:2]:
        staged = pipe.stage(_records(steps), wms)
        fields = staged.lease.arrays[1].copy()
        pipe.dispatch(staged, defer=True).resolve()
    third = pipe.stage(_records(groups[2][0]), groups[2][1])
    srel, field = third.lease.arrays[:2]
    assert third.lease.reused
    assert (srel[:, 520:] == -1).all() and (srel[:, :520] >= 0).all()
    np.testing.assert_array_equal(field[:, 520:900], fields[:, 520:900])


def test_a_reused_key_id_set_zeroes_its_dead_values(copied):
    """Key ids carry values where the aggregate reads them: a dead lane's
    value is 0 in a reused set as in a fresh one (the chip's matmul
    histogram multiplies it by a zero one-hot, and 0 x inf is NaN)."""
    groups = _groups(9, [(2, 900), (2, 900), (2, 780)])
    groups[1][0][0][0][:, 1] = np.inf        # a stale value to be cleared
    pipe = FusedWindowPipeline(ASSIGNER, "sum", backend="xla", **GEOM)
    for steps, wms in groups[:2]:
        pipe.dispatch(pipe.stage(_key_ids(steps), wms), defer=True).resolve()
    third = pipe.stage(_key_ids(groups[2][0]), groups[2][1])
    idx, vals = third.lease.arrays
    assert third.lease.reused
    assert (idx[:, 780:] == -1).all() and (vals[:, 780:] == 0).all()
    np.testing.assert_array_equal(vals[0, :780], groups[2][0][0][0][:, 1])


class _Pinned:
    """A latency controller pinned to one rung (as test_latency_controller
    pins it): the ring fills to its configured depth."""

    def __init__(self, steps):
        self._steps = steps

    def observe(self, n_steps, now=None):
        pass

    def steps(self, now=None):
        return self._steps

    def current_steps(self):
        return self._steps

    def reset(self):
        pass


def _stream(op, seed=11, steps=40):
    r = np.random.default_rng(seed)
    out = []
    for s in range(steps):
        keys = r.integers(0, 96, 48)
        vals = (keys % 5 + 1).astype(np.float32)
        ts = (s * 250 + r.integers(0, 250, 48)).astype(np.int64)
        op.process_batch(keys, vals, ts)
        op.process_watermark(s * 250 + 125)
        out.extend(op.drain_output())
    op.process_watermark(MAX_WATERMARK - 1)
    out.extend(op.drain_output())
    return sorted((int(k), int(w.start), float(v)) for k, w, v, _ in out)


def _operator(latency=None, rung=None):
    op = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "sum",
                             key_capacity=256, superbatch_steps=8,
                             latency=latency)
    if rung is not None:
        op._controller = _Pinned(rung)
    op.attach_stage_clock(StageClock())
    return op


@pytest.mark.parametrize("depth,readback", [(1, 0), (3, 0), (3, 2)])
def test_no_set_is_handed_out_while_its_dispatch_is_unresolved(
        copied, depth, readback):
    want = _stream(_operator())
    latency = (None if depth == 1 and not readback else LatencySpec(
        target_ms=50, max_inflight=depth, readback_steps=readback))
    op = _operator(latency, rung=None if latency is None else 4)
    pool, take = op.pipe._staging, op.pipe._staging.take
    leases, most_out = [], 0

    def checked_take(geometry):
        lease = take(geometry)
        held = {id(a) for d, *_rest in op._inflight
                if d.lease is not None for a in d.lease.arrays}
        assert not held & {id(a) for a in lease.arrays}
        leases.append(lease)
        nonlocal most_out
        most_out = max(most_out, len(op._inflight) + 1)
        return lease

    pool.take = checked_take
    assert _stream(op) == want
    assert len(op._inflight) == 0
    assert all(lease.pool is None for lease in leases)   # every one back
    link = op.stage_clock.link()
    assert link["stagingSetsReused"] > 0
    assert link["stagingSetsAllocated"] + link["stagingSetsReused"] \
        == link["dispatches"] == len(leases)
    # the pool is as deep as the ring: no geometry ever had more sets
    # than were out at once
    assert most_out == depth + 1
    for free in pool._free.values():
        assert len(free) <= most_out
    if readback:
        assert op.pipe.readback_steps == readback


def test_an_aliasing_device_put_keeps_fresh_sets(monkeypatch):
    """Sets on a 64-byte boundary: the CPU backend's `device_put` makes
    its device arrays of their memory, so the geometry never reuses."""
    monkeypatch.setattr(fwp, "_fresh", _at(0))
    groups = _groups(3, [(8, 900)] * 4)
    pipe = FusedWindowPipeline(ASSIGNER, "sum", prologue=PROLOGUE,
                               backend="xla", **GEOM)
    got, link = _run(pipe, groups, _records)
    assert link["stagingSetsReused"] == 0
    assert link["stagingSetsAllocated"] == len(groups)
    assert pipe._staging._aliased and not any(pipe._staging._free.values())
    monkeypatch.setattr(fwp, "_fresh", _at(16))
    want, _ = _run(FusedWindowPipeline(ASSIGNER, "sum", prologue=PROLOGUE,
                                       backend="xla", **GEOM),
                   groups, _records)
    _same(got, want)


def test_the_alias_probe_reads_shards_and_views():
    geometry = (((4, 1024), np.int32, 0),)
    (copied_host,) = _at(16)(geometry)
    (aliased_host,) = _at(0)(geometry)
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    dealt = jax.device_put(
        np.swapaxes(copied_host.reshape(4, 4, 256), 0, 1),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("shards")))
    assert not fwp._aliases((copied_host,),
                            (jax.device_put(copied_host), dealt))
    # a device buffer anywhere inside the host array's memory aliases it
    assert fwp._aliases((aliased_host,), (jax.device_put(aliased_host[1:]),))

    class _Unreadable:
        @property
        def addressable_shards(self):
            raise NotImplementedError

    assert fwp._aliases((copied_host,), (_Unreadable(),))


def test_only_the_newest_geometries_keep_sets():
    pool = fwp._StagingPool()
    geoms = [(((t, 256), np.int32, None),) for t in (32, 16, 4, 1)]
    held = [pool.take(g) for g in geoms[:1] for _ in range(2)]
    for lease in held:
        lease.release()
    assert len(pool._free[geoms[0]]) == 2
    again = pool.take(geoms[0])
    assert again.reused and again.arrays is held[1].arrays
    for g in geoms[1:]:
        pool.take(g).release()
    assert list(pool._free) == geoms[-pool.KEEP:]
    again.release()                 # its geometry was dropped: not kept
    assert geoms[0] not in pool._free
    fresh = pool.take(geoms[-1])
    assert fresh.reused and not pool.take(geoms[-1]).reused


@pytest.mark.parametrize("payload", ["record", "key_ids"])
def test_the_mesh_deals_pooled_sets_and_matches_fresh(copied, payload):
    groups = _groups(5, SIZES)
    prologue, as_steps = ((PROLOGUE, _records) if payload == "record"
                          else (None, _key_ids))
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    pooled = ShardedFusedPipeline(mesh, ASSIGNER, "sum", prologue=prologue,
                                  **GEOM)
    fresh = ShardedFusedPipeline(mesh, ASSIGNER, "sum", prologue=prologue,
                                 **GEOM)
    fresh.planner._staging = _FreshPool()
    got, link = _run(pooled, groups, as_steps)
    want, _ = _run(fresh, groups, as_steps)
    _same(got, want)
    assert (link["stagingSetsAllocated"], link["stagingSetsReused"]) == (4, 4)
    assert not pooled.planner._staging._aliased


def test_the_link_counters_count_sets():
    clock = StageClock()
    arrays = (np.zeros(4, np.int32),)
    clock.staged(arrays, events=4, reused=False)
    clock.staged(arrays, events=4, reused=True)
    clock.staged(arrays, events=4, reused=True)
    clock.staged(arrays, events=0)          # a group with no staging set
    link = clock.link()
    assert (link["stagingSetsAllocated"], link["stagingSetsReused"]) == (1, 2)
