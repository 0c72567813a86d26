"""A dispatch's record lanes written by one native call (`_NativeLanes`).

`_fill` hands the record steps it can to `stage_record_lanes`
(native/flink_tpu_native.cpp), one call a dispatch, and stages every other
step with numpy as before. Held here: the staged set the native call
leaves equals the numpy path's byte for byte over the live lanes, srel and
its dead tails, for a scalar plan, a step that straddles a slice boundary
(an srel array), a masked plan (late records' srel -1), empty steps and a
watermark-only group; the steps that must keep numpy (a record that
narrows, fields that are not unit-strided, no column layout, staged
timestamps) fall back and still match; the mesh deals a native fill; a missing native library stages all
by numpy; `stepsStagedNative` + `stepsStagedNumpy` count the steps that
carried records; each writer count gives the same bytes.
"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.metrics.task_io import StageClock
from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
from flink_tpu.runtime import fused_window_pipeline as fwp
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    TracedPrologue,
)
from flink_tpu.utils import native_bridge

K, CHUNK, SLIDE = 64, 256, 250
ASSIGNER = SlidingEventTimeWindows.of(1000, SLIDE)
GEOM = dict(key_capacity=K, num_slices=16, nsb=4, fires_per_step=4,
            out_rows=16, chunk=CHUNK)
#: key in field 0, value in field 1, field 2 filters; field 3 never read
PROLOGUE = TracedPrologue(
    transforms=(("filter", lambda col: col[:, 2] < 0.75),),
    key_fn=lambda col: col[:, 0].astype(jnp.int32),
    value_fn=lambda col: col[:, 1])
#: the same job reading its timestamps: they are staged
TS_PROLOGUE = TracedPrologue(
    transforms=(("map_ts", lambda col, ts: col),
                ("filter", lambda col: col[:, 2] < 0.75)),
    key_fn=lambda col: col[:, 0].astype(jnp.int32),
    value_fn=lambda col: col[:, 1])
#: a scalar record per event: no column layout, the record staged whole
SCALAR_PROLOGUE = TracedPrologue(
    transforms=(), key_fn=lambda col: (col % K).astype(jnp.int32),
    value_fn=None)


@pytest.fixture(autouse=True)
def _native_library():
    if native_bridge.get_lib() is None:
        pytest.skip(f"native library unavailable: {native_bridge.load_error()}")


def _rec(rng, n, dtype=np.float32):
    return np.stack([rng.integers(0, K, n), rng.integers(1, 99, n),
                     rng.random(n), rng.random(n) * 1e3],
                    axis=1).astype(dtype)


def _groups(seed, shape, *, offset=0, late=(), record=_rec):
    """One group per entry of `shape` (records of each step: 0 = an empty
    step), event time advancing 250 ms a step from `offset` ms into the
    step's slice (an offset > 0 straddles two slices), the watermark
    100 ms behind; steps named in `late` (group, step) carry one record
    from the first slice, late by then."""
    rng = np.random.default_rng(seed)
    groups, t = [], 0
    for g, sizes in enumerate(shape):
        steps, wms = [], []
        for s, n in enumerate(sizes):
            ts = (t * SLIDE + offset + rng.integers(0, SLIDE, n)).astype(
                np.int64)
            if (g, s) in late:
                ts[0] = 0
            steps.append((record(rng, n), None, ts))
            wms.append((t + 1) * SLIDE - 100)
            t += 1
        groups.append((steps, wms))
    return groups


def _staged_runs(pipe, groups, native, monkeypatch):
    """Stage and dispatch each group (one in flight): each group's host
    lane arrays as staged, every fire, the link counters."""
    clock = StageClock()
    pipe.attach_stage_clock(clock)
    sets, fired, ring = [], [], collections.deque()
    with monkeypatch.context() as m:
        if not native:
            m.setattr(native_bridge, "get_lib", lambda: None)
        for steps, wms in groups:
            staged = pipe.stage(steps, wms)
            sets.append(None if staged.lease is None else
                        tuple(a.copy() for a in staged.lease.arrays))
            ring.append(pipe.dispatch(staged, defer=True))
            while len(ring) > 1:
                fired.extend(ring.popleft().resolve())
        while ring:
            fired.extend(ring.popleft().resolve())
    rows = [(w.start, np.asarray(c), np.asarray(f["sum"]))
            for w, c, f in fired]
    return sets, rows, clock.link()


def _same_sets(groups, got, want):
    """srel whole rows, every other array over each step's live lanes."""
    assert len(got) == len(want) == len(groups)
    for (steps, _wms), a, b in zip(groups, got, want):
        if a is None or b is None:
            assert a is b is None
            continue
        assert len(a) == len(b)
        assert [x.dtype for x in a] == [y.dtype for y in b]
        np.testing.assert_array_equal(a[0], b[0])
        for t, step in enumerate(steps):
            n = len(step[2])
            for x, y in zip(a[1:], b[1:]):
                assert x[t, :n].tobytes() == y[t, :n].tobytes()


def _same_rows(a, b):
    assert len(a) == len(b) and a
    for (wa, ca, fa), (wb, cb, fb) in zip(a, b):
        assert wa == wb
        np.testing.assert_array_equal(ca, cb)
        np.testing.assert_array_equal(fa.view(np.uint32), fb.view(np.uint32))


def _record_steps(groups):
    return sum(1 for steps, _w in groups for s in steps if len(s[2]))


def _both(monkeypatch, groups, make):
    got = _staged_runs(make(), groups, True, monkeypatch)
    want = _staged_runs(make(), groups, False, monkeypatch)
    _same_sets(groups, got[0], want[0])
    _same_rows(got[1], want[1])
    assert want[2]["stepsStagedNative"] == 0
    assert want[2]["stepsStagedNumpy"] == _record_steps(groups)
    return got[2]


def _pipe(prologue=PROLOGUE):
    return lambda: FusedWindowPipeline(ASSIGNER, "sum", prologue=prologue,
                                       backend="xla", **GEOM)


#: a full group, one whose steps shrink (stale lanes past them in the
#: reused set), empty steps among records, a watermark-only group, a T=1
#: flush
SHAPE = [[900] * 6, [900, 700, 0, 520, 900, 0], [0, 0, 0], [40]]


@pytest.mark.parametrize("offset", [0, 125], ids=["scalar", "straddle"])
def test_native_lanes_match_numpy_byte_for_byte(monkeypatch, offset):
    groups = _groups(3, SHAPE, offset=offset)
    link = _both(monkeypatch, groups, _pipe())
    assert link["stepsStagedNative"] == _record_steps(groups)
    assert link["stepsStagedNumpy"] == 0


def test_a_straddling_step_is_planned_with_an_srel_array(monkeypatch):
    groups = _groups(4, [[900] * 3], offset=125)
    pipe = _pipe()()
    taken = []
    take = fwp._NativeLanes.take

    def spy(self, t, n, step, plan):
        taken.append(isinstance(plan.srel, np.ndarray))
        return take(self, t, n, step, plan)

    monkeypatch.setattr(fwp._NativeLanes, "take", spy)
    staged = pipe.stage(*groups[0])
    assert taken == [True] * 3
    srel = staged.lease.arrays[0]
    assert set(np.unique(srel[:, :900])) == {0, 1}
    assert (srel[:, 900:] == -1).all()


def test_a_masked_plan_goes_native_and_matches(monkeypatch):
    groups = _groups(5, [[900] * 4] * 4, late={(2, 1), (3, 3)})
    link = _both(monkeypatch, groups, _pipe())
    assert link["stepsPlannedMasked"] == 2
    assert link["stepsStagedNumpy"] == 0
    assert link["stepsStagedNative"] == _record_steps(groups)


@pytest.mark.parametrize("dtype", [np.float64, np.int64])
def test_a_narrowing_record_falls_back_and_matches(monkeypatch, dtype):
    groups = _groups(6, SHAPE, record=lambda r, n: _rec(r, n, dtype))
    link = _both(monkeypatch, groups, _pipe())
    assert link["stepsStagedNative"] == 0
    assert link["stepsStagedNumpy"] == _record_steps(groups)


def test_fields_that_are_not_unit_strided_fall_back(monkeypatch):
    """A record read from a wider array: every other column of it (fields
    8 bytes apart) keeps numpy; every other ROW of it (unit-strided fields,
    rows of any stride) goes native; a Fortran-order record keeps numpy."""
    def wide(r, n):
        return np.repeat(_rec(r, n), 2, axis=1)[:, ::2]

    def sparse_rows(r, n):
        return np.repeat(_rec(r, n), 2, axis=0)[::2]

    def fortran(r, n):
        return np.asfortranarray(_rec(r, n))

    for record, native in ((wide, False), (sparse_rows, True),
                           (fortran, False)):
        groups = _groups(7, SHAPE, record=record)
        link = _both(monkeypatch, groups, _pipe())
        steps = _record_steps(groups)
        assert (link["stepsStagedNative"], link["stepsStagedNumpy"]) == \
            ((steps, 0) if native else (0, steps))


def test_a_record_without_a_column_layout_keeps_numpy(monkeypatch):
    groups = _groups(8, SHAPE, record=lambda r, n: r.integers(
        0, 10 * K, n).astype(np.float32))
    link = _both(monkeypatch, groups, _pipe(SCALAR_PROLOGUE))
    assert link["stepsStagedNative"] == 0


def test_staged_timestamps_keep_numpy(monkeypatch):
    groups = _groups(9, SHAPE)
    link = _both(monkeypatch, groups, _pipe(TS_PROLOGUE))
    assert link["stepsStagedNative"] == 0


def test_the_mesh_deals_a_native_fill(monkeypatch):
    groups = _groups(10, SHAPE, offset=125)
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    link = _both(monkeypatch, groups, lambda: ShardedFusedPipeline(
        mesh, ASSIGNER, "sum", prologue=PROLOGUE, **GEOM))
    assert link["stepsStagedNative"] == _record_steps(groups)
    assert link["stepsStagedNumpy"] == 0


def test_without_the_native_library_every_step_is_numpy(monkeypatch):
    """`_both`'s reference side is this: `get_lib()` None."""
    groups = _groups(11, SHAPE)
    pipe = _pipe()()
    _sets, rows, link = _staged_runs(pipe, groups, False, monkeypatch)
    assert rows
    assert (link["stepsStagedNative"], link["stepsStagedNumpy"]) == \
        (0, _record_steps(groups))


def test_the_counters_sum_to_the_steps_that_carried_records(monkeypatch):
    groups = _groups(12, SHAPE + [[900, 0, 900]], late={(1, 3)})
    _sets, _rows, link = _staged_runs(_pipe()(), groups, True, monkeypatch)
    assert link["stepsStagedNative"] + link["stepsStagedNumpy"] == \
        _record_steps(groups) == 13
    assert link["stepsPlannedMasked"] == 1
    assert link["stepsStagedNumpy"] == 0
    # a key-id job stages key ids by numpy; a step without records is a
    # dead row whichever path writes it, and counts for neither
    ids = [[(s[0][:, 0].astype(np.int32), s[0][:, 1], s[2]) for s in steps]
           for steps, _w in groups]
    pipe = FusedWindowPipeline(ASSIGNER, "sum", backend="xla", **GEOM)
    clock = StageClock()
    pipe.attach_stage_clock(clock)
    for steps, (_s, wms) in zip(ids, groups):
        pipe.dispatch(pipe.stage(steps, wms), defer=True).resolve()
    assert (clock.link()["stepsStagedNative"],
            clock.link()["stepsStagedNumpy"]) == (0, 13)


@pytest.mark.parametrize("writers", [1, 2, 3, 4, 8])
def test_every_writer_count_stages_the_same_bytes(monkeypatch, writers):
    """Contiguous step ranges over `writers` threads: more writers than
    steps, ranges of unequal length, a straddling step's srel array."""
    monkeypatch.setattr(fwp, "_LANE_WRITERS", writers)
    groups = _groups(13, [[900] * 7, [900, 0, 300, 900], [40]], offset=125)
    link = _both(monkeypatch, groups, _pipe())
    assert link["stepsStagedNative"] == _record_steps(groups)


def test_the_writer_count_is_capped_by_the_cores():
    import os

    assert 1 <= fwp._LANE_WRITERS <= len(os.sched_getaffinity(0))
