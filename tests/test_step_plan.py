"""A data step is planned once: the normalizer proves from a batch's two
timestamp extremes that no record is late, none lies beyond the ring and the
batch spans fewer than NSB slices, and the step carries its finished slice
plan to staging (`FusedWindowPipeline.plan_scalar`). Every other batch takes
the masked path. Here the same seeded streams run through both — the scalar
plan where it engages, and the masked path forced for every step — and what
staging hands the device must be equal array for array: `srel_h` / `idx_h`,
`smin_pos`, the fire / purge plan, the planned fires, the staged record
fields, the late and held-back counters, and the rows the job emits.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.core.time import MAX_WATERMARK, MIN_WATERMARK
from flink_tpu.metrics.task_io import StageClock
from flink_tpu.runtime.fused_window_operator import FusedWindowOperator
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    StepPlan,
    TracedPrologue,
)

N = 48            # records per batch
K = 64
GEOM = dict(key_capacity=K, superbatch_steps=4, nsb=4, num_slices=16,
            chunk=16)
# one prologue object for every run: the chained executables are cached on it
PROLOGUE = TracedPrologue(
    transforms=(("filter", lambda col: col[:, 2] < 0.5),),
    key_fn=lambda col: col[:, 0].astype(jnp.int32),
    value_fn=lambda col: col[:, 1])


# ---------------------------------------------------------------------------
# streams: [("data", ts int64[n]) | ("wm", watermark)]
# ---------------------------------------------------------------------------

def _in_order(rng, start=0, batches=12):
    out = []
    for k in range(batches):
        t0 = start + k * 300
        out.append(("data", np.sort(rng.integers(t0, t0 + 300, N))))
        out.append(("wm", t0 - 50))
    return out


def _jitter_inside_one_slice(rng):
    out = []
    for k in range(8):
        out.append(("data", rng.integers(k * 1000 + 100, k * 1000 + 900, N)))
        out.append(("wm", k * 1000))
    return out


def _straddles_a_slice_boundary(rng):
    out = []
    for k in range(8):
        out.append(("data", rng.integers(k * 1000 + 700, k * 1000 + 1300, N)))
        out.append(("wm", k * 1000 + 500))
    return out


def _late_records(rng):
    out = _in_order(rng, batches=10)          # watermark now 2650
    ts = rng.integers(2700, 3000, N)
    ts[::7] = rng.integers(0, 900, len(ts[::7]))      # slice 0: purged
    out += [("data", ts), ("wm", 2900)]
    out += [("data", rng.integers(100, 1900, N)), ("wm", 3000)]   # all late
    return out + _in_order(rng, start=3000, batches=4)


def _far_future_record(rng):
    out = _in_order(rng, batches=4)
    ts = rng.integers(1200, 1500, N)
    ts[5], ts[17] = 40_000, 41_500            # beyond the 16-slice ring
    out += [("data", ts), ("wm", 1300)]
    out += _in_order(rng, start=1500, batches=6)
    # the ring opens as the watermark passes; the held records re-enter
    return out + [("wm", 20_000), ("wm", 36_000), ("wm", 39_000)]


def _span_of_nsb_or_more_slices(rng):
    out = _in_order(rng, batches=3)
    out += [("data", rng.integers(1000, 10_000, N)), ("wm", 900)]   # 9 slices
    out += [("data", rng.integers(1000, 5000, N)), ("wm", 1000)]    # 4 = NSB
    return out + _in_order(rng, start=5000, batches=3)


def _negative_timestamps(rng):
    out = []
    for k in range(10):
        t0 = -5000 + k * 650
        out.append(("data", rng.integers(t0, t0 + 900, N)))
        out.append(("wm", t0 - 100))
    return out


def _empty_step_between_data_steps(rng):
    out = _in_order(rng, batches=3)
    # two advances in a row: the second is a step of its own, not a rider;
    # a jump over many windows stages several fire-bounded empty steps
    out += [("wm", 1500), ("wm", 2500), ("data", np.empty(0, np.int64)),
            ("data", rng.integers(9000, 9300, N)), ("wm", 8900)]
    return out + _in_order(rng, start=9300, batches=3)


def _everything(rng):
    """One stream through every branch, for the flavours that run once."""
    out = _straddles_a_slice_boundary(rng)            # watermark 7500
    ts = rng.integers(8000, 8300, N)
    ts[::5] = rng.integers(0, 5000, len(ts[::5]))     # late
    ts[3] = 60_000                                    # far future
    out += [("data", ts), ("wm", 7900)]
    out += [("data", rng.integers(8000, 17_000, N)), ("wm", 8000)]  # split
    out += [("wm", 12_000), ("wm", 16_500)]
    out += _in_order(rng, start=17_000, batches=5)
    return out + [("wm", 50_000), ("wm", 58_000)]


STREAMS = {
    "in_order": _in_order,
    "jitter_inside_one_slice": _jitter_inside_one_slice,
    "straddles_a_slice_boundary": _straddles_a_slice_boundary,
    "late_records": _late_records,
    "far_future_record": _far_future_record,
    "span_of_nsb_or_more_slices": _span_of_nsb_or_more_slices,
    "negative_timestamps_and_offset": _negative_timestamps,
    "empty_step_between_data_steps": _empty_step_between_data_steps,
    "everything": _everything,
}
OFFSET_WINDOW = TumblingEventTimeWindows.of(1000, 300)


def _assigner(stream: str):
    if stream == "negative_timestamps_and_offset":
        return OFFSET_WINDOW
    if stream == "jitter_inside_one_slice":
        return SlidingEventTimeWindows.of(2000, 1000)
    return TumblingEventTimeWindows.of(1000)


# ---------------------------------------------------------------------------
# flavours: which operator, which staging function carries the plan
# ---------------------------------------------------------------------------

def _operator(flavour: str, stream: str) -> FusedWindowOperator:
    if flavour == "host_keyed":
        return FusedWindowOperator(_assigner(stream), "sum", **GEOM)
    if flavour == "shared_partials":
        return FusedWindowOperator(
            None, "sum", prologue=PROLOGUE,
            assigners=[TumblingEventTimeWindows.of(1000),
                       TumblingEventTimeWindows.of(2000)], **GEOM)
    mesh = None
    if flavour == "sharded_planner":
        mesh = Mesh(np.array(jax.devices()[:2]), ("shards",))
    return FusedWindowOperator(_assigner(stream), "sum", prologue=PROLOGUE,
                               mesh=mesh, **GEOM)


def _drive(flavour: str, stream: str, seed: int):
    """Run one seeded stream; returns everything staging handed the device,
    the counters along the way, the rows, and the clock's link row."""
    rng = np.random.default_rng(seed)
    events = STREAMS[stream](rng)
    op = _operator(flavour, stream)
    clock = StageClock()
    op.attach_stage_clock(clock)
    planner = getattr(op.pipe, "_planner", op.pipe)   # the mesh plans in one
    staged, pushed = [], []

    def fires_of(fires):
        return [dataclasses.astuple(f) for f in fires]

    fill = planner._fill

    def record_fill(payload, steps, wms):
        filled = fill(payload, steps, wms)
        xs_h, lanes, _layout, plan_np, fires, _lease = filled
        live = xs_h[0] >= 0         # srel_h / idx_h
        staged.append(("raw" if payload.record else "keyed", xs_h[0].copy(),
                       [a.copy() for a in plan_np], fires_of(fires),
                       [a[live] for a in xs_h[1:lanes]]))
        return filled

    planner._fill = record_fill
    push = op._push_steps

    def count_pushed(steps):
        pushed.extend(s for s in steps if len(s.ts))
        push(steps)

    op._push_steps = count_pushed

    rows, counters = [], []
    for kind, arg in events + [("wm", MAX_WATERMARK - 1)]:
        if kind == "wm":
            op.process_watermark(arg)
        else:
            ts = np.asarray(arg, np.int64)
            rec = np.stack([rng.integers(0, K, len(ts)),
                            rng.integers(1, 9, len(ts)),
                            rng.integers(0, 2, len(ts))],
                           axis=1).astype(np.float32)
            if flavour == "host_keyed":
                op.process_batch(rec[:, 0].astype(np.int64), rec[:, 1], ts)
            else:
                op.process_raw_batch(rec, ts)
        counters.append((op.norm.num_future_held, len(op.norm._future)))
        lanes = ([op.drain_output()] if op.spec_outputs is None else
                 [op.drain_spec_output(i) for i in range(len(op.spec_outputs))])
        rows.extend((i, k, w.start, v) for i, lane in enumerate(lanes)
                    for k, w, v, _ts in lane)
    return dict(pushed=pushed, fills=staged, counters=counters, rows=rows,
                late=op.num_late_records_dropped, link=clock.link())


def _force_masked(monkeypatch):
    """Every step through the masked path: the normalizer's and staging's."""
    monkeypatch.setattr(FusedWindowPipeline, "plan_scalar",
                        lambda self, ts, wm, limit_of=None: None)


def _assert_same_staging(got, ref):
    assert len(got["fills"]) == len(ref["fills"]) > 0
    for g, r in zip(got["fills"], ref["fills"]):
        assert g[0] == r[0]
        np.testing.assert_array_equal(g[1], r[1])       # srel_h / idx_h
        assert g[1].dtype == r[1].dtype == np.int32
        for a, b in zip(g[2], r[2]):    # smin_pos, fire_*, purge_mask
            np.testing.assert_array_equal(a, b)
        assert g[3] == r[3]                             # the planned fires
        for a, b in zip(g[4], r[4]):                    # the live lanes' data
            np.testing.assert_array_equal(a, b)
    assert got["late"] == ref["late"]
    assert got["counters"] == ref["counters"]
    assert sorted(got["rows"]) == sorted(ref["rows"]) and got["rows"]


CASES = ([("traced_chain", s) for s in STREAMS]
         + [("host_keyed", s) for s in STREAMS]
         + [("shared_partials", "everything"),
            ("shared_partials", "in_order"),
            ("sharded_planner", "everything"),
            ("sharded_planner", "straddles_a_slice_boundary")])


@pytest.mark.parametrize("flavour,stream", CASES,
                         ids=[f"{f}-{s}" for f, s in CASES])
def test_scalar_plan_equals_the_masked_path(flavour, stream, monkeypatch):
    seed = 2700 + sorted(STREAMS).index(stream)
    got = _drive(flavour, stream, seed)
    _force_masked(monkeypatch)
    ref = _drive(flavour, stream, seed)
    _assert_same_staging(got, ref)

    link, rlink = got["link"], ref["link"]
    data_steps = len(got["pushed"])
    assert len(ref["pushed"]) == data_steps     # the same steps either way
    assert (link["stepsPlannedScalar"] + link["stepsPlannedMasked"]
            == data_steps)
    assert (rlink["stepsPlannedScalar"], rlink["stepsPlannedMasked"]) == \
        (0, data_steps)
    # a step leaves the normalizer with a plan exactly when two scalars
    # prove it; the forced run plans nothing there
    carried = sum(s.plan is not None for s in got["pushed"])
    assert carried > 0 and not any(s.plan for s in ref["pushed"])
    assert all(isinstance(s.plan, StepPlan) and not s.plan.masked
               for s in got["pushed"] if s.plan is not None)

    batches = sum(1 for kind, a in STREAMS[stream](np.random.default_rng(0))
                  if kind == "data" and len(a))
    held = max(c[0] for c in got["counters"])
    if stream in ("in_order", "jitter_inside_one_slice",
                  "straddles_a_slice_boundary",
                  "negative_timestamps_and_offset",
                  "empty_step_between_data_steps"):
        # the benchmark's kind of stream: every data step from two scalars
        assert carried == data_steps == batches
        assert link["stepsPlannedMasked"] == 0 and got["late"] == 0
        assert held == 0
    if stream in ("late_records", "everything"):
        assert got["late"] > 0 and link["stepsPlannedMasked"] >= 1
    if stream in ("far_future_record", "everything"):
        assert held > 0 and got["counters"][-1] == (0, 0)
    if stream in ("span_of_nsb_or_more_slices", "everything"):
        assert data_steps > batches             # the split's sub-steps
    if stream == "straddles_a_slice_boundary":
        # both forms of srel: the int 0 inside one slice, an int32 array
        # across a boundary
        kinds = {type(s.plan.srel) for s in got["pushed"]}
        assert np.ndarray in kinds
        assert all(s.plan.srel.dtype == np.int32 for s in got["pushed"]
                   if isinstance(s.plan.srel, np.ndarray))


# ---------------------------------------------------------------------------
# the plan functions themselves
# ---------------------------------------------------------------------------

def _pipe(assigner=None, **kw):
    geom = dict(key_capacity=K, nsb=4, num_slices=16, chunk=16)
    geom.update(kw)
    return FusedWindowPipeline(
        assigner or TumblingEventTimeWindows.of(1000), "count", **geom)


@pytest.mark.parametrize("assigner", [
    TumblingEventTimeWindows.of(1000), OFFSET_WINDOW,
    SlidingEventTimeWindows.of(3000, 1000, 700),
    # NSB * g beyond int32: the relative index is divided in int64
    TumblingEventTimeWindows.of(1 << 30)],
    ids=["tumbling", "offset", "sliding_offset", "wide_slices"])
def test_plan_scalar_is_the_masked_plan_without_the_masks(assigner):
    pipe = _pipe(assigner)
    rng = np.random.default_rng(27)
    g = pipe.g
    for lo, hi in [(-3 * g, -3 * g + g // 2), (-g // 3, g // 3),
                   (5 * g + 1, 8 * g - 1), (2 * g, 2 * g + 1)]:
        ts = rng.integers(lo, hi, 257).astype(np.int64)
        assert pipe.slice_span(ts) == (int(pipe._slice_of(ts).min()),
                                       int(pipe._slice_of(ts).max()))
        scalar = pipe.plan_scalar(ts, MIN_WATERMARK)
        masked = pipe.plan_masked(ts, MIN_WATERMARK)
        assert (scalar.smin, scalar.smax, scalar.late, scalar.masked) == \
            (masked.smin, masked.smax, 0, False)
        assert masked.masked and masked.srel.dtype == np.int32
        np.testing.assert_array_equal(
            np.broadcast_to(scalar.srel, ts.shape), masked.srel)
        step = pipe.plan_step(ts, MIN_WATERMARK)      # picks the scalar form
        assert not step.masked and np.array_equal(step.srel, scalar.srel)


def test_plan_scalar_declines_what_two_scalars_cannot_prove():
    pipe = _pipe()
    ts = np.array([5100, 5900, 6100], np.int64)
    assert pipe.plan_scalar(ts, MIN_WATERMARK) is not None
    # the span: NSB slices or more
    wide = np.array([5100, 9100], np.int64)
    assert pipe.plan_scalar(wide, MIN_WATERMARK) is None
    # a late record: the watermark 5999 fired the window of slice 5
    assert pipe.plan_scalar(ts, 5999) is None
    assert pipe.plan_scalar(ts, 5998) is not None
    masked = pipe.plan_step(ts, 5999)
    assert masked.masked and masked.late == 2
    assert (masked.smin, masked.smax) == (6, 6)
    np.testing.assert_array_equal(masked.srel, [-1, -1, 0])
    # the normalizer's hold-back bound
    assert pipe.plan_scalar(ts, MIN_WATERMARK, lambda smin: 6) is None
    assert pipe.plan_scalar(ts, MIN_WATERMARK, lambda smin: 7) is not None
    # every record late: nothing to observe, the whole row is -1
    all_late = pipe.plan_step(ts, 9000)
    assert all_late == StepPlan(-1, None, None, 3, True)


def test_a_bare_step_is_planned_at_staging_and_a_wrong_plan_is_caught():
    """Direct callers pass bare tuples: staging plans them itself, by the
    same functions. A plan that claims a span the frontier has purged trips
    the plan cursor's check."""
    pipe = FusedWindowPipeline(TumblingEventTimeWindows.of(1000), "count",
                               key_capacity=K, nsb=4, num_slices=16, chunk=16,
                               prologue=PROLOGUE)
    clock = StageClock()
    pipe.attach_stage_clock(clock)
    rec = np.zeros((3, 3), np.float32)
    ts = np.array([5100, 5900, 6100], np.int64)
    (srel_h, *_raw), _lanes, _layout, plan_np, _fires, _lease = pipe._fill(
        pipe._payload, [(rec, None, ts), (rec[:0], None, ts[:0])],
        [5999, 5999])
    np.testing.assert_array_equal(srel_h[0, :3], [0, 0, 1])
    assert (srel_h[0, 3:] == -1).all() and (srel_h[1] == -1).all()
    assert plan_np[0][0] == 5 % 16
    assert clock.link()["stepsPlannedScalar"] == 1
    # slice 5 was purged by the watermark above: a plan that still claims it
    stale = StepPlan(0, 5, 5)
    with pytest.raises(AssertionError, match="late-drop"):
        pipe._fill(pipe._payload, [(rec, None, ts, stale)], [6000])


# ---------------------------------------------------------------------------
# the counters of the stage clock
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flavour", ["traced_chain", "host_keyed"])
@pytest.mark.parametrize("stream", ["in_order", "late_records"])
def test_link_says_how_many_steps_were_planned_from_two_scalars(
        flavour, stream):
    got = _drive(flavour, stream, 27)
    link = got["link"]
    data_steps = len(got["pushed"])
    assert data_steps > 0
    assert (link["stepsPlannedScalar"] + link["stepsPlannedMasked"]
            == data_steps)
    if stream == "in_order":
        assert link["stepsPlannedMasked"] == 0
        assert link["stepsPlannedScalar"] == data_steps
    else:
        # the two batches with late records, and nothing else
        assert link["stepsPlannedMasked"] == 2
        assert link["stepsPlannedScalar"] == data_steps - 2
        assert got["late"] > N
