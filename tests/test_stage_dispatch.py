"""One staging loop and one dispatch path under every window program.

`FusedWindowPipeline.stage` + `.dispatch` serve five compiled programs: the
pallas kernel and the XLA superscan over key ids, the chained superscan over
a record, and the two sharded programs of the mesh. The same seeded steps go
through each; what staging wrote on the host (the payload's arrays, pad
tails, late lanes, `smin_pos`) is held to a numpy reference written out
here, what reached the device to the placement's own layout, and the
resolved fires to the per-record oracle the pipeline tests use
(OracleWindowOperator). The fire / purge plan depends on timestamps alone,
so every program's plan arrays must equal the plain XLA pipeline's.

Then the two things `dispatch` decides besides the program: a watermark-only
group of a record job runs the classic program, and latency mode's step
groups (`readback_steps` < T) resolve byte for byte as the one-group
dispatch does.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.core.time import MIN_WATERMARK
from flink_tpu.ops.aggregators import resolve
from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    TracedPrologue,
)
from flink_tpu.runtime.oracle_window_operator import OracleWindowOperator

PROGRAMS = ["pallas_superscan", "fused_superscan", "fused_chained_superscan",
            "sharded_superscan", "sharded_chained_superscan"]
RECORD = {"fused_chained_superscan", "sharded_chained_superscan"}
MESH = {"sharded_superscan", "sharded_chained_superscan"}

K, NSB, S, CHUNK, N_DEV = 128, 4, 16, 1024, 4
SIZE, SLIDE, STEP_MS, LAG_MS = 1000, 250, 250, 300
ASSIGNER = SlidingEventTimeWindows.of(SIZE, SLIDE)
GEOM = dict(key_capacity=K, num_slices=S, nsb=NSB, fires_per_step=4,
            out_rows=16, chunk=CHUNK)
#: key in field 0, value in field 1; field 2 is never read, so never staged
PROLOGUE = TracedPrologue(
    transforms=(), key_fn=lambda col: col[:, 0].astype(jnp.int32),
    value_fn=lambda col: col[:, 1])
T = 8


class _Calls:
    """A CompileTracker stand-in: which program each dispatch ran, at
    which T."""

    def __init__(self):
        self.calls = []

    def call(self, program, fn, args, signature):
        self.calls.append((program, signature["T"]))
        return fn(*args)


def _pipe(program):
    if program in MESH:
        pipe = ShardedFusedPipeline(
            Mesh(np.array(jax.devices()[:N_DEV]), ("shards",)), ASSIGNER,
            "sum", prologue=PROLOGUE if program in RECORD else None, **GEOM)
    else:
        pipe = FusedWindowPipeline(
            ASSIGNER, "sum", prologue=PROLOGUE if program in RECORD else None,
            backend="pallas" if program == "pallas_superscan" else "xla",
            pallas_interpret=True, **GEOM)
    calls = _Calls()
    pipe.attach_device_stats(calls, phase_counters=False)
    return pipe, calls


def _groups(seed=28):
    """Two groups of T steps: (records [n, 3] f32, ts int64[n]) per step
    and the watermark after it. Out of order inside LAG_MS, one empty
    step, a few records far behind the watermark (late: dropped and
    counted), one step whose records are all late."""
    rng = np.random.default_rng(seed)
    groups = []
    for g in range(2):
        steps, wms = [], []
        for s in range(T):
            t = g * T + s
            n = 0 if t == 3 else int(rng.integers(200, 400))
            ts = t * STEP_MS + rng.integers(0, STEP_MS, n) \
                - rng.integers(0, LAG_MS, n)
            if t == 6:
                ts[:5] = 10                 # long purged
            if t == 12:
                ts[:] = rng.integers(0, 200, n)
            rec = np.stack([rng.integers(0, K, n), rng.integers(1, 9, n),
                            rng.integers(0, 2, n)], axis=1).astype(np.float32)
            steps.append((rec, np.maximum(ts, 0).astype(np.int64)))
            wms.append((t + 1) * STEP_MS - LAG_MS)
        groups.append((steps, wms))
    return groups


def _steps_for(program, steps):
    if program in RECORD:
        return [(rec, None, ts) for rec, ts in steps]
    return [(rec[:, 0].astype(np.int32), rec[:, 1], ts) for rec, ts in steps]


def _min_live_slice(wm):
    """First slice a record may still land in at watermark `wm`."""
    if wm <= MIN_WATERMARK:
        return None
    return (wm + 1 - SIZE) // SLIDE + 1     # slide == slice: one per window


def _expected_lanes(rec, ts, wm_before):
    """numpy: (srel int32[n], idx int32[n], vals f32[n], smin | None)."""
    s_abs = ts // SLIDE
    floor = _min_live_slice(wm_before)
    keep = np.ones(len(ts), bool) if floor is None else s_abs >= floor
    if not keep.any():
        dead = np.full(len(ts), -1, np.int32)
        return dead, dead, np.zeros(len(ts), np.float32), None
    smin = int(s_abs[keep].min())
    srel = np.where(keep, s_abs - smin, -1).astype(np.int32)
    idx = np.where(keep, rec[:, 0].astype(np.int32) * NSB + srel, -1)
    return srel, idx.astype(np.int32), np.where(keep, rec[:, 1], 0.0), smin


def _oracle(groups):
    op = OracleWindowOperator(ASSIGNER, resolve("sum").python_equivalent())
    for steps, wms in groups:
        for (rec, ts), wm in zip(steps, wms):
            for row, t in zip(rec, ts):
                op.process_record(int(row[0]), float(row[1]), int(t))
            op.process_watermark(wm)
    rows = {(key, w.start): v for key, w, v, _ts in op.drain_output()}
    return rows, op.num_late_records_dropped


def _rows(fired):
    out = {}
    for window, counts, fields in fired:
        for k in np.flatnonzero(np.asarray(counts) > 0):
            out[(int(k), window.start)] = float(fields["sum"][k])
    return out


def _host_view(program, a, lanes_shape):
    """A staged device array back in the [T, B] form staging filled."""
    a = np.asarray(a)
    if program in MESH:        # [n, T, Bs]: lanes dealt over source shards
        return np.swapaxes(a, 0, 1).reshape(lanes_shape)
    return a.reshape(lanes_shape)           # pallas: a flat [T*B] stream


def _fill_recorder(pipe):
    planner = getattr(pipe, "_planner", pipe)     # the mesh plans in one
    fills, fill = [], planner._fill

    def record(payload, steps, wms):
        out = fill(payload, steps, wms)
        fills.append(out)
        return out

    planner._fill = record
    return fills


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_program_is_staged_by_one_loop_and_fires_at_parity(program):
    groups = _groups()
    pipe, calls = _pipe(program)
    fills = _fill_recorder(pipe)
    plain = FusedWindowPipeline(ASSIGNER, "sum", backend="xla", **GEOM)
    fired, wm_before = [], MIN_WATERMARK
    for steps, wms in groups:
        staged = pipe.stage(_steps_for(program, steps), wms)
        xs_h, lanes, layout, plan_np, fires, _lease = fills.pop()
        assert not fills and lanes == len(xs_h) == len(staged.xs)
        B = xs_h[0].shape[1]
        assert B == CHUNK and xs_h[0].dtype == np.int32
        for t, (rec, ts) in enumerate(steps):
            n = len(ts)
            srel, idx, vals, smin = _expected_lanes(rec, ts, wm_before)
            np.testing.assert_array_equal(
                xs_h[0][t, :n], srel if program in RECORD else idx)
            assert (xs_h[0][t, n:] == -1).all()         # the pad tail
            if smin is not None:
                assert plan_np[0][t] == smin % S        # smin_pos
            if program in RECORD:
                # the two fields the chain reads, strided out of the record
                assert layout.columns == (0, 1) and layout.width == 3
                for field_h, c in zip(xs_h[1:], layout.columns):
                    np.testing.assert_array_equal(field_h[t, :n], rec[:, c])
            else:
                assert layout is None
                np.testing.assert_array_equal(xs_h[1][t, :n], vals)
            wm_before = max(wm_before, wms[t])
        # the placement: what reached the device is what staging filled
        # (np.empty staging: compare where a lane is alive)
        alive = xs_h[0] >= 0
        for a_h, a_d in zip(xs_h, staged.xs):
            if program in MESH:
                assert a_d.shape == (N_DEV, T, B // N_DEV)
                assert len(a_d.sharding.device_set) == N_DEV
            elif program == "pallas_superscan":
                assert a_d.shape == (T * B,)
            got = _host_view(program, a_d, a_h.shape)
            np.testing.assert_array_equal(got[alive], a_h[alive])
        np.testing.assert_array_equal(
            _host_view(program, staged.xs[0], xs_h[0].shape), xs_h[0])
        # the plan reads timestamps alone: equal under every payload,
        # placement and program (the mesh's placement lays the five side
        # by side in one array, which its programs split again)
        ref = plain.stage(_steps_for("fused_superscan", steps), wms)
        plan = staged.plan
        if program in MESH:
            (packed,) = plan
            assert packed.shape == (T, 1 + 3 * pipe.F + pipe.S)
            plan = pipe._split_plan(np.asarray(packed))
        assert len(plan) == len(ref.plan) == 5
        for a, b in zip(plan, ref.plan):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert [dataclasses.astuple(f) for f in staged.fires] \
            == [dataclasses.astuple(f) for f in ref.fires]
        fired.extend(pipe.dispatch(staged))
    assert calls.calls == [(program, T)] * len(groups)
    want, late = _oracle(groups)
    assert pipe.num_late_records_dropped == late > 0
    assert _rows(fired) == want and len(want) > 100


@pytest.mark.parametrize("program", ["fused_superscan",
                                     "fused_chained_superscan",
                                     "sharded_chained_superscan"])
def test_a_watermark_only_group_runs_the_classic_program(program):
    """With zero rows the prologue has nothing to read: a record job's
    group is staged as key ids (every lane dead) and fires through the
    classic program of its deployment, over the same device state."""
    (steps, wms), _second = _groups()
    pipe, calls = _pipe(program)
    fired = pipe.process_superbatch(_steps_for(program, steps), wms)
    empty = (np.empty((0, 3), np.float32), None, np.empty(0, np.int64))
    flush = [wms[-1] + SIZE, wms[-1] + 2 * SIZE]
    staged = pipe.stage([empty, empty], flush)
    assert staged.payload.record is False
    assert (np.asarray(staged.xs[0]) == -1).all()
    fired += pipe.dispatch(staged)
    classic = {"fused_chained_superscan": "fused_superscan",
               "sharded_chained_superscan": "sharded_superscan"}
    assert calls.calls == [(program, T), (classic.get(program, program), 2)]
    # every window the first group's records belong to has now fired
    want, _late = _oracle([(steps, wms), ([empty[::2]] * 2, flush)])
    assert _rows(fired) == want and len(want) > 100


@pytest.mark.parametrize("program,calls_per_dispatch", [
    ("fused_superscan", 4), ("fused_chained_superscan", 4),
    # the kernel and the mesh programs keep span-granular readback
    ("pallas_superscan", 1), ("sharded_chained_superscan", 1)])
def test_step_groups_resolve_as_the_one_group_dispatch(program,
                                                       calls_per_dispatch):
    """Latency mode's streamed readback is the same dispatch body over
    T / Tg groups: same windows in the same order, same rows, byte for
    byte."""
    groups = _groups()
    whole, _ = _pipe(program)
    grouped, calls = _pipe(program)
    grouped.readback_steps = 2
    for steps, wms in groups:
        want = whole.process_superbatch(_steps_for(program, steps), wms)
        handle = grouped.process_superbatch(_steps_for(program, steps), wms,
                                            defer=True)
        got = handle.resolve()
        assert len(got) == len(want) > 0
        for (ww, wc, wf), (gw, gc, gf) in zip(want, got):
            assert ww == gw
            assert np.asarray(wc).tobytes() == np.asarray(gc).tobytes()
            assert wf.keys() == gf.keys()
            for name in wf:
                assert np.asarray(wf[name]).tobytes() \
                    == np.asarray(gf[name]).tobytes()
    Tg = T // calls_per_dispatch
    assert calls.calls == [(program, Tg)] * (calls_per_dispatch * len(groups))
