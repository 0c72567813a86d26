"""The matmul ingest's width (`ops/superscan.make_superscan_step`): a step whose
live records lie in one slice contracts K segments, a step that straddles a
slice boundary K * NSB, picked per step from the step's own lanes.

Held here on the CPU with `ingest="matmul"` forced at small K: the step alone
against the scatter ingest and against the wide-only histogram (the form every
step took before), then the three programs that run it (the classic
`_build_superscan`, the chained program, the sharded step on virtual devices)
against their scatter twins or a numpy count, over dispatches whose steps are
all one-slice, all wide and mixed; the fourth phase count is the number of
one-slice steps the test built.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.metrics.device_stats import CompileTracker
from flink_tpu.ops import matmul_hist
from flink_tpu.ops import superscan as superscan_mod
from flink_tpu.ops.aggregators import VALUE, resolve
from flink_tpu.ops.superscan import PHASE_COUNTS, make_superscan_step
from flink_tpu.parallel import sharded_superscan as sharded_mod
from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    TracedPrologue,
)

S, NSB, F, R, B, CHUNK = 16, 4, 2, 4, 64, 32
# what a step's live lanes span: "one" the step's lowest slice alone (a third
# of its lanes dead), "empty" no live lane, "span<n>" n slices
DISPATCHES = {
    "one_slice": ["one"] * 5,
    "wide": ["span2", "span3", "span4", "span2"],
    "mixed": ["one", "span2", "empty", "one", "span4", "one"],
}
FLOAT_TOL = 1e-4     # tests/test_fused_pipeline.py's, for an exact-split sum


def _lanes(rng, kinds, K):
    """idx [T, B] (`key * NSB + srel`, -1 dead), vals [T, B], and how many of
    the steps are one-slice."""
    idx = np.full((len(kinds), B), -1, np.int32)
    for t, kind in enumerate(kinds):
        if kind == "empty":
            continue
        span = 1 if kind == "one" else int(kind[4:])
        srel = rng.integers(0, span, B)
        srel[0], srel[1] = 0, span - 1        # both ends of the span are hit
        idx[t] = rng.integers(0, K, B) * NSB + srel
        if kind == "one":
            idx[t, rng.random(B) < 0.33] = -1
    vals = np.where(idx >= 0, rng.normal(size=idx.shape), 0).astype(np.float32)
    return idx, vals, sum(k in ("one", "empty") for k in kinds)


def _scan(step, agg, K, idx, vals, seed, phases=False):
    """T steps of `step` over a ring with something in every cell; no fire,
    no purge. Returns (state, count[, phase counts]) as numpy."""
    rng = np.random.default_rng(seed)
    T = idx.shape[0]
    count = rng.integers(0, 100, (K, S)).astype(np.int32)
    state = {f.name: rng.integers(-50, 50, (K, S)).astype(f.dtype)
             for f in agg.fields if f.source == VALUE}
    outs = {n: np.zeros((R, K), v.dtype) for n, v in state.items()}
    carry = (state, count, outs, np.zeros((R, K), np.int32))
    if phases:
        carry += (jnp.zeros((PHASE_COUNTS,), jnp.int32),)
    i32 = jnp.int32
    xs = (jnp.asarray(idx), jnp.asarray(vals),
          jnp.asarray(rng.integers(0, S, T), i32),     # smin_pos: wraps too
          jnp.zeros((T, F), i32), jnp.zeros((T, F), i32),
          jnp.zeros((T, F), i32), jnp.ones((T, S), i32))
    carry, _ = jax.jit(lambda c, xs: jax.lax.scan(step, c, xs))(carry, xs)
    state, count, _outs, _count_out, *pc = jax.tree.map(np.asarray, carry)
    return (state, count, *pc)


def _wide_only_step(agg, K):
    """Every step contracts K * NSB segments: the matmul ingest as it was."""
    names = [f.name for f in agg.fields if f.source == VALUE]

    def step(carry, args):
        state, count, outs, count_out = carry
        idx, vals, smin_pos = args[:3]
        cols = (smin_pos + jnp.arange(NSB, dtype=jnp.int32)) % S
        count = count.at[:, cols].add(matmul_hist.count_hist(
            idx, K * NSB, chunk=CHUNK).reshape(K, NSB))
        state = {n: state[n].at[:, cols].add(matmul_hist.weighted_hist(
            idx, vals, K * NSB, chunk=CHUNK).reshape(K, NSB)) for n in names}
        return (state, count, outs, count_out), None

    return step


@pytest.mark.parametrize("K", [40, 256], ids=["K40", "K256"])
@pytest.mark.parametrize("agg_name", ["count", "sum", "mean"])
@pytest.mark.parametrize("dispatch", list(DISPATCHES))
def test_a_step_ingests_the_same_cells_at_either_width(dispatch, agg_name, K):
    """Counts bit for bit, float sums within the exact split's tolerance,
    against the scatter ingest and against the wide-only histogram; K = 40
    leaves the narrow histogram's 128 lanes mostly padding, K = 256 gives it
    two rows."""
    agg = resolve(agg_name)
    idx, vals, one_slice_steps = _lanes(
        np.random.default_rng(K + len(dispatch)), DISPATCHES[dispatch], K)

    def build(ingest, **kw):
        return make_superscan_step(agg, K, S, NSB, F, R, 1, CHUNK, True,
                                   ingest=ingest, **kw)

    state, count, phase_c = _scan(build("matmul", phase_counters=True),
                                  agg, K, idx, vals, seed=3, phases=True)
    assert phase_c.tolist() == [int((idx >= 0).sum()), 0, 0, one_slice_steps]
    for other in (build("scatter"), _wide_only_step(agg, K)):
        want_state, want_count = _scan(other, agg, K, idx, vals, seed=3)
        np.testing.assert_array_equal(count, want_count)
        assert state.keys() == want_state.keys()
        for name in state:
            np.testing.assert_allclose(state[name], want_state[name],
                                       rtol=FLOAT_TOL, atol=FLOAT_TOL)
    # the scatter ingest counts the same steps (the count describes the
    # traffic, whatever the backend's ingest)
    *_, scatter_c = _scan(build("scatter", phase_counters=True), agg, K, idx,
                          vals, seed=3, phases=True)
    assert scatter_c.tolist() == phase_c.tolist()


def test_a_dead_lane_is_no_record_of_the_highest_slice():
    """-1 % NSB is NSB - 1: a one-slice step with dead lanes stays one-slice,
    and its dead lanes land in no key's cell at either width."""
    K = 40
    agg = resolve("count")
    idx = np.full((1, B), -1, np.int32)
    idx[0, :5] = np.array([0, 3, 3, 39, 7]) * NSB
    step = make_superscan_step(agg, K, S, NSB, F, R, 1, CHUNK, True,
                               ingest="matmul", phase_counters=True)
    zeros = np.zeros_like(idx, np.float32)
    _state, count, phase_c = _scan(step, agg, K, idx, zeros, seed=1, phases=True)
    _state, before, _pc = _scan(step, agg, K, np.full_like(idx, -1), zeros,
                                seed=1, phases=True)
    assert phase_c.tolist() == [5, 0, 0, 1]
    added = count - before
    assert added.sum() == 5 and added[3].sum() == 2 and added[39].sum() == 1


# ---------------------------------------------------------------------------
# the programs: classic, chained, sharded
# ---------------------------------------------------------------------------

SLICE_MS, NUM_KEYS, N = 500, 64, 600
ASSIGNER = SlidingEventTimeWindows.of(2000, SLICE_MS)
GEOM = dict(key_capacity=NUM_KEYS, num_slices=16, nsb=NSB, fires_per_step=4,
            out_rows=32, chunk=1024)


CLOSING = 4     # empty steps whose watermarks fire what is left, three a step


def _records(kinds, seed):
    """[(record [n, 7], ts)] and watermarks: step t starts at slice t and
    spans what its kind says; the key in field 5, a 0/1 flag in field 2 (the
    filter keeps 0), the value in field 1 (small integers: float sums exact
    in any order). Ends with CLOSING empty steps."""
    rng = np.random.default_rng(seed)
    steps, wms = [], []
    for t, kind in enumerate(kinds):
        n = 0 if kind == "empty" else N
        span = 1 if kind in ("one", "empty") else int(kind[4:])
        rec = rng.integers(0, 6, (n, 7)).astype(np.float32)
        rec[:, 5] = rng.integers(0, NUM_KEYS, n)
        rec[:, 2] = rng.integers(0, 2, n)
        ts = t * SLICE_MS + rng.integers(0, span * SLICE_MS, n)
        # every key has a record the filter keeps in the span's first and
        # in its last slice: a wide step is wide on every shard of a mesh
        both_ends = np.arange(n) < 2 * NUM_KEYS
        rec[both_ends, 5] = np.arange(n)[both_ends] % NUM_KEYS
        rec[both_ends, 2] = 0
        ts[:NUM_KEYS] = t * SLICE_MS
        ts[NUM_KEYS:2 * NUM_KEYS] = (t + span) * SLICE_MS - 1
        steps.append((rec, ts.astype(np.int64)))
        wms.append(t * SLICE_MS - 1)
    for _ in range(CLOSING):
        steps.append((np.zeros((0, 7), np.float32), np.zeros(0, np.int64)))
        wms.append(wms[-1] + 3 * SLICE_MS)
    return steps, wms


def _one_slice_steps(kinds):
    # a closing step has no live lane: one-slice
    return sum(k in ("one", "empty") for k in kinds) + CLOSING


def _prologue():
    """The traced chain of the programs below. Functions of its own per
    call: `_CHAINED_CACHE` keys on the prologue and not on the ingest, and
    must not hand one pipeline another's program."""
    return TracedPrologue(
        transforms=(("filter", lambda col: col[:, 2] < 0.5),),
        key_fn=lambda col: col[:, 5].astype(jnp.int32),
        value_fn=lambda col: col[:, 1])


def _host_steps(steps):
    """The chain run on the host: (key ids, values, ts) of the records the
    filter keeps."""
    out = []
    for rec, ts in steps:
        keep = rec[:, 2] < 0.5
        out.append((rec[keep, 5].astype(np.int32), rec[keep, 1], ts[keep]))
    return out


def _rows(out):
    rows = [(w.start, np.asarray(c).astype(np.int64),
             {k: np.asarray(v) for k, v in f.items()}) for w, c, f in out]
    return sorted(rows, key=lambda r: r[0])


def _numpy_rows(steps):
    """(count, sum) per window start and key over the records the filter
    keeps: the plain reference of every program below."""
    kid, val, ts = map(np.concatenate, zip(*_host_steps(steps)))
    rows = {}
    for start in range(-2000 + SLICE_MS, int(ts.max()) + 1, SLICE_MS):
        inside = (ts >= start) & (ts < start + 2000)
        if inside.any():
            rows[start] = (
                np.bincount(kid[inside], minlength=NUM_KEYS),
                np.bincount(kid[inside], weights=val[inside],
                            minlength=NUM_KEYS))
    return rows


def _assert_rows(got, steps, agg_name):
    want = _numpy_rows(steps)
    assert [r[0] for r in got] == sorted(want)
    for start, counts, fields in got:
        np.testing.assert_array_equal(counts, want[start][0])
        if agg_name != "count":
            live = counts > 0
            np.testing.assert_allclose(fields["sum"][live],
                                       want[start][1][live], rtol=1e-6)


def _tracked(pipe):
    pipe.attach_device_stats(CompileTracker())
    return pipe


def _force_matmul(monkeypatch):
    # ops/superscan.default_ingest picks by backend: build the chip's ingest
    monkeypatch.setattr(superscan_mod, "default_ingest", lambda: "matmul")
    monkeypatch.setattr(sharded_mod, "default_ingest", lambda: "matmul")


def _chained(agg_name):
    return _tracked(FusedWindowPipeline(
        ASSIGNER, agg_name, backend="xla", prologue=_prologue(), **GEOM))


def _assert_same_rows(got, want):
    """Two programs' fires: counts and every live key's fields bit for bit."""
    for (ws, wc, wf), (gs, gc, gf) in zip(want, got, strict=True):
        assert ws == gs
        np.testing.assert_array_equal(gc, wc)
        for name in wf:
            np.testing.assert_array_equal(gf[name][wc > 0], wf[name][wc > 0])


@pytest.mark.parametrize("agg_name", ["count", "sum", "mean"])
@pytest.mark.parametrize("dispatch", list(DISPATCHES))
def test_the_classic_program_counts_its_one_slice_steps(dispatch, agg_name):
    """`_build_superscan` ingests by matmul on every backend."""
    kinds = DISPATCHES[dispatch]
    steps, wms = _records(kinds, seed=len(dispatch))
    pipe = _tracked(FusedWindowPipeline(ASSIGNER, agg_name, backend="xla",
                                        **GEOM))
    got = _rows(pipe.process_superbatch(_host_steps(steps), wms))
    _assert_rows(got, steps, agg_name)
    assert pipe.phase_totals[3] == _one_slice_steps(kinds)
    assert pipe.phase_totals[0] == sum(len(s[2]) for s in _host_steps(steps))


@pytest.mark.parametrize("agg_name", ["count", "sum", "mean"])
@pytest.mark.parametrize("dispatch", list(DISPATCHES))
def test_the_chained_program_matches_its_scatter_twin(dispatch, agg_name,
                                                      monkeypatch):
    kinds = DISPATCHES[dispatch]
    steps, wms = _records(kinds, seed=10 + len(dispatch))
    steps = [(rec, None, ts) for rec, ts in steps]
    twin = _chained(agg_name)
    want = _rows(twin.process_superbatch(steps, wms))
    _force_matmul(monkeypatch)
    pipe = _chained(agg_name)
    got = _rows(pipe.process_superbatch(steps, wms))
    _assert_rows(got, [(s[0], s[2]) for s in steps], agg_name)
    _assert_same_rows(got, want)
    assert (pipe.phase_totals[3] == twin.phase_totals[3]
            == _one_slice_steps(kinds))


@pytest.mark.parametrize("agg_name", ["count", "sum"])
@pytest.mark.parametrize("dispatch", list(DISPATCHES))
def test_each_shard_of_a_mesh_decides_for_its_own_lanes(dispatch, agg_name,
                                                        monkeypatch):
    """The sharded step on four virtual devices: every shard counts the
    steps whose RECEIVED lanes lie in one slice, and the total is their sum."""
    n = 4
    kinds = DISPATCHES[dispatch]
    steps, wms = _records(kinds, seed=20 + len(dispatch))
    steps = [(rec, None, ts) for rec, ts in steps]
    _force_matmul(monkeypatch)
    pipe = _tracked(ShardedFusedPipeline(
        Mesh(np.array(jax.devices()[:n]), ("shards",)), ASSIGNER, agg_name,
        prologue=_prologue(), **GEOM))
    got = _rows(pipe.process_superbatch(steps, wms))
    _assert_rows(got, [(s[0], s[2]) for s in steps], agg_name)
    assert pipe.phase_totals[3] == n * _one_slice_steps(kinds)


def test_the_count_starts_anew_after_a_restore_and_the_rows_do_not(monkeypatch):
    """Phase counts are a job attempt's, not state: a restored pipeline
    counts the steps it was given, and fires what the unbroken run fires."""
    kinds = DISPATCHES["mixed"]
    steps, wms = _records(kinds, seed=31)
    steps = [(rec, None, ts) for rec, ts in steps]
    _force_matmul(monkeypatch)
    whole = _chained("sum")
    want = _rows(whole.process_superbatch(steps, wms))
    first = _chained("sum")
    got = first.process_superbatch(steps[:3], wms[:3])
    assert first.phase_totals[3] == 2           # one, span2, empty
    second = _chained("sum")
    second.restore(first.snapshot())
    got = _rows(got + second.process_superbatch(steps[3:], wms[3:]))
    assert second.phase_totals[3] == 2 + CLOSING    # one, span4, one
    assert whole.phase_totals[3] == 4 + CLOSING
    _assert_same_rows(got, want)
