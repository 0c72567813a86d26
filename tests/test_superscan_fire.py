"""The shared fire body (`ops/superscan.make_superscan_step`) driven one
step at a time: what a fire reads out of the [K, S] slice ring and where it
writes it, against a numpy reduction over the same cells.

Every program that fires a keyed window runs this body (the chained and the
plain single-chip superscan, both sharded builds), so the cases here hold for
all of them: window lengths of one, two and five slices, a window that wraps
the ring's end and one that does not, two fire slots valid in one step,
slots of unequal length (`fire_spws`, the shared-partials programs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.ops.aggregators import VALUE, resolve
from flink_tpu.ops.superscan import make_global_scan_step, make_superscan_step

K, NSB, F, R, B = 48, 4, 3, 8, 16
NP_REDUCE = {"add": np.sum, "min": np.min, "max": np.max}
UNFIRED = -7          # what the fire buffers hold before the step


def _ring(rng, agg, shape):
    """A ring with something in every cell: counts, and per VALUE field
    integer-valued f32 (sums exact in any order) or plain f32 (min / max)."""
    count = rng.integers(0, 1000, size=shape).astype(np.int32)
    state = {}
    for f in agg.fields:
        if f.source != VALUE:
            continue
        if f.scatter == "add":
            state[f.name] = rng.integers(-500, 500, size=shape).astype(f.dtype)
        else:
            state[f.name] = rng.normal(size=shape).astype(f.dtype)
    return state, count


def _fire_once(step, state, count, out_shape, fire_pos, fire_valid, fire_row):
    S = count.shape[-1]
    outs = {n: np.full(out_shape, UNFIRED, v.dtype) for n, v in state.items()}
    count_out = np.full(out_shape, UNFIRED, np.int32)
    plan = (jnp.int32(0), jnp.asarray(fire_pos, jnp.int32),
            jnp.asarray(fire_valid, jnp.int32), jnp.asarray(fire_row, jnp.int32),
            jnp.ones((S,), jnp.int32))
    lanes = (jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), jnp.float32))
    carry, _ = jax.jit(step)((state, count, outs, count_out), lanes + plan)
    return jax.tree_util.tree_map(np.asarray, carry)


def _window(ring, first, spw):
    return ring[..., (first + np.arange(spw)) % ring.shape[-1]]


def _check(agg, spws, S, fire_pos, fire_valid, fire_row, seed):
    rng = np.random.default_rng(seed)
    state, count = _ring(rng, agg, (K, S))
    step = make_superscan_step(agg, K, S, NSB, F, R, max(spws), 8, True,
                               ingest="scatter", fire_spws=spws)
    new_state, new_count, outs, count_out = _fire_once(
        step, state, count, (R, K), fire_pos, fire_valid, fire_row)
    # the fire reads the ring and leaves it as it was
    np.testing.assert_array_equal(new_count, count)
    for name in state:
        np.testing.assert_array_equal(new_state[name], state[name])
    scatter = {f.name: f.scatter for f in agg.fields if f.source == VALUE}
    fired = {}
    for f in range(F):
        if fire_valid[f]:
            fired[fire_row[f]] = (fire_pos[f], spws[f])
    assert len(fired) == sum(fire_valid)
    for row in range(R):
        if row not in fired:
            assert (count_out[row] == UNFIRED).all()
            for name in state:
                assert (outs[name][row] == UNFIRED).all()
            continue
        first, spw = fired[row]
        want = _window(count, first, spw).sum(axis=1, dtype=np.int32)
        assert count_out[row].dtype == np.int32
        np.testing.assert_array_equal(count_out[row], want)
        for name in state:
            want = NP_REDUCE[scatter[name]](_window(state[name], first, spw), axis=1)
            assert outs[name][row].dtype == want.dtype
            # bit for bit: same cells, same dtype
            assert outs[name][row].tobytes() == want.tobytes()


@pytest.mark.parametrize("agg_name", ["count", "sum", "min", "max"])
@pytest.mark.parametrize("wraps", [False, True], ids=["inside", "wraps"])
@pytest.mark.parametrize("S", [16, 32])
@pytest.mark.parametrize("spw", [1, 2, 5])
def test_a_fire_reads_its_windows_slices_and_nothing_else(spw, S, wraps, agg_name):
    """Two slots fire in one step, the third does not; at spw = 1 a window
    cannot straddle the ring's end, so `wraps` puts it on the last slice."""
    first = S - max(spw - 1, 1) if wraps else 3
    assert (first + spw > S) == (wraps and spw > 1)
    second = (first + 7) % S
    _check(resolve(agg_name), (spw,) * F, S, fire_pos=[first, 0, second],
           fire_valid=[1, 0, 1], fire_row=[5, 0, 2], seed=spw * 100 + S)


@pytest.mark.parametrize("agg_name", ["count", "sum", "min", "max"])
@pytest.mark.parametrize("S", [16, 32])
def test_fire_slots_of_unequal_length_each_read_their_own_run(S, agg_name):
    """`fire_spws`: one ring, a slice-run length per fire slot (the
    shared-partials programs); all three fire, the five-slice one wraps."""
    _check(resolve(agg_name), (1, 5, 2), S, fire_pos=[S - 1, S - 2, 4],
           fire_valid=[1, 1, 1], fire_row=[0, 7, 3], seed=S)


@pytest.mark.parametrize("agg_name", ["count", "sum", "min", "max"])
@pytest.mark.parametrize("wraps", [False, True], ids=["inside", "wraps"])
def test_the_global_window_fire_folds_the_same_run_to_one_scalar(wraps, agg_name):
    """The ring without keys (`make_global_scan_step`, [S] vectors) reads
    its window through the same function."""
    S, spw = 16, 5
    agg = resolve(agg_name)
    rng = np.random.default_rng(7)
    state, count = _ring(rng, agg, (S,))
    step = make_global_scan_step(agg, S, NSB, F, R, spw)
    first = S - 2 if wraps else 6
    new_state, new_count, outs, count_out = _fire_once(
        step, state, count, (R,), [first, 0, 0], [1, 0, 0], [4, 0, 0])
    np.testing.assert_array_equal(new_count, count)
    want = np.full((R,), UNFIRED, np.int32)
    want[4] = _window(count, first, spw).sum(dtype=np.int32)
    np.testing.assert_array_equal(count_out, want)
    for f in agg.fields:
        if f.source != VALUE:
            continue
        np.testing.assert_array_equal(new_state[f.name], state[f.name])
        got = outs[f.name]
        assert (np.delete(got, 4) == UNFIRED).all()
        assert got[4].tobytes() == NP_REDUCE[f.scatter](
            _window(state[f.name], first, spw)).tobytes()


def _compiled_step_text(spw, S):
    agg = resolve("count")
    Kc, Fc, Rc = 1024, 4, 256
    step = make_superscan_step(agg, Kc, S, NSB, Fc, Rc, spw, 8, True,
                               ingest="scatter")
    i32 = jnp.int32
    carry = ({}, jax.ShapeDtypeStruct((Kc, S), i32), {},
             jax.ShapeDtypeStruct((Rc, Kc), i32))
    args = (jax.ShapeDtypeStruct((256,), i32),
            jax.ShapeDtypeStruct((256,), jnp.float32),
            jax.ShapeDtypeStruct((), i32)) + (
        jax.ShapeDtypeStruct((Fc,), i32),) * 3 + (
        jax.ShapeDtypeStruct((S,), i32),)
    return jax.jit(step).lower(carry, args).compile().as_text()


@pytest.mark.parametrize("spw,S", [(1, 16), (5, 32)])
def test_the_compiled_fire_holds_no_gather(spw, S):
    """The form, not a speed: a window of several slices is read like a
    window of one, never by a gather along the ring's minor axis (the CPU
    backend's compile of the count step; before PR 33 it held one gather
    per fire slot at spw = 5 and none at spw = 1)."""
    assert " gather(" not in _compiled_step_text(spw, S)
