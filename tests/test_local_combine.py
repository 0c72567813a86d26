"""Combiner exactness property test (ISSUE-15): every builtin
DeviceAggregator run combined-then-exchanged (parallel.mesh.local-combine)
vs exchanged-raw vs the scalar host oracle — byte parity under uniform AND
zipf keys, ragged batches and dead lanes — plus proof that a
non-decomposable aggregate refuses the combine path and still matches.

Byte parity is the bar because the combine is exact BY CONSTRUCTION: the
per-(source shard, key, rel-slice) partials are pre-reduced by the same
add/min/max scatter combiners the ring ingest applies, so the ring holds
identical values regardless of which side of the interconnect did the
folding. Values are integer-valued f32 (the repo-wide convention for
exact-equality float parity)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.ops.aggregators import (
    AccField,
    DeviceAggregator,
    decomposable,
    resolve,
)
from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
from flink_tpu.runtime.oracle_window_operator import OracleWindowOperator

NUM_KEYS = 192
WINDOW_MS, SLIDE_MS = 2_000, 500


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("shards",))


def _zipf_keys(rng, size, num_keys, s=1.0):
    ranks = np.arange(1, num_keys + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks ** s)
    return np.searchsorted(cdf / cdf[-1], rng.random(size)).astype(np.int32)


def _stream(seed, skewed, with_vals):
    """Ragged batches (including an EMPTY one): the planner pads the
    short steps with dead (-1) lanes, so raggedness IS the dead-lane
    case — the combine's partial scatter must drop them exactly like the
    raw exchange's receive side does."""
    rng = np.random.default_rng(seed)
    sizes = [600, 173, 0, 512, 41, 600, 257, 600]
    batches, wms = [], []
    t = 0.0
    for size in sizes:
        if skewed:
            keys = _zipf_keys(rng, size, NUM_KEYS)
        else:
            keys = rng.integers(0, NUM_KEYS, size).astype(np.int32)
        base = t + np.sort(rng.random(size)) * 400.0 if size else \
            np.empty(0, np.float64)
        ts = np.maximum(base.astype(np.int64)
                        - rng.integers(0, 120, size), 0)
        vals = (rng.integers(0, 9, size).astype(np.float32)
                if with_vals else None)
        batches.append((keys, vals, ts))
        wms.append(int(t + 400.0) - 150)
        t += 400.0
    return batches, wms


def _drain(pipe, batches, wms):
    out = []
    for lo in range(0, len(batches), 3):
        out.extend(pipe.process_superbatch(
            batches[lo:lo + 3], wms[lo:lo + 3]))
    return out


def _raw_rows(out):
    """(window start, counts, raw field arrays) — the byte-parity view."""
    rows = []
    for (w, counts, fields) in out:
        rows.append((w.start, np.asarray(counts),
                     {k: np.asarray(v) for k, v in fields.items()}))
    rows.sort(key=lambda r: r[0])
    return rows


def _assert_byte_equal(a, b):
    assert len(a) == len(b) > 0
    for (sa, ca, fa), (sb, cb, fb) in zip(a, b):
        assert sa == sb
        np.testing.assert_array_equal(ca, cb)
        assert fa.keys() == fb.keys()
        for name in fa:
            np.testing.assert_array_equal(fa[name], fb[name])


def _oracle(agg_name, batches, wms):
    op = OracleWindowOperator(
        SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS),
        resolve(agg_name).python_equivalent())
    for (kid, vals, ts), wm in zip(batches, wms):
        for i in range(len(ts)):
            v = 1.0 if vals is None else float(vals[i])
            op.process_record(int(kid[i]), v, int(ts[i]))
        op.process_watermark(wm)
    return {(key, w.start): value
            for key, w, value, _ts in op.drain_output()}


def _extract(agg_name, rows):
    """Pipeline rows -> {(key, window start): oracle-comparable value}."""
    out = {}
    field = {"sum": "sum", "min": "min", "max": "max"}.get(agg_name)
    for start, counts, fields in rows:
        for k in np.flatnonzero(counts > 0):
            if agg_name == "count":
                out[(int(k), start)] = int(counts[k])
            elif agg_name == "mean":
                out[(int(k), start)] = float(fields["sum"][k]) / counts[k]
            else:
                out[(int(k), start)] = float(fields[field][k])
    return out


def _pipe(aggregate, local_combine):
    return ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS), aggregate,
        key_capacity=NUM_KEYS, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, local_combine=local_combine)


@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "zipf"])
@pytest.mark.parametrize("agg", ["count", "sum", "min", "max", "mean"])
def test_combined_matches_raw_and_oracle(agg, skewed):
    batches, wms = _stream(11 if skewed else 5, skewed, agg != "count")
    raw = _pipe(agg, False)
    combined = _pipe(agg, True)
    assert combined.local_combine, "combine flag did not engage"
    assert not raw.local_combine
    rows_raw = _raw_rows(_drain(raw, batches, wms))
    rows_comb = _raw_rows(_drain(combined, batches, wms))
    # byte parity: same windows, same counts, same field BYTES
    _assert_byte_equal(rows_raw, rows_comb)
    # and both equal the scalar host oracle's extracted values
    expect = _oracle(agg, batches, wms)
    got = _extract(agg, rows_comb)
    assert got.keys() == expect.keys()
    for key in expect:
        assert got[key] == pytest.approx(expect[key]), key


def test_non_decomposable_refuses_combine_and_still_matches():
    """A DeviceAggregator that opts out of pre-aggregation (modeling the
    closure tier — q5's top-K post-processing never resolves to a
    DeviceAggregator at all, but a custom spec can also declare its merge
    non-decomposable) must transparently keep the route-raw exchange
    under the flag, at identical results."""
    closed = DeviceAggregator(
        "sum_closed",
        (AccField("sum", np.float32, 0, "add"),),
        lambda f: f["sum"],
        combinable=False,
    )
    assert not decomposable(closed)
    assert decomposable(resolve("sum"))
    batches, wms = _stream(3, True, True)
    refused = _pipe(closed, True)
    assert not refused.local_combine, (
        "non-decomposable aggregate must refuse the combine path")
    rows_refused = _raw_rows(_drain(refused, batches, wms))
    rows_raw = _raw_rows(_drain(_pipe("sum", False), batches, wms))
    _assert_byte_equal(rows_raw, rows_refused)


def test_combine_under_routing_table_matches_raw():
    """Both layers composed: local combine OVER a non-identity routing
    table (hot groups remapped mid-stream) still produces the raw path's
    exact bytes — placement and pre-reduction are independent and neither
    changes a result."""
    batches, wms = _stream(17, True, True)
    raw = _pipe("sum", False)
    both = ShardedFusedPipeline(
        _mesh(), SlidingEventTimeWindows.of(WINDOW_MS, SLIDE_MS), "sum",
        key_capacity=NUM_KEYS, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=512, local_combine=True, skew_routing=True)
    out = []
    for i, lo in enumerate(range(0, len(batches), 3)):
        out.extend(both.process_superbatch(
            batches[lo:lo + 3], wms[lo:lo + 3]))
        if i == 0:
            loads = both.mesh_group_loads()
            from flink_tpu.parallel.routing import plan_balanced_assignment

            both.set_routing_assignment(
                plan_balanced_assignment(loads, both.n))
    _assert_byte_equal(_raw_rows(_drain(raw, batches, wms)),
                       _raw_rows(out))
    assert both.routing_version() == 1
