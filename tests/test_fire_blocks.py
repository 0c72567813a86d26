"""One block per fire from the fused operator to the sink's edge
(runtime/fire_block.py) against the per-row loops it replaced.

The loops are kept HERE, as the reference: `_old_emit` is the operator's
emission as it was (one scalar index, one `.item()`, one 4-tuple and one
`append` per row), `_old_batch` the runners' `_drain` comprehension. Every
case holds what downstream receives to them element for element and Python
type for type, because a sink's `write_batch` must not see the difference.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
from flink_tpu.config import Configuration, ExecutionOptions
from flink_tpu.connectors.sink import Sink, SinkWriter
from flink_tpu.connectors.source import Batch, DataGeneratorSource
from flink_tpu.core.time import MAX_WATERMARK, TimeWindow
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.metrics.task_io import StageClock
from flink_tpu.ops.aggregators import ONE
from flink_tpu.runtime.fire_block import (
    FireBlock,
    blocks_of,
    downstream_batch,
    rows_of,
)
from flink_tpu.runtime.fused_window_operator import FusedWindowOperator
from flink_tpu.runtime.fused_window_pipeline import TracedPrologue
from flink_tpu.runtime.oracle_window_operator import OracleWindowOperator
from flink_tpu.state.tier_manager import TierConfig
from flink_tpu.utils.arrays import obj_array


# ---------------------------------------------------------------------------
# the reference: the per-row loops as they were before the block
# ---------------------------------------------------------------------------

def _old_dense_rows(op, window, counts, fields, sink):
    counts = np.asarray(counts)
    live = np.flatnonzero(counts > 0)
    if live.size == 0:
        return
    fdict = {f.name: (counts if f.source == ONE
                      else np.asarray(fields[f.name]))
             for f in op.agg.fields}
    result = np.asarray(op.agg.extract(fdict))
    ts = window.max_timestamp()
    if op.columnar_output:
        sink.append((None, window, (window, live, result[live]), ts))
        return
    for i in live:
        sink.append((int(i), window, result[i].item(), ts))


def _old_keydict_rows(op, window, counts, fields, sink):
    counts = np.asarray(counts)[: len(op.keydict)]
    live = np.flatnonzero(counts > 0)
    if live.size == 0:
        return
    fdict = {}
    for f in op.agg.fields:
        if f.source == ONE:
            fdict[f.name] = counts
        else:
            fdict[f.name] = np.asarray(fields[f.name])[: len(op.keydict)]
    result = np.asarray(op.agg.extract(fdict))
    ts = window.max_timestamp()
    if op.columnar_output:
        sink.append((None, window, (window, live, result[live]), ts))
        return
    for k, i in zip([op.keydict._keys[int(i)] for i in live], live):
        sink.append((k, window, result[i].item(), ts))


def _old_tiered_rows(op, window, counts, fields, sink):
    p = op.pipe
    j = (window.start - p.offset) // p.slide_ms
    slice_range = range(j * p.sl, j * p.sl + p.spw)
    counts = np.asarray(counts).astype(np.int64).copy()
    vals = {f.name: np.asarray(fields[f.name]).copy()
            for f in op.agg.fields if f.source != ONE}
    cold = op.tier.cold_fire(slice_range)
    combine = {"add": lambda a, b: a + b, "min": min, "max": max}
    extras = []
    if cold is not None:
        ckids, cfields, ccounts = cold
        vocab = op.tier.vocab
        for i, cid in enumerate(ckids):
            key = vocab.key_of_cold_id(int(cid))
            hid = None if key is None else vocab.resident_id(key)
            if hid is not None:
                counts[hid] += int(ccounts[i])
                for f in op.agg.fields:
                    if f.source == ONE:
                        continue
                    vals[f.name][hid] = combine[f.scatter](
                        vals[f.name][hid].item(), cfields[f.name][i].item())
            elif key is not None:
                extras.append((key, int(ccounts[i]),
                               {n: cfields[n][i] for n in cfields}))
    ts = window.max_timestamp()
    live = np.flatnonzero(counts > 0)
    if live.size:
        fdict = {f.name: (counts if f.source == ONE else vals[f.name])
                 for f in op.agg.fields}
        result = np.asarray(op.agg.extract(fdict))
        for i in live:
            sink.append((op.tier.vocab.key_of_id(int(i)), window,
                         result[i].item(), ts))
    if extras:
        e_counts = np.asarray([e[1] for e in extras], np.int64)
        fdict_e = {
            f.name: (e_counts if f.source == ONE
                     else np.asarray([e[2][f.name] for e in extras],
                                     np.dtype(f.dtype)))
            for f in op.agg.fields}
        result_e = np.asarray(op.agg.extract(fdict_e))
        for i, (key, _c, _f) in enumerate(extras):
            sink.append((key, window, result_e[i].item(), ts))


def _old_emit(op, lanes, window, counts, fields):
    """FusedWindowOperator._emit as it was; `lanes` = [output] or the
    shared-partials lanes."""
    if op.spec_outputs is not None:
        spec, win = window
        _old_dense_rows(op, win, counts, fields, lanes[spec])
    elif op.tier is not None:
        _old_tiered_rows(op, window, counts, fields, lanes[0])
    elif op.prologue is not None:
        _old_dense_rows(op, window, counts, fields, lanes[0])
    else:
        _old_keydict_rows(op, window, counts, fields, lanes[0])


def _old_batch(out, window_fn=None):
    """The runners' `_drain` as it was: rows -> (vals, ts)."""
    vals = obj_array([r if (window_fn is not None or k is None) else (k, r)
                      for (k, _w, r, _t) in out])
    ts = np.asarray([t for (_k, _w, _r, t) in out], dtype=np.int64)
    return vals, ts


def _same(a, b):
    """Equal, and of the same Python type, all the way down."""
    assert type(a) is type(b), (type(a), type(b))
    if isinstance(a, tuple):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same(x, y)
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    else:
        assert a == b


def _same_batch(got, want):
    (gv, gt), (wv, wt) = got, want
    assert gv.dtype == object and gv.ndim == 1 and gt.dtype == np.int64
    assert wv.dtype == object and wv.ndim == 1 and wt.dtype == np.int64
    assert gv.shape == wv.shape and gt.shape == wt.shape == gv.shape
    np.testing.assert_array_equal(gt, wt)
    for g, w in zip(gv, wv):
        _same(g, w)


# ---------------------------------------------------------------------------
# every emission path of the operator x every result type
# ---------------------------------------------------------------------------

N_KEYS = 96


def _key0(col):
    return col[:, 0].astype(jnp.int32)


def _val1(col):
    return col[:, 1]


def _stream(steps=12, batch=64, seed=5):
    """(keys, values, timestamps, watermark) steps; values have fractions so
    a float sum is no disguised integer."""
    r = np.random.default_rng(seed)
    for s in range(steps):
        keys = r.integers(0, N_KEYS, batch)
        vals = (keys % 7 + 0.25).astype(np.float32)
        ts = (s * 250 + r.integers(0, 250, batch)).astype(np.int64)
        yield keys, vals, ts, s * 250 + 125


def _operator(path, agg):
    tumbling = TumblingEventTimeWindows.of(1000)
    traced = TracedPrologue(transforms=(), key_fn=_key0, value_fn=_val1)
    kw = dict(superbatch_steps=4)
    if path in ("dense", "columnar"):
        return FusedWindowOperator(tumbling, agg, key_capacity=128,
                                   prologue=traced,
                                   columnar_output=path == "columnar", **kw)
    if path in ("keydict", "keydict_columnar"):
        return FusedWindowOperator(tumbling, agg, key_capacity=128,
                                   columnar_output=path != "keydict", **kw)
    if path == "shared_lane":
        return FusedWindowOperator(
            None, agg, key_capacity=128, prologue=traced,
            assigners=[tumbling, TumblingEventTimeWindows.of(2000)], **kw)
    assert path == "tiered"
    return FusedWindowOperator(tumbling, agg,
                               tier=TierConfig(hot_key_capacity=32), **kw)


def _drive(op, reference_lanes):
    """Feed the stream; yield (blocks, reference rows) per lane per drain.
    The reference runs on each fire's own inputs, before the operator's
    emission of the same fire."""
    emit = op._emit

    def spy(window, counts, fields):
        _old_emit(op, reference_lanes, window, counts, fields)
        emit(window, counts, fields)

    op._emit = spy
    lanes = range(len(reference_lanes))

    def drained():
        for i in lanes:
            blocks = (op.drain_blocks() if op.spec_outputs is None
                      else op.drain_spec_blocks(i))
            rows, reference_lanes[i][:] = list(reference_lanes[i]), []
            yield blocks, rows

    for keys, vals, ts, wm in _stream():
        if op.prologue is not None:
            rec = np.stack([keys.astype(np.float32), vals], axis=1)
            op.process_raw_batch(rec, ts)
        else:
            # keys far from their dense ids: the dictionary has to map back
            op.process_batch(keys * 1000 + 7, vals, ts)
        op.process_watermark(wm)
        yield from drained()
    op.process_watermark(MAX_WATERMARK - 1)
    yield from drained()


@pytest.mark.parametrize("agg", ["count", "sum", "mean"])
@pytest.mark.parametrize("path", ["dense", "keydict", "shared_lane", "tiered",
                                  "columnar", "keydict_columnar"])
def test_downstream_batch_equals_the_old_loops(path, agg):
    op = _operator(path, agg)
    op.attach_stage_clock(StageClock())
    reference = [[] for _ in (op.spec_outputs or [None])]
    result_type = int if agg == "count" else float
    fires = rows = 0
    for blocks, want_rows in _drive(op, reference):
        assert all(type(b) is FireBlock for b in blocks)
        assert bool(blocks) == bool(want_rows)
        if not blocks:
            continue
        fires += len(blocks)
        vals, ts = got = downstream_batch(blocks, bare=False)
        _same_batch(got, _old_batch(want_rows))
        if "columnar" in path:
            # one packed row per fire: (window, dense ids, result column)
            assert len(vals) == len(blocks)
            assert all(type(v) is tuple and len(v) == 3 for v in vals)
            rows += sum(len(v[1]) for v in vals)
        else:
            assert all(type(v) is tuple and type(v[0]) is int
                       and type(v[1]) is result_type for v in vals)
            rows += len(vals)
        # the row contract of drain_output(), from the same blocks
        got_rows = rows_of(blocks)
        assert len(got_rows) == len(want_rows)
        for g, w in zip(got_rows, want_rows):
            _same(g, w)
        # a checkpoint carries rows: a restored lane hands over the same
        _same_batch(downstream_batch(blocks_of(got_rows), bare=False),
                    _old_batch(want_rows))
        at = 0
        for b in blocks:
            # a fire's rows are contiguous, under one timestamp, and in
            # ascending id order where the id is the key
            assert (ts[at:at + len(b)] == b.ts).all()
            if isinstance(b.keys, np.ndarray):
                assert (np.diff(b.keys) > 0).all()
                assert [k for k, _r in vals[at:at + len(b)]] == b.keys.tolist()
            at += len(b)
    assert fires >= 3
    link = op.stage_clock.link()
    assert (link["fireBlocks"], link["rowsEmitted"]) == (fires, rows)
    if path == "tiered":      # cold-only keys rode the same blocks
        assert op.tier.vocab.num_evictions > 0


def test_an_empty_fire_appends_nothing():
    op = _operator("dense", "count")
    op.attach_stage_clock(StageClock())
    window = TimeWindow(0, 1000)
    op._emit(window, np.zeros(128, np.int32), {})
    assert op.output == [] and op.drain_output() == []
    assert op.stage_clock.link()["fireBlocks"] == 0
    host_keyed = _operator("keydict", "count")
    host_keyed.process_batch(np.arange(8), np.ones(8, np.float32),
                             np.full(8, 10, np.int64))
    host_keyed._emit(window, np.zeros(128, np.int32), {})
    assert host_keyed.output == []


@pytest.mark.parametrize("path", ["dense", "keydict"])
def test_a_65536_row_fire_is_one_block_whatever_its_size(path):
    """The cost guard, and it times nothing: with 65 536 live keys one
    `_emit` appends ONE entry to the lane; the callers that want rows still
    get 65 536 of them."""
    k = 65_536
    op = FusedWindowOperator(
        TumblingEventTimeWindows.of(1000), "count", key_capacity=k,
        superbatch_steps=4,
        prologue=(TracedPrologue(transforms=(), key_fn=_key0)
                  if path == "dense" else None))
    op.attach_stage_clock(StageClock())
    if path == "keydict":
        op.keydict.lookup_or_insert(np.arange(k, dtype=np.int64) * 3 + 1)
    window = TimeWindow(0, 1000)
    counts = np.arange(1, k + 1, dtype=np.int32)
    op._emit(window, counts, {})
    assert len(op.output) == 1 and len(op.output[0]) == k
    link = op.stage_clock.link()
    assert (link["fireBlocks"], link["rowsEmitted"]) == (1, k)
    vals, ts = downstream_batch(op.output, bare=False)
    assert vals.shape == ts.shape == (k,) and vals.dtype == object
    rows = op.drain_output()
    assert op.output == [] and type(rows) is list and len(rows) == k
    key_of = (lambda i: i) if path == "dense" else (lambda i: i * 3 + 1)
    for i in (0, 1, k // 2, k - 1):
        _same(rows[i], (key_of(i), window, i + 1, 999))
        _same(vals[i], (key_of(i), i + 1))
    op._emit(window, counts, {})
    link = op.stage_clock.link()
    assert (link["fireBlocks"], link["rowsEmitted"]) == (2, 2 * k)


# ---------------------------------------------------------------------------
# whole jobs: what a sink's write_batch receives
# ---------------------------------------------------------------------------

class _BatchSink(Sink):
    """Keeps every `write_batch` call as it came."""

    def __init__(self):
        self.batches = []

    def create_writer(self):
        batches = self.batches

        class Writer(SinkWriter):
            def write_batch(self, values, timestamps=None):
                batches.append((values, timestamps))

        return Writer()

    def whole(self):
        assert self.batches
        for vals, ts in self.batches:
            assert isinstance(vals, np.ndarray) and vals.dtype == object
            assert vals.ndim == 1 and ts.dtype == np.int64
            assert vals.shape == ts.shape
        return (np.concatenate([v for v, _t in self.batches]),
                np.concatenate([t for _v, t in self.batches]))


N = 6_000


def _job(kind, monkeypatch):
    """Run one job; (what each sink received, what the old loops make of
    the same fires)."""
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, 512)
    cfg.set(ExecutionOptions.KEY_CAPACITY, 64)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    cfg.set(ExecutionOptions.COLUMNAR_OUTPUT, kind == "columnar")

    def gen(idx):
        col = np.stack([(idx * 2654435761) % 48, idx % 5 + 0.5],
                       axis=1).astype(np.float32)
        return Batch(col, (1_000 + idx * 20_000 // N).astype(np.int64))

    env = StreamExecutionEnvironment(cfg)
    ds = env.from_source(
        DataGeneratorSource(gen, N, num_splits=1),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0))
    traced = ds.key_by(_key0, traceable=True)
    assigners = [TumblingEventTimeWindows.of(1_000)]
    window_fn = None
    if kind in ("count", "columnar"):
        streams = [traced.window(assigners[0]).count()]
    elif kind == "host_keyed_sum":
        streams = [ds.key_by(lambda col: col[:, 0].astype(np.int64) * 5,
                             vectorized=True)
                   .window(assigners[0])
                   .aggregate("sum", lambda col: col[:, 1],
                              value_vectorized=True)]
    elif kind == "traced_mean":
        streams = [traced.window(assigners[0])
                   .aggregate("mean", _val1, value_traceable=True)]
    elif kind == "shared":
        assigners.append(TumblingEventTimeWindows.of(2_000))
        streams = [traced.window(a).count() for a in assigners]
    else:
        assert kind == "window_fn"
        from flink_tpu.api.functions import ProcessWindowFunction

        class Spread(ProcessWindowFunction):
            def process(self, key, context, elements):
                yield (key, len(elements), context.window.start)

        window_fn = Spread()
        streams = [ds.key_by(lambda row: int(row[0]))
                   .window(assigners[0])
                   .aggregate("count", window_fn=window_fn)]
    sinks = [_BatchSink() for _ in streams]
    for stream, sink in zip(streams, sinks):
        stream.sink_to(sink)

    # the reference rides along: every fire's inputs through the old loops
    lanes = {}
    emit = FusedWindowOperator._emit

    def spy_emit(op, window, counts, fields):
        _old_emit(op, lanes.setdefault(
            id(op), [[] for _ in (op.spec_outputs or [None])]),
            window, counts, fields)
        emit(op, window, counts, fields)

    monkeypatch.setattr(FusedWindowOperator, "_emit", spy_emit)
    drain = OracleWindowOperator.drain_output

    def spy_drain(op):
        rows = drain(op)
        lanes.setdefault(id(op), [[]])[0].extend(rows)
        return rows

    monkeypatch.setattr(OracleWindowOperator, "drain_output", spy_drain)
    env.execute("fire-blocks-" + kind)
    (reference,) = lanes.values()
    assert len(reference) == len(sinks)
    return ([s.whole() for s in sinks],
            [_old_batch(rows, window_fn) for rows in reference])


@pytest.mark.parametrize("kind", ["count", "host_keyed_sum", "traced_mean",
                                  "shared", "columnar", "window_fn"])
def test_a_sinks_write_batch_receives_what_it_always_did(kind, monkeypatch):
    received, reference = _job(kind, monkeypatch)
    for got, want in zip(received, reference):
        assert len(want[0]) > (0 if kind == "columnar" else 48)
        _same_batch(got, want)
    vals = received[0][0]
    if kind == "window_fn":
        # the window function's output, bare
        assert all(type(v) is tuple and len(v) == 3 for v in vals)
    elif kind == "columnar":
        assert all(type(v) is tuple and len(v) == 3
                   and isinstance(v[1], np.ndarray) for v in vals)
    else:
        result_type = int if kind in ("count", "shared") else float
        assert all(type(v) is tuple and type(v[0]) is int
                   and type(v[1]) is result_type for v in vals)


def test_fires_of_stamps_once_per_block_and_once_per_row():
    from flink_tpu.runtime.fire_block import fires_of

    w1, w2 = TimeWindow(0, 1000), TimeWindow(1000, 2000)
    blocks = [FireBlock(w1, np.arange(5), np.ones(5, np.int64), 999),
              FireBlock(w2, None, [(w2, np.arange(3), np.ones(3))], 1999)]
    assert list(fires_of(blocks)) == [(w1, 999), (w2, 1999)]
    rows = rows_of(blocks)
    assert len(rows) == 6 and rows[5][0] is None
    assert list(fires_of(rows)) == [(w1, 999)] * 5 + [(w2, 1999)]
    assert [(b.window, b.ts, len(b)) for b in blocks_of(rows)] == \
        [(w1, 999, 5), (w2, 1999, 1)]
