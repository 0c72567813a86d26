"""NEXMark Query 5, Hot Items, at small sizes on the CPU: a keyed hopping
count on the fused chain, then `window_all(...).max_by(1)`, against a plain
loop over the same seeded stream.

The fast path (a fire reduced as columns on its way into the all-window,
runtime/fire_block.reduce_block) and the null-key window every other
aggregate gets are held to the same rows; the tie rule (the first row among
equals, which on a fused fire is the lowest key id) has a stream of its own.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.functions import (
    AggregateFunction,
    ProcessWindowFunction,
    null_key,
)
from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.config import Configuration, ExecutionOptions
from flink_tpu.connectors.source import Batch, DataGeneratorSource
from flink_tpu.core.keygroups import assign_to_key_group
from flink_tpu.core.time import TimeWindow
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.graph.transformation import plan
from flink_tpu.ops.aggregators import (
    PositionalAggregate,
    max_by_agg,
    min_by_agg,
    resolve,
)
from flink_tpu.runtime.executor import JobRuntime, WindowStepRunner
from flink_tpu.runtime.fire_block import FireBlock, reduce_block
from flink_tpu.runtime.oracle_window_operator import OracleWindowOperator

N = 24_000            # events
KEYS = 48
SPAN_MS = 24_000      # event time the stream covers
BID_BELOW = 46        # of 50 kinds


# ---------------------------------------------------------------------------
# seeded streams: [auction, event_kind] f32 records in event-time order
# ---------------------------------------------------------------------------

def _hash(idx, salt):
    return (idx * 2654435761 + salt * 40503) % 2147483647


def _uniform(idx):
    return _hash(idx, 1) % KEYS


def _zipf(idx):
    w = 1.0 / np.arange(1, KEYS + 1)
    u = (_hash(idx, 2) % 100_003 + 0.5) / 100_003
    return np.minimum(np.searchsorted(np.cumsum(w) / w.sum(), u), KEYS - 1)


def _tied(idx):
    # auctions 7, 3 and 11, in that order of arrival, take ten bids each in
    # every stretch of 40 events, ten other auctions one each; an event a
    # millisecond, so every window holds whole stretches
    at = idx % 40
    return np.where(at < 30, np.take([7, 3, 11], at % 3), 12 + at - 30)


STREAMS = {"uniform": _uniform, "zipf": _zipf, "tied": _tied}


def _columns(kind, idx):
    """(auction, event_kind, ts) of the events `idx`: an event a
    millisecond from t = 1 000, in order."""
    kinds = np.zeros_like(idx) if kind == "tied" else _hash(idx, 3) % 50
    return STREAMS[kind](idx), kinds, 1_000 + idx * SPAN_MS // N


def _source(kind):
    def gen(idx):
        key, ev, ts = _columns(kind, idx)
        return Batch(np.stack([key, ev], axis=1).astype(np.float32),
                     ts.astype(np.int64))

    return DataGeneratorSource(gen, N, num_splits=1)


def _plain_hot_items(kind, size, slide, ties=None):
    """[(auction, num), window.end - 1] per window that holds a bid: a loop
    over windows, `bincount` per window, the first maximum. `ties` collects
    the windows whose maximum more than one auction reaches."""
    key, ev, ts = _columns(kind, np.arange(N, dtype=np.int64))
    bid = ev < BID_BELOW
    out = []
    first = (ts.min() - size) // slide + 1
    for j in range(first, ts.max() // slide + 1):
        lo = j * slide
        inside = bid & (ts >= lo) & (ts < lo + size)
        if inside.any():
            counts = np.bincount(key[inside], minlength=KEYS)
            best = int(np.argmax(counts))
            out.append(((best, int(counts[best])), lo + size - 1))
            if ties is not None and (counts == counts[best]).sum() > 1:
                ties.append(lo + size - 1)
    return out


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

def _is_bid(col):
    return col[:, 1] < BID_BELOW - 0.5


def _auction_of(col):
    return col[:, 0].astype(jnp.int32)


class _PlainMaxBy1(AggregateFunction):
    """`max_by(1)` written by a user: the fast path cannot know it."""

    def create_accumulator(self):
        return None

    def add(self, value, acc):
        return value if acc is None or value[1] > acc[1] else acc

    def get_result(self, acc):
        return acc

    def merge(self, a, b):
        return b if a is None else a if b is None else self.add(b, a)


class _Hottest(ProcessWindowFunction):
    def process(self, key, context, elements):
        best = elements[0]
        for e in elements[1:]:
            if e[1] > best[1]:
                best = e
        yield best


def _config():
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, 512)
    cfg.set(ExecutionOptions.KEY_CAPACITY, 64)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    return cfg


class _Sink:
    """What a sink's `write_batch` received, whole."""

    def __init__(self):
        from flink_tpu.connectors.sink import Sink, SinkWriter

        rows = self.rows = []

        class Writer(SinkWriter):
            def write_batch(self, values, timestamps=None):
                rows.extend(zip(list(values),
                                np.asarray(timestamps).tolist()))

        class S(Sink):
            def create_writer(self):
                return Writer()

        self.sink = S()


def _build(kind, size, slide, second, all_size=None):
    env = StreamExecutionEnvironment(_config())
    ds = env.from_source(
        _source(kind),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0))
    assigner = (TumblingEventTimeWindows.of(size) if size == slide
                else SlidingEventTimeWindows.of(size, slide))
    counts = ds.filter(_is_bid, traceable=True) \
        .key_by(_auction_of, traceable=True).window(assigner).count()
    win = counts.window_all(TumblingEventTimeWindows.of(all_size or slide))
    if second == "max_by":
        out = win.max_by(1)
    elif second == "aggregate":
        out = win.aggregate(_PlainMaxBy1())
    else:
        out = win.process(_Hottest())
    sink = _Sink()
    out.sink_to(sink.sink)
    return env, sink


def _link(result):
    (op,) = [o for o in result.metrics["device"]["operators"].values()
             if "link" in o]
    return op["link"], op["stages"]


@pytest.mark.parametrize("kind", ["uniform", "zipf", "tied"])
@pytest.mark.parametrize("size,slide", [(10_000, 2_000), (2_000, 2_000)])
def test_hot_items_against_the_plain_loop(kind, size, slide):
    env, sink = _build(kind, size, slide, "max_by")
    result = env.execute("hot-items")
    ties = []
    want = _plain_hot_items(kind, size, slide, ties)
    assert sink.rows == want
    assert len(want) >= SPAN_MS // slide
    # bare rows of Python scalars, one per window, each window once
    assert all(type(v) is tuple and type(v[0]) is int and type(v[1]) is int
               for v, _t in sink.rows)
    assert len({t for _v, t in sink.rows}) == len(sink.rows)
    winners = {v[0] for v, _t in sink.rows}
    if kind == "tied":
        # three auctions reach the maximum of every whole window: the
        # lowest id wins, not the first to have arrived (7)
        assert len(ties) == len(want) and winners == {3}
    elif kind == "uniform":
        assert len(winners) > 3      # the answer moves from window to window
    # every fire went in as a block and one row of it came out
    link, stages = _link(result)
    assert link["fireBlocks"] == len(want)
    assert link["fireRowsReduced"] == link["rowsEmitted"] > len(want)
    assert link["fireRowsKept"] == link["fireBlocks"]
    assert stages["fire.reduce"]["count"] == len(want)


@pytest.mark.parametrize("kind", ["uniform", "tied"])
@pytest.mark.parametrize("second", ["aggregate", "process"])
def test_the_null_key_fallback_gives_the_fast_paths_rows(kind, second):
    fast_env, fast = _build(kind, 10_000, 2_000, "max_by")
    fast_env.execute("fast")
    env, sink = _build(kind, 10_000, 2_000, second)
    result = env.execute("fallback")
    assert sink.rows == fast.rows == _plain_hot_items(kind, 10_000, 2_000)
    link, stages = _link(result)
    assert link["fireRowsReduced"] == 0 and link["fireRowsKept"] == 0
    assert "fire.reduce" not in stages


def test_an_all_window_longer_than_the_hop_keeps_the_first_of_two_fires():
    """Not aligned with the upstream fire: a 4 s all-window holds two fires
    of the 2 s hop; one partial row per block, the earlier block first."""
    env, sink = _build("tied", 10_000, 2_000, "max_by", all_size=4_000)
    result = env.execute("two-fires")
    ref_env, ref = _build("tied", 10_000, 2_000, "aggregate", all_size=4_000)
    ref_env.execute("two-fires-ref")
    assert sink.rows == ref.rows and len(sink.rows) >= SPAN_MS // 4_000
    link, _stages = _link(result)
    assert link["fireRowsKept"] == link["fireBlocks"] > len(sink.rows)


@pytest.mark.parametrize("capture_at", [6_000, 9_000, 12_500, 17_000])
def test_a_checkpoint_between_the_two_fires_restores_to_the_same_output(
        capture_at):
    want = _plain_hot_items("uniform", 10_000, 2_000)
    env, _sink = _build("uniform", 10_000, 2_000, "max_by")
    rt = JobRuntime(plan(env._sinks), env.config)
    captured = {}

    class OneShot:
        def register_on_complete(self, fn):
            pass

        def maybe_trigger(self, capture):
            if not captured and rt.records_in >= capture_at:
                captured["snap"] = capture()
                raise KeyboardInterrupt     # crash right after the capture

    with pytest.raises(KeyboardInterrupt):
        rt.run(coordinator=OneShot())
    env2, sink2 = _build("uniform", 10_000, 2_000, "max_by")
    rt2 = JobRuntime(plan(env2._sinks), env2.config)
    rt2.restore(captured["snap"])
    rt2.run()
    seen = {t for _v, t in sink2.rows}
    assert sink2.rows == [r for r in want if r[1] in seen]
    assert sink2.rows and sink2.rows[-1] == want[-1]
    # the first job's sink and the second's share no window
    first = {t for _v, t in _sink.rows}
    assert not (first & seen) and len(first) + len(seen) == len(want)


def test_a_capture_can_hold_a_keyed_fire_the_all_window_has_not_seen():
    """The state the test above restores from, seen directly: a capture
    resolves the dispatches in flight, so a fire can sit in the fused
    operator's undrained output; restored, it comes back as a block of
    tuples, which takes the row way into the all-window."""
    env, _sink = _build("uniform", 10_000, 2_000, "max_by")
    rt = JobRuntime(plan(env._sinks), env.config)
    held = []

    class Every:
        def register_on_complete(self, fn):
            pass

        def maybe_trigger(self, capture):
            for snap in capture()["runners"].values():
                out = snap.get("operator", {}).get("output")
                if out:
                    held.append(len(out))

    rt.run(coordinator=Every())
    assert held, "no capture held an undrained keyed fire"


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------

def test_positional_aggregates_keep_the_first_row_among_equals():
    for agg, col, want in ((max_by_agg(1), [3, 9, 9, 1], 1),
                           (min_by_agg(1), [3, 1, 9, 1], 1),
                           (max_by_agg(0), [5, 5, 2, 5], 0)):
        rows = [(c, c) for c in col] if agg.position == 0 else \
            [(i, c) for i, c in enumerate(col)]
        acc = agg.create_accumulator()
        for r in rows:
            acc = agg.add(r, acc)
        assert agg.get_result(acc) == rows[want]
        assert agg.pick(np.asarray(col)) == want
        # merge keeps the earlier side's row among equals
        left = agg.add(rows[1], agg.add(rows[0], None))
        right = agg.add(rows[3], agg.add(rows[2], None))
        assert agg.merge(left, right) == rows[want]
        assert agg.merge(None, right) == right
        assert agg.merge(left, None) == left
        assert isinstance(agg, PositionalAggregate) and resolve(agg) is None


@pytest.mark.parametrize("position", [0, 1])
def test_reduce_block_is_the_row_the_rows_would_have_kept(position):
    rng = np.random.RandomState(5)
    w = TimeWindow(0, 2_000)
    for agg in (max_by_agg(position), min_by_agg(position)):
        for dtype in (np.int32, np.int64, np.float32):
            keys = np.sort(rng.choice(500, 64, replace=False)).astype(np.int64)
            res = rng.randint(0, 6, 64).astype(dtype)     # many ties
            block = FireBlock(w, keys, res, w.max_timestamp(), seq=3)
            acc = None
            for row in block.rows():
                acc = agg.add((row[0], row[2]), acc)
            got = reduce_block(block, agg)
            assert got == acc
            assert type(got[0]) is int
            assert type(got[1]) is (float if dtype is np.float32 else int)


def test_reduce_block_leaves_what_only_rows_can_judge():
    w = TimeWindow(0, 2_000)
    ts = w.max_timestamp()
    agg = max_by_agg(1)
    keys = np.arange(4)
    assert reduce_block(FireBlock(w, None, [(w, keys, keys)], ts), agg) is None
    assert reduce_block(FireBlock(w, keys, [1, 2, 3, 4], ts), agg) is None
    assert reduce_block(FireBlock(
        w, keys, np.asarray([1.0, np.nan, 3.0, 2.0], np.float32), ts),
        agg) is None
    assert reduce_block(FireBlock(w, keys, keys, ts), max_by_agg(2)) is None
    assert reduce_block(FireBlock(w, keys[:0], keys[:0], ts), agg) is None
    # a list of keys (the host-keyed path) with a plain result column
    assert reduce_block(FireBlock(w, ["a", "b", "c", "d"],
                                  np.asarray([1, 7, 7, 2]), ts),
                        agg) == ("b", 7)


def test_window_all_is_the_null_key_window():
    env = StreamExecutionEnvironment(_config())
    ds = env.from_collection([(1, 5), (2, 9), (3, 9)],
                             timestamp_fn=lambda v: 100)
    out = ds.window_all(TumblingEventTimeWindows.of(1_000)).max_by(1)
    t = out.transform
    assert t.kind == "window_aggregate"
    assert t.config["key_selector"] is null_key and null_key("x") is None
    assert assign_to_key_group(None, 128) == assign_to_key_group(None, 128)
    sink = out.collect()
    env.execute("null-key")
    assert sink.results == [(2, 9)]        # bare, the first among equals
    (runner,) = [r for r in JobRuntime(plan(env._sinks), env.config).runners
                 if isinstance(r, WindowStepRunner)]
    assert isinstance(runner.op, OracleWindowOperator)
    assert runner._block_agg is t.config["aggregate"]


def test_only_a_plain_positional_null_key_window_takes_blocks():
    from flink_tpu.api.windowing.triggers import CountTrigger

    def runner_of(build):
        env = StreamExecutionEnvironment(_config())
        ds = env.from_collection([(1, 5)], timestamp_fn=lambda v: 100)
        build(ds).collect()
        return [r for r in JobRuntime(plan(env._sinks), env.config).runners
                if isinstance(r, WindowStepRunner)][-1]

    tumbling = TumblingEventTimeWindows.of(1_000)
    assert runner_of(lambda ds: ds.window_all(tumbling).min_by(0)
                     )._block_agg is not None
    # a key, a trigger that counts elements, another aggregate: rows
    assert runner_of(lambda ds: ds.key_by(lambda v: 0).window(tumbling)
                     .max_by(1))._block_agg is None
    assert runner_of(lambda ds: ds.window_all(tumbling)
                     .trigger(CountTrigger.of(2)).max_by(1)
                     )._block_agg is None
    assert runner_of(lambda ds: ds.window_all(tumbling)
                     .aggregate(_PlainMaxBy1()))._block_agg is None
    r = runner_of(lambda ds: ds.window_all(tumbling).max_by(1))
    w = TimeWindow(0, 1_000)
    keys = np.arange(3)
    # rows, a bare hand-over, a block behind the watermark: not taken
    assert not r.on_fires_n(0, [(1, w, 2, 999)], False, None)
    block = FireBlock(w, keys, keys, 999)
    assert not r.on_fires_n(0, [block], True, None)
    r.op.process_watermark(999)
    assert not r.on_fires_n(0, [block], False, None)
