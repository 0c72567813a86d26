"""NEXMark Query 5 as its SQL statement (`q5.sql`: a window-TVF self-join
with MAX that keeps every auction at a window's maximum) through
`StreamTableEnvironment.sql_query` and `env.execute()`.

The statement parses as shipped; the planner rewrites it onto the fused hop
window (planner/rules.rewrite_window_maxima) and refuses, with its reason,
every near miss; the job's rows equal the benchmark's plain reference
(`benchmarks/references/hot_items_ties.py`) cell for cell on a seed whose
full windows tie and on one whose do not; the interpreted path gives the
same rows; the hand-over that picks a fire's maxima on its columns
(runtime/fire_block.window_maxima) and the row form beside it agree. The
cell `q5_sql_catchup` is rehearsed here too.
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import harness, reader
from benchmarks import reference as ref
from benchmarks.stream import T0_MS, build_cycle
from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.config import Configuration
from flink_tpu.connectors.sink import Sink, SinkWriter
from flink_tpu.connectors.source import (
    Batch, Source, SourceReader, SourceSplit, SplitEnumerator)
from flink_tpu.core.time import TimeWindow
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.runtime.fire_block import FireBlock, window_maxima
from flink_tpu.table import StreamTableEnvironment, TableSchema
from flink_tpu.table.sql import parse_query

CONFIG, CELL = "nexmark_q5_sql", "q5_sql_catchup"
KEYS, BATCH = 512, 1024
# 2 000 events per event-second over a 10 s cycle: ~36 bids an auction in a
# full window, so the maximum ties on some seeds and not on others
TRAFFIC = {"density_events_per_event_s": 2000, "cycle_ms": 10_000,
           "jitter_ms": 200}
TIED, UNTIED = 4000000002, 4000000001       # full windows: two maxima / one
EVENTS = 50 * BATCH                         # two laps and a half
OPTIONS = {"execution.step.batch-size": BATCH,
           "execution.state.key-capacity": KEYS}
SQL = harness.load_json("configs", CONFIG + ".json")["sql"]


def small_config():
    """The configuration's file with a key space a CPU test can afford."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG + ".json"))
    for column in cfg["stream"]["columns"]:
        if column["name"] == "auction":
            column["mod"] = KEYS
    cfg["reference"]["keys"] = KEYS
    return cfg


class CycleSource(Source):
    """The first `events` events of the stream, lap after lap, in batches."""

    boundedness = "BOUNDED"

    def __init__(self, cycle, events):
        self.cycle, self.events = cycle, events

    def create_enumerator(self):
        return SplitEnumerator([SourceSplit("cycle-0", {})])

    def create_reader(self):
        cycle, events = self.cycle, self.events

        class Reader(SourceReader):
            handed = 0

            def add_split(self, split) -> None:
                pass

            def poll_batch(self, max_records: int):
                if self.handed >= events:
                    return None
                lap, at = divmod(self.handed, cycle.events)
                n = min(max_records, events - self.handed)
                self.handed += n
                return Batch(cycle.values[at:at + n],
                             cycle.ts[at:at + n] + lap * cycle.cycle_ms)

        return Reader()


class RowSink(Sink):
    def __init__(self):
        self.rows = []          # (values as written, ts i64), a batch each

    def create_writer(self):
        rows = self.rows

        class Writer(SinkWriter):
            def write_batch(self, values, timestamps=None) -> None:
                rows.append((values, np.asarray(timestamps, np.int64)))

        return Writer()


def environment(**options):
    config = Configuration()
    for key, value in {**OPTIONS, **options}.items():
        config.set_string(key, value)
    return StreamExecutionEnvironment.get_execution_environment(config)


def run_job(cfg, cycle, events=EVENTS, **options):
    """The benchmark's job builder through `env.execute()`."""
    env = environment(**options)
    sink = RowSink()
    harness.load_module("jobs", cfg["job"]).build(
        env, CycleSource(cycle, events), sink, cfg, {})
    return sink.rows, env.execute("q5_sql_test")


def expected(cfg, cycle, events=EVENTS):
    refmod = harness.load_module("references", cfg["reference"]["module"])
    return refmod.expected(cycle, cfg["reference"], {}, cfg["window"],
                           events, TRAFFIC["jitter_ms"])


def table_env(cycle=None, events=0, **options):
    """`nexmark` and the `bid` view as the job builder registers them."""
    env = environment(**options)
    if cycle is None:
        cycle = build_cycle(small_config()["stream"], TRAFFIC, 1, wrap=BATCH)
    t_env = StreamTableEnvironment.create(env)
    stream = env.from_source(
        CycleSource(cycle, events),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(
            4000))
    t_env.register_table(
        SQL["table"], stream,
        TableSchema(list(SQL["columns"]), rowtime=SQL["rowtime"],
                    field_types=["int"] * len(SQL["columns"])),
        columnar=True)
    t_env.create_temporary_view(SQL["view"]["name"], SQL["view"]["statement"])
    return env, t_env


def nth(text, old, new, n):
    """`text` with the n-th (0-based) occurrence of `old` replaced."""
    at = -1
    for _ in range(n + 1):
        at = text.index(old, at + 1)
    return text[:at] + new + text[at + len(old):]


# -- the statement and the rewrite ----------------------------------------------

def test_the_statement_parses_as_shipped():
    q = parse_query(SQL["statement"])
    dj = q.derived_join
    assert (dj.left_alias, dj.right_alias) == ("AuctionBids", "MaxBids")
    assert [i.output_name for i in q.select] == ["auction", "num"]
    a, b = dj.left, dj.right
    assert a.tvf and a.table == "bid" and a.group_by == ["auction"]
    assert (a.window.kind, a.window.time_col, a.window.size_ms,
            a.window.slide_ms) == ("hop", "dateTime", 10_000, 2_000)
    assert [(i.kind, i.output_name) for i in a.select] == [
        ("column", "auction"), ("agg", "num"), ("window_start", "starttime"),
        ("window_end", "endtime")]
    assert b.table == "CountBids" and b.subquery.tvf
    assert b.group_by == ["CountBids.starttime", "CountBids.endtime"]
    assert b.select[0].func == "MAX" and b.select[0].name == "CountBids.num"
    assert dj.on_text.endswith("AuctionBids.num >= MaxBids.maxn")
    assert SQL["statement"].rstrip().endswith(";")


def test_the_rewrite_applies_to_q5_sql():
    _env, t_env = table_env()
    report = t_env.explain_sql(SQL["statement"])
    assert report.path == "fused" and report.reason is None
    out = report.plan.output
    assert out.maxima and out.roles == [("auction", "key"), ("num", "agg")]
    wa = report.plan.window_agg
    assert (wa.group_col, wa.agg.device_agg, wa.window.slice_ms) == \
        ("auction", "count", 2_000)
    # the view's WHERE is the filter, pushed into the device prologue
    assert report.plan.filter.text == "event_kind < 46"
    assert report.plan.filter.below_window
    assert report.plan.scan.table.name == "nexmark"
    assert "keep=window maxima, ties kept" in report.describe()


@pytest.mark.parametrize("old,new,n,why", [
    ("AuctionBids.num >= MaxBids.maxn", "AuctionBids.num > MaxBids.maxn", 0,
     "neither a window bound equated"),
    ("INTERVAL '2' SECOND", "INTERVAL '5' SECOND", 1,
     "differ in their window"),
    ("count(*) AS num", "max(price) AS num", 1, "differ in their aggregate"),
    ("TABLE bid", "TABLE nexmark", 1, "differ in their filter"),
    ("GROUP BY auction, window_start, window_end",
     "GROUP BY auction, window_start, window_end HAVING num > 3", 0,
     "HAVING"),
], ids=["strictly_greater", "two_windows", "two_aggregates", "two_filters",
        "a_having"])
def test_the_rewrite_does_not_apply(old, new, n, why):
    """A near miss keeps the interpreted path, with its reason."""
    _env, t_env = table_env()
    sql = nth(SQL["statement"], old, new, n)
    report = t_env.explain_sql(sql)
    assert report.path == "interpreted"
    assert report.reason == "window-maxima" and why in report.detail
    t_env.sql_query(sql)                    # the same report at translation
    assert t_env.last_plan_report.reason == "window-maxima"


def test_an_or_in_the_condition_is_refused_on_both_paths():
    _env, t_env = table_env()
    sql = SQL["statement"].replace(
        "AuctionBids.num >= MaxBids.maxn",
        "AuctionBids.num >= MaxBids.maxn OR AuctionBids.num = 1")
    report = t_env.explain_sql(sql)
    assert report.path == "interpreted" and report.reason == "window-maxima"
    assert "OR in the join condition" in report.detail
    with pytest.raises(NotImplementedError, match="OR in the condition"):
        t_env.sql_query(sql)


def test_a_near_miss_runs_on_the_interpreted_path():
    """`num > maxn` keeps no row on the host path either; the job says so
    in its metrics."""
    cfg = small_config()
    cycle = build_cycle(cfg["stream"], TRAFFIC, TIED, wrap=BATCH)
    env, t_env = table_env(cycle, 24 * BATCH)
    sql = SQL["statement"].replace(">=", ">")
    sink = RowSink()
    t_env.sql_query(sql).map(lambda r: (r["auction"], r["num"])).sink_to(sink)
    result = env.execute("q5_strict")
    assert result.metrics["sql"] == [t_env.last_plan_report.summary()]
    assert result.metrics["sql"][0]["path"] == "interpreted"
    assert sum(len(v) for v, _t in sink.rows) == 0


# -- the job against the reference ------------------------------------------------

@pytest.mark.parametrize("seed", [TIED, UNTIED], ids=["tied", "untied"])
def test_q5_sql_equals_the_reference_exactly(seed):
    cfg = small_config()
    cycle = build_cycle(cfg["stream"], TRAFFIC, seed, wrap=BATCH)
    rows, result = run_job(cfg, cycle)
    expect, j0 = expected(cfg, cycle)
    cmp = ref.compare(reader.unpack_rows(rows), expect, j0, cfg["window"],
                      result.records_in, EVENTS)
    assert cmp["numbers"] == {name: 0 for name in ref.LIMITS}
    per_window = (expect > 0).sum(axis=1)
    # the windows that hold one whole cycle: all the same auctions
    size, slide = cfg["window"]["size_ms"], cfg["window"]["slide_ms"]
    starts = (j0 + np.arange(len(expect))) * slide
    last_ms = (T0_MS + EVENTS * 1000 // TRAFFIC["density_events_per_event_s"]
               - TRAFFIC["jitter_ms"])
    full = per_window[(starts >= T0_MS) & (starts + size <= last_ms)]
    assert len(full) >= 5 and (full == full[0]).all()
    assert full[0] == (2 if seed == TIED else 1)
    assert cmp["rows_compared"] == cmp["cells_compared"] == per_window.sum()
    assert result.metrics["sql"][0]["path"] == "fused"
    (op,) = result.metrics["device"]["operators"].values()
    assert "fused_chained_superscan" in op["compile"]["programs"]
    # the maxima were picked on the fire's columns: every row the fires
    # held was reduced, the kept ones built as rows, one span a window
    link, stages = op["link"], op["stages"]
    assert link["fireRowsReduced"] == link["rowsEmitted"]
    assert link["fireRowsKept"] == cmp["rows_compared"]
    assert stages["fire.reduce"]["count"] == link["fireBlocks"]
    assert stages["table.output"]["count"] == (per_window > 0).sum()


def test_the_interpreted_path_gives_the_same_rows():
    cfg = small_config()
    cycle = build_cycle(cfg["stream"], TRAFFIC, TIED, wrap=BATCH)
    events = 24 * BATCH
    fused, _r = run_job(cfg, cycle, events)
    env, t_env = table_env(cycle, events, **{"table.device-fusion": "false"})
    sink = RowSink()
    t_env.sql_query(SQL["statement"]).map(
        lambda r: (r["auction"], r["num"])).sink_to(sink)
    result = env.execute("q5_interpreted")
    interpreted = sink.rows
    assert result.metrics["sql"][0]["reason"] == "disabled"

    def rows_of(batches):
        return sorted((int(t), int(k), int(n)) for values, ts in batches
                      for (k, n), t in zip(values, ts))

    assert rows_of(interpreted) == rows_of(fused) and len(rows_of(fused))
    expect, j0 = expected(cfg, cycle, events)
    cmp = ref.compare(reader.unpack_rows(interpreted), expect, j0,
                      cfg["window"], result.records_in, events)
    assert cmp["numbers"] == {name: 0 for name in ref.LIMITS}


def test_the_build_refuses_a_plan_that_is_not_fused():
    cfg = small_config()
    cfg["sql"]["statement"] = cfg["sql"]["statement"].replace(">=", ">")
    cycle = build_cycle(cfg["stream"], TRAFFIC, UNTIED, wrap=BATCH)
    with pytest.raises(RuntimeError, match="not fused"):
        run_job(cfg, cycle, 4 * BATCH)


# -- the hand-over: columns and rows ----------------------------------------------

def block(keys, results, ts, seq=None):
    w = TimeWindow(ts + 1 - 10_000, ts + 1)
    return FireBlock(w, np.asarray(keys), np.asarray(results), ts, seq)


def test_window_maxima_keeps_every_tied_key_in_key_order():
    kept = window_maxima([block([9, 2, 5, 7], [4, 6, 6, 1], 1999, seq=3),
                          block([1, 4], [6, 2], 1999),
                          block([3], [8], 3999, seq=4),
                          block([], [], 5999)])
    assert [(b.ts, b.keys.tolist(), b.results.tolist(), b.seq)
            for b in kept] == [(1999, [1, 2, 5], [6, 6, 6], 3),
                               (3999, [3], [8], 4)]
    # only rows can say: no key column, a NaN, a column of objects
    assert window_maxima([FireBlock(None, None, np.ones(2), 9)]) is None
    assert window_maxima([block([1, 2], [np.nan, 1.0], 9)]) is None
    assert window_maxima([FireBlock(None, [1], [2], 9)]) is None


def test_the_row_form_keeps_what_the_block_form_keeps():
    _env, t_env = table_env()
    t = t_env.sql_query(SQL["statement"]).transform
    assert t.kind == "flat_map" and t.config["with_timestamps"]
    blocks = [block([9, 2, 5, 7], [4, 6, 6, 1], 1999),
              block([3, 1], [8, 8], 3999), block([0], [1], 5999)]
    by_columns = [r for b in window_maxima(blocks)
                  for r in t.config["window_maxima_rows"](b)]
    pairs = [(k, r) for b in blocks
             for k, r in zip(b.keys.tolist(), b.results.tolist())]
    ts = np.repeat([b.ts for b in blocks], [len(b) for b in blocks])
    vals = np.empty(len(pairs), dtype=object)
    vals[:] = pairs
    by_rows, idx = t.config["fn"](vals, ts)
    assert list(by_rows) == by_columns == [
        {"auction": 2, "num": 6}, {"auction": 5, "num": 6},
        {"auction": 1, "num": 8}, {"auction": 3, "num": 8},
        {"auction": 0, "num": 1}]
    assert ts[idx].tolist() == [1999, 1999, 3999, 3999, 5999]


# -- views and derived tables ------------------------------------------------------

def test_a_statement_over_the_view_fuses_with_its_filter():
    _env, t_env = table_env()
    report = t_env.explain_sql(
        "SELECT auction, COUNT(*) AS num FROM bid GROUP BY auction, "
        "HOP(dateTime, INTERVAL '2' SECOND, INTERVAL '10' SECOND)")
    assert report.path == "fused"
    assert report.plan.filter.text == "event_kind < 46"
    # a column the view leaves out is not there for the statement
    hidden = t_env.explain_sql(
        "SELECT auction, COUNT(*) AS n FROM bid WHERE event_kind < 3 "
        "GROUP BY auction, HOP(dateTime, INTERVAL '2' SECOND, "
        "INTERVAL '10' SECOND)")
    assert (hidden.path, hidden.reason) == ("interpreted", "unknown-column")


def test_a_view_that_is_no_projection_keeps_the_interpreted_path():
    _env, t_env = table_env()
    t_env.create_temporary_view(
        "busy", "SELECT auction, COUNT(*) AS n FROM nexmark GROUP BY "
        "auction, TUMBLE(dateTime, INTERVAL '10' SECOND)")
    report = t_env.explain_sql(
        "SELECT auction, MAX(n) AS m FROM busy GROUP BY auction, "
        "TUMBLE(dateTime, INTERVAL '10' SECOND)")
    assert (report.path, report.reason) == ("interpreted", "view")


def test_a_per_window_max_over_a_derived_table_runs_interpreted():
    cfg = small_config()
    cycle = build_cycle(cfg["stream"], TRAFFIC, TIED, wrap=BATCH)
    events = 24 * BATCH
    env, t_env = table_env(cycle, events)
    sql = ("SELECT max(c.num) AS maxn, c.endtime FROM ("
           "SELECT count(*) AS num, window_end AS endtime FROM TABLE("
           "HOP(TABLE bid, DESCRIPTOR(dateTime), INTERVAL '2' SECOND, "
           "INTERVAL '10' SECOND)) GROUP BY auction, window_start, "
           "window_end) AS c GROUP BY c.endtime")
    sink = RowSink()
    t_env.sql_query(sql).map(
        lambda r: (r["endtime"], r["maxn"])).sink_to(sink)
    assert t_env.last_plan_report.reason == "derived-table"
    env.execute("per_window_max")
    got = sorted((int(e), int(m)) for values, _ts in sink.rows
                 for e, m in values)
    expect, j0 = expected(cfg, cycle, events)
    size, slide = cfg["window"]["size_ms"], cfg["window"]["slide_ms"]
    want = sorted(((j0 + j) * slide + size, int(row.max()))
                  for j, row in enumerate(expect) if row.any())
    assert got == want


def test_stream_table_environment_is_the_table_environment():
    env = environment()
    t_env = StreamTableEnvironment.create(env)
    assert t_env.env is env
    with pytest.raises(ValueError, match="registered table"):
        table_env()[1].create_temporary_view("nexmark", "SELECT a FROM b")


# -- the benchmark's cell ------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.HERE, "references",
                           "hot_items_ties.py")) as f:
        assert "flink_tpu" not in f.read().replace(
            "imports nothing of `flink_tpu`", "")


def test_the_rehearsal_is_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", "4000000011", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True
    assert all(c["value"] == 0 for c in out["compared"].values())
