"""`nexmark_q5_sql` is what it says it is: the configuration's file against
`nexmark_q5_hot_items.json` (the same stream word for word, the statement and
the view as text), the cell's entries in `BENCHMARK.json` by the names this
file knows (no exact list: an entry a later PR appends breaks nothing here),
the plain reference's tied windows against a loop on one seed, the reader of
`table_output_pct.catchup` over a hand-made trace, and the cell's rehearsal.
The job against the reference at small sizes runs in tier-1:
`tests/test_q5_sql.py`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr
from benchmarks.stream import build_cycle

CONFIG, TWIN, CELL = "nexmark_q5_sql", "nexmark_q5_hot_items", "q5_sql_catchup"
TWIN_CELL, METRIC = "q5_hot_items_catchup", "table_output_pct.catchup"


def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_file_isolates_the_front_door():
    cfg = harness.load_json("configs", CONFIG + ".json")
    twin = harness.load_json("configs", TWIN + ".json")
    (entry,) = [c for c in bench()["configs"] if c["name"] == CONFIG]
    assert cfg["name"] == CONFIG and len(cfg["source"]) <= 200
    assert entry["source"] == cfg["source"] and "q5.sql" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["num_auctions"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    # the pair differs in the front door and the tie rule alone
    for key in ("stream", "window", "out_of_orderness_ms", "options",
                "programs", "trace_modules", "roofline", "num_auctions"):
        assert cfg[key] == twin[key], key
    assert cfg["reference"] == dict(twin["reference"],
                                    module="hot_items_ties")
    assert cfg["job"] == "q5_sql" and twin["job"] == "hot_items_traced"
    assert set(cfg["guarantees"]) == set(twin["guarantees"])
    assert "every tied auction" in cfg["guarantees"]["results"]
    assert {"num_auctions", "auctions", "ties", "columns", "view",
            "statement"} <= set(cfg["assumed"])
    sql = cfg["sql"]
    assert sql["table"] == "nexmark" and sql["rowtime"] == "dateTime"
    assert len(sql["columns"]) == len(cfg["stream"]["columns"])
    assert sql["view"]["name"] == "bid"
    assert sql["view"]["statement"].endswith("WHERE event_kind < 46")
    assert "HOP(TABLE bid, DESCRIPTOR(dateTime), INTERVAL '2' SECOND, " \
        "INTERVAL '10' SECOND)" in sql["statement"]
    assert sql["statement"].count("JOIN") == 1
    assert "AuctionBids.num >= MaxBids.maxn" in sql["statement"]


def test_the_cell_is_declared_as_new_entries_beside_q5_hot_items_catchup():
    b = bench()
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "catchup", 1)
    assert len(cell["why"]) <= 200
    names = [w["name"] for w in b["workloads"]]
    assert names.index(CELL) > names.index(TWIN_CELL)     # appended
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"events_per_s",
                                                       "setup_s"}
    mine = {m["name"] for m in spec["per_layer"]}
    twin = {m["name"] for m in harness.load_cell(TWIN_CELL)["per_layer"]}
    # every metric that lists the twin lists the cell, and one more
    assert mine - twin == {METRIC} and twin <= mine
    (metric,) = [m for m in b["per_layer"] if m["name"] == METRIC]
    assert metric == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "emission and sink",
        "moves": "events_per_s", "workloads": [CELL]}
    assert [m["name"] for m in b["per_layer"]].index(METRIC) == \
        len(b["per_layer"]) - 1                             # appended
    assert os.path.isfile(os.path.join(harness.HERE, "layer_metrics",
                                       METRIC + ".py"))
    assert os.path.isfile(os.path.join(harness.HERE, "jobs", "q5_sql.py"))


def test_the_reference_counts_the_tied_windows_on_one_seed():
    """Every window that holds a bid keeps each auction at its maximum: the
    reference's rows against a loop over the same seeded events."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    for column in cfg["stream"]["columns"]:
        if column["name"] == "auction":
            column["mod"] = 64
    sem = dict(cfg["reference"], keys=64)
    traffic = {"density_events_per_event_s": 400, "cycle_ms": 10_000,
               "jitter_ms": 200}
    cycle = build_cycle(cfg["stream"], traffic, 4000000002, wrap=256)
    events = cycle.events * 2 + 1_000
    refmod = harness.load_module("references", "hot_items_ties")
    expect, j0 = refmod.expected(cycle, sem, {}, cfg["window"], events, 200)
    size, slide = cfg["window"]["size_ms"], cfg["window"]["slide_ms"]
    counts = {}
    for i in range(events):
        lap, at = divmod(i, cycle.events)
        if cycle.values[at, 1] >= 46:
            continue
        ts = int(cycle.ts[at]) + lap * cycle.cycle_ms
        for j in range((ts - size) // slide + 1, ts // slide + 1):
            cell = (j, int(cycle.values[at, 0]))
            counts[cell] = counts.get(cell, 0) + 1
    top = {}
    for (j, _k), n in counts.items():
        top[j] = max(top.get(j, 0), n)
    want = {cell: n for cell, n in counts.items() if n == top[cell[0]]}
    got = {(j0 + r, k): int(v) for (r, k), v in np.ndenumerate(expect) if v}
    assert got == want
    tied = sum(1 for j in top if sum(1 for c in want if c[0] == j) > 1)
    assert tied == int(((expect > 0).sum(axis=1) > 1).sum()) > 0
    assert tied < len(top)


def test_the_reader_takes_the_self_time_of_the_table_spans():
    """job thread, window [0, 1000): drain [100, 400) holds fire.reduce
    [110, 130), table.output [130, 180) and sink.write [200, 220)."""
    trace = tr.Trace({tr.HOST_PLANE: {"job": [
        ("benchmark.poll_batch", 0, 100), ("flink_tpu.drain", 100, 400),
        ("flink_tpu.fire.reduce", 110, 130),
        ("flink_tpu.table.output", 130, 180),
        ("flink_tpu.sink.write", 200, 220)]}})
    ctx = {"trace": trace, "trace_window": (0, 1000)}

    def read(name):
        return harness.load_module("layer_metrics", name).read(ctx)

    assert read(METRIC) == pytest.approx(5.0)
    assert read("fire_reduce_pct.catchup") == pytest.approx(2.0)
    # a program that writes no table span: the reader finds nothing
    trace.planes[tr.HOST_PLANE]["job"].pop(3)
    assert read(METRIC) is None


def test_the_rehearsal_is_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", "4000000012", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True
    assert all(c["value"] == 0 for c in out["compared"].values())
