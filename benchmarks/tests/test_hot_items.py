"""`nexmark_q5_hot_items` is what it says it is: the plain reference against
a brute-force loop, the configuration's file against the issue's wording and
against `ysb_keys64k.json`, the cell through `run.py --rehearse-cpu` and its
control, and the reader of `fire_reduce_pct.catchup` on a hand-made span
list."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import harness, span_lib
from benchmarks import trace_reduce as tr
from benchmarks.stream import T0_MS, Cycle, build_cycle

CONFIG, TWIN, CELL = "nexmark_q5_hot_items", "ysb_keys64k", "q5_hot_items_catchup"
RUN = os.path.join(harness.HERE, "run.py")


# -- the plain reference ------------------------------------------------------

def tiny_cycle(events=2_000, keys=8):
    """A 2 000-event cycle of 2 s, one event a millisecond, keys in a fixed
    rotation so that every window's maximum is reached by several keys."""
    idx = np.arange(events)
    key = np.take([5, 2, 6, 2, 5, 6, 1], idx % 7) % keys
    kind = idx % 50
    values = np.stack([key, kind], axis=1).astype(np.float32)
    ts = (T0_MS + idx - idx % 3).astype(np.int64)       # up to 2 ms behind
    return Cycle(values, ts, events, 2_000, ["auction", "event_kind"], 1000.0)


def brute_force(cycle, events, window, keys, below, jitter_ms):
    """{window index: (auction, num)} by a loop over events and windows."""
    size, slide = window["size_ms"], window["slide_ms"]
    counts = {}
    for i in range(events):
        lap, at = divmod(i, cycle.events)
        if cycle.values[at, 1] >= below:
            continue
        ts = int(cycle.ts[at]) + lap * cycle.cycle_ms
        for j in range((ts - size) // slide + 1, ts // slide + 1):
            row = counts.setdefault(j, [0] * keys)
            row[int(cycle.values[at, 0])] += 1
    out = {}
    for j, row in counts.items():
        best = max(row)
        out[j] = (row.index(best), best)         # the lowest id among equals
    return out


@pytest.mark.parametrize("window", [{"size_ms": 1000, "slide_ms": 200},
                                    {"size_ms": 400, "slide_ms": 400}])
def test_the_reference_is_the_brute_force_loop(window):
    refmod = harness.load_module("references", "hot_items")
    cycle = tiny_cycle()
    sem = {"filter": {"column": "event_kind", "keep_below": 46},
           "key": {"column": "auction"}, "keys": 8, "tables": {}}
    events = 2_000 + 700                           # a lap and a part of one
    expect, j0 = refmod.expected(cycle, sem, {}, window, events, 2)
    want = brute_force(cycle, events, window, 8, 46, 2)
    got = {j0 + r: (int(np.flatnonzero(row)[0]), int(row.max()))
           for r, row in enumerate(expect) if row.any()}
    assert got == want
    assert all((row > 0).sum() <= 1 for row in expect)
    # ties are in it: some window's maximum is reached by two auctions
    counts, _ = refmod.kwc.expected(cycle, sem, {}, window, events, 2)
    tied = [(row == row.max()).sum() > 1 for row in counts if row.any()]
    assert any(tied)
    # the control moves the answer: the first 300 events counted twice
    broken, _ = refmod.expected(cycle, sem, {}, window, events, 2,
                                replay=(0, 300))
    assert (broken != expect).any()


def test_hottest_keeps_one_cell_per_window_the_lowest_id_among_equals():
    refmod = harness.load_module("references", "hot_items")
    counts = np.array([[0, 0, 0, 0], [1, 3, 3, 2], [0, 0, 5, 5], [4, 0, 0, 4]],
                      np.int32)
    assert refmod.hottest(counts).tolist() == [
        [0, 0, 0, 0], [0, 3, 0, 0], [0, 0, 5, 0], [4, 0, 0, 0]]


def test_the_reference_imports_nothing_of_the_program():
    for name in ("hot_items.py", "keyed_window_count.py"):
        with open(os.path.join(harness.HERE, "references", name)) as f:
            assert "flink_tpu" not in f.read().replace(
                "imports nothing of `flink_tpu`", "")


# -- the configuration's file ---------------------------------------------------

def test_the_configuration_file_says_what_the_issue_says():
    cfg = harness.load_json("configs", CONFIG + ".json")
    twin = harness.load_json("configs", TWIN + ".json")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert cfg["name"] == CONFIG and len(cfg["source"]) <= 200
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cols = cfg["stream"]["columns"]
    assert [c["name"] for c in cols] == [
        "auction", "event_kind", "bidder", "price", "channel", "url",
        "date_time"]
    assert [c.get("mod") for c in cols] == [65536, 50, 1000, 10000, 4, 256, None]
    assert cols[-1]["kind"] == "event_time_ms"
    assert not any("dist" in c for c in cols)             # uniform
    assert cfg["stream"]["draw_order"][:2] == ["auction", "event_kind"]
    assert cfg["window"] == {"size_ms": 10000, "slide_ms": 2000}
    assert cfg["out_of_orderness_ms"] == 4000 and cfg["options"] == {}
    assert cfg["reduced"] == ["num_auctions"] and cfg["num_auctions"] == 65536
    assert set(cfg["assumed"]) == {
        "num_auctions", "auctions", "ties", "columns", "density",
        "out_of_orderness_ms", "jitter_ms"}
    assert cfg["reference"] == {
        "module": "hot_items",
        "filter": {"column": "event_kind", "keep_below": 46},
        "key": {"column": "auction"}, "keys": 65536, "tables": {}}
    assert cfg["programs"] == ["fused_chained_superscan"]
    assert cfg["trace_modules"] == ["jit_run_fused_chained_superscan"]
    assert cfg["roofline"]["staged_bytes_per_event"] == 12
    assert cfg["roofline"]["share_of_events_reaching_device"] == 1.0
    # the twin's guarantees, but for what a result row is and the bound
    # on out-of-orderness the watermark states
    assert set(cfg["guarantees"]) == set(twin["guarantees"])
    differ = {k for k in twin["guarantees"]
              if cfg["guarantees"][k] != twin["guarantees"][k]}
    assert differ == {"results", "late_events"}
    assert "lowest auction id among equals" in cfg["guarantees"]["results"]
    assert "none dropped" in cfg["guarantees"]["late_events"]


def test_the_cell_is_declared_as_new_entries_beside_keys64k_catchup():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "catchup", 1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    spec = harness.load_cell(CELL)
    assert [m["name"] for m in spec["end_to_end"]] == ["events_per_s", "setup_s"]
    mine = {m["name"] for m in spec["per_layer"]}
    twin = {m["name"] for m in harness.load_cell("keys64k_catchup")["per_layer"]}
    assert mine - twin == {"fire_reduce_pct.catchup"} and twin <= mine
    (metric,) = [m for m in bench["per_layer"]
                 if m["name"] == "fire_reduce_pct.catchup"]
    assert metric == {
        "name": "fire_reduce_pct.catchup", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "emission and sink",
        "moves": "events_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] is metric


def test_a_cycle_is_92_pct_bids_and_its_partial_windows_move_the_winner():
    """The real density on one seed. The traffic replays one 10 s cycle, so
    every full window holds one whole cycle: one winner. The partial windows
    at both ends of a run have winners of their own, some by the tie rule."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    traffic = harness.load_json("traffic", "catchup.json")
    cycle = build_cycle(cfg["stream"], traffic, 3200000007, wrap=65536)
    assert cycle.events == 10_000_000
    assert abs(np.mean(cycle.column("event_kind") < 46) - 0.92) < 0.001
    auctions = cycle.column("auction").astype(np.int64)
    assert auctions.max() == 65_535 and len(np.unique(auctions)) == 65_536
    refmod = harness.load_module("references", "hot_items")
    counts, _j0 = refmod.kwc.expected(
        cycle, cfg["reference"], {}, cfg["window"], 3 * cycle.events, 200)
    live = counts[counts.any(axis=1)]
    top = live.max(axis=1)
    winners = live.argmax(axis=1)
    at_top = (live == top[:, None]).sum(axis=1)
    full = top == top.max()
    assert len(live) == 20 and full.sum() == 10 and top.max() == 198
    assert len(set(winners[full].tolist())) == 1
    assert len(set(winners[~full].tolist())) >= 8      # the ends move
    assert (at_top[~full] > 1).sum() == 3              # decided by the tie rule
    assert winners[0] != 0 and live[full][0].mean() == pytest.approx(140.4, abs=0.3)


# -- the cell, rehearsed --------------------------------------------------------

def rehearse(*flags):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3200000011",
         "--rehearse-cpu", *flags],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_rehearsal_is_correct_and_its_control_is_not():
    out = rehearse()
    assert out["rehearsal"] is True and out["correct"] is True
    assert all(c["value"] == 0 for c in out["compared"].values())
    control = rehearse("--control", "replay_batch")
    assert control["correct"] is False
    assert control["compared"]["cells_wrong"]["value"] \
        + control["compared"]["cells_missing"]["value"] >= 1


# -- the reader -------------------------------------------------------------------

def span_trace(with_reduce=True):
    """The job's thread, ns, window [0, 1000): drain [600,800) holds
    fire.reduce [610,650) and [660,670) and sink.write [700,760)."""
    events = [["benchmark.traced_window", 0, 1000],
              ["benchmark.poll_batch", 0, 100],
              ["flink_tpu.stage.fill", 100, 300],
              ["flink_tpu.emit", 500, 50],
              ["flink_tpu.drain", 600, 200],
              ["flink_tpu.sink.write", 700, 60]]
    if with_reduce:
        events += [["flink_tpu.fire.reduce", 610, 40],
                   ["flink_tpu.fire.reduce", 660, 10]]
    return tr.Trace({tr.HOST_PLANE: {"job": [tr._norm(*e) for e in events]}})


def test_fire_reduce_pct_reads_the_spans_self_time():
    reader = harness.load_module("layer_metrics", "fire_reduce_pct.catchup")
    emit = harness.load_module("layer_metrics", "emit_pct.catchup")
    trace = span_trace()
    ctx = {"trace": trace, "trace_window": tr.window_of(trace)}
    assert ctx["trace_window"] == (0, 1000)
    assert reader.read(ctx) == pytest.approx(5.0)
    # the reduce is taken out of the drain that encloses it
    assert emit.read(ctx) == pytest.approx(5.0 + (200 - 50 - 60) / 10)
    times = span_lib.self_times(ctx)
    assert times["flink_tpu.fire.reduce"] == 50


def test_fire_reduce_pct_is_absent_where_no_fire_is_reduced():
    reader = harness.load_module("layer_metrics", "fire_reduce_pct.catchup")
    trace = span_trace(with_reduce=False)
    ctx = {"trace": trace, "trace_window": tr.window_of(trace)}
    assert reader.read(ctx) is None
    # a program without the stage clock: no flink_tpu.* span at all
    host = trace.planes[tr.HOST_PLANE]
    host["job"] = [e for e in host["job"] if not e[0].startswith(span_lib.PROGRAM)]
    assert reader.read(ctx) is None
