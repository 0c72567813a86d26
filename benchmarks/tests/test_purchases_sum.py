"""`purchases_windowed_sum` is what it says it is: the configuration's file
against the issue's wording and against `ysb_keys64k.json`, the cell's
entries in `BENCHMARK.json` by the names this file knows (no exact list: an
entry a later PR appends breaks nothing here), the plain reference against a
brute-force loop and its control through the comparison, and the reader of
`value_ingest_ms.catchup` over the hand-written capture. The cell's rehearsal
(`run.py --rehearse-cpu`, with and without `--control replay_batch`) and the
check that the reference imports nothing of the program run in tier-1:
`tests/test_purchases_sum.py`."""

import json
import os

import numpy as np
import pytest

from benchmarks import harness
from benchmarks import reference as ref
from benchmarks import trace_reduce as tr
from benchmarks.stream import T0_MS, Cycle, build_cycle

CONFIG, TWIN, CELL = "purchases_windowed_sum", "ysb_keys64k", "purchases_sum_catchup"
TWIN_CELL, METRIC = "keys64k_catchup", "value_ingest_ms.catchup"
FIXTURES = os.path.join(harness.HERE, "fixtures")


# -- the plain reference ------------------------------------------------------

def tiny_cycle(events=2_000, keys=8):
    """A 2 000-event cycle of 2 s, one purchase a millisecond, up to 2 ms
    behind its creation; prices below 10 000, some of them 0."""
    idx = np.arange(events)
    key = np.take([5, 2, 6, 2, 5, 6, 1], idx % 7) % keys
    price = (idx * 7919) % 10_000 * (idx % 11 != 0)
    values = np.stack([key, price, idx % keys, idx], axis=1).astype(np.float32)
    ts = (T0_MS + idx - idx % 3).astype(np.int64)
    return Cycle(values, ts, events, 2_000,
                 ["gem_pack_id", "price", "user_id", "time"], 1000.0)


def brute_force(cycle, events, window):
    """{(window index, gem pack): sum of prices} by a loop over purchases and
    the windows each belongs to."""
    size, slide = window["size_ms"], window["slide_ms"]
    sums = {}
    for i in range(events):
        lap, at = divmod(i, cycle.events)
        ts = int(cycle.ts[at]) + lap * cycle.cycle_ms
        for j in range((ts - size) // slide + 1, ts // slide + 1):
            cell = (j, int(cycle.values[at, 0]))
            sums[cell] = sums.get(cell, 0) + int(cycle.values[at, 1])
    return sums


@pytest.mark.parametrize("window", [{"size_ms": 800, "slide_ms": 400},
                                    {"size_ms": 1000, "slide_ms": 200},
                                    {"size_ms": 400, "slide_ms": 400}])
def test_the_reference_is_the_brute_force_loop(window):
    refmod = harness.load_module("references", "keyed_window_sum")
    cycle = tiny_cycle()
    sem = {"key": {"column": "gem_pack_id"}, "value": {"column": "price"},
           "keys": 8, "tables": {}}
    events = 2_000 + 700                           # a lap and a part of one
    expect, j0 = refmod.expected(cycle, sem, {}, window, events, 2)
    want = {cell: v for cell, v in brute_force(cycle, events, window).items()
            if v}
    got = {(j0 + r, k): int(v) for (r, k), v in np.ndenumerate(expect) if v}
    assert got == want and expect.dtype == np.int32
    # the control moves the answer: the first 300 purchases summed twice
    broken, _ = refmod.expected(cycle, sem, {}, window, events, 2,
                                replay=(0, 300))
    assert (broken != expect).any() and (broken >= expect).all()
    # and the comparison sees it, as wrong cells and nothing else
    numbers = ref.compare(ref.rows_of(broken, j0, window), expect, j0, window,
                          events, events)["numbers"]
    assert numbers["cells_wrong"] >= 1 and not ref.verdict(numbers, ref.LIMITS)
    sound = ref.compare(ref.rows_of(expect, j0, window), expect, j0, window,
                        events, events)["numbers"]
    assert ref.verdict(sound, ref.LIMITS)


# -- the configuration's file ---------------------------------------------------

def test_the_configuration_file_says_what_the_issue_says():
    cfg = harness.load_json("configs", CONFIG + ".json")
    twin = harness.load_json("configs", TWIN + ".json")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert cfg["name"] == CONFIG and len(cfg["source"]) <= 200
    assert "arXiv:1802.08496" in cfg["source"]
    assert "SELECT SUM(price) FROM PURCHASES [Range 8s, Slide 4s] " \
        "GROUP BY gemPackID" in cfg["source"]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cols = cfg["stream"]["columns"]
    assert [c["name"] for c in cols] == ["gem_pack_id", "price", "user_id", "time"]
    assert [c.get("mod") for c in cols] == [65536, 10000, 65536, None]
    assert cols[-1]["kind"] == "event_time_ms"
    assert not any("dist" in c for c in cols)             # uniform
    assert cfg["stream"]["draw_order"] == ["gem_pack_id", "price", "user_id"]
    assert cfg["window"] == {"size_ms": 8000, "slide_ms": 4000}
    assert cfg["out_of_orderness_ms"] == 250 and cfg["options"] == {}
    assert cfg["num_gem_packs"] == 65536
    assert {"num_gem_packs", "gem_packs", "price", "user_id", "density",
            "out_of_orderness_ms"} <= set(cfg["assumed"])
    assert all(len(why) > 40 for why in cfg["assumed"].values())
    assert cfg["job"] == "keyed_sum_traced"
    assert cfg["reference"] == {
        "module": "keyed_window_sum", "key": {"column": "gem_pack_id"},
        "value": {"column": "price"}, "keys": 65536, "tables": {}}
    assert cfg["programs"] == ["fused_chained_superscan"]
    assert cfg["trace_modules"] == ["jit_run_fused_chained_superscan"]
    assert cfg["roofline"]["staged_bytes_per_event"] == 12
    assert cfg["roofline"]["share_of_events_reaching_device"] == 1.0
    assert "ONE i32 ring" in cfg["roofline"]["why"]
    # the twin's guarantees, but for what is summed and what a row holds
    assert set(cfg["guarantees"]) == set(twin["guarantees"])
    same = {k for k in twin["guarantees"]
            if cfg["guarantees"][k] == twin["guarantees"][k]}
    assert same == {"late_events", "completeness"}
    assert "exactly-once" in cfg["guarantees"]["delivery"]
    assert "exact: the integer sum of the prices" in cfg["guarantees"]["results"]


def test_the_cell_is_declared_as_new_entries_beside_keys64k_catchup():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "catchup", 1)
    assert len(cell["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) > names.index(TWIN_CELL)     # appended, not put in
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"events_per_s", "setup_s"}
    mine = {m["name"] for m in spec["per_layer"]}
    twin = {m["name"] for m in harness.load_cell(TWIN_CELL)["per_layer"]}
    # every metric that lists the twin lists the cell; what the cell has
    # besides reads what only a VALUE field gives
    assert twin <= mine and METRIC in mine - twin
    entries = {m["name"]: m for m in bench["per_layer"]}
    metric = entries[METRIC]
    assert {k: metric[k] for k in metric if k != "workloads"} == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device program",
        "moves": "events_per_s"}
    assert CELL in metric["workloads"] and TWIN_CELL not in metric["workloads"]
    per_layer = [m["name"] for m in bench["per_layer"]]
    assert per_layer.index(METRIC) > per_layer.index("ingest_ms.catchup")
    for name in mine:
        assert os.path.isfile(os.path.join(
            harness.HERE, "layer_metrics", name + ".py")), name
    for kind, name in (("jobs", "keyed_sum_traced"),
                       ("references", "keyed_window_sum")):
        assert os.path.isfile(os.path.join(harness.HERE, kind, name + ".py"))
    # the mesh's and the other cells' own metrics stay theirs
    for other in ("key_lookup_pct.catchup", "fire_reduce_pct.catchup",
                  "collective_ms.catchup", "exchange_ms.catchup"):
        assert CELL not in entries[other]["workloads"]


def test_a_cycle_is_what_the_configuration_says():
    """The real density on one seed: every gem pack is bought, ~122 times a
    window; a window's sums lie near 587 000 and far below 2^24, where f32
    holds every integer."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    traffic = harness.load_json("traffic", "catchup.json")
    cycle = build_cycle(cfg["stream"], traffic, 3700000007, wrap=65536)
    assert cycle.events == 10_000_000
    packs = cycle.column("gem_pack_id").astype(np.int64)
    assert packs.max() == 65_535 and len(np.unique(packs)) == 65_536
    price = cycle.column("price")
    assert price.max() == 9_999 and (price == np.rint(price)).all()
    # a 16-bit field mod 10 000: prices below 5 536 come 7 times in 65 536,
    # the others 6 times, so the mean is 4 811 and not 4 999.5
    assert abs(float(price.mean(dtype=np.float64)) - 4811.0) < 3
    refmod = harness.load_module("references", "keyed_window_sum")
    sums, _j0 = refmod.expected(cycle, cfg["reference"], {}, cfg["window"],
                                2 * cycle.events, 200)
    counts, _ = refmod.kwc.expected(cycle, cfg["reference"], {}, cfg["window"],
                                    2 * cycle.events, 200)
    # windows wholly inside the stream: 8 M purchases, give or take the jitter
    full = abs(counts.sum(axis=1) - 8_000_000) < 20_000
    assert full.sum() >= 3
    assert counts[full].mean() == pytest.approx(122.07, abs=0.01)
    assert sums[full].mean() == pytest.approx(122.07 * 4811.0, rel=2e-3)
    assert 0 < sums.max() < 1 << 21 and (sums >= 0).all()
    # a (window, gem pack) whose purchases all cost 0 (a price is 0 once in
    # 9 362 draws) is emitted with the sum 0 and is not due in the
    # comparison: none in a full window, a few where a partial window at
    # either end of the run holds one purchase of a gem pack
    free = (counts > 0) & (sums == 0)
    assert not free[full].any() and 0 < free.sum() < 40
    assert not ((sums > 0) & (counts == 0)).any()


# -- the reader -------------------------------------------------------------------

COUNT_DOT = b"ingest/hist/while/body/closed_call/dot_general"


def traced(tmp_path, monkeypatch, value_scope: bool):
    """A ctx as `harness.traced_metrics` hands it to the readers, over
    `fixtures/phases.xspace.txt`; with `value_scope` the capture's one dot
    (`fusion.43`, [2000, 5000) ns with a copy of the compiler's inside it)
    lies under the weighted histogram's scope instead of the count's (a name
    of the same length: the stored module is a length-prefixed message)."""
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "phases.xspace.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    if value_scope:
        name = b"ingest/hist/cond/hist.value/body/dot_general"
        name += b"_" * (len(COUNT_DOT) - len(name))
        assert len(name) == len(COUNT_DOT) and raw.count(COUNT_DOT) == 1
        raw = raw.replace(COUNT_DOT, name)
    run = tmp_path / "plugins" / "profile" / "2026_10_05_12_00_00"
    run.mkdir(parents=True, exist_ok=True)
    (run / "vm.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    trace = tr.load_xplane(str(run / "vm.xplane.pb"))
    return {"trace": trace, "trace_window": tr.window_of(trace),
            "cfg": {"trace_modules": ["jit_run"]}}


def test_value_ingest_ms_reads_the_value_fields_nested_rows(tmp_path,
                                                            monkeypatch):
    reader = harness.load_module("layer_metrics", METRIC)
    ingest = harness.load_module("layer_metrics", "ingest_ms.catchup")
    ctx = traced(tmp_path, monkeypatch, value_scope=True)
    # the dot's self time and the copy inside it, one execution in the window
    assert reader.read(ctx) == pytest.approx(3000 / 1e6)
    # a part of the phase, which reads as it did
    assert ingest.read(dict(ctx)) == pytest.approx(6000 / 1e6)


def test_value_ingest_ms_is_absent_where_no_value_field_is(tmp_path,
                                                          monkeypatch):
    reader = harness.load_module("layer_metrics", METRIC)
    # a count-only program, and a program older than the names (the parent)
    ctx = traced(tmp_path, monkeypatch, value_scope=False)
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, cfg={"trace_modules": ["no_such"]})) is None
    # a program older than its phase table; no capture; no trace
    monkeypatch.setattr(reader, "device_phases", None)
    assert reader.read(ctx) is None
    monkeypatch.undo()
    ctx = traced(tmp_path, monkeypatch, value_scope=True)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "none"))
    assert reader.read(ctx) is None
    assert reader.read({"trace": None}) is None
