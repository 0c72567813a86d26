"""The reader the zipf mesh cell brings, `exchange_fill_pct.catchup`, on
hand-made `per_device` blocks; and the cell's declaration."""

import json
import os

import pytest

from benchmarks import harness

CELL, TWIN = "keys64k_zipf_mesh4_catchup", "keys64k_mesh4_catchup"
NAME = "exchange_fill_pct.catchup"


def read(per_device):
    counters = {} if per_device is None else {"per_device": per_device}
    return harness.load_module("layer_metrics", NAME).read(
        {"counters": counters})


def block(*pairs):
    return [{"device": d, "records": 1, "routed": r, "lanes": n}
            for d, (r, n) in enumerate(pairs)]


@pytest.mark.parametrize("per_device,want", [
    (block((25, 300), (25, 300), (25, 300), (25, 300)), 100 * 25 / 300),
    (block((88, 300), (6, 300), (4, 300), (2, 300)), 100 * 88 / 300),
    (block((0, 300), (0, 300)), 0.0),
    # a parent without the counters, a job on one chip, a fold without lanes
    ([{"device": d, "records": 5} for d in range(4)], None),
    ([], None), (None, None), (block((7, 300)), None),
    (block((0, 0), (0, 0)), None),
])
def test_exchange_fill_is_the_fullest_device_s_live_share(per_device, want):
    got = read(per_device)
    assert got == (None if want is None else pytest.approx(want))


def test_the_zipf_mesh_cell_is_declared_as_files_and_entries():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == ("ysb_keys64k_zipf_mesh4", "catchup", 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2 \
        <= len(bench["workloads"]) // 2
    # every metric of the uniform twin, and the one this cell brings to both
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads")
        assert listed is None or (TWIN in listed) == (CELL in listed), m["name"]
    (fill,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert (fill["layer"], fill["moves"], fill["workloads"]) == \
        ("exchange", "events_per_s", [TWIN, CELL])
    spec = harness.load_cell(CELL)
    assert [m["name"] for m in spec["end_to_end"]] == ["events_per_s", "setup_s"]
    assert {m["name"] for m in spec["per_layer"]} == \
        {m["name"] for m in harness.load_cell(TWIN)["per_layer"]}
