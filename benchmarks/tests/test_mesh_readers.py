"""The four readers the mesh cell brings, on the hand-made traces (ns, window
[0, 1000)); every number below is worked out by hand.

fixtures/synthetic_trace.json, two devices, the window program `jit_run` ran
twice on each:
  dev 0  all-to-all [150,180); fusion.2 starts at 170 -> 30 in, 20 exposed
  dev 1  all-to-all [150,180); nothing beside it      -> 30 in, 30 exposed
fixtures/synthetic_trace_spans.json holds no `stage.shard` (a one-chip job):
the test lays one into `stage.fill` [200,300).
"""

import json
import os

import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "fixtures")
CELL = "keys64k_mesh4_catchup"
NEW = ("collective_ms.catchup", "collective_exposed_ms.catchup",
       "shard_skew.catchup", "stage_shard_pct.catchup")


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def device_ctx(planes=None):
    trace = tr.load_json(os.path.join(FIXTURES, "synthetic_trace.json"))
    if planes is not None:
        trace.planes = {p: v for p, v in trace.planes.items()
                        if not tr.DEVICE_PLANE.match(p) or p in planes}
    return {"trace": trace, "trace_window": tr.window_of(trace),
            "cfg": {"trace_modules": ["jit_run"]}}


def test_collective_time_per_dispatch_on_the_device_where_it_is_largest():
    ctx = device_ctx()
    assert read("collective_ms.catchup", ctx) == pytest.approx(30 / 2 / 1e6)
    assert read("collective_exposed_ms.catchup", ctx) == \
        pytest.approx(30 / 2 / 1e6)                       # dev 1's
    ctx = device_ctx(planes=["/device:TPU:0"])
    assert read("collective_ms.catchup", ctx) == pytest.approx(30 / 2 / 1e6)
    assert read("collective_exposed_ms.catchup", ctx) == \
        pytest.approx(20 / 2 / 1e6)


def test_no_collective_or_no_window_program_gives_nothing_to_read():
    ctx = device_ctx()
    for plane in ctx["trace"].device_planes():            # a one-chip job
        ops = ctx["trace"].planes[plane][tr.OPS_LINE]
        ops[:] = [e for e in ops if not tr.COLLECTIVE.search(e[0])]
    ctx2 = dict(device_ctx(), cfg={"trace_modules": ["no_such_module"]})
    for name in NEW[:2]:
        assert read(name, ctx) is None
        assert read(name, ctx2) is None


@pytest.mark.parametrize("per_device,want", [
    ([110, 100, 95, 95], 1.1),
    ([25, 25, 25, 25], 1.0),
    ([400, 0, 0, 0], 4.0),          # PR 22's cell: every record on device 0
    ([], None), ([7], None), ([0, 0, 0, 0], None), (None, None),
])
def test_shard_skew_is_the_fullest_device_over_the_mean(per_device, want):
    counters = {} if per_device is None else {
        "per_device": [{"device": d, "records": r}
                       for d, r in enumerate(per_device)]}
    got = read("shard_skew.catchup", {"counters": counters})
    assert got == (None if want is None else pytest.approx(want))


def test_stage_shard_nests_in_stage_fill_and_takes_its_time_out_of_it():
    trace = tr.load_json(os.path.join(FIXTURES, "synthetic_trace_spans.json"))
    ctx = {"trace": trace, "trace_window": tr.window_of(trace)}
    assert read("stage_shard_pct.catchup", ctx) is None   # one chip: no deal
    assert read("stage_fill_pct.catchup", ctx) == pytest.approx(10.0)
    (line,) = [evs for evs in trace.planes[tr.HOST_PLANE].values()
               if any(n == "benchmark.poll_batch" for n, _a, _b in evs)]
    line.append(("flink_tpu.stage.shard", 220, 260))
    assert read("stage_shard_pct.catchup", ctx) == pytest.approx(4.0)
    assert read("stage_fill_pct.catchup", ctx) == pytest.approx(6.0)


def test_the_mesh_cell_is_declared_as_files_and_entries():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ysb_keys64k_mesh4", "catchup", 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == entry["reduced"] == ["num_campaigns", "parallelism"]
    assert cfg["options"] == {"parallel.mesh.enabled": True,
                              "parallel.mesh.devices": 4}
    assert cfg["expect"] == {"mesh_devices": 4, "devices_with_records": 4}
    # the one-chip twin's deployment, but for what the mesh adds
    twin = harness.load_json("configs", "ysb_keys64k.json")
    for key in ("job", "stream", "window", "out_of_orderness_ms", "reference",
                "num_campaigns"):
        assert cfg[key] == twin[key], key
    assert set(twin["guarantees"].items()) < set(cfg["guarantees"].items())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert (m["moves"], m["workloads"]) == ("events_per_s", [CELL])
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", name + ".py"))
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["per_layer"]} == \
        set(declared) - {"key_lookup_pct.catchup"}
    assert [m["name"] for m in spec["end_to_end"]] == ["events_per_s", "setup_s"]
