"""The six phase readers over the hand-written capture
`fixtures/phases.xspace.txt` (an XSpace text proto, written to a temporary
`TRACE_DIR` as the `.xplane.pb` a traced run leaves there). Worked by hand, ns:

  window [0, 15000) (`benchmark.traced_window` on the host plane): of the
  window program's two executions on /device:TPU:0 the first, [1000, 11000),
  starts inside it; /device:TPU:0 is the fullest plane (busy 8600 against
  /device:TPU:1's 3000)

    ingest    while.40 [1500, 7500) with fusion.43 and a copy of the
              compiler's inside it                                    6000
    fire      fusion.60 [9000, 9500)                                   500
    exchange  all-to-all.11 [9500, 9800)                               300
    other     copy.11 [8000, 9000) under no scope + 2200 under no op  3200
    no op of the execution lies under `prologue` or `purge`

  so superscan_ms = 10000 ns = ingest + fire + exchange + other.
"""

import json
import os

import pytest

from benchmarks import harness, phase_lib
from benchmarks import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "fixtures")
ROOT = os.path.dirname(os.path.dirname(FIXTURES))
READERS = {
    "prologue_ms.catchup": None, "ingest_ms.catchup": 6000,
    "fire_ms.catchup": 500, "purge_ms.catchup": None,
    "program_other_ms.catchup": 3200, "exchange_ms.catchup": 300,
}


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A ctx as `harness.traced_metrics` hands it to the readers, the
    capture still on disk."""
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "phases.xspace.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    run = tmp_path / "plugins" / "profile" / "2026_10_04_12_00_00"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    trace = tr.load_xplane(str(run / "vm.xplane.pb"))
    return {"trace": trace, "trace_window": tr.window_of(trace),
            "cfg": {"trace_modules": ["jit_run"]}}


def test_each_phase_per_dispatch_and_their_sum(traced):
    assert traced["trace_window"] == (0, 15000)
    values = {name: read(name, dict(traced)) for name in READERS}
    for name, ns in READERS.items():
        assert values[name] == (None if ns is None
                                else pytest.approx(ns / 1e6)), name
    program = read("superscan_ms.catchup", traced)
    assert program == pytest.approx(10000 / 1e6)
    assert sum(v for v in values.values() if v is not None) == \
        pytest.approx(program)


def test_the_capture_is_read_once_a_run(traced, monkeypatch):
    calls = []
    table = phase_lib.device_phases.phase_table
    monkeypatch.setattr(phase_lib.device_phases, "phase_table",
                        lambda *a, **kw: calls.append(kw) or table(*a, **kw))
    for name in READERS:
        read(name, traced)
    assert len(calls) == 1
    assert calls[0]["planes"] == ["/device:TPU:0"]
    assert calls[0]["window"] == (0, 15000)
    assert calls[0]["programs"] == ["jit_run"]


def test_nothing_to_read_gives_none_and_never_raises(traced, tmp_path,
                                                     monkeypatch):
    # no module of the configuration's in the window
    ctx = dict(traced, cfg={"trace_modules": ["no_such_module"]})
    assert all(read(name, ctx) is None for name in READERS)
    # a program under no scope (the Pallas programs: one custom call)
    ctx = dict(traced, cfg={"trace_modules": ["jit_shape_fire_rows"]})
    assert all(read(name, ctx) is None for name in READERS)
    # a program that has no phase table (the parent of the PR that brought it)
    monkeypatch.setattr(phase_lib, "device_phases", None)
    assert all(read(name, dict(traced)) is None for name in READERS)
    monkeypatch.undo()
    # no capture on disk: the JSON fixtures, the CPU rehearsal
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "none"))
    assert all(read(name, dict(traced)) is None for name in READERS)
    trace = tr.load_json(os.path.join(FIXTURES, "synthetic_trace.json"))
    ctx = {"trace": trace, "trace_window": tr.window_of(trace),
           "cfg": {"trace_modules": ["jit_run"]}}
    assert all(read(name, ctx) is None for name in READERS)
    assert all(read(name, {"trace": None}) is None for name in READERS)


def test_the_readers_are_declared_as_files_and_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    traced_chain = {"ysb_catchup", "keys64k_catchup", "keys64k_mesh4_catchup",
                    "keys64k_zipf_mesh4_catchup", "q5_hot_items_catchup"}
    for name in READERS:
        m = entries[name]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "events_per_s")
        assert set(m["workloads"]) <= set(cells)
        if name == "exchange_ms.catchup":
            assert m["layer"] == "exchange"
            assert all(cells[w]["chips"] == 4 for w in m["workloads"])
        else:
            assert m["layer"] == "device program"
            assert set(m["workloads"]) == traced_chain
    # they come last: what was there is as it was
    assert [m["name"] for m in bench["per_layer"]][-6:] == [
        "prologue_ms.catchup", "ingest_ms.catchup", "fire_ms.catchup",
        "purge_ms.catchup", "program_other_ms.catchup", "exchange_ms.catchup"]
