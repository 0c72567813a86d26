"""trace_reduce on a hand-made trace: every number below is worked out by
hand from fixtures/synthetic_trace.json (ns, window [0, 1000))."""

import os

import pytest

from benchmarks import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "fixtures", "synthetic_trace.json")


def load():
    trace = tr.load_json(FIXTURE)
    return trace, tr.window_of(trace)


def test_window_and_planes():
    trace, (lo, hi) = load()
    assert (lo, hi) == (0, 1000)
    assert trace.device_planes() == ["/device:TPU:0", "/device:TPU:1"]


def test_busy_is_the_union_of_op_intervals():
    trace, (lo, hi) = load()
    # dev 0: [100,210) u [400,500) u [600,650); dev 1: [120,180) u [400,460)
    assert tr.busy_by_device(trace, lo, hi) == {
        "/device:TPU:0": 260, "/device:TPU:1": 120}
    # clipped to a narrower window
    assert tr.busy_by_device(trace, 150, 450)["/device:TPU:0"] == 60 + 50


def test_program_time_by_module_name():
    trace, (lo, hi) = load()
    assert tr.program_times(trace, ["jit_run"], lo, hi) == {
        "/device:TPU:0": (2, 210), "/device:TPU:1": (2, 120)}
    assert tr.program_times(trace, ["no_such"], lo, hi)["/device:TPU:0"] == (0, 0)


def test_collective_and_exposed():
    trace, (lo, hi) = load()
    coll = tr.collective_times(trace, lo, hi)
    # dev 0: all-to-all [150,180); fusion.2 starts at 170 -> 20 ns exposed
    # (the while loop that contains it is no other op)
    assert coll["/device:TPU:0"] == (30, 20)
    # dev 1: fusion.1 ends at 150 -> wholly exposed
    assert coll["/device:TPU:1"] == (30, 30)


def test_gap_attribution_innermost_event_wins():
    trace, (lo, hi) = load()
    by_name, idle = tr.attribute_gaps(trace, "/device:TPU:0", lo, hi)
    assert idle == 740
    assert by_name == {
        "benchmark.poll_batch": 40, "np.asarray(jax.Array)": 50,
        "PjRt::Await": 50, "DevicePut": 60, "benchmark.sink_write": 100,
        tr.UNATTRIBUTED: 440}
    assert sum(by_name.values()) == idle


def test_thread_time_in_transfer_counts_nesting_once():
    trace, (lo, hi) = load()
    assert tr.thread_time_in(trace, tr.TRANSFER, lo, hi) == 100 + 60


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]


def test_every_reader_of_benchmark_json_reads_the_fixture():
    """Each per-layer metric BENCHMARK.json names has a reader file, and on
    the hand-made trace and hand-made spans it reads what is worked out here."""
    import json
    import types

    from benchmarks import harness

    trace, (lo, hi) = load()
    cfg = harness.load_json("configs", "ysb_campaigns.json")
    state = types.SimpleNamespace(
        poll_s=0.02, sink_s=0.05, rows_in_window=300, batch=1000,
        handed_at=[0.1, 0.2, 0.3, 5.0])
    ctx = {"cfg": cfg, "state": state, "window_s": 2.0, "compiles_in_window": 0,
           "counters": {"mesh_devices": None}, "trace": trace,
           "trace_window": (lo, hi), "trace_wall": (0.0, 1.0),
           "arrivals": [(0.5, "measure", 100, [10_000]), (7.0, "drain", 100, [20_000])],
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"]}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    got = {n: harness.load_module("layer_metrics", n).read(ctx) for n in names}
    # 3 batches of 1000 events x 32 B and one fire of 100 keys x (2 + 1) slices
    # x 4 B inside the traced stretch: 97 200 B over 819 GB/s against 210 ns
    assert got.pop("superscan_roofline_pct.catchup") == pytest.approx(
        100 * (97_200 / 819e9) / 210e-9)
    assert got == {
        "gen_share_pct.catchup": pytest.approx(1.0),
        "sink_share_pct.catchup": pytest.approx(2.5),
        "rows_out_per_s.catchup": 150.0,
        "compiles_in_window.catchup": 0.0,
        "device_idle_pct.catchup": pytest.approx(74.0),       # dev 0 busy 260
        "op_device_wait_pct.catchup": pytest.approx(16.0),    # 100 + 60
        "host_code_pct.catchup": pytest.approx(58.0),         # 740 - 50 - 50 - 60
        "superscan_ms.catchup": pytest.approx(105e-6),        # 210 ns / 2
    }
    # a reader that finds nothing to read returns nothing, never 0
    ctx["cfg"] = dict(cfg, trace_modules=["no_such_module"])
    for n in ("superscan_ms.catchup", "superscan_roofline_pct.catchup"):
        assert harness.load_module("layer_metrics", n).read(ctx) is None
