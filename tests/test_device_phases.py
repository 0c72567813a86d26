"""The device program's phase table (`flink_tpu/metrics/device_phases.py`).

`phase_of` over `op_name`s copied from the chip's own compile of NEXMark
q5's program (PR 33's `q5_slices_t.chip.hlo`) and from this tree's; the
table over a hand-written capture, `benchmarks/fixtures/phases.xspace.txt`,
read by the real loader (`jax.profiler.ProfileData`) on the CPU. Every
number below is worked by hand from that file (ns, on the capture's clock):

  /device:TPU:0, module jit_run_fused_chained_superscan, two executions
  [1000, 11000) and [20000, 23000), and one of jit_shape_fire_rows

  execution 1, 10000 ns
    while.40   [1500, 7500)  ingest/hist/while            self 6000 - 3000
      fusion.43  [2000, 5000)  ingest/hist/.../dot_general  self 3000 - 1000
        copy.7     [2500, 3500)  no scope: inherits ingest/hist      1000
    copy.11    [8000, 9000)  no scope, under no op: other              1000
    fusion.60  [9000, 9500)  fire/cond/...                              500
    all-to-all.11 [9500, 9800)  exchange/all_to_all                     300
    under no op: 10000 - 6000 - 1000 - 500 - 300                      2200
  execution 2, 3000 ns (outside a window that ends before 20000)
    fusion.48  [20500, 22500)  prologue/t1.map/gather                 2000
    under no op                                                       1000
"""

import os

import pytest

from flink_tpu.metrics import device_phases as dp

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "fixtures", "phases.xspace.txt")
RUN = "jit(run_fused_chained_superscan)/while/body/closed_call/"


@pytest.mark.parametrize("op_name,expect", [
    (RUN + "ingest/while/body/closed_call/dot_general", ("ingest", None)),
    (RUN + "ingest/hist/while/body/closed_call/dot_general",
     ("ingest", "hist")),
    (RUN + "ingest/hist/reshape;ingest/hist/reshape", ("ingest", "hist")),
    (RUN + "ingest/fold/scatter-add", ("ingest", "fold")),
    # the VALUE fields' share: a scope of its own beside the count's, or
    # named deeper where one op (the histogram's conditional) serves both
    (RUN + "ingest/fold.value/while/body/add", ("ingest", "fold.value")),
    (RUN + "ingest/scatter.value/scatter-min", ("ingest", "scatter.value")),
    (RUN + "ingest/hist/cond/branch_1_fun/hist.value/while/body/dot_general",
     ("ingest", "hist.value")),
    (RUN + "ingest/hist/cond/branch_1_fun/while/body/dot_general",
     ("ingest", "hist")),
    (RUN + "ingest/hist/cond", ("ingest", "hist")),
    # no other piece's deeper name, and no other phase's
    (RUN + "ingest/fold/while/body/hist.value/add", ("ingest", "fold")),
    (RUN + "prologue/value/select_n", ("prologue", "value")),
    (RUN + "prologue/key/value.value/convert", ("prologue", "key")),
    (RUN + "prologue/t1.map/gather", ("prologue", "t1.map")),
    (RUN + "prologue/t12.map_ts/add", ("prologue", "t12.map_ts")),
    (RUN + "prologue/bounds/reduce_max", ("prologue", "bounds")),
    (RUN + "prologue/reduce_max", ("prologue", None)),
    (RUN + "purge/cond", ("purge", None)),
    ("fire/cond/branch_1_fun/reduce_sum", ("fire", None)),
    ("jit(run_sharded_chained_superscan)/shard_map/while/body/exchange/"
     "all_to_all", ("exchange", None)),
    # a scope of the user's own inside a phase is no nested name of ours
    (RUN + "prologue/t0.filter/ingest/lt", ("prologue", "t0.filter")),
    (RUN + "fire/hist/add", ("fire", None)),
    # under no scope: the step's own lines, the scan's slicing, nothing
    (RUN[:-1], (None, None)),
    ("jit(run_fused_chained_superscan)/while/body/dynamic_slice",
     (None, None)),
    ("jit(ingest_all)/firefly/purged", (None, None)),
    ("", (None, None)),
])
def test_phase_of(op_name, expect):
    assert dp.phase_of(op_name) == expect


def test_the_programs_take_their_scope_names_from_phases():
    """One spelling: no window program opens a `named_scope` on a literal."""
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("flink_tpu/ops/superscan.py",
                "flink_tpu/runtime/fused_window_pipeline.py",
                "flink_tpu/parallel/sharded_superscan.py"):
        with open(os.path.join(root, rel)) as f:
            source = f.read()
        sites = re.findall(r"named_scope\(([^)]*)\)", source)
        assert sites, rel
        assert not [s for s in sites if '"' in s or "'" in s], (rel, sites)
    assert dp.PHASES == ("prologue", "exchange", "ingest", "fire", "purge")
    assert dp.transform_scope(2, "map_ts") == "t2.map_ts"


def test_a_value_fields_ops_have_rows_of_their_own_and_the_phases_still_sum():
    """One execution of a SUM program cut by hand: the conditional under
    `ingest/hist` holds the count's loop and the weighted histogram's; the
    latter's ops, and a compiler-made copy inside its loop, go to
    `ingest/hist.value`, the conditional's own time and the count's loop stay
    `ingest/hist`, the sum ring's fold is `ingest/fold.value`; the phases and
    `other` sum to the module's time as they do without a value field."""
    scopes = {"jit_run_x(1)": {
        "cond.1": RUN + "ingest/hist/cond",
        "while.2": RUN + "ingest/hist/cond/branch_1_fun/while",
        "fusion.3": RUN + "ingest/hist/cond/branch_1_fun/while/body/dot_general",
        "while.4": RUN + "ingest/hist/cond/branch_1_fun/hist.value/while",
        "fusion.5": RUN + "ingest/hist/cond/branch_1_fun/hist.value/while/"
                    "body/dot_general",
        "while.7": RUN + "ingest/fold/while",
        "while.8": RUN + "ingest/fold.value/while",
        "fusion.9": RUN + "prologue/value/select_n"}}
    ops = [(0, 10, "%fusion.9 = f32[8] fusion()"),
           (10, 100, "%cond.1 = (s32[4,8], f32[4,8]) conditional()"),
           (12, 30, "%while.2 = () while()"),
           (14, 28, "%fusion.3 = s32[8] fusion()"),
           (30, 95, "%while.4 = () while()"),
           (32, 80, "%fusion.5 = f32[8] fusion()"),
           (80, 90, "%copy.6 = f32[8] copy()"),       # no op_name: inherits
           (100, 110, "%while.7 = () while()"),
           (110, 125, "%while.8 = () while()")]
    table = dp._cut([("jit_run_x(1)", 0, 130)], ops, scopes, top=5)
    (m,) = table.values()
    assert m["sub"] == pytest.approx({
        "ingest/fold": 10e-6, "ingest/fold.value": 15e-6,
        "ingest/hist": (7 + 4 + 14) * 1e-6,
        "ingest/hist.value": (7 + 48 + 10) * 1e-6,
        "prologue/value": 10e-6})
    assert m["phases"] == pytest.approx({"prologue": 10e-6, "ingest": 115e-6})
    assert sum(m["phases"].values()) + m["other"] == pytest.approx(m["ms"])
    assert m["other"] == pytest.approx(5e-6)


# -- the table over the hand-written capture ---------------------------------

WINDOW_PROGRAM = "jit_run_fused_chained_superscan(7)"


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """The text fixture as the `.xplane.pb` a capture leaves on disk."""
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    run = tmp_path_factory.mktemp("capture") / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(raw)
    return str(run / "vm.xplane.pb")


def test_the_capture_stores_each_programs_scopes(capture):
    """The `/host:metadata` plane's `Hlo Proto` per program: every
    instruction that has an `op_name`, the `while` ops among them."""
    scopes = dp.op_scopes(capture)
    assert set(scopes) == {WINDOW_PROGRAM, "jit_shape_fire_rows(9)"}
    assert scopes["jit_shape_fire_rows(9)"] == {
        "fusion.1": "jit(shape_fire_rows)/slice"}
    assert scopes[WINDOW_PROGRAM] == {
        "while.39": "jit(run_fused_chained_superscan)/while",
        "while.40": RUN + "ingest/hist/while",
        "fusion.43": RUN + "ingest/hist/while/body/closed_call/dot_general",
        "fusion.60": RUN + "fire/cond/branch_1_fun/reduce_sum",
        "fusion.48": RUN + "prologue/t1.map/gather",
        "all-to-all.11": RUN + "exchange/all_to_all",
    }       # copy.7 and copy.11 have no metadata


def test_self_times_inheritance_and_other(capture):
    """Execution 1 alone (the window ends before the second starts)."""
    table = dp.phase_table(capture, programs=["jit_run"],
                           planes=["/device:TPU:0"], window=(0, 15000))
    assert set(table) == {"/device:TPU:0"}
    (name, m), = table["/device:TPU:0"].items()
    assert name == WINDOW_PROGRAM
    assert (m["executions"], m["ms"]) == (1, pytest.approx(0.010))
    # ingest: while.40's self 3000 + fusion.43's self 2000 + the copy the
    # compiler made inside it 1000
    assert m["phases"] == {"exchange": pytest.approx(0.0003),
                           "ingest": pytest.approx(0.006),
                           "fire": pytest.approx(0.0005)}
    assert list(m["phases"]) == ["exchange", "ingest", "fire"]  # PHASES' order
    assert m["sub"] == {"ingest/hist": pytest.approx(0.006)}
    # copy.11 under no scoped op 1000 + 2200 under no op at all
    assert m["other"] == pytest.approx(0.0032)
    assert m["other_ops"] == [[dp.NO_OP, pytest.approx(0.0022)],
                              ["copy.11", pytest.approx(0.001)]]
    assert sum(m["phases"].values()) + m["other"] == pytest.approx(m["ms"])
    assert m["phase_ops"]["ingest"] == [
        ["while.40", pytest.approx(0.003), RUN + "ingest/hist/while"],
        ["fusion.43", pytest.approx(0.002),
         RUN + "ingest/hist/while/body/closed_call/dot_general"],
        ["copy.7", pytest.approx(0.001), ""]]


def test_every_execution_plane_and_program_when_not_asked_otherwise(capture):
    table = dp.phase_table(capture)
    assert set(table) == {"/device:TPU:0", "/device:TPU:1"}
    both = table["/device:TPU:0"][WINDOW_PROGRAM]
    assert (both["executions"], both["ms"]) == (2, pytest.approx(0.013))
    assert both["phases"]["prologue"] == pytest.approx(0.002)
    assert both["sub"]["prologue/t1.map"] == pytest.approx(0.002)
    assert both["other"] == pytest.approx(0.0042)
    # a program with no phase of ours: all of it is `other`
    shaper = table["/device:TPU:0"]["jit_shape_fire_rows(9)"]
    assert shaper["phases"] == {} and shaper["other"] == pytest.approx(0.001)
    assert shaper["other_ops"][0] == ["fusion.1", pytest.approx(0.0008)]
    other = table["/device:TPU:1"][WINDOW_PROGRAM]
    assert other["phases"] == {"ingest": pytest.approx(0.003)}
    assert other["other"] == pytest.approx(0.001)
    for modules in table.values():
        for m in modules.values():
            assert sum(m["phases"].values()) + m["other"] == \
                pytest.approx(m["ms"])


def test_per_execution_render_and_the_command(capture, capsys):
    """The busiest plane, ms per execution; a capture's directory does as
    well as its file."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(capture)))
    assert dp.capture_file(root) == capture
    report = dp.per_execution(dp.phase_table(root, programs=["jit_run"]))
    assert report["plane"] == "/device:TPU:0"
    m = report["programs"][WINDOW_PROGRAM]
    assert m["ms"] == pytest.approx(0.0065) and m["executions"] == 2
    assert m["phases"]["ingest"] == pytest.approx(0.003)
    assert m["other_ops"][0] == [dp.NO_OP, pytest.approx(0.0016)]
    assert dp.per_execution({}) == {}
    assert dp.main([root, "--programs", "jit_run", "--ops"]) == 0
    out = capsys.readouterr().out
    assert f"{WINDOW_PROGRAM} on /device:TPU:0: 2 executions" in out
    assert "ingest" in out and "hist" in out and "copy.11" in out
    assert RUN + "prologue/t1.map/gather" in out


def test_a_capture_without_a_device_plane_reads_empty(tmp_path):
    """A CPU backend's capture: no `/device:TPU:n` plane, an empty table."""
    from jax.profiler import ProfileData

    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }'))
    assert dp.phase_table(str(path)) == {}
    (tmp_path / "no_capture").mkdir()
    with pytest.raises(FileNotFoundError):
        dp.capture_file(str(tmp_path / "no_capture"))


def test_the_jobs_own_capture_is_read_back(tmp_path, monkeypatch, capture):
    """`observability.profiler.enabled`: after `stop_trace` the capture's
    table lands in the device payload's `profiler.phaseMs`, beside the
    operators' `phases` step counts; with the profiler off nothing is read
    and the key is absent."""
    import numpy as np

    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.config import Configuration, ObservabilityOptions
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.utils.arrays import obj_array

    def gen(idx):
        return Batch(obj_array([int(i) for i in idx]),
                     (idx * 10).astype(np.int64))

    def job(cfg):
        env = StreamExecutionEnvironment(cfg)
        env.from_source(
            DataGeneratorSource(gen, count=64),
            watermark_strategy=WatermarkStrategy.for_monotonous_timestamps(),
        ).map(lambda x: x).sink_to(CollectSink())
        client = env.execute_async("phase-table")
        assert client.wait(120).value == "FINISHED"
        return client._runtime.device_snapshot()["profiler"]

    read = []
    table_of = dp.phase_table
    monkeypatch.setattr(dp, "phase_table",
                        lambda where: read.append(where) or table_of(capture))
    assert "phaseMs" not in job(Configuration())
    assert read == []
    cfg = Configuration()
    cfg.set(ObservabilityOptions.PROFILER_ENABLED, True)
    cfg.set(ObservabilityOptions.PROFILER_DIR, str(tmp_path / "prof"))
    profiler = job(cfg)
    assert read == [str(tmp_path / "prof")] and profiler["captures"] == 1
    assert profiler["phaseMs"]["plane"] == "/device:TPU:0"
    program = profiler["phaseMs"]["programs"][WINDOW_PROGRAM]
    assert program["phases"]["ingest"] == pytest.approx(0.003)
