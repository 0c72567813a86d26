"""The job thread's stage clock (metrics/task_io.py) and the names the window
programs carry on the device.

- the clock alone: nesting gives self time, counts, the outer sections, off;
- one `env.execute()` job per served path (traced chain, host-keyed, a
  4-device mesh) under `jax.profiler.start_trace`, read back the way the
  benchmark reads a trace (`benchmarks.trace_reduce.load_xplane`): every
  documented span lies on the job's thread, spans nest, one dispatch's
  stages share a `seq`, and the `stages` / `link` tables agree with the job;
- the tuple of stage names equals the table in docs/observability.md;
- every window program lowers to a module `jit_run_<program>` of its own.
"""

import glob
import os
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import trace_reduce as tr
from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.config import (
    Configuration,
    ExecutionOptions,
    ObservabilityOptions,
    ParallelOptions,
)
from flink_tpu.connectors.sink import CollectSink
from flink_tpu.connectors.source import Batch, DataGeneratorSource
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.metrics import task_io
from flink_tpu.metrics.registry import Histogram
from flink_tpu.metrics.task_io import STAGES, StageClock, stage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the clock alone
# ---------------------------------------------------------------------------

def test_nested_stages_count_self_time():
    clock = StageClock()
    with stage(clock, "resolve"):
        time.sleep(0.004)
        for _ in range(2):
            with stage(clock, "emit"):
                time.sleep(0.003)
    resolve, emit = clock.stages["resolve"], clock.stages["emit"]
    assert (resolve[0], emit[0]) == (1, 2)
    assert emit[1] >= 2 * 0.003e9
    # the parent's row holds its own 4 ms, not the 10 ms it was open for
    assert 0.004e9 <= resolve[1] < 0.004e9 + emit[1]
    table = clock.stage_table()
    assert set(table) == {"resolve", "emit"}       # only stages that ran
    assert table["emit"]["count"] == 2
    assert table["emit"]["ms"] == pytest.approx(emit[1] / 1e6, abs=1e-3)


def test_stage_nesting_is_per_thread():
    clock = StageClock()
    inner_done = threading.Event()

    def other():
        with stage(clock, "sink.write"):
            time.sleep(0.002)
        inner_done.set()

    with stage(clock, "drain"):
        t = threading.Thread(target=other)
        t.start()
        assert inner_done.wait(5.0)
        t.join(5.0)
        time.sleep(0.002)
    # another thread's stage is no child of this thread's open stage
    assert clock.stages["drain"][1] >= 0.004e9
    assert clock.stages["sink.write"][0] == 1


def test_outer_sections_hold_their_nested_stages_whole():
    h = Histogram()
    clock = StageClock(histogram=h)
    for _ in range(2):
        with clock.section():
            with stage(clock, "normalize"):
                time.sleep(0.002)
            with task_io.dispatch_stage(clock, "dispatch"):
                time.sleep(0.002)
    # deviceTimeMsTotal / deviceDispatches / deviceDispatchMs: the whole
    # section, nested stages included, one histogram sample per section
    assert clock.dispatches == 2
    assert h.stats()["count"] == 2
    staged_s = sum(ns for _c, ns in clock.stages.values()) / 1e9
    assert clock.total_s >= staged_s >= 0.008
    # an outer section is no stage: it neither nests nor takes self time
    assert set(clock.stage_table()) == {"normalize", "dispatch"}


def test_link_counters_and_seq():
    clock = StageClock()
    clock.seq += 1
    clock.staged((np.zeros((4, 8), np.float32), None,
                  np.zeros(3, np.int32)), events=4)
    assert clock.link() == {"h2dBytes": 4 * 8 * 4 + 12, "d2hBytes": 0,
                            "eventsStaged": 4, "columnsStaged": 0,
                            "recordColumns": 0, "rowsEmitted": 0,
                            "fireBlocks": 0, "fireRowsReduced": 0,
                            "fireRowsKept": 0,
                            "dispatches": 1, "stepsPlannedScalar": 0,
                            "stepsPlannedMasked": 0,
                            "stagingSetsAllocated": 0,
                            "stagingSetsReused": 0,
                            "stepsStagedNative": 0,
                            "stepsStagedNumpy": 0}
    # a traced chain's dispatch says how much of the record it shipped;
    # the gauge is the newest dispatch's, not a sum
    clock.staged((np.zeros(2, np.int32),), events=2, columns=(2, 7))
    clock.staged((np.zeros(2, np.int32),), events=2, columns=(2, 7))
    link = clock.link()
    assert (link["columnsStaged"], link["recordColumns"]) == (2, 7)
    assert link["eventsStaged"] == 8
    assert task_io.dispatch_stage(clock, "stage.fill").seq == 1
    assert stage(clock, "emit", 7).seq == 7 and stage(clock, "drain").seq is None
    task_io.tag_dispatch("fused_superscan")     # no open span: a no-op


def test_off_is_one_shared_noop():
    assert stage(None, "emit") is stage(None, "drain")
    assert task_io.dispatch_stage(None, "dispatch") is stage(None, "emit")
    with stage(None, "emit") as s:
        assert s is None


# ---------------------------------------------------------------------------
# one job per served path, under a profiler capture
# ---------------------------------------------------------------------------

N, N_KEYS, BATCH, SPAN_MS = 24_000, 192, 1536, 40_000
SPAN = task_io.SPAN_PREFIX
#: what each path must show on the job's thread, beyond these no others
COMMON = {"source.poll", "source.watermark", "chain.host", "normalize",
          "stage.fill", "stage.put", "dispatch", "resolve", "emit", "drain",
          "sink.write", "keys.stats"}
PATHS = {
    "traced_chain": dict(mesh=0, host_keyed=False,
                         program="fused_chained_superscan"),
    "host_keyed": dict(mesh=0, host_keyed=True, program="fused_superscan"),
    "mesh4": dict(mesh=4, host_keyed=False,
                  program="sharded_chained_superscan"),
}


def _run_job(mesh: int, host_keyed: bool, timing: bool = True):
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, BATCH)
    cfg.set(ExecutionOptions.KEY_CAPACITY, N_KEYS)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    cfg.set(ObservabilityOptions.DEVICE_TIMING_ENABLED, timing)
    if mesh:
        cfg.set(ParallelOptions.MESH_ENABLED, True)
        cfg.set(ParallelOptions.MESH_DEVICES, mesh)

    def gen(idx):
        col = np.stack([(idx * 2654435761) % N_KEYS, idx % 3],
                       axis=1).astype(np.float32)
        return Batch(col, (10_000 + idx * SPAN_MS // N).astype(np.int64))

    env = StreamExecutionEnvironment(cfg)
    ds = env.from_source(
        DataGeneratorSource(gen, N, num_splits=1),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0))
    sink = CollectSink()
    if host_keyed:
        keyed = (ds.filter(lambda col: col[:, 1] < 0.5, vectorized=True)
                   .key_by(lambda col: col[:, 0].astype(np.int64),
                           vectorized=True))
    else:
        keyed = (ds.filter(lambda col: col[:, 1] < 0.5, traceable=True)
                   .key_by(lambda col: col[:, 0].astype(jnp.int32),
                           traceable=True))
    keyed.window(TumblingEventTimeWindows.of(5_000)).count().sink_to(sink)
    return env.execute("stage-clock"), sink


def _traced(tmp_path, **job):
    """Run the job under a capture made the way the benchmark makes one;
    (result, sink, Trace, {(name, start, end): stats} of the host plane)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        result, sink = _run_job(**job)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    stats = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN):
                    a = int(e.start_ns)
                    stats[(e.name, a, a + int(e.duration_ns))] = dict(e.stats)
    return result, sink, tr.load_xplane(path), stats


@pytest.fixture(scope="module", params=sorted(PATHS))
def traced_job(request, tmp_path_factory):
    path = PATHS[request.param]
    result, sink, trace, stats = _traced(
        tmp_path_factory.mktemp(request.param),
        mesh=path["mesh"], host_keyed=path["host_keyed"])
    thread = tr.job_thread(trace, marker=SPAN + "source.poll")
    spans = sorted((e for e in thread if e[0].startswith(SPAN)),
                   key=lambda e: (e[1], -e[2]))
    return dict(path, name=request.param, result=result, sink=sink,
                spans=spans, stats=stats)


def test_every_documented_span_is_on_the_job_thread(traced_job):
    names = {n[len(SPAN):] for n, _a, _b in traced_job["spans"]}
    want = COMMON | ({"keys.lookup"} if traced_job["host_keyed"] else set()) \
        | ({"stage.shard"} if traced_job["mesh"] else set())
    assert names == want
    assert names <= set(STAGES)
    # nothing of the program's lies on another thread
    assert {k[0] for k in traced_job["stats"]} == \
        {n for n, _a, _b in traced_job["spans"]}
    assert len(traced_job["stats"]) == len(traced_job["spans"])


def test_no_span_name_reads_as_a_transfer(traced_job):
    for name in STAGES:
        assert not tr.TRANSFER.search(SPAN + name), name


def test_spans_nest_and_never_overlap(traced_job):
    open_ends = []
    for _name, a, b in traced_job["spans"]:       # by start, longest first
        while open_ends and open_ends[-1] <= a:
            open_ends.pop()
        assert not open_ends or b <= open_ends[-1], \
            "a span straddles the end of the span it started in"
        open_ends.append(b)


def test_one_dispatch_shares_a_seq_from_fill_to_emit(traced_job):
    by_seq = {}
    for (name, _a, _b), st in traced_job["stats"].items():
        if "seq" in st:
            by_seq.setdefault(int(st["seq"]), set()).add(name[len(SPAN):])
        else:
            assert name[len(SPAN):] in {
                "source.poll", "source.watermark", "chain.host",
                "keys.lookup", "normalize", "drain", "sink.write",
                "keys.stats"}
    link = _operator(traced_job)["link"]
    assert sorted(by_seq) == list(range(1, link["dispatches"] + 1))
    for seq, names in by_seq.items():
        assert {"stage.fill", "stage.put", "dispatch", "resolve"} <= names
    if traced_job["mesh"]:
        # the deal over the shards lies inside the fill of its dispatch
        # (by start: where the planner's own fill nests in the mesh's, the
        # outer one is kept)
        at = {}
        for (name, a, b), st in sorted(traced_job["stats"].items(),
                                       key=lambda kv: kv[0][1]):
            if "seq" in st:
                at.setdefault((name[len(SPAN):], int(st["seq"])), (a, b))
        shards = [k for k in at if k[0] == "stage.shard"]
        assert sorted(seq for _n, seq in shards) == sorted(by_seq)
        for _n, seq in shards:
            (fa, fb), (sa, sb) = at["stage.fill", seq], at["stage.shard", seq]
            assert fa <= sa and sb <= fb
    assert any("emit" in names for names in by_seq.values())
    programs = {st["program"] for (n, _a, _b), st in
                traced_job["stats"].items() if n == SPAN + "dispatch"}
    assert traced_job["program"] in programs
    assert programs <= {traced_job["program"], "fused_superscan",
                        "sharded_superscan"}   # + the watermark-only flush


def _operator(job):
    (entry,) = [e for e in job["result"].metrics["device"]
                ["operators"].values() if "link" in e]
    return entry


def test_stage_and_link_tables_agree_with_the_job(traced_job):
    result, entry = traced_job["result"], _operator(traced_job)
    link, stages = entry["link"], entry["stages"]
    passed = N // 3 + (1 if N % 3 else 0)          # idx % 3 == 0
    assert result.records_in == N
    # the traced chain filters on the device: every record is staged
    assert link["eventsStaged"] == (passed if traced_job["host_keyed"] else N)
    rows = traced_job["sink"].results
    assert link["rowsEmitted"] == len(rows) > 0
    # one block per fire: as many blocks as emit spans, every row in one
    assert link["fireBlocks"] == entry["stages"]["emit"]["count"] > 0
    assert sum(v for _k, v in rows) == passed
    assert link["h2dBytes"] > 0 and link["d2hBytes"] > 0
    # the 2-field record: the traced chain reads both (filter, key); the
    # host-keyed path ships key ids, no record
    assert (link["columnsStaged"], link["recordColumns"]) == \
        ((0, 0) if traced_job["host_keyed"] else (2, 2))
    # each table row counts what the trace shows of that stage
    seen = {}
    for n, _a, _b in traced_job["spans"]:
        seen[n[len(SPAN):]] = seen.get(n[len(SPAN):], 0) + 1
    whole = result.metrics["device"]["stages"]
    assert {k: v["count"] for k, v in whole.items()} == seen
    assert set(stages) <= set(whole)
    assert stages["dispatch"]["count"] == link["dispatches"]
    # the derived gauges: the outer sections hold the operator's stages
    # whole (chain.host, drain and keys.stats lie outside them)
    inside = sum(v["ms"] for k, v in stages.items()
                 if k not in ("chain.host", "drain", "keys.stats"))
    assert entry["deviceTimeMsTotal"] >= inside * 0.99
    assert entry["deviceDispatches"] > 0
    if traced_job["mesh"]:
        assert result.metrics["mesh_devices"] == traced_job["mesh"]
        # only the fire rows a dispatch used come back from the shards
        # (all R = 256 rows of four slabs would be 32 B per event here)
        assert link["d2hBytes"] / link["eventsStaged"] < 4
        assert stages["stage.shard"]["count"] == link["dispatches"]


def test_timing_off_enters_no_site(tmp_path):
    result, sink, trace, stats = _traced(tmp_path, mesh=0, host_keyed=False,
                                         timing=False)
    assert sink.results
    assert not stats                                 # no span anywhere
    device = result.metrics["device"]
    assert device["stages"] == {}
    for entry in device["operators"].values():
        assert "stages" not in entry and "link" not in entry
        assert "deviceTimeMsTotal" not in entry


# ---------------------------------------------------------------------------
# the link row says how much of the record a traced chain ships
# ---------------------------------------------------------------------------

def _seven_field_chain(reads_all: bool):
    from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

    weights = jnp.arange(7, dtype=jnp.float32)
    if reads_all:     # a matmul over the fields: the analysis gives up
        transforms = (("filter", lambda col: (col @ weights) >= 0.0),)
    else:
        transforms = (("filter", lambda col: col[:, 2] < 0.5),)
    return TracedPrologue(transforms=transforms,
                          key_fn=lambda col: col[:, 5].astype(jnp.int32))


@pytest.mark.parametrize("mesh", [0, 4], ids=["one_chip", "mesh4"])
@pytest.mark.parametrize("reads_all,staged", [(False, 2), (True, 7)],
                         ids=["two_of_seven", "fallback_seven_of_seven"])
def test_link_row_counts_the_columns_staged(mesh, reads_all, staged):
    from jax.sharding import Mesh

    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
    from flink_tpu.runtime.fused_window_pipeline import FusedWindowPipeline

    geom = dict(key_capacity=64, num_slices=16, nsb=4, chunk=256,
                fires_per_step=4, out_rows=16,
                prologue=_seven_field_chain(reads_all))
    assigner = TumblingEventTimeWindows.of(1_000)
    if mesh:
        pipe = ShardedFusedPipeline(
            Mesh(np.array(jax.devices()[:mesh]), ("shards",)), assigner,
            "count", **geom)
    else:
        pipe = FusedWindowPipeline(assigner, "count", backend="xla", **geom)
    clock = StageClock()
    pipe.attach_stage_clock(clock)
    rng = np.random.RandomState(11)
    n = 300
    rec = rng.randint(0, 2, (n, 7)).astype(np.float32)
    rec[:, 5] = rng.randint(0, 64, n)
    ts = np.sort(rng.randint(0, 900, n)).astype(np.int64)
    rows = pipe.process_superbatch([(rec, None, ts)], [2_000])
    link = clock.link()
    assert (link["columnsStaged"], link["recordColumns"]) == (staged, 7)
    B = 512          # 300 lanes staged at the next power-of-two of chunks
    # per staged lane: 4 B a staged field + the 4 B slice index
    plan_bytes = link["h2dBytes"] - B * 4 * (staged + 1)
    assert 0 < plan_bytes < 1024
    kept = rec if reads_all else rec[rec[:, 2] < 0.5]
    assert sum(int(counts.sum()) for _w, counts, _f in rows) == len(kept)


# ---------------------------------------------------------------------------
# the list of stages is one tuple, and the docs tabulate it
# ---------------------------------------------------------------------------

def test_stage_tuple_equals_the_documented_table():
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    rows = re.findall(r"^\| `flink_tpu\.([a-z.]+)` \|", text, re.M)
    assert tuple(rows) == STAGES
    assert len(set(STAGES)) == len(STAGES)


# ---------------------------------------------------------------------------
# programs carry their own names on the device
# ---------------------------------------------------------------------------

PROGRAMS = ("fused_superscan", "fused_chained_superscan", "global_superscan",
            "pallas_superscan", "pallas_global_superscan",
            "sharded_superscan", "sharded_chained_superscan")


class _ModuleNames:
    """Stands where a CompileTracker stands: lowers what is dispatched and
    keeps the module's name."""

    def __init__(self):
        self.modules = {}

    def call(self, program, fn, args, signature, clock=None):
        text = fn.lower(*args).as_text()
        self.modules[program] = re.search(r"module @(\S+)", text).group(1)
        return fn(*args)


@pytest.fixture(scope="module")
def module_names():
    from jax.sharding import Mesh

    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedGlobalWindowPipeline,
        FusedWindowPipeline,
        TracedPrologue,
    )

    rec = _ModuleNames()
    assigner = SlidingEventTimeWindows.of(2000, 500)
    geom = dict(num_slices=16, nsb=4, chunk=1024)
    keyed = dict(geom, key_capacity=128, fires_per_step=4, out_rows=16)
    rng = np.random.RandomState(5)
    ts = np.sort(rng.randint(0, 1500, 512)).astype(np.int64)
    kid = rng.randint(0, 128, 512).astype(np.int32)
    batches, wms = [(kid, None, ts)], [400]
    prologue = TracedPrologue(
        transforms=(), key_fn=lambda col: col[:, 0].astype(jnp.int32),
        value_fn=None)
    raw = [(np.stack([kid, kid], axis=1).astype(np.float32), None, ts)]
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))

    pipes = [
        (FusedWindowPipeline(assigner, "count", backend="xla", **keyed),
         False),
        (FusedWindowPipeline(assigner, "count", backend="pallas",
                             pallas_interpret=True, **keyed), False),
        (FusedWindowPipeline(assigner, "count", backend="xla",
                             prologue=prologue, **keyed), True),
        (FusedGlobalWindowPipeline(assigner, "count", backend="xla", **geom),
         False),
        (FusedGlobalWindowPipeline(assigner, "count", backend="pallas",
                                   pallas_interpret=True, **geom), False),
        (ShardedFusedPipeline(mesh, assigner, "count", **keyed), False),
        (ShardedFusedPipeline(mesh, assigner, "count", prologue=prologue,
                              **keyed), True),
    ]
    for pipe, is_raw in pipes:
        pipe.attach_device_stats(rec, phase_counters=False)
        pipe.process_superbatch(raw if is_raw else batches, wms)
    return rec.modules


@pytest.mark.parametrize("program", PROGRAMS)
def test_lowered_module_is_named_after_its_program(module_names, program):
    assert module_names[program] == f"jit_run_{program}"


def test_module_names_differ_per_program(module_names):
    assert sorted(module_names) == sorted(PROGRAMS)
    assert len(set(module_names.values())) == len(PROGRAMS)
    # the prefix every shipped configuration's trace_modules entry matches
    assert all("jit_run" in m for m in module_names.values())
