"""`purchases_windowed_sum` (Karimov et al.'s windowed aggregation: SUM(price)
per gem pack over a sliding window) is summed exactly: the benchmark's job
builder through `env.execute()` against the benchmark's plain reference, on
seeded cycles of `benchmarks.stream.build_cycle` at a small size, cell by cell
with no tolerance.

Both ingest forms are held to it: the CPU's scatter and the TPU's
`ingest="matmul"` (`ops/matmul_hist.weighted_hist`, three bf16 terms a value),
taken here by patching `ops.superscan.default_ingest`. A sum taken with ONE
bf16 term (`exact_sums=False`) fails the same comparison: the prices have 14
bits and bf16 keeps 8, so the comparison is tight enough to catch a lower
precision. The chip itself is asked in `benchmarks/run.py --workload
purchases_sum_catchup`; the rehearsal of that cell runs here too.
"""

import copy
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, reader
from benchmarks import reference as ref
from benchmarks.stream import T0_MS, Cycle, build_cycle
from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.config import Configuration
from flink_tpu.connectors.sink import Sink, SinkWriter
from flink_tpu.connectors.source import (
    Batch, Source, SourceReader, SourceSplit, SplitEnumerator)
from flink_tpu.ops import superscan as superscan_mod
from flink_tpu.ops.aggregators import resolve
from flink_tpu.ops.superscan import make_superscan_step
from flink_tpu.runtime import fire_block
from flink_tpu.runtime import fused_window_pipeline as pipeline_mod

CONFIG, CELL = "purchases_windowed_sum", "purchases_sum_catchup"
KEYS, BATCH = 512, 1024
SLIDING = {"size_ms": 8000, "slide_ms": 4000}
TUMBLING = {"size_ms": 4000, "slide_ms": 4000}
# 2 000 purchases per event-second over a 20 s cycle: a batch spans half a
# second of event time plus the jitter, so about one step in six straddles a
# 4 s slice boundary and takes the wide histogram, the others the narrow one
TRAFFIC = {"density_events_per_event_s": 2000, "cycle_ms": 20_000,
           "jitter_ms": 200}
EVENTS = 60 * BATCH                 # a lap and a half: 61 440 purchases
OPTIONS = {"execution.step.batch-size": BATCH,
           "execution.state.key-capacity": KEYS}


def small_config(window):
    """The configuration's file with a key space a CPU test can afford."""
    cfg = copy.deepcopy(harness.load_json("configs", CONFIG + ".json"))
    for column in cfg["stream"]["columns"]:
        if column["name"] in ("gem_pack_id", "user_id"):
            column["mod"] = KEYS
    cfg["reference"]["keys"] = KEYS
    cfg["window"] = dict(window)
    return cfg


class CycleSource(Source):
    """The first `events` events of the stream, lap after lap, in batches."""

    boundedness = "BOUNDED"

    def __init__(self, cycle: Cycle, events: int):
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


def run_job(cfg, cycle, events=EVENTS):
    """The benchmark's job builder through `env.execute()`; the sink's
    batches and the run's result."""
    config = Configuration()
    for key, value in OPTIONS.items():
        config.set_string(key, value)
    env = StreamExecutionEnvironment.get_execution_environment(config)
    sink = RowSink()
    harness.load_module("jobs", cfg["job"]).build(
        env, CycleSource(cycle, events), sink, cfg, {})
    result = env.execute("purchases_sum_test")
    return sink.rows, result


def compare(cfg, cycle, rows, records_in, events=EVENTS):
    """`harness.run_cell`'s comparison: the sink's rows against the plain
    reference, every number beside its limit."""
    refmod = harness.load_module("references", cfg["reference"]["module"])
    expect, j0 = refmod.expected(cycle, cfg["reference"], {}, cfg["window"],
                                 events, TRAFFIC["jitter_ms"])
    cmp = ref.compare(reader.unpack_rows(rows), expect, j0, cfg["window"],
                      records_in, events)
    return cmp, expect


@pytest.fixture
def matmul_ingest(monkeypatch):
    """Build the program a TPU would: the one-hot matmul histograms. (Every
    `harness.load_module` of the job builder makes its functions anew, so no
    run finds another's program in `_CHAINED_CACHE`.)"""
    monkeypatch.setattr(superscan_mod, "default_ingest", lambda: "matmul")


# -- the system against the reference -------------------------------------------

@pytest.mark.parametrize("ingest", ["scatter", "matmul"])
@pytest.mark.parametrize("window", [SLIDING, TUMBLING],
                         ids=["sliding_8s_4s", "tumbling_4s"])
@pytest.mark.parametrize("seed", [3700000001, 11])
def test_every_window_sum_equals_the_reference_exactly(
        request, seed, window, ingest):
    if ingest == "matmul":
        request.getfixturevalue("matmul_ingest")
    cfg = small_config(window)
    cycle = build_cycle(cfg["stream"], TRAFFIC, seed, wrap=BATCH)
    rows, result = run_job(cfg, cycle)
    cmp, expect = compare(cfg, cycle, rows, result.records_in)
    assert cmp["numbers"] == {name: 0 for name in ref.LIMITS}
    # the comparison compared something: every gem pack in every full window
    assert cmp["cells_compared"] == int((expect > 0).sum()) > 6 * KEYS
    assert cmp["rows_compared"] == cmp["cells_compared"]
    programs = {
        prog for op in result.metrics["device"]["operators"].values()
        for prog in op["compile"]["programs"]}
    assert "fused_chained_superscan" in programs
    # both branches of the histogram ran: steps that lay in one slice and
    # steps that straddled a boundary
    (op,) = result.metrics["device"]["operators"].values()
    steps = op["link"]["stepsPlannedScalar"] + op["link"]["stepsPlannedMasked"]
    assert steps >= EVENTS // BATCH
    assert 0 < op["phases"]["oneSliceSteps"] < 32 * op["link"]["dispatches"]
    assert op["valueFields"] == 1
    # a count ring and a sum ring of [KEYS, S] four-byte cells
    assert op["ringBytes"] % (2 * KEYS * 4) == 0 and op["ringBytes"] > 0


def test_one_bf16_term_fails_the_comparison(monkeypatch, matmul_ingest):
    """`exact_sums=False` (one bf16 term a price) through the same job: the
    comparison reads wrong cells, nearly all of them."""
    init = pipeline_mod.FusedWindowPipeline.__init__

    def one_term(self, *args, **kwargs):
        init(self, *args, **{**kwargs, "exact_sums": False})

    monkeypatch.setattr(pipeline_mod.FusedWindowPipeline, "__init__", one_term)
    cfg = small_config(SLIDING)
    cycle = build_cycle(cfg["stream"], TRAFFIC, 3700000002, wrap=BATCH)
    rows, result = run_job(cfg, cycle)
    cmp, _expect = compare(cfg, cycle, rows, result.records_in)
    numbers = cmp["numbers"]
    assert numbers["cells_wrong"] > cmp["cells_compared"] // 2
    assert not ref.verdict(numbers, ref.LIMITS)
    # the rows themselves are all there, once each: only the sums are off
    assert numbers["cells_missing"] == numbers["cells_twice"] == 0


# -- the reference against a loop -------------------------------------------------

def tiny_cycle(events=1_500, keys=8):
    """1 500 purchases over a 2 s cycle, up to 2 ms behind their creation."""
    idx = np.arange(events)
    rng = np.random.default_rng(5)
    values = np.stack([rng.integers(0, keys, events),
                       rng.integers(0, 10_000, events),
                       rng.integers(0, keys, events), idx * 4 // 3],
                      axis=1).astype(np.float32)
    ts = (T0_MS + idx * 4 // 3 - idx % 3).astype(np.int64)
    return Cycle(values, ts, events, 2_000,
                 ["gem_pack_id", "price", "user_id", "time"], 750.0)


@pytest.mark.parametrize("window", [{"size_ms": 800, "slide_ms": 400},
                                    {"size_ms": 400, "slide_ms": 400}],
                         ids=["sliding", "tumbling"])
def test_the_reference_is_the_per_record_loop(window):
    refmod = harness.load_module("references", "keyed_window_sum")
    cycle = tiny_cycle()
    sem = {"key": {"column": "gem_pack_id"}, "value": {"column": "price"},
           "keys": 8}
    events = cycle.events + 600                   # a lap and a part of one
    expect, j0 = refmod.expected(cycle, sem, {}, window, events, 2)
    size, slide = window["size_ms"], window["slide_ms"]
    want = {}
    for i in range(events):
        lap, at = divmod(i, cycle.events)
        ts = int(cycle.ts[at]) + lap * cycle.cycle_ms
        for j in range((ts - size) // slide + 1, ts // slide + 1):
            cell = (j, int(cycle.values[at, 0]))
            want[cell] = want.get(cell, 0) + int(cycle.values[at, 1])
    got = {(j0 + r, k): int(v) for (r, k), v in np.ndenumerate(expect) if v}
    assert got == want and expect.dtype == np.int32
    # the control: the first 300 purchases summed a second time
    broken, _ = refmod.expected(cycle, sem, {}, window, events, 2,
                                replay=(0, 300))
    assert (broken >= expect).all() and (broken != expect).any()
    assert int(broken.sum() - expect.sum()) == int(
        cycle.values[:300, 1].sum()) * (size // slide)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("keyed_window_sum.py", "keyed_window_count.py"):
        with open(os.path.join(harness.HERE, "references", name)) as f:
            assert "flink_tpu" not in f.read().replace(
                "imports nothing of `flink_tpu`", "")


# -- the step: the narrow and the wide histogram with a value leaf ----------------

@pytest.mark.parametrize("exact", [True, False], ids=["three_terms", "one_term"])
def test_a_straddling_step_and_a_one_slice_step_sum_the_same_prices(exact):
    """The same (gem pack, price) records once as steps that lie in one slice
    (the narrow histogram, slice 0 of the partial) and once spread over the
    step's four slices (the wide one): with three bf16 terms each ring cell
    holds the integer sum of its prices either way; with one it does not."""
    K, S, NSB, F, R, B, chunk, T = 256, 16, 4, 2, 4, 1024, 256, 3
    agg = resolve("sum")
    rng = np.random.default_rng(37)
    keys = rng.integers(0, K, (T, B))
    prices = rng.integers(0, 10_000, (T, B))
    keys[:, ::7] = -1                             # dead lanes in both forms
    srel = {"one_slice": np.zeros((T, B), np.int64),
            "straddling": rng.integers(0, NSB, (T, B))}
    step = make_superscan_step(agg, K, S, NSB, F, R, 2, chunk, exact,
                               ingest="matmul", phase_counters=True)
    sums = {}
    for form, rel in srel.items():
        idx = np.where(keys >= 0, keys * NSB + rel, -1).astype(np.int32)
        vals = np.where(keys >= 0, prices, 0).astype(np.float32)
        carry = ({"sum": jnp.zeros((K, S), jnp.float32)},
                 jnp.zeros((K, S), jnp.int32),
                 {"sum": jnp.zeros((R, K), jnp.float32)},
                 jnp.zeros((R, K), jnp.int32), jnp.zeros((4,), jnp.int32))
        i32 = jnp.int32
        xs = (jnp.asarray(idx), jnp.asarray(vals), jnp.zeros((T,), i32),
              jnp.zeros((T, F), i32), jnp.zeros((T, F), i32),
              jnp.zeros((T, F), i32), jnp.ones((T, S), i32))
        (state, count, _o, _c, phase_c), _ = jax.jit(
            lambda c, xs: jax.lax.scan(step, c, xs))(carry, xs)
        assert int(phase_c[3]) == (T if form == "one_slice" else 0)
        live = keys >= 0
        want = np.zeros((K, S), np.int64)
        np.add.at(want, (keys[live], rel[live]), prices[live])
        assert state["sum"].dtype == jnp.float32
        if exact:
            np.testing.assert_array_equal(np.asarray(state["sum"]), want)
        else:
            assert (np.asarray(state["sum"]) != want).sum() > K // 2
        # per gem pack over the step's slices: what a window over them reads
        sums[form] = np.asarray(state["sum"]).sum(axis=1)
        np.testing.assert_array_equal(
            np.asarray(count).sum(axis=1),
            np.bincount(keys[live], minlength=K))
    if exact:
        np.testing.assert_array_equal(sums["one_slice"], sums["straddling"])


@pytest.mark.parametrize("values", ["prices", "wide", "tiny"])
def test_three_bf16_terms_add_up_to_the_value_bit_for_bit(values):
    """`matmul_hist.bf16_terms`: v == t0 + t1 + t2 exactly, each term a
    value bf16 holds (so the convert to bf16 rounds nothing, and a compiler
    that skips it changes nothing). On the CPU the form that failed on the
    chip (a convert to bf16 and back) passes this too: only the chip run of
    `purchases_sum_catchup` tells them apart (PERF.md section 6, PR 37)."""
    from flink_tpu.ops.matmul_hist import bf16_terms

    rng = np.random.default_rng(3)
    v = {"prices": rng.integers(0, 10_000, 4096).astype(np.float32),
         "wide": (rng.normal(size=4096) * 1e6).astype(np.float32),
         "tiny": (rng.normal(size=4096) * 1e-20).astype(np.float32)}[values]
    v[:3] = [0.0, -0.0, 16_777_215.0]            # 2^24 - 1: all 24 bits set
    terms = [np.asarray(t) for t in bf16_terms(jnp.asarray(v))]
    assert all(t.dtype == jnp.bfloat16 for t in terms)
    total = sum(t.astype(np.float64) for t in terms)
    np.testing.assert_array_equal(total, v.astype(np.float64))
    if values == "prices":      # 14 bits: two terms hold a price, never one
        assert (terms[1] != 0).any() and not (terms[2] != 0).any()


# -- fire, readback, emission -----------------------------------------------------

def test_a_fire_hands_drain_an_f32_column_and_the_sink_integers(monkeypatch):
    """The block a fire appends carries the live gem packs and an f32 result
    column; `downstream_batch` builds `(int, float)` pairs from it, and
    `reader.unpack_rows` reads those back as the integers they are."""
    blocks = []
    from flink_tpu.runtime.fused_window_operator import FusedWindowOperator

    original = FusedWindowOperator._append_fire

    def spy(self, lane, window, live, results, keys_of=None):
        original(self, lane, window, live, results, keys_of)
        blocks.append(lane[-1])

    monkeypatch.setattr(FusedWindowOperator, "_append_fire", spy)
    cfg = small_config(SLIDING)
    cycle = build_cycle(cfg["stream"], TRAFFIC, 3700000003, wrap=BATCH)
    rows, _result = run_job(cfg, cycle, events=24 * BATCH)
    assert blocks and all(type(b) is fire_block.FireBlock for b in blocks)
    for block in blocks:
        assert block.results.dtype == np.float32
        assert block.keys.dtype.kind == "i" and len(block.keys) == len(block)
        assert (block.results == np.rint(block.results)).all()
    fullest = max(blocks, key=len)
    assert len(fullest) == KEYS                   # every gem pack is live
    values, ts = fire_block.downstream_batch([fullest], bare=False)
    key, total = values[0]
    assert type(key) is int and type(total) is float and ts[0] == fullest.ts
    (keys, sums, _ts), = reader.unpack_rows([(values, ts)])
    assert sums.dtype == np.int64
    np.testing.assert_array_equal(keys, fullest.keys)
    np.testing.assert_array_equal(sums, fullest.results.astype(np.int64))
    # what the sink got is those blocks, row for row
    assert sum(len(v) for v, _t in rows) == sum(map(len, blocks))


# -- the cell, rehearsed ----------------------------------------------------------

def rehearse(*flags):
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", "3700000011", "--rehearse-cpu", *flags],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_rehearsal_is_correct():
    out = rehearse()
    assert out["rehearsal"] is True and out["correct"] is True
    assert all(c["value"] == 0 for c in out["compared"].values())


def test_the_rehearsals_control_is_not_correct():
    control = rehearse("--control", "replay_batch")
    assert control["correct"] is False
    assert control["compared"]["cells_wrong"]["value"] >= 1
