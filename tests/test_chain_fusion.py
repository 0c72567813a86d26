"""Whole-graph fusion (graph/fusion.py + DeviceChainRunner): parity of the
fused device chain with the host ChainRunner path and the per-record oracle.

The fused path compiles a traceable map/filter/map_ts prologue, key/value
extraction, and the windowed aggregation into ONE jitted multi-step device
program (`lax.scan` over T batches). These tests pin that the compiled
program produces results identical to (a) today's ChainRunner + fused
window operator path and (b) the per-record OracleWindowOperator, including
the mixed-chain fallback boundary, empty/watermark-only steps, and the
out-of-range-key hard error. Values are integer-valued floats with window
sums far below 2**24, so float32 accumulation is exact in any order and
the comparisons are exact, not approximate.
"""

import warnings

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.functions import AggregateFunction
from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.config import Configuration, ExecutionOptions
from flink_tpu.connectors.source import Batch, DataGeneratorSource
from flink_tpu.core.time import MAX_WATERMARK
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.graph.fusion import (
    DeviceChainPlan,
    plan_device_chains,
    window_is_device_fusable,
)
from flink_tpu.graph.transformation import plan
from flink_tpu.runtime.executor import (
    ChainRunner,
    DeviceChainRunner,
    StepRunner,
    WindowStepRunner,
    _FanOut,
    build_runners,
)
from flink_tpu.utils.arrays import as_device_column

NUM_KEYS = 7


def _source(n=4000, seed=3, disorder=40):
    """Deterministic 2-column (key, value) records: value columns are small
    integers so float32 sums are exact; timestamps mildly out of order
    within `disorder` ms."""
    rng = np.random.default_rng(seed)
    jitter = rng.integers(0, disorder, size=n)

    def gen(idx):
        keys = (idx * 7919) % NUM_KEYS
        vals = ((idx * 31) % 19 + 1).astype(np.float64)
        ts = 10_000 + idx * 13 - jitter[idx]
        return Batch(
            np.stack([keys, vals], axis=1).astype(np.float64),
            ts.astype(np.int64),
        )

    return DataGeneratorSource(gen, n)


class _SumAgg(AggregateFunction):
    """Python sum: forces the per-record OracleWindowOperator."""

    def create_accumulator(self):
        return 0.0

    def add(self, value, acc):
        return acc + float(value)

    def get_result(self, acc):
        return acc

    def merge(self, a, b):
        return a + b


_ORACLE_AGGS = {"sum": _SumAgg}


def _program(path, aggregate="sum", assigner=None, batch_size=512, n=4000,
             superbatch_steps=4, extra_conf=None):
    """Build + run one program through `path` in {'oracle','chain','fused'}:
    filter -> map (projection) -> keyBy -> window -> aggregate -> collect.
    Identical logical semantics in all three.

    Returns (sorted results, runner type names)."""
    assigner = assigner or SlidingEventTimeWindows.of(2_000, 1_000)
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, batch_size)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, superbatch_steps)
    cfg.set(ExecutionOptions.CHAIN_FUSION, path == "fused")
    for opt, v in (extra_conf or {}).items():
        cfg.set(opt, v)
    env = StreamExecutionEnvironment.get_execution_environment(cfg)
    ds = env.from_source(
        _source(n=n),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(50),
    )
    if path == "oracle":
        ds = ds.filter(lambda r: r[1] > 3)
        ds = ds.map(lambda r: (r[0], r[1] * 2.0))
        win = (
            ds.key_by(lambda r: int(r[0]))
            .window(assigner)
            .aggregate(_ORACLE_AGGS[aggregate](), value_fn=lambda r: r[1])
        )
    else:
        ds = ds.filter(lambda col: col[:, 1] > 3, traceable=True)
        ds = ds.map(lambda col: col * jnp.asarray([1.0, 2.0]), traceable=True)
        win = (
            ds.key_by(lambda col: col[:, 0].astype(jnp.int32), traceable=True)
            .window(assigner)
            .aggregate(aggregate, value_fn=lambda col: col[:, 1],
                       value_traceable=True)
        )
    sink = win.collect()
    runners, _ = build_runners(plan(env._sinks), cfg)
    kinds = [type(r).__name__ for r in runners]
    env.execute()
    out = sorted((int(k), float(v)) for k, v in sink.results)
    return out, kinds


# ---------------------------------------------------------------------------
# three-way parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("assigner_fn", [
    lambda: TumblingEventTimeWindows.of(1_000),
    lambda: SlidingEventTimeWindows.of(2_000, 1_000),
], ids=["tumbling", "sliding"])
def test_three_way_parity_sum(assigner_fn):
    fused, kf = _program("fused", assigner=assigner_fn())
    chain, kc = _program("chain", assigner=assigner_fn())
    oracle, ko = _program("oracle", assigner=assigner_fn())
    assert "DeviceChainRunner" in kf
    assert "DeviceChainRunner" not in kc and "ChainRunner" in kc
    assert "DeviceChainRunner" not in ko
    assert len(fused) > 0
    assert fused == chain          # exact: integer-valued float32 sums
    assert fused == oracle


@pytest.mark.parametrize("aggregate", ["count", "min", "max"])
def test_parity_fused_vs_chain_all_scatter_kinds(aggregate):
    """min/max exercise the scatter-combine fields, count the ONE-source
    field; parity against today's ChainRunner + fused window path."""
    fused, kf = _program("fused", aggregate=aggregate)
    chain, kc = _program("chain", aggregate=aggregate)
    assert "DeviceChainRunner" in kf and "DeviceChainRunner" not in kc
    assert len(fused) > 0
    assert fused == chain


@pytest.mark.parametrize("batch_size,superbatch_steps", [(64, 2), (251, 7)])
def test_parity_across_batch_geometries(batch_size, superbatch_steps):
    """Ragged last batches, odd superbatch sizes: the staged [T, B] geometry
    must not leak into results."""
    fused, _ = _program("fused", batch_size=batch_size, n=1777,
                        superbatch_steps=superbatch_steps)
    chain, _ = _program("chain", batch_size=batch_size, n=1777,
                        superbatch_steps=superbatch_steps)
    assert len(fused) > 0
    assert fused == chain


# ---------------------------------------------------------------------------
# fallback boundaries
# ---------------------------------------------------------------------------

def _build_env(traceable_chain=True, traceable_key=True, flat_map=False,
               aggregate="sum", second_consumer=False):
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, 512)
    env = StreamExecutionEnvironment.get_execution_environment(cfg)
    ds = env.from_source(
        _source(n=600),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(50),
    )
    ds = ds.filter(lambda col: col[:, 1] > 3, traceable=True)
    if not traceable_chain:
        # vectorized but NOT declared traceable: pins the chain on host
        ds = ds.map(lambda col: np.asarray(col) * np.asarray([1.0, 2.0]),
                    vectorized=True)
    else:
        ds = ds.map(lambda col: col * jnp.asarray([1.0, 2.0]), traceable=True)
    if flat_map:
        def dup(col):
            col = np.asarray(col)
            return np.repeat(col, 2, axis=0), np.repeat(np.arange(len(col)), 2)
        ds = ds.flat_map(dup, vectorized=True)
    if second_consumer:
        ds.map(lambda col: col[:, 1], traceable=True).collect()
    key_kw = {"traceable": True} if traceable_key else {"vectorized": True}
    win = (
        ds.key_by(lambda col: jnp.asarray(col)[:, 0].astype(jnp.int32),
                  **key_kw)
        .window(SlidingEventTimeWindows.of(2_000, 1_000))
        .aggregate(aggregate, value_fn=lambda col: jnp.asarray(col)[:, 1],
                   value_vectorized=True,
                   value_traceable=traceable_key)
    )
    sink = win.collect()
    return env, cfg, sink


def _kinds(env, cfg):
    runners, _ = build_runners(plan(env._sinks), cfg)
    return [type(r).__name__ for r in runners]


def test_fully_traceable_chain_is_absorbed():
    env, cfg, _ = _build_env()
    kinds = _kinds(env, cfg)
    # chain + window collapse into ONE DeviceChainRunner: no ChainRunner
    assert "DeviceChainRunner" in kinds
    assert "ChainRunner" not in kinds


def test_untraceable_transform_keeps_chain_on_host():
    """Mixed chain: one vectorized-but-not-traceable transform pins the
    chain on host, but key/value extraction + window still fuse; results
    match the fully-host path exactly."""
    env, cfg, sink = _build_env(traceable_chain=False)
    kinds = _kinds(env, cfg)
    assert "ChainRunner" in kinds and "DeviceChainRunner" in kinds
    env.execute()
    got = sorted((int(k), float(v)) for k, v in sink.results)

    env2, cfg2, sink2 = _build_env(traceable_chain=False)
    cfg2.set(ExecutionOptions.CHAIN_FUSION, False)
    env2.execute()
    want = sorted((int(k), float(v)) for k, v in sink2.results)
    assert len(got) > 0 and got == want


def test_flat_map_always_falls_back():
    """flat_map changes cardinality dynamically: no static-shape trace
    exists, so the chain keeps the host path (results still correct)."""
    env, cfg, sink = _build_env(flat_map=True)
    kinds = _kinds(env, cfg)
    assert "ChainRunner" in kinds
    env.execute()
    got = sorted((int(k), float(v)) for k, v in sink.results)

    env2, cfg2, sink2 = _build_env(flat_map=True)
    cfg2.set(ExecutionOptions.CHAIN_FUSION, False)
    env2.execute()
    want = sorted((int(k), float(v)) for k, v in sink2.results)
    assert len(got) > 0 and got == want


def test_undeclared_key_selector_keeps_window_path():
    """key_by without traceable=True: the window step keeps today's
    WindowStepRunner (the key dictionary path)."""
    env, cfg, _ = _build_env(traceable_key=False)
    kinds = _kinds(env, cfg)
    assert "DeviceChainRunner" not in kinds
    assert "WindowStepRunner" in kinds


def test_second_consumer_pins_chain_on_host():
    """A chain whose output feeds a second consumer cannot be absorbed
    (fusing would corrupt the other consumer's input); the window still
    fuses key/value extraction alone."""
    env, cfg, _ = _build_env(second_consumer=True)
    kinds = _kinds(env, cfg)
    assert "ChainRunner" in kinds and "DeviceChainRunner" in kinds


def test_fusion_config_off_disables_the_path():
    env, cfg, _ = _build_env()
    cfg.set(ExecutionOptions.CHAIN_FUSION, False)
    kinds = _kinds(env, cfg)
    assert "DeviceChainRunner" not in kinds


def test_oracle_aggregate_function_not_fusable():
    """An AggregateFunction has no device form: the planner must refuse."""
    env = StreamExecutionEnvironment.get_execution_environment()
    win = (
        env.from_source(_source(n=100))
        .key_by(lambda col: col[:, 0].astype(np.int64), traceable=True)
        .window(SlidingEventTimeWindows.of(2_000, 1_000))
        .aggregate(_SumAgg(), value_fn=lambda col: col[:, 1],
                   value_traceable=True)
    )
    win.collect()
    g = plan(env._sinks)
    plans, absorbed = plan_device_chains(g)
    assert plans == {} and absorbed == set()
    for s in g.steps:
        if s.terminal is not None and s.terminal.kind == "window_aggregate":
            assert not window_is_device_fusable(s.terminal)


# ---------------------------------------------------------------------------
# empty batches / watermark-only steps
# ---------------------------------------------------------------------------

def test_empty_and_watermark_only_steps():
    """Drive a DeviceChainRunner directly with empty object-dtype batches
    (the stage reader's idle poll shape) and watermark-only advances: no
    warning, no error, and results match the host path fed identically."""
    from flink_tpu.utils.arrays import obj_array

    def build(fused):
        cfg = Configuration()
        cfg.set(ExecutionOptions.CHAIN_FUSION, fused)
        cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 2)
        env = StreamExecutionEnvironment.get_execution_environment(cfg)
        win = (
            env.from_source(_source(n=10))
            .filter(lambda col: col[:, 1] > 0, traceable=True)
            .key_by(lambda col: col[:, 0].astype(jnp.int32), traceable=True)
            .window(TumblingEventTimeWindows.of(1_000))
            .aggregate("sum", value_fn=lambda col: col[:, 1],
                       value_traceable=True)
        )
        win.collect()
        runners, feeds = build_runners(plan(env._sinks), cfg)
        entry = runners[0]
        results = []
        runners[-1].downstream = None
        sink = runners[-1]
        orig = sink.on_batch

        def capture(vals, ts):
            results.extend(
                (int(k), float(v)) for k, v in vals)
            orig(vals, ts)
        sink.on_batch = capture
        return entry, results

    def drive(entry):
        empty = obj_array([])
        ets = np.asarray([], dtype=np.int64)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            entry.on_batch(empty, ets)                      # idle poll
            entry.on_watermark(9_000)                       # watermark-only
            vals = np.asarray([[1.0, 5.0], [2.0, 7.0]])
            entry.on_batch(vals, np.asarray([10_100, 10_150], dtype=np.int64))
            entry.on_batch(empty, ets)
            entry.on_watermark(11_500)
            entry.on_batch(np.asarray([[1.0, 3.0]]),
                           np.asarray([11_700], dtype=np.int64))
            entry.on_watermark(13_000)
            # the run loop's finish() ends every stream with MAX watermark;
            # the fused operator flushes there (superbatch granularity)
            entry.on_watermark(MAX_WATERMARK - 1)
            entry.on_end()

    e_fused, r_fused = build(True)
    assert isinstance(e_fused, DeviceChainRunner)
    drive(e_fused)
    e_host, r_host = build(False)
    drive(e_host)
    assert len(r_fused) > 0
    assert sorted(r_fused) == sorted(r_host)


# ---------------------------------------------------------------------------
# hard errors: map_batch 1:N, out-of-range traced keys
# ---------------------------------------------------------------------------

def test_map_batch_non_1_to_1_raises_loudly():
    """The bare assert became an attributed ValueError: a 1:N map_batch
    must fail loudly (asserts vanish under python -O) instead of silently
    corrupting timestamp alignment."""
    env = StreamExecutionEnvironment.get_execution_environment()
    ds = env.from_collection(
        [(1, 10_000), (2, 10_001), (3, 10_002)], timestamp_fn=lambda r: r[1]
    )
    ds.map_batch(lambda vs: vs[:-1], name="bad_batch").collect()
    with pytest.raises(ValueError, match="bad_batch.*must be.*1:1"):
        env.execute()


@pytest.mark.parametrize("bad_key", [200, -3], ids=["over", "negative"])
def test_traced_key_out_of_range_is_a_hard_error(bad_key):
    """Dense device keying cannot grow mid-dispatch: a traced selector
    emitting a key outside [0, capacity) must raise at resolve — never
    silently alias another key's row or drop the record."""
    cfg = Configuration()
    cfg.set(ExecutionOptions.KEY_CAPACITY, 64)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 1)
    env = StreamExecutionEnvironment.get_execution_environment(cfg)
    win = (
        env.from_source(_source(n=50))
        .key_by(lambda col: col[:, 0].astype(jnp.int32) + bad_key,
                traceable=True)
        .window(TumblingEventTimeWindows.of(1_000))
        .aggregate("sum", value_fn=lambda col: col[:, 1],
                   value_traceable=True)
    )
    win.collect()
    with pytest.raises(ValueError, match="key-capacity|non-negative"):
        env.execute()


def test_record_mode_source_columnarizes_with_warning():
    """A record-mode (object column) source feeding a fused chain pays a
    per-batch columnarization pass and warns once; results stay correct."""
    rows = [(float(i % 3), float(i % 5 + 1), 10_000 + i * 13)
            for i in range(400)]

    def build(fused):
        cfg = Configuration()
        cfg.set(ExecutionOptions.CHAIN_FUSION, fused)
        env = StreamExecutionEnvironment.get_execution_environment(cfg)
        win = (
            env.from_collection(
                rows, timestamp_fn=lambda r: int(r[2]),
                watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(0),
            )
            .key_by(lambda col: col[:, 0].astype(jnp.int32), traceable=True)
            .window(TumblingEventTimeWindows.of(1_000))
            .aggregate("sum", value_fn=lambda col: col[:, 1],
                       value_traceable=True)
        )
        sink = win.collect()
        return env, sink

    env, sink = build(True)
    with pytest.warns(RuntimeWarning, match="record-mode"):
        env.execute()
    env2, sink2 = build(False)
    env2.execute()
    got = sorted((int(k), float(v)) for k, v in sink.results)
    want = sorted((int(k), float(v)) for k, v in sink2.results)
    assert len(got) > 0 and got == want


# ---------------------------------------------------------------------------
# snapshot / restore through the fused runner
# ---------------------------------------------------------------------------

def test_fused_runner_snapshot_restore_parity():
    """Snapshot mid-stream, restore into a fresh runner, continue: the
    union of outputs matches an uninterrupted run (checkpointed jobs take
    the fused path by default now)."""
    def build():
        cfg = Configuration()
        cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 2)
        env = StreamExecutionEnvironment.get_execution_environment(cfg)
        win = (
            env.from_source(_source(n=10))
            .key_by(lambda col: col[:, 0].astype(jnp.int32), traceable=True)
            .window(TumblingEventTimeWindows.of(1_000))
            .aggregate("sum", value_fn=lambda col: col[:, 1],
                       value_traceable=True)
        )
        win.collect()
        runners, _ = build_runners(plan(env._sinks), cfg)
        entry = runners[0]
        assert isinstance(entry, DeviceChainRunner)
        out = []
        # wired as build_runners wires a consumer: the fused runner hands
        # its fires to the fan-out, which builds the rows for a step that
        # takes rows
        entry.downstream = _FanOut()
        entry.downstream.add(_Collect(out), 0)
        return entry, out

    class _Collect(StepRunner):
        def __init__(self, out):
            self.out = out

        def on_batch(self, vals, ts):
            self.out.extend((int(k), float(v)) for k, v in vals)

        def on_watermark(self, wm):
            pass

        def on_end(self):
            pass

    def batches():
        for t0 in range(0, 8):
            base = 10_000 + t0 * 400
            vals = np.asarray(
                [[float(t0 % 3), 2.0], [float((t0 + 1) % 3), 3.0]])
            ts = np.asarray([base, base + 100], dtype=np.int64)
            yield vals, ts, base

    # uninterrupted
    r1, out1 = build()
    for vals, ts, base in batches():
        r1.on_batch(vals, ts)
        r1.on_watermark(base)
    r1.on_end()

    # snapshot after 4 batches, restore, continue
    r2, out2 = build()
    it = list(batches())
    for vals, ts, base in it[:4]:
        r2.on_batch(vals, ts)
        r2.on_watermark(base)
    snap = r2.snapshot()
    r3, out3 = build()
    r3.restore(snap)
    for vals, ts, base in it[4:]:
        r3.on_batch(vals, ts)
        r3.on_watermark(base)
    r3.on_end()
    assert sorted(out1) == sorted(out2 + out3)


def test_restore_preserves_held_future_column_dtype():
    """Held-back far-future raw columns survive a snapshot/restore round
    trip at their ORIGINAL dtype: the raw-payload cast is dtype-free, and a
    tolist() round-trip would promote float32 to float64 — making the first
    post-restore dispatch trip the fused pipeline's fixed-geometry check."""
    from flink_tpu.runtime.fused_window_operator import StepNormalizer
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedWindowPipeline,
        TracedPrologue,
    )

    pro = TracedPrologue(transforms=(),
                         key_fn=lambda col: col[:, 0].astype(jnp.int32))
    pipe = FusedWindowPipeline(
        SlidingEventTimeWindows.of(10_000, 1_000), "count",
        key_capacity=8, prologue=pro,
    )
    norm = StepNormalizer(pipe, raw_payload=True)
    col = np.asarray([[1.0, 2.0]], dtype=np.float32)
    norm.push(col, None, np.asarray([10_000], np.int64))
    # far enough past the ring frontier to be held back, not staged
    far = 10_000 + (pipe.S + pipe.NSB + 1) * pipe.sl * pipe.slice_ms \
        if hasattr(pipe, "slice_ms") else 10_000 + 10_000_000
    norm.push(col, None, np.asarray([far], np.int64))
    assert norm.num_future_held == 1, "harness: record was not held back"

    restored = StepNormalizer(pipe, raw_payload=True)
    restored.restore(norm.snapshot())
    held_col = restored._future[0][0]
    assert held_col.dtype == np.float32, (
        f"held column came back as {held_col.dtype}: restore must preserve "
        "the raw payload dtype or the geometry check kills the job"
    )


def test_all_empty_superbatch_does_not_pin_geometry():
    """A watermark-only dispatch before any data (the restore-then-watermark
    ordering) must not pin the scalar placeholder column shape on the
    pipeline — the first real batch afterwards is NOT a mid-stream geometry
    change."""
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedWindowPipeline,
        TracedPrologue,
    )

    pro = TracedPrologue(transforms=(),
                         key_fn=lambda col: col[:, 0].astype(jnp.int32))
    pipe = FusedWindowPipeline(
        TumblingEventTimeWindows.of(1_000), "count",
        key_capacity=8, prologue=pro,
    )
    empty = (np.empty((0, 2), np.float32), None, np.empty(0, np.int64))
    pipe.process_superbatch([empty, empty], [11_000, 12_000])
    assert pipe._raw_shape is None
    data = (np.asarray([[1.0, 0.0]], np.float32), None,
            np.asarray([13_000], np.int64))
    pipe.process_superbatch([data, empty], [13_000, 13_500])  # no raise
    assert pipe._raw_shape == (2,)


def test_wide_integer_columns_raise_instead_of_wrapping():
    """An int64 record column whose values exceed int32 must raise loudly
    on BOTH paths (fused staging and the host fallback cast) — narrowing
    silently would re-key records differently than 64-bit host math."""
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedWindowPipeline,
        TracedPrologue,
    )
    from flink_tpu.utils.arrays import canonical_column

    big = np.asarray([[5_000_000_000]], dtype=np.int64)  # > 2**31
    with pytest.raises(TypeError, match="would silently wrap"):
        canonical_column(big, "key_by selector input")

    pro = TracedPrologue(transforms=(),
                         key_fn=lambda col: (col[:, 0] // 1_000_000_000))
    pipe = FusedWindowPipeline(
        TumblingEventTimeWindows.of(1_000), "count",
        key_capacity=8, prologue=pro,
    )
    with pytest.raises(TypeError, match="would silently wrap"):
        pipe.stage([(big, None, np.asarray([10_000], np.int64))], [10_000])

    # in-range wide columns narrow cleanly (and floats guard overflow)
    ok = canonical_column(np.asarray([[7]], np.int64), "x")
    assert ok.dtype == np.int32
    with pytest.raises(TypeError, match="overflow"):
        canonical_column(np.asarray([1e300]), "x")


def test_epoch_scale_timestamps_with_traced_map_ts_raise_loudly():
    """With jax x64 disabled the staged timestamp column is int32; epoch-ms
    timestamps do not fit and would silently wrap inside a traceable map_ts
    UDF — that must be a loud error, never silent divergence from the host
    path."""
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedWindowPipeline,
        TracedPrologue,
    )

    pro = TracedPrologue(
        transforms=(("map_ts", lambda col, ts: col),),
        key_fn=lambda col: col[:, 0].astype(jnp.int32),
    )
    pipe = FusedWindowPipeline(
        TumblingEventTimeWindows.of(1_000), "count",
        key_capacity=8, prologue=pro,
    )
    epoch_ms = 1_760_000_000_000  # far beyond int32
    step = (np.asarray([[1.0, 0.0]], np.float32), None,
            np.asarray([epoch_ms], np.int64))
    with pytest.raises(TypeError, match="do not fit"):
        pipe.stage([step], [epoch_ms])


# ---------------------------------------------------------------------------
# ingest edge: as_device_column
# ---------------------------------------------------------------------------

def test_as_device_column_zero_copy_and_compaction():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    assert as_device_column(a) is a                      # contiguous: untouched
    ro = np.frombuffer(a.tobytes(), dtype=np.float32).reshape(3, 4)
    ro.flags.writeable = False
    assert as_device_column(ro) is ro                    # wire view: untouched
    nc = a[:, ::2]
    out = as_device_column(nc)
    assert out is not nc and out.flags.c_contiguous
    np.testing.assert_array_equal(out, nc)
    objs = np.empty(2, dtype=object)
    assert as_device_column(objs) is objs                # record mode: pass
    assert as_device_column([1, 2]) == [1, 2]            # non-ndarray: pass


def test_plan_describe_names_the_chain():
    env, cfg, _ = _build_env()
    plans, absorbed = plan_device_chains(plan(env._sinks))
    assert len(plans) == 1 and len(absorbed) == 1
    (p,) = plans.values()
    assert isinstance(p, DeviceChainPlan)
    assert "=>" in p.name


# ---------------------------------------------------------------------------
# the staged form of the record: one [T, B] array per field the chain reads
# ---------------------------------------------------------------------------

_W = 7                      # fields of the record, as the YSB event has
_KEYS = 11
_weights = jnp.arange(_W, dtype=jnp.float32)


def _key5(col):
    return col[:, 5].astype(jnp.int32)


def _val1(col):
    return col[:, 1]


def _record_batches(shape, n=1500, seed=23):
    """Seeded batches of integer-valued records of `shape` per event: the
    key in field 5 (or the scalar itself), small values elsewhere."""
    rng = np.random.default_rng(seed)
    rec = rng.integers(0, 6, size=(n,) + shape).astype(np.float32)
    if shape == (_W,):
        rec[:, 5] = rng.integers(0, _KEYS, size=n)
        rec[:, 2] = rng.integers(0, 2, size=n)
    ts = (10_000 + np.arange(n) * 7 - rng.integers(0, 30, size=n))
    return rec, ts.astype(np.int64)


#: case -> (record shape, transforms, key_fn, value_fn, aggregate,
#:          the fields staged: a tuple, "all", or None for today's form)
COLUMN_CASES = {
    # static col[:, c] in a filter / a map / the key / the value
    "filter_and_key": (
        (_W,), (("filter", lambda col: col[:, 2] < 0.5),), _key5, None,
        "count", (2, 5)),
    "map_projects": (
        (_W,), (("map", lambda col: jnp.stack(
            [col[:, 5], col[:, 3] * 2.0], axis=1)),),
        lambda col: col[:, 0].astype(jnp.int32), lambda col: col[:, 1],
        "sum", (3, 5)),
    "key_and_value": ((_W,), (), _key5, _val1, "sum", (1, 5)),
    "static_range": (
        (_W,), (), _key5, lambda col: col[:, 1:3].sum(axis=1), "sum",
        (1, 2, 5)),
    "identity_map_then_key": (
        (_W,), (("map", lambda col: col),), _key5, _val1, "max", (1, 5)),
    "map_ts_and_key": (
        (_W,), (("map_ts", lambda col, ts: jnp.stack(
            [col[:, 5], col[:, 4] + (ts % 2).astype(jnp.float32)],
            axis=1)),),
        lambda col: col[:, 0].astype(jnp.int32), lambda col: col[:, 1],
        "sum", (4, 5)),
    # consumers the analysis does not read: every field is staged
    "matmul": (
        (_W,), (("filter", lambda col: (col @ _weights) >= 20.0),), _key5,
        None, "count", "all"),
    "reduce_over_fields": (
        (_W,), (), _key5, lambda col: col.sum(axis=1), "sum", "all"),
    "traced_index": (
        (_W,), (), _key5,
        lambda col: col[:, jnp.argmin(jnp.zeros(3)) + 1], "sum", "all"),
    "nested_jit": (
        (_W,), (), _key5, lambda col: __import__("jax").jit(_val1)(col),
        "sum", "all"),
    "negative_index": (
        (_W,), (), _key5, lambda col: col[:, -1], "sum", "all"),
    # records that are not rank 1 per event keep today's form
    "scalar_record": (
        (), (), lambda col: col.astype(jnp.int32) % _KEYS, None, "count",
        None),
    "scalar_record_identity_value": (
        (), (), lambda col: col.astype(jnp.int32) % _KEYS, None, "sum",
        None),
    "rank2_record": (
        (2, 2), (), lambda col: col[:, 0, 1].astype(jnp.int32),
        lambda col: col[:, 1, 0], "sum", None),
}


def _column_job(case, fused):
    shape, transforms, key_fn, value_fn, aggregate, _cols = COLUMN_CASES[case]
    rec, ts = _record_batches(shape)
    cfg = Configuration()
    cfg.set(ExecutionOptions.BATCH_SIZE, 200)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    cfg.set(ExecutionOptions.KEY_CAPACITY, 16)
    cfg.set(ExecutionOptions.CHAIN_FUSION, fused)
    from flink_tpu.config import ObservabilityOptions
    cfg.set(ObservabilityOptions.DEVICE_TIMING_ENABLED, True)
    env = StreamExecutionEnvironment.get_execution_environment(cfg)
    ds = env.from_source(
        DataGeneratorSource(lambda idx: Batch(rec[idx], ts[idx]), len(ts)),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(40))
    for kind, fn in transforms:
        if kind == "filter":
            ds = ds.filter(fn, traceable=True)
        elif kind == "map":
            ds = ds.map(fn, traceable=True)
        else:
            ds = ds.map_with_timestamp(fn, traceable=True)
    keyed = ds.key_by(key_fn, traceable=True)
    win = keyed.window(TumblingEventTimeWindows.of(1_000))
    if value_fn is None:
        sink = win.aggregate(aggregate).collect()
    else:
        sink = win.aggregate(aggregate, value_fn=value_fn,
                             value_traceable=True).collect()
    result = env.execute()
    links = [op["link"] for op in
             result.metrics["device"]["operators"].values() if "link" in op]
    return sorted((int(k), float(v)) for k, v in sink.results), links


@pytest.mark.parametrize("case", sorted(COLUMN_CASES))
def test_column_analysis_and_parity_with_the_host_chain(case):
    """Which fields a traced chain reads is read off its jaxpr,
    conservatively; whatever it decides, the fused job gives the host
    chain's rows on the same seeded batches."""
    from flink_tpu.runtime.fused_window_pipeline import (
        ColumnLayout,
        TracedPrologue,
    )

    shape, transforms, key_fn, value_fn, aggregate, want = COLUMN_CASES[case]
    needs_vals = aggregate != "count"
    pro = TracedPrologue(transforms=transforms, key_fn=key_fn,
                         value_fn=value_fn)
    layout = pro.column_layout(shape, np.float32, needs_vals)
    if want is None:
        assert layout is None
        staged = total = int(np.prod(shape))
    else:
        cols = tuple(range(_W)) if want == "all" else want
        assert layout == ColumnLayout(cols, _W, "float32")
        staged, total = len(cols), _W

    fused, links = _column_job(case, fused=True)
    host, _ = _column_job(case, fused=False)
    assert fused == host and len(fused) > 20
    (link,) = links
    assert (link["columnsStaged"], link["recordColumns"]) == (staged, total)


def test_identity_value_is_never_a_layout_that_drops_fields():
    """value_fn=None hands the record itself to the aggregate ("the column
    IS the value"). Over a [n] record that is today's form (the case
    `scalar_record_identity_value` above); over a [n, 7] record it was never
    a program on any path, and the analysis refuses it with the trace's own
    error rather than answer with a subset."""
    from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

    pro = TracedPrologue(transforms=(), key_fn=_key5, value_fn=None)
    with pytest.raises(ValueError, match="Incompatible shapes"):
        pro.column_layout((_W,), np.float32, True)
    # the same chain under a count never touches the value: key only
    assert pro.column_layout((_W,), np.float32, False).columns == (5,)
    # and a float64 record is staged in the canonical dtype
    assert pro.column_layout((_W,), np.float64, False).dtype == "float32"


def _count_by_key(rec, keep, key_col=5):
    return np.bincount(rec[keep][:, key_col].astype(np.int64),
                       minlength=_KEYS)


def _pipeline_counts(pipe, rec, ts):
    rows = pipe.process_superbatch([(rec, None, ts)], [int(ts.max()) + 5_000])
    total = np.zeros(pipe.K, np.int64)
    for _window, counts, _fields in rows:
        total += counts
    return total[:_KEYS]


def test_chains_reading_different_fields_get_their_own_executables():
    from flink_tpu.runtime import fused_window_pipeline as fwp

    rec, ts = _record_batches((_W,), n=400)
    geom = dict(key_capacity=16, num_slices=16, nsb=4, chunk=256,
                fires_per_step=8, out_rows=16, backend="xla")
    assigner = TumblingEventTimeWindows.of(1_000)
    by_type = fwp.TracedPrologue(
        transforms=(("filter", lambda col: col[:, 2] < 0.5),), key_fn=_key5)
    by_value = fwp.TracedPrologue(
        transforms=(("filter", lambda col: col[:, 4] < 2.5),), key_fn=_key5)
    before = set(fwp._CHAINED_CACHE)
    got_type = _pipeline_counts(
        fwp.FusedWindowPipeline(assigner, "count", prologue=by_type, **geom),
        rec, ts)
    got_value = _pipeline_counts(
        fwp.FusedWindowPipeline(assigner, "count", prologue=by_value,
                                **geom), rec, ts)
    np.testing.assert_array_equal(got_type,
                                  _count_by_key(rec, rec[:, 2] < 0.5))
    np.testing.assert_array_equal(got_value,
                                  _count_by_key(rec, rec[:, 4] < 2.5))
    # ONE chain over records of two widths stages the same two [T, B]
    # arrays: only the layout in the key keeps the executables apart
    wide = np.concatenate([rec, rec[:, :2]], axis=1)
    got_wide = _pipeline_counts(
        fwp.FusedWindowPipeline(assigner, "count", prologue=by_type, **geom),
        wide, ts)
    np.testing.assert_array_equal(got_wide, got_type)
    layouts = sorted(str(k[-1]) for k in set(fwp._CHAINED_CACHE) - before)
    assert layouts == ["2+5/7", "2+5/9", "4+5/7"]


def test_narrowing_check_covers_the_staged_fields_only():
    """An int64 field too wide for int32 raises when a traced function
    reads it; an unread one never reaches a traced function, is not
    staged, and is not checked."""
    from flink_tpu.runtime.fused_window_pipeline import (
        FusedWindowPipeline,
        TracedPrologue,
    )

    rec = np.zeros((6, _W), np.int64)
    rec[:, 5] = [0, 1, 2, 0, 1, 2]
    rec[:, 3] = 5_000_000_000                     # > 2**31, never read
    ts = np.arange(6, dtype=np.int64) * 10 + 10_000
    geom = dict(key_capacity=8, num_slices=16, chunk=256, backend="xla")
    reads_5 = TracedPrologue(transforms=(), key_fn=_key5)
    pipe = FusedWindowPipeline(TumblingEventTimeWindows.of(1_000), "count",
                               prologue=reads_5, **geom)
    (_srel_h, *raw_h), *_ = pipe._fill(pipe._payload, [(rec, None, ts)],
                                       [10_000])
    assert [a.dtype for a in raw_h] == [np.int32] and raw_h[0].shape[0] == 1
    np.testing.assert_array_equal(_pipeline_counts(
        FusedWindowPipeline(TumblingEventTimeWindows.of(1_000), "count",
                            prologue=reads_5, **geom), rec, ts)[:3],
        [2, 2, 2])
    reads_3 = TracedPrologue(
        transforms=(), key_fn=lambda col: col[:, 3] // 1_000_000_000)
    wide = FusedWindowPipeline(TumblingEventTimeWindows.of(1_000), "count",
                               prologue=reads_3, **geom)
    with pytest.raises(TypeError, match="column 3.*would silently wrap"):
        wide.stage([(rec, None, ts)], [10_000])


# ---------------------------------------------------------------------------
# per-field staging under the other callers of the traced-chain path
# ---------------------------------------------------------------------------

def _seven_field_stream():
    from flink_tpu.testing.harness import seven_field_stream

    steps, wms = seven_field_stream(24, 48, 96)
    return [(rec, ts, wm) for (rec, ts), wm in zip(steps, wms)]


def _expected_sums(size_ms):
    """numpy: sum of field 1 per (key in field 5, tumbling window) over
    the records whose field 2 is 0."""
    want = {}
    for rec, ts, _wm in _seven_field_stream():
        for row, t in zip(rec, ts):
            if row[2] < 0.5:
                k = (int(row[5]), int(t) // size_ms * size_ms)
                want[k] = want.get(k, 0.0) + float(row[1])
    return sorted((k, w, v) for (k, w), v in want.items())


def _sum_chain():
    from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

    return TracedPrologue(
        transforms=(("filter", lambda col: col[:, 2] < 0.5),),
        key_fn=_key5, value_fn=_val1)


def _raw_op(**kw):
    from flink_tpu.runtime.fused_window_operator import FusedWindowOperator

    assigner = None if "assigners" in kw else TumblingEventTimeWindows.of(1000)
    return FusedWindowOperator(assigner, "sum", key_capacity=256,
                               superbatch_steps=8, prologue=_sum_chain(),
                               **kw)


def _rows(out):
    return sorted((int(k), int(w.start), float(v)) for k, w, v, _ in out)


def _feed(op, stream, drain):
    out = []
    for rec, ts, wm in stream:
        op.process_raw_batch(rec, ts)
        op.process_watermark(wm)
        out.extend(drain(op))
    return out


def _caller_latency_grouped_readback():
    """Latency mode's streamed readback: an 8-step span dispatched as four
    2-step programs, every staged field sliced [lo:hi] per group."""
    from flink_tpu.scheduler.latency_controller import LatencySpec

    class Pinned:
        def observe(self, n_steps, now=None):
            pass

        def steps(self, now=None):
            return 8

        current_steps = steps

        def reset(self):
            pass

    op = _raw_op(latency=LatencySpec(target_ms=50, max_inflight=2,
                                     readback_steps=2))
    op._controller = Pinned()
    from flink_tpu.runtime import fused_window_pipeline as fwp

    grouped = []
    inner = fwp._CHAINED.call

    def spy(pipe, run, group, Tg, B):
        (raw_d, _srel_d), _signature = group.scan_xs()
        grouped.append((Tg, len(raw_d), raw_d[0].shape == (Tg, B)))
        return inner(pipe, run, group, Tg, B)

    fwp._CHAINED.call = spy      # the one chained program of the process
    try:
        out = _feed(op, _seven_field_stream(), lambda o: o.drain_output())
        op.process_watermark(MAX_WATERMARK - 1)
        out.extend(op.drain_output())
    finally:
        del fwp._CHAINED.call
    assert len(grouped) > 2 and set(grouped) == {(2, 3, True)}
    return _rows(out), _expected_sums(1000)


def _caller_shared_partials():
    """SharedWindowPipeline inherits the chained program: two correlated
    tumbling windows over one scan, fed per-field."""
    op = _raw_op(assigners=[TumblingEventTimeWindows.of(1000),
                            TumblingEventTimeWindows.of(2000)])
    lanes = ([], [])

    def drain(o):
        for i, lane in enumerate(lanes):
            lane.extend(o.drain_spec_output(i))
        return ()

    _feed(op, _seven_field_stream(), drain)
    op.process_watermark(MAX_WATERMARK - 1)
    drain(op)
    assert op.pipe._layout().columns == (1, 2, 5)
    return ((_rows(lanes[0]), _rows(lanes[1])),
            (_expected_sums(1000), _expected_sums(2000)))


def _caller_snapshot_restore_mid_superbatch():
    """A snapshot with three steps of an eight-step superbatch buffered
    flushes them as a padded tail; the restored operator stages the rest."""
    stream = _seven_field_stream()
    first = _raw_op()
    out = _feed(first, stream[:11], lambda o: o.drain_output())
    assert 0 < len(first._steps) < 8
    snap = first.snapshot()     # the flushed tail's rows travel in it
    second = _raw_op()
    second.restore(snap)
    out.extend(_feed(second, stream[11:], lambda o: o.drain_output()))
    second.process_watermark(MAX_WATERMARK - 1)
    out.extend(second.drain_output())
    assert second.pipe._layout().columns == (1, 2, 5)
    return _rows(out), _expected_sums(1000)


@pytest.mark.parametrize("caller", [
    _caller_latency_grouped_readback,
    _caller_shared_partials,
    _caller_snapshot_restore_mid_superbatch,
], ids=lambda f: f.__name__[len("_caller_"):])
def test_per_field_staging_under_the_other_callers(caller):
    got, want = caller()
    assert got == want and len(got)
