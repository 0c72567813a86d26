"""Compile-only checks against a chip-less TPU v5e topology.

libtpu can describe a v5e without one attached, so Mosaic and XLA:TPU run
for real here under JAX_PLATFORMS=cpu: a kernel the installed compiler
refuses fails this file instead of a user's first dispatch on the chip.
Every Pallas geometry the executor defaults and bench.py select is
compiled, and the support gates are held to the compiler both ways:
admitted => compiles, refused => never selected.

Nothing here executes on a device; results are checked elsewhere
(interpret-mode parity tests here on the CPU, chip_smoke.py on the chip).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.ops import pallas_superscan as ps
from flink_tpu.ops.aggregators import (
    VALUE,
    count_agg,
    max_agg,
    mean_agg,
    sum_agg,
)
from flink_tpu.runtime.fused_window_pipeline import (
    FusedGlobalWindowPipeline,
    FusedWindowPipeline,
    TracedPrologue,
)

AGGS = {
    "count": count_agg(),
    "sum": sum_agg(),
    "mean": mean_agg(),
    "max8": max_agg(domain_bits=8),
    "max": max_agg(),
}

# executor defaults (runtime/executor.py WindowStepRunner -> FusedWindowOperator):
# 10 s / 1 s sliding windows give S=32 SPW=10; nsb 4, fires_per_step 4,
# out_rows 256, chunk _fused_chunk(65536) = 4096, 32 steps of 65536 records;
# key capacity starts at 1024 and doubles with the key dictionary
EXEC = dict(S=32, NSB=4, F=4, SPW=10, R=256, T=32, B=1 << 16, CH=4096)
# bench.py run_tpu_stream: K=8192, out_rows 64, chunk 32768 count-only /
# 8192 weighted / 1024 max8
BENCH = dict(K=8192, S=32, NSB=4, F=4, SPW=10, R=64)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is locked
        pytest.skip(f"no chip-less TPU topology available: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo


def _on(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(dev))


def _plan_specs(dev, T, F, S):
    i32 = jnp.int32
    return (_on(dev, (T,), i32), _on(dev, (T, F), i32), _on(dev, (T, F), i32),
            _on(dev, (T, F), i32), _on(dev, (T, S), i32))


def _compile_keyed(dev, agg, *, K, S, NSB, F, SPW, R, T, B, CH,
                   fire_spws=None):
    run = ps.build_superscan(agg, K, S, NSB, F, SPW, R, T, B, CH, True,
                             False, fire_spws)
    KB = K // ps.LANE
    vf = [f for f in agg.fields if f.source == VALUE]
    args = _plan_specs(dev, T, F, S) + (
        _on(dev, (S * KB, ps.LANE), jnp.int32),
        tuple(_on(dev, (S * KB, ps.LANE), jnp.dtype(f.dtype)) for f in vf),
        _on(dev, (T * B,), jnp.int32),
        _on(dev, (T * B,), jnp.float32) if vf else None,
    )
    return run.trace(*args).lower().compile()


def _ladder(agg, geom):
    """Key capacities the executor's doubling reaches while the gate
    admits them, and the first one it refuses."""
    admitted, K = [], 1024
    while ps.supports(agg, K, geom["R"], geom["S"], geom["NSB"], geom["CH"]):
        admitted.append(K)
        K *= 2
    return admitted, K


def _largest(name):
    # max8's largest admitted executor-default geometry alone takes Mosaic
    # ~15 s, a third of this file: it runs with the slow tests
    return pytest.param(name, -1, id=f"{name}-largest",
                        marks=[pytest.mark.slow] if name == "max8" else [])


@pytest.mark.parametrize("name,end", [
    ("count", 0), ("sum", 0), ("max8", 0),
    _largest("count"), _largest("sum"), _largest("mean"), _largest("max8"),
])
def test_executor_default_ladder_ends_compile(v5e, name, end):
    """Mosaic's need grows with K, so the ends of each doubling ladder
    stand for the capacities between them (mean is sum-shaped)."""
    admitted, _refused = _ladder(AGGS[name], EXEC)
    assert admitted[0] == 1024
    _compile_keyed(v5e.devices[0], AGGS[name], K=admitted[end], **EXEC)


def test_gate_refuses_what_the_compiler_refuses(v5e):
    """The harness really reaches Mosaic's VMEM check, and the gate is on
    the right side of it: sum's first refused capacity does not compile
    under the stated limit."""
    _admitted, refused = _ladder(AGGS["sum"], EXEC)
    with pytest.raises(Exception, match="vmem"):
        _compile_keyed(v5e.devices[0], AGGS["sum"], K=refused, **EXEC)


@pytest.mark.parametrize("name,CH,T,B,extra", [
    ("count", 32768, 48, 1 << 20, {}),          # headline, q5, wordcount
    ("sum", 8192, 48, 1 << 20, {}),             # weighted
    ("max8", 1024, 24, 1 << 18,                 # q7 keyed leg, tumbling
     {"S": 8, "NSB": 2, "SPW": 1, "R": 16}),
])
def test_bench_geometries_compile(v5e, name, CH, T, B, extra):
    geom = {**BENCH, **extra}
    agg = AGGS[name]
    assert ps.supports(agg, geom["K"], geom["R"], geom["S"], geom["NSB"], CH)
    _compile_keyed(v5e.devices[0], agg, T=T, B=B, CH=CH, **geom)


def test_shared_partials_kernel_compiles(v5e):
    """fire_spws: two window shapes over one ring, four fire slots each."""
    geom = {**EXEC, "F": 8}
    assert ps.supports(AGGS["sum"], 1024, geom["R"], geom["S"], geom["NSB"],
                       geom["CH"])
    _compile_keyed(v5e.devices[0], AGGS["sum"], K=1024,
                   fire_spws=(10,) * 4 + (5,) * 4, **geom)


@pytest.mark.parametrize("name,CH", [
    ("count", 8192), ("sum", 8192), ("max", 8192), ("max8", 8192),
    ("max", ps.MAX_GLOBAL_CHUNK)])
def test_global_kernel_compiles(v5e, name, CH):
    """bench.py's q7 geometry (S=8 NSB=2 R=16, 96 x 2^18, chunk 8192), and
    the largest chunk the gate admits."""
    agg = AGGS[name]
    S, NSB, F, SPW, R, T, B = 8, 2, 4, 1, 16, 96, 1 << 18
    assert not ps.supports_global(agg, S, R, NSB, 2 * ps.MAX_GLOBAL_CHUNK)
    assert ps.supports_global(agg, S, R, NSB, CH)
    run = ps.build_global_superscan(agg, S, NSB, F, SPW, R, T, B, CH, False)
    dev = v5e.devices[0]
    vf = [f for f in agg.fields if f.source == VALUE]
    args = _plan_specs(dev, T, F, S) + (
        _on(dev, (1, ps.LANE), jnp.int32),
        tuple(_on(dev, (1, ps.LANE), jnp.dtype(f.dtype)) for f in vf),
        _on(dev, (T * B,), jnp.int32),
        _on(dev, (T * B,), jnp.float32) if vf else None,
    )
    run.trace(*args).lower().compile()


def test_refused_geometry_is_never_selected(monkeypatch):
    """`auto` on a TPU backend follows the gate exactly; nothing catches a
    compile error afterwards, so the gate is the only guard."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def pipe(agg, K, backend="auto"):
        return FusedWindowPipeline(
            SlidingEventTimeWindows.of(10_000, 1_000), agg, key_capacity=K,
            fires_per_step=EXEC["F"], out_rows=EXEC["R"], chunk=EXEC["CH"],
            backend=backend, plan_only=True)

    for name in ("count", "sum", "mean", "max8"):
        admitted, refused = _ladder(AGGS[name], EXEC)
        assert pipe(AGGS[name], admitted[-1])._use_pallas() is True
        assert pipe(AGGS[name], refused)._use_pallas() is False
        with pytest.raises(ValueError, match="does not support"):
            pipe(AGGS[name], refused, backend="pallas")._use_pallas()
    # unbounded max has no matmul form at any size
    assert pipe(AGGS["max"], 1024)._use_pallas() is False
    g = FusedGlobalWindowPipeline(
        SlidingEventTimeWindows.of(10_000, 10_000), "max", num_slices=64,
        nsb=2, out_rows=16)
    assert not ps.supports_global(g.agg, g.S, g.R, g.NSB, g.chunk)
    assert g._use_pallas() is False


_COMPILED = {}     # what this file compiled, by name: each program once


def _compile_chained(v5e, pipe, run, T, B):
    """A chained program over two staged f32 fields, for the first chip; a
    ring and a fire buffer per VALUE field of the aggregate beside the
    count's."""
    dev, i32, K = v5e.devices[0], jnp.int32, pipe.K
    vf = {f.name: jnp.dtype(f.dtype) for f in pipe._value_fields}
    args = ({n: _on(dev, (K, pipe.S), dt) for n, dt in vf.items()},
            _on(dev, (K, pipe.S), i32),
            {n: _on(dev, (pipe.R, K), dt) for n, dt in vf.items()},
            _on(dev, (pipe.R, K), i32),
            (_on(dev, (T, B), jnp.float32),) * 2, _on(dev, (T, B), i32),
            ) + _plan_specs(dev, T, pipe.F, pipe.S)
    return run.trace(*args).lower().compile()


def _served_chain(v5e, monkeypatch, slide_ms):
    """The program a default-config `env.execute()` job dispatches: traced
    filter + key + count over a 10 s window hopping by `slide_ms`, key
    capacity 65536, 32 steps of 65536 two-column f32 records."""
    name = f"chain.{slide_ms}"
    if name in _COMPILED:
        return _COMPILED[name]
    # ops/superscan.default_ingest picks the matmul-histogram ingest by
    # backend: build the program the chip would build
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    K, T, B = 1 << 16, 32, 1 << 16
    pipe = FusedWindowPipeline(
        SlidingEventTimeWindows.of(10_000, slide_ms), "count", key_capacity=K,
        fires_per_step=EXEC["F"], out_rows=EXEC["R"], chunk=EXEC["CH"],
        plan_only=True,
        prologue=TracedPrologue(
            transforms=(("filter", lambda col: col[:, 1] < 0.5),),
            key_fn=lambda col: col[:, 0].astype(jnp.int32)),
    )
    # the staged form of the two-field record: both fields are read, each
    # ships as its own [T, B] array
    pipe._raw_shape, pipe._raw_dtype = (2,), jnp.float32
    layout = pipe._layout()
    assert layout.columns == (0, 1)
    run = pipe._build_chained_superscan(T, B, layout)
    _COMPILED[name] = pipe, _compile_chained(v5e, pipe, run, T, B)
    return _COMPILED[name]


def _sum_chain(v5e, monkeypatch):
    """`purchases_sum_catchup`'s program (benchmarks/jobs/keyed_sum_traced.py):
    no filter, a traced key and a traced value function over the 4-field
    purchase, SUM per key over an 8 s window sliding by 4 s: a count ring and
    an f32 sum ring, the weighted histogram with its three bf16 terms."""
    if "sum" in _COMPILED:
        return _COMPILED["sum"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    K, T, B = 1 << 16, 32, 1 << 16
    pipe = FusedWindowPipeline(
        SlidingEventTimeWindows.of(8_000, 4_000), "sum", key_capacity=K,
        fires_per_step=EXEC["F"], out_rows=EXEC["R"], chunk=EXEC["CH"],
        plan_only=True,
        prologue=TracedPrologue(
            transforms=(),
            key_fn=lambda col: col[:, 0].astype(jnp.int32),
            value_fn=lambda col: col[:, 1]),
    )
    pipe._raw_shape, pipe._raw_dtype = (4,), jnp.float32
    layout = pipe._layout()
    # the two fields the chain reads of the purchase's four
    assert (layout.columns, layout.width) == ((0, 1), 4)
    run = pipe._build_chained_superscan(T, B, layout)
    _COMPILED["sum"] = pipe, _compile_chained(v5e, pipe, run, T, B)
    return _COMPILED["sum"]


def test_chained_superscan_compiles_at_served_defaults(v5e, monkeypatch):
    """chip_smoke.py leg 1's program (a 10 s window sliding by 1 s)."""
    pipe, compiled = _served_chain(v5e, monkeypatch, 1_000)
    K = pipe.K
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 << 30
    # the scan carries the [K, 32] ring as ingest and fire use it, key-minor:
    # carried slice-minor (32 lanes padded to 128, 33.5 MB of temporaries) it
    # was turned over twice in every step, ~4 ms a dispatch on the chip
    # (PERF.md section 6, PR 33); and a ten-slice fire reads the ring by a
    # mask, not by a gather along its minor axis
    hlo = compiled.as_text()
    carried = [ln for ln in hlo.splitlines() if " while(" in ln
               and f"s32[{K},{pipe.S}]" in ln]
    assert carried and not any(f"s32[{K},{pipe.S}]{{1,0" in ln for ln in carried)
    assert mem.temp_size_in_bytes < 8 << 20
    assert " gather(" not in hlo


def _ysb_chain(v5e, monkeypatch):
    """The benchmark's advertising chain (benchmarks/jobs/ysb_traced.py:
    view filter, ad -> campaign join as a table gather, count per campaign
    per 10 s window) over the 7-field event."""
    import numpy as np

    if "ysb" in _COMPILED:
        return _COMPILED["ysb"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    table = jnp.asarray(np.arange(1000, dtype=np.int32) % 100)

    def project_and_join(col):
        campaign = jnp.take(table, col[:, 2].astype(jnp.int32), axis=0)
        return jnp.stack([campaign.astype(jnp.float32), col[:, 2]], axis=1)

    K, T, B = 1 << 16, 32, 1 << 16
    pipe = FusedWindowPipeline(
        SlidingEventTimeWindows.of(10_000, 10_000), "count", key_capacity=K,
        fires_per_step=EXEC["F"], out_rows=EXEC["R"], chunk=EXEC["CH"],
        plan_only=True,
        prologue=TracedPrologue(
            transforms=(("filter", lambda col: col[:, 4] < 0.5),
                        ("map", project_and_join)),
            key_fn=lambda col: col[:, 0].astype(jnp.int32)),
    )
    pipe._raw_shape, pipe._raw_dtype = (7,), jnp.float32
    layout = pipe._layout()
    assert (layout.columns, layout.width) == ((2, 4), 7)
    run = pipe._build_chained_superscan(T, B, layout)
    _COMPILED["ysb"] = pipe, _compile_chained(v5e, pipe, run, T, B)
    return _COMPILED["ysb"]


def test_ysb_chain_compiles_per_column_and_builds_no_record(v5e, monkeypatch):
    """Two of the event's seven fields are staged, and the compiled program
    reads them as they are — the record the user's functions are handed is
    never built on the device."""
    pipe, compiled = _ysb_chain(v5e, monkeypatch)
    K, T, B = pipe.K, 32, 1 << 16
    hlo = compiled.as_text()
    assert f"f32[{B},7]" not in hlo and f"f32[{T},{B},7]" not in hlo
    mem = compiled.memory_analysis()
    # two [T, B] f32 fields + the slice index, not seven
    assert mem.argument_size_in_bytes < (K * pipe.S + pipe.R * K) * 4 \
        + 3 * T * B * 4 + (1 << 20)


@pytest.mark.parametrize("chain", ["ysb", "keys64k"])
def test_the_joins_gather_is_a_contraction_and_nothing_else_moves(
        v5e, monkeypatch, chain):
    """`ysb_catchup`'s join, `jnp.take` over the 1 000-row campaign table, is
    not a gather in the compiled program: a dot (a `convolution` on the
    chip) under `t1.map/lookup` (PERF.md section 6, PR 38: the gather cost
    11.2 ms a dispatch). A count chain with no gather, `keys64k_catchup`'s
    geometry, compiles with no `lookup` scope at all."""
    if chain == "ysb":
        pipe, compiled = _ysb_chain(v5e, monkeypatch)
        assert pipe.prologue.gathers() == (1, ())
    else:
        pipe, compiled = _served_chain(v5e, monkeypatch, 10_000)
        assert pipe.prologue.gathers() == (0, ())
    hlo = compiled.as_text()
    assert " gather(" not in hlo
    lookups = [line for line, op_name, _p, sub in _scopes(compiled)
               if "/lookup/" in op_name]
    if chain == "keys64k":
        assert not lookups
        return
    assert any(" convolution(" in line and "/t1.map/lookup/" in line
               for line in lookups), lookups


def _sharded_chain(v5e, monkeypatch):
    """chip_smoke.py leg 3's program: the served job sharded over the four
    chips of one host, the keyBy exchange as an in-scan all-to-all."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flink_tpu.parallel.sharded_superscan import ShardedFusedPipeline

    if "sharded" in _COMPILED:
        return _COMPILED["sharded"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the topology's devices cannot hold arrays: plan and compile only
    monkeypatch.setattr(ShardedFusedPipeline, "_init_state",
                        lambda self: None)
    mesh = Mesh(np.array(v5e.devices), ("shards",))
    n, K, T, B = 4, 1 << 16, 32, 1 << 16
    pipe = ShardedFusedPipeline(
        mesh, SlidingEventTimeWindows.of(10_000, 1_000), "count",
        key_capacity=K, fires_per_step=EXEC["F"], out_rows=EXEC["R"],
        chunk=EXEC["CH"],
        prologue=TracedPrologue(
            transforms=(("filter", lambda col: col[:, 1] < 0.5),),
            key_fn=lambda col: col[:, 0].astype(jnp.int32)))
    pipe.planner._raw_shape, pipe.planner._raw_dtype = (2,), jnp.float32
    layout = pipe.planner._layout()
    assert layout.columns == (0, 1)
    run = pipe._build_raw(T, B // n, layout)

    def on_mesh(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    i32, F, S = jnp.int32, pipe.F, pipe.S
    _COMPILED["sharded"] = pipe, run.trace(
        on_mesh((n, K // n, S), i32, "shards"), (),
        (on_mesh((n, T, B // n), jnp.float32, "shards"),) * 2,
        on_mesh((n, T, B // n), i32, "shards"),
        on_mesh((T, 1 + 3 * F + S), i32),       # the plan, side by side
    ).lower().compile()
    return _COMPILED["sharded"]


def test_sharded_chained_superscan_compiles_on_the_2x2_mesh(v5e, monkeypatch):
    _sharded_chain(v5e, monkeypatch)


def _scopes(compiled):
    """[(HLO line, op_name, phase, nested name)] of a compiled program's
    instructions that carry an `op_name`."""
    from flink_tpu.metrics.device_phases import phase_of

    out = []
    for line in compiled.as_text().splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if " = " in line and op_name:
            out.append((line, op_name.group(1), *phase_of(op_name.group(1))))
    return out


@pytest.mark.parametrize("program", ["q5", "sharded", "sum"])
def test_compiled_window_programs_carry_their_phases(v5e, monkeypatch,
                                                     program):
    """The chip's compiler keeps the scopes `metrics/device_phases.py` reads a
    capture by: the chained program at NEXMark q5's geometry (a 10 s window
    hopping by 2 s) names four of PHASES, the sharded program all five, and
    every `while` and `conditional` inside the scan's body lies under one —
    a loop under no phase would put its whole time in the table's `other`.
    The SUM program's VALUE field has nested rows of its own under `ingest`
    (inside the histogram's conditional and beside the count's fold), and a
    `value` row under `prologue`; a count-only program has none of them."""
    from flink_tpu.metrics.device_phases import EXCHANGE, PHASES

    _pipe, compiled = {
        "q5": lambda: _served_chain(v5e, monkeypatch, 2_000),
        "sharded": lambda: _sharded_chain(v5e, monkeypatch),
        "sum": lambda: _sum_chain(v5e, monkeypatch)}[program]()
    scopes = _scopes(compiled)
    found = {phase for _l, _o, phase, _sub in scopes if phase}
    expect = set(PHASES) if program == "sharded" else set(PHASES) - {EXCHANGE}
    assert found == expect
    loops = [(op_name, phase) for line, op_name, phase, _sub in scopes
             if (" while(" in line or " conditional(" in line)
             and "/while/body/" in op_name]
    assert len(loops) >= 6      # ingest's loop(s), four fire slots, the purge
    assert all(phase for _o, phase in loops), loops
    ingest = {sub for _l, _o, phase, sub in scopes if phase == "ingest"}
    assert ingest >= {"hist", "fold"}
    of_values = {"hist.value", "fold.value"}
    assert ingest & of_values == (of_values if program == "sum" else set())
    if program == "sum":
        # the weighted histogram's loops lie inside the conditional, under
        # the value's name; the sum ring's fold is a loop of its own
        deep = [op_name for line, op_name, _p, sub in scopes
                if sub == "hist.value" and " while(" in line]
        assert len(deep) == 2 and all(
            "/ingest/hist/cond/branch_" in o for o in deep), deep
        assert "value" in {sub for _l, _o, phase, sub in scopes
                           if phase == "prologue"}


def _computations(hlo):
    """{computation: its instruction lines} of a compiled program's text."""
    comps, lines = {}, None
    for line in hlo.splitlines():
        header = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if header:
            lines = comps[header.group(1)] = []
        elif line.startswith("}"):
            lines = None
        elif lines is not None and " = " in line:
            lines.append(line)
    return comps


def _called(line):
    """The computations an instruction runs (branches, loop body and
    condition, a fusion's)."""
    names = re.findall(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)", line)
    for branches in re.findall(r"branch_computations=\{([^}]*)\}", line):
        names += re.findall(r"%([\w.\-]+)", branches)
    return names


def _under(comps, roots):
    """Every instruction line of `roots` and of what they run, nested."""
    seen, todo, out = set(), list(roots), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            out.append(line)
            todo.extend(_called(line))
    return out


@pytest.mark.parametrize("program", ["chain.1000", "chain.2000", "ysb",
                                     "sharded", "sum"])
def test_the_ingest_is_as_wide_as_the_step_and_leaves_the_ring_alone(
        v5e, monkeypatch, program):
    """What PERF.md section 6 (PR 36) read in the compiled step before a
    chip was asked for, held for the served chain at both slides, the YSB
    chain and a shard of the 2x2 mesh: ONE conditional under `ingest/hist`
    picks the step's histogram, K / 128 rows of 128 where the step's records
    lie in one slice and K * NSB / 128 where they do not; the `[K * NSB]`
    reshape and the `[NSB, K]` relayout run in the wide branch alone; the
    ring is no operand of it (XLA:TPU gives a conditional's operands their
    default layout: slice-minor, a relayout of the whole ring in every
    step), is carried key-minor through the scan and through the fold's
    loop, and nothing in the scan's own body copies or transposes it. The SUM
    program (`purchases_sum_catchup`) is held to the same for BOTH its rings:
    the conditional yields an f32 partial beside the i32 one, from three bf16
    dots a branch, and the sum ring has a fold loop of its own."""
    from flink_tpu.metrics.device_phases import INGEST, phase_of

    if program == "sharded":
        pipe, compiled = _sharded_chain(v5e, monkeypatch)
        K = pipe.K_local
    elif program == "ysb":
        pipe, compiled = _ysb_chain(v5e, monkeypatch)
        K = pipe.K
    elif program == "sum":
        pipe, compiled = _sum_chain(v5e, monkeypatch)
        K = pipe.K
    else:
        pipe, compiled = _served_chain(v5e, monkeypatch,
                                       int(program.split(".")[1]))
        K = pipe.K
    S, NSB, R = pipe.S, pipe.NSB, EXEC["R"]
    ring, key_minor = f"s32[{K},{S}]", f"s32[{K},{S}]{{0,1"
    # the rings the scan carries: the count's, and one per VALUE field
    rings = [ring] + ([f"f32[{K},{S}]"] if program == "sum" else [])
    comps = _computations(compiled.as_text())
    every = [ln for lines in comps.values() for ln in lines]

    # the scan: the one loop that carries the ring beside the fire buffer
    (scan,) = [ln for ln in every if " while(" in ln and ring in ln
               and f"s32[{R},{K}]" in ln]
    body = comps[re.search(r"body=%([\w.\-]+)", scan).group(1)]
    for a_ring in rings:
        assert f"{a_ring}{{0,1" in scan and f"{a_ring}{{1,0" not in scan
        assert not [ln for ln in body
                    if a_ring in ln.split(" = ")[1].split("(")[0]
                    and (" copy(" in ln or " transpose(" in ln)]

    # ingest's conditional, in the scan's body, under ingest/hist
    (cond,) = [ln for ln in body if " conditional(" in ln
               and "/ingest/hist/cond" in ln]
    branches = _called(cond)
    assert len(branches) == 2
    inside = _under(comps, branches)
    assert not [ln for ln in inside                      # operands included
                if any(a_ring in ln for a_ring in rings)]
    dots = sorted(ln.split(" = ")[1].split("{")[0] for ln in inside
                  if " convolution(" in ln)
    # a VALUE field that adds: three bf16 terms a value, a dot each, in
    # either branch (`matmul_hist.weighted_hist`, `exact_sums=True`)
    terms = 3 if program == "sum" else 0
    assert dots == sorted([f"s32[{K // 128},128]",
                           f"s32[{K * NSB // 128},128]"]
                          + terms * [f"f32[{K // 128},128]",
                                     f"f32[{K * NSB // 128},128]"])
    # the terms are rounded by `reduce-precision`, two a branch, which the
    # chip honours: rounded by a convert to bf16 and back, which XLA:TPU
    # may skip, the chip summed ONE term and 99.6 % of 44 M window sums were
    # wrong (`matmul_hist.bf16_terms`; PERF.md section 6, PR 37)
    assert len([ln for ln in inside if " reduce-precision(" in ln
                and "exponent_bits=8, mantissa_bits=7" in ln]) == (
                    4 if program == "sum" else 0)
    narrow, wide = sorted(branches, key=lambda b: f"s32[{K * NSB // 128},128]"
                          in "".join(_under(comps, [b])))
    reshaped = f"s32[{K},{NSB}]"
    assert [ln for ln in comps[wide] if reshaped in ln]
    assert not [ln for ln in _under(comps, [narrow])
                if reshaped in ln or " copy(" in ln and f"[{NSB},{K}]" in ln]
    assert [ln for ln in comps[narrow] if " pad(" in ln]
    # a capture's phase table (`device_phases.phase_table`) gives an op its
    # own `op_name`'s phase and one whose `op_name` names none (the relayout
    # copy, the loops' counters) its enclosing op's, here the conditional's:
    # no instruction in either branch names another phase
    # (a branch's parameter keeps its producer's name and runs nothing)
    ours = {cond, *inside}
    scoped = [phase for line, _o, phase, _sub in _scopes(compiled)
              if line in ours and " parameter(" not in line]
    assert len(scoped) > 20 and set(scoped) - {None} == {INGEST}
    assert phase_of(re.search(r'op_name="([^"]*)"', cond).group(1)) == (
        INGEST, "hist")

    # the fold: a loop of its own that carries the ring key-minor
    (fold,) = [ln for ln in body if " while(" in ln and "/ingest/fold/" in ln]
    assert key_minor in fold
    if program == "sum":
        (fold,) = [ln for ln in body
                   if " while(" in ln and "/ingest/fold.value/" in ln]
        assert f"f32[{K},{S}]{{0,1" in fold


def test_ysb_prologue_shows_one_scope_per_transform(v5e, monkeypatch):
    """`ysb_catchup`'s chain under the prologue: the view filter, the ad ->
    campaign join, the key and the key bounds each have a row of their own."""
    _pipe, compiled = _ysb_chain(v5e, monkeypatch)
    scopes = _scopes(compiled)
    subs = {sub for _l, _o, phase, sub in scopes if phase == "prologue" and sub}
    assert {s for s in subs if s.startswith("t")} == {"t0.filter", "t1.map"}
    assert subs >= {"key", "bounds"}
    # the join is the map's: its lookup, nested under it
    assert any("/t1.map/lookup/" in op_name for _l, op_name, _p, sub in scopes
               if sub == "t1.map")


@pytest.mark.parametrize("routed", [False, True], ids=["static", "table"])
def test_fire_shape_program_compiles_on_the_2x2_mesh(v5e, routed):
    """The program a mesh dispatch enqueues after its window program, at the
    mesh cells' shape: four [256, 16 384] slabs cut to 16 rows and laid side
    by side. Without a routing table every chip keeps its own columns: no
    collective, the readback's [16, 65 536] sharded over the key axis."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flink_tpu.parallel.sharded_superscan import _fire_shaper

    mesh = Mesh(np.array(v5e.devices), ("shards",))
    n, K, R, used = 4, 1 << 16, EXEC["R"], 16

    def on_mesh(shape, *spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(mesh, P(*spec)))

    compiled = _fire_shaper(used).trace(
        (on_mesh((n, R, K // n), "shards"),),
        on_mesh((K,)) if routed else None).lower().compile()
    (rows,) = compiled.output_shardings
    if not routed:
        assert rows.is_equivalent_to(NamedSharding(mesh, P(None, "shards")), 2)
        hlo = compiled.as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert op not in hlo, op


def test_compile_cache_placement(monkeypatch, tmp_path):
    """Placed from outside => nothing is set in code; otherwise the fixed
    in-checkout path (never a temp name, pid or time)."""
    from flink_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        expect = os.path.join(checkout, ".jax_cache")
        assert compile_cache.configure_compile_cache() == expect
        assert jax.config.jax_compilation_cache_dir == expect
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
