"""A small constant table's gather inside the traced prologue, done as an
exact one-hot contraction (ops/table_lookup.py): the lookup against the
gather it replaces, the pass's choices, and the YSB chain end to end."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from flink_tpu.api.datastream import StreamExecutionEnvironment
from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows
from flink_tpu.config import Configuration, ExecutionOptions, ParallelOptions
from flink_tpu.connectors.source import Batch, DataGeneratorSource
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.ops import table_lookup as tl
from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

I32 = np.iinfo(np.int32)
FILL, CLIP = jax.lax.GatherScatterMode.FILL_OR_DROP, jax.lax.GatherScatterMode.CLIP


def _through_pass(fn, *args):
    """`fn` run as the prologue runs it (jitted, through `table_lookup.call`),
    its compiled text, and the lowering the pass chose."""
    run = jax.jit(lambda *a: tl.call(fn, *a)[0])
    low = tl.lowering(fn, tuple(tl.aval_of(jnp.asarray(a)) for a in args))
    return np.asarray(run(*args)), run.lower(*args).as_text(), low


def _take(table, mode=None):
    dev = jnp.asarray(table)
    if mode is None:
        return lambda idx: jnp.take(dev, idx)
    return lambda idx: jnp.take(dev, idx, mode=mode)


def _expect(table, idx, mode="fill"):
    """numpy's reading of `jnp.take`: clipped, an index reads the nearest
    row; filled, a negative index wraps once, then one outside the table
    reads the fill."""
    n = len(table)
    if mode == "clip":
        return np.take(table, np.clip(idx, 0, n - 1))
    wrapped = np.where(idx < 0, idx + n, idx)
    inside = (wrapped >= 0) & (wrapped < n)
    got = np.take(table, np.clip(wrapped, 0, n - 1))
    fill = True if table.dtype == np.bool_ else (
        np.iinfo(table.dtype).min if np.issubdtype(table.dtype, np.signedinteger)
        else np.iinfo(table.dtype).max)
    return np.where(inside, got, fill)


@pytest.mark.parametrize("rows", [1, 100, 1000, tl.MAX_ROWS])
def test_the_lookup_reads_what_the_gather_reads(rows):
    rng = np.random.default_rng(rows)
    table = rng.integers(0, 100, rows).astype(np.int32)
    idx = rng.integers(0, rows, 3000).astype(np.int32)
    got, hlo, low = _through_pass(_take(table), idx)
    assert (low.lowered, low.kept) == (1, ())
    assert "gather" not in hlo and "dot_general" in hlo
    np.testing.assert_array_equal(got, np.asarray(jax.jit(_take(table))(idx)))
    np.testing.assert_array_equal(got, table[idx])


@pytest.mark.parametrize("planes,lo,hi,dtype", [
    (1, 0, 100, np.int32),                 # YSB's campaign ids
    (1, -128, 127, np.int8),
    (1, 0, 1, np.bool_),
    (2, -300, 65_000, np.int32),
    (2, 0, 65_535, np.uint16),
    (3, -(1 << 23), (1 << 23) - 1, np.int32),
    (4, int(I32.min), int(I32.max), np.int32),
    (4, 0, (1 << 32) - 1, np.uint32),
], ids=["1-ysb", "1-int8", "1-bool", "2-neg", "2-uint16", "3-neg", "4-int32",
        "4-uint32"])
def test_byte_planes_as_few_as_the_range_needs(planes, lo, hi, dtype):
    rng = np.random.default_rng(planes)
    table = rng.integers(lo, hi, 5000, endpoint=True, dtype=np.int64)
    table[:2] = lo, hi                     # the range's two ends are values
    table = table.astype(dtype)
    tp = tl.planes_of(table)
    assert tp.planes.shape == (planes * 40, tl.LANES)
    assert tp.planes.dtype == jnp.bfloat16
    # every plane entry is an integer 0..255, which bf16 holds exactly
    as_f = tp.planes.astype(np.float32)
    assert as_f.min() >= 0 and as_f.max() <= 255 and (as_f == np.round(as_f)).all()
    idx = rng.integers(0, 5000, 4000).astype(np.int32)
    idx[:2] = 0, 1
    got, _hlo, low = _through_pass(_take(table), idx)
    assert low.lowered == 1
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(got, table[idx])


@pytest.mark.parametrize("mode", ["fill", "clip"])
@pytest.mark.parametrize("where", ["inside", "negative", "at-or-past-n",
                                   "below-minus-n"])
def test_indices_outside_the_table_read_as_the_gather_reads_them(mode, where):
    rows = 1000
    rng = np.random.default_rng(7)
    table = rng.integers(I32.min, I32.max, rows, endpoint=True,
                         dtype=np.int64).astype(np.int32)
    lo, hi = {"inside": (0, rows), "negative": (-rows, 0),
              "at-or-past-n": (rows, 3 * rows),
              "below-minus-n": (-5 * rows, -rows)}[where]
    idx = rng.integers(lo, hi, 2048).astype(np.int32)
    fn = _take(table, mode)
    got, hlo, low = _through_pass(fn, idx)
    assert low.lowered == 1 and "gather" not in hlo
    np.testing.assert_array_equal(got, _expect(table, idx, mode))
    np.testing.assert_array_equal(got, np.asarray(jax.jit(fn)(idx)))


def test_the_lookup_reproduces_the_gathers_own_fill_value():
    table = np.arange(300, dtype=np.int32)
    tp = tl.planes_of(table)
    idx = jnp.asarray([-1, 0, 299, 300, 7], jnp.int32)
    got = tl.lookup(tp, idx, mode=FILL, fill_value=-5)
    np.testing.assert_array_equal(np.asarray(got), [-5, 0, 299, -5, 7])
    got = tl.lookup(tp, idx, mode=CLIP)
    np.testing.assert_array_equal(np.asarray(got), [0, 0, 299, 299, 7])


def _traced_operand(col):
    table = col[:, 1].astype(jnp.int32)
    return jnp.take(table, col[:, 0].astype(jnp.int32))


_F32 = jnp.asarray(np.linspace(0.0, 1.0, 100, dtype=np.float32))
_BIG = jnp.asarray(np.arange(tl.MAX_ROWS + 1, dtype=np.int32))
_ROWS = jnp.asarray(np.arange(200, dtype=np.int32).reshape(100, 2))


@pytest.mark.parametrize("fn,why", [
    (_traced_operand, tl.NOT_CONSTANT),
    (lambda col: jnp.take(_F32, col[:, 0].astype(jnp.int32)), tl.NOT_INTEGER),
    (lambda col: jnp.take(_BIG, col[:, 0].astype(jnp.int32)), tl.TOO_MANY_ROWS),
    (lambda col: jnp.take(_ROWS, col[:, 0].astype(jnp.int32), axis=0)[:, 0],
     tl.NOT_SCALAR_ROW),
], ids=["traced-operand", "f32-table", "16385-rows", "row-gather"])
def test_a_gather_the_pass_refuses_stays_a_gather(fn, why):
    col = np.stack([np.arange(64) % 50, np.arange(64)], axis=1).astype(np.float32)
    got, hlo, low = _through_pass(fn, col)
    assert (low.lowered, low.kept, low.jaxpr) == (0, (why,), None)
    assert "gather" in hlo
    np.testing.assert_array_equal(got, np.asarray(jax.jit(fn)(col)))
    # the operator's counters read what the prologue's trace chose
    pro = TracedPrologue(transforms=(("map", lambda c: fn(c)[:, None]),),
                         key_fn=lambda c: c[:, 0].astype(jnp.int32))
    assert pro.gathers() == (0, ())
    _chain_jaxpr(pro, 2, False)
    assert pro.gathers() == (0, (why,))


_OWNERS = np.arange(1000, dtype=np.int32) % 7 - 3
_CAMPAIGNS = (np.arange(1000, dtype=np.int32) * 37) % 100


def _two_tables(col):
    """Two lookups of one shape and dtype in one callable: `jnp.take` traces
    each to the one shared `_take` jaxpr, its table an argument bound to a
    different constant at each call site."""
    ad = col[:, 0].astype(jnp.int32)
    return jnp.stack([jnp.take(jnp.asarray(_CAMPAIGNS), ad),
                      jnp.take(jnp.asarray(_OWNERS), ad)], axis=1)


@jax.jit
def _user_jit(col):
    return _two_tables(col) + _two_tables(col[::-1])[::-1]


@pytest.mark.parametrize("fn,n", [(_two_tables, 2), (_user_jit, 4)],
                         ids=["one-callable", "under-a-user-jit"])
def test_two_tables_of_one_shape_each_read_their_own(fn, n):
    rng = np.random.default_rng(11)
    col = np.stack([rng.integers(0, 1000, 4096),
                    np.zeros(4096)], axis=1).astype(np.float32)
    got, hlo, low = _through_pass(fn, col)
    assert (low.lowered, low.kept) == (n, ()) and "gather" not in hlo
    ad = col[:, 0].astype(np.int32)
    once = np.stack([np.take(_CAMPAIGNS, ad), np.take(_OWNERS, ad)], axis=1)
    expect = once if n == 2 else once + once[::-1][::-1]
    np.testing.assert_array_equal(got, expect)
    np.testing.assert_array_equal(got, np.asarray(jax.jit(fn)(col)))


def test_a_large_constant_is_not_read_to_the_host(monkeypatch):
    """Only a gather operand that passes the shape and dtype checks is
    copied to the host: a UDF closing over a large matrix traces with the
    matrix left where it is."""
    big = jnp.ones((512, 512), jnp.float32)
    seen = []
    real = np.asarray

    def spy(a, *args, **kw):
        if getattr(a, "shape", None) == big.shape:
            seen.append(a.shape)
        return real(a, *args, **kw)

    def fn(col):
        return col @ big[:2, :2] + jnp.sum(big)

    monkeypatch.setattr(np, "asarray", spy)
    low = tl.lowering(fn, (tl.aval_of(jnp.ones((8, 2), jnp.float32)),))
    assert (low.lowered, low.kept, seen) == (0, (), [])


def _chain_jaxpr(pro, width, needs_vals):
    B = 16
    return str(jax.make_jaxpr(
        lambda raw, srel, ts, kb: pro.apply(raw, srel, ts, kb, K=64, NSB=4,
                                            needs_vals=needs_vals))(
        jax.ShapeDtypeStruct((B, width), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32) if pro.needs_ts else None,
        jax.ShapeDtypeStruct((2,), jnp.int32)))


_NO_GATHER = {
    "keys64k": (TracedPrologue(
        transforms=(("filter", lambda col: col[:, 4] < 0.5),),
        key_fn=lambda col: col[:, 2].astype(jnp.int32)), 7, False),
    "sum": (TracedPrologue(
        transforms=(), key_fn=lambda col: col[:, 0].astype(jnp.int32),
        value_fn=lambda col: col[:, 1]), 4, True),
    "map_ts": (TracedPrologue(
        transforms=(("map_ts", lambda col, ts: col + (ts % 7)[:, None]),
                    ("map", lambda col: col * 2.0)),
        key_fn=lambda col: col[:, 0].astype(jnp.int32)), 2, False),
}


@pytest.mark.parametrize("chain", sorted(_NO_GATHER))
def test_a_chain_without_a_gather_traces_as_it_did_without_the_pass(
        chain, monkeypatch):
    pro, width, needs_vals = _NO_GATHER[chain]
    with_pass = _chain_jaxpr(pro, width, needs_vals)
    assert pro.gathers() == (0, ())
    none = tl.Lowering(None, None, {}, 0, ())
    monkeypatch.setattr(tl, "call", lambda fn, *args: (fn(*args), none))
    assert _chain_jaxpr(pro, width, needs_vals) == with_pass


# -- the YSB chain end to end ----------------------------------------------

ADS, CAMPAIGNS, EVENTS, VIEW = 1000, 100, 6000, 0


def _ysb_source():
    """(ad_id, event_type, user) records: a third are views."""
    def gen(idx):
        ad = (idx * 7919) % ADS
        kind = idx % 3
        ts = 10_000 + idx * 3
        return Batch(np.stack([ad, kind, idx % 13], axis=1).astype(np.float32),
                     ts.astype(np.int64))
    return DataGeneratorSource(gen, EVENTS)


def _ysb_job(fused: bool, mesh: bool = False):
    table = jnp.asarray((np.arange(ADS) * 37 % CAMPAIGNS).astype(np.int32))
    cfg = Configuration()
    if mesh:
        cfg.set(ParallelOptions.MESH_ENABLED, True)
        cfg.set(ParallelOptions.MESH_DEVICES, 4)
    cfg.set(ExecutionOptions.BATCH_SIZE, 512)
    cfg.set(ExecutionOptions.SUPERBATCH_STEPS, 4)
    cfg.set(ExecutionOptions.CHAIN_FUSION, fused)
    env = StreamExecutionEnvironment.get_execution_environment(cfg)
    ds = env.from_source(
        _ysb_source(),
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(50))
    ds = ds.filter(lambda col: col[:, 1] < VIEW + 0.5, traceable=True)

    def project_and_join(col):
        campaign = jnp.take(table, col[:, 0].astype(jnp.int32), axis=0)
        return jnp.stack([campaign.astype(jnp.float32), col[:, 0]], axis=1)

    ds = ds.map(project_and_join, traceable=True)
    sink = (ds.key_by(lambda col: col[:, 0].astype(jnp.int32), traceable=True)
            .window(TumblingEventTimeWindows.of(1_000)).aggregate("count")
            .collect())
    result = env.execute()
    return sorted((int(k), int(v)) for k, v in sink.results), result


@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "mesh4"])
def test_the_ysb_chain_fused_gives_the_host_chains_rows(mesh):
    """The join lowered in the single-device program and in the sharded one
    (the prologue runs on each shard's lanes before the exchange)."""
    fused, result = _ysb_job(True, mesh)
    host, host_result = _ysb_job(False)
    assert fused == host and len(fused) > CAMPAIGNS
    assert sum(v for _k, v in fused) == EVENTS // 3
    (op,) = [op for op in result.metrics["device"]["operators"].values()
             if "prologueGathersLowered" in op]
    assert op["prologueGathersLowered"] == 1
    assert op["prologueGathersKept"] == 0
    assert set(op["compile"]["programs"]) == {
        "sharded_chained_superscan" if mesh else "fused_chained_superscan"}
    # the host chain keys on the host: no prologue, nothing lowered or kept
    (op,) = host_result.metrics["device"]["operators"].values()
    assert (op["prologueGathersLowered"], op["prologueGathersKept"]) == (0, 0)
