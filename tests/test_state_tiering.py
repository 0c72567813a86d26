"""Million-key state plane (ISSUE-12): direct unit tests for the
previously-unexercised state/spillable.py, state/cold_tier.py and
state/changelog.py, plus the new vocabulary (state/vocab.py) and tier
manager (state/tier_manager.py), and the FusedWindowOperator integration
(hot/cold routing, demote/promote, merged emission, incremental
changelog checkpoints, the sharded path)."""

import os
import pickle
import tempfile

import numpy as np
import pytest

from flink_tpu.api.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.core.time import MAX_WATERMARK
from flink_tpu.ops.aggregators import resolve
from flink_tpu.runtime.fused_window_operator import FusedWindowOperator
from flink_tpu.state.changelog import (
    ChangelogKeyedStateBackend,
    FsStateChangelog,
)
from flink_tpu.state.cold_tier import ColdKeyTier, ColdTierError
from flink_tpu.state.heap import HeapKeyedStateBackend, StateDescriptor
from flink_tpu.state.spillable import SpillableKeyedStateBackend, SpillReadError
from flink_tpu.state.tier_manager import TierConfig, TieredStateManager
from flink_tpu.state.vocab import DynamicKeyVocabulary


# ---------------------------------------------------------------------------
# spillable heap backend
# ---------------------------------------------------------------------------

def _heap(max_parallelism: int = 8) -> HeapKeyedStateBackend:
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.state.heap import reducing_state

    b = HeapKeyedStateBackend(KeyGroupRange(0, max_parallelism - 1),
                              max_parallelism)
    b.register(StateDescriptor("v", "value"))
    b.register(reducing_state("r", lambda a, c: a + c))
    return b


def test_spillable_round_trip_under_pressure():
    sp = SpillableKeyedStateBackend(_heap(), max_entries_in_memory=4)
    for k in range(16):
        sp.set_current_key(k)
        sp.put("v", k * 10)
    assert sp.num_spills > 0
    for k in range(16):
        sp.set_current_key(k)   # faults spilled key-groups back in
        assert sp.get("v") == k * 10
    assert sp.num_faults > 0


def test_spillable_evicts_coldest_key_group_first():
    sp = SpillableKeyedStateBackend(_heap(max_parallelism=4),
                                    max_entries_in_memory=2)
    # touch groups in a known order; keep re-touching key 0's group so it
    # stays hot — the first spilled group must NOT be key 0's
    sp.set_current_key(0)
    sp.put("v", 0)
    kg_hot = sp.inner._current_key_group
    for k in range(1, 12):
        sp.set_current_key(k)
        sp.put("v", k)
        sp.set_current_key(0)   # re-heat
    assert kg_hot not in sp._spilled, (
        "the most recently used key-group was spilled before colder ones")


def test_spillable_snapshot_faults_everything_in():
    sp = SpillableKeyedStateBackend(_heap(), max_entries_in_memory=2)
    for k in range(12):
        sp.set_current_key(k)
        sp.put("v", k)
    snap = sp.snapshot()
    assert not sp._spilled
    r = SpillableKeyedStateBackend(_heap(), max_entries_in_memory=2)
    r.restore(snap, {"v": StateDescriptor("v", "value")})
    r.set_current_key(7)
    assert r.get("v") == 7


def test_spillable_missing_artifact_is_a_typed_error():
    sp = SpillableKeyedStateBackend(_heap(), max_entries_in_memory=2)
    for k in range(12):
        sp.set_current_key(k)
        sp.put("v", k)
    kg, path = next(iter(sp._spilled.items()))
    os.unlink(path)
    with pytest.raises(SpillReadError):
        sp._fault_in(kg)
    # the artifact registration survives the failure (no silent
    # empty-key-group substitution)
    assert kg in sp._spilled


def test_spillable_corrupt_artifact_is_a_typed_error():
    sp = SpillableKeyedStateBackend(_heap(), max_entries_in_memory=2)
    for k in range(12):
        sp.set_current_key(k)
        sp.put("v", k)
    kg, path = next(iter(sp._spilled.items()))
    with open(path, "wb") as f:
        f.write(b"\x80garbage-not-a-pickle")
    with pytest.raises(SpillReadError):
        sp._fault_in(kg)


# ---------------------------------------------------------------------------
# cold tier
# ---------------------------------------------------------------------------

def _cold(agg="sum", S=32) -> ColdKeyTier:
    return ColdKeyTier(resolve(agg), S)


def test_cold_tier_ingest_fire_matches_numpy():
    ct = _cold()
    rng = np.random.default_rng(3)
    kid = rng.integers(0, 50, 500).astype(np.int64)
    s = rng.integers(0, 8, 500).astype(np.int64)
    vals = rng.random(500).astype(np.float32)
    ct.ingest(kid, s, vals)
    res, counts = ct.fire(50, range(0, 8))
    expect = np.zeros(50)
    np.add.at(expect, kid, vals.astype(np.float64))
    assert np.allclose(res, expect, rtol=1e-6)
    cexp = np.bincount(kid, minlength=50)
    assert np.array_equal(counts.astype(int), cexp)


def test_cold_tier_absorb_read_clear_rows():
    ct = _cold()
    # absorb pre-aggregated rows (the demotion path), twice — combines
    ct.absorb_rows(np.asarray([1, 2]), np.asarray([3, 4]),
                   np.asarray([[5.0], [7.0]]), np.asarray([2.0, 3.0]))
    ct.absorb_rows(np.asarray([1]), np.asarray([3]),
                   np.asarray([[1.5]]), np.asarray([1.0]))
    rows, counts, found = ct.read_rows(1, np.asarray([3, 4]))
    assert found[0] and not found[1]
    assert rows[0, 0] == pytest.approx(6.5) and counts[0] == 3.0
    ct.clear_rows(1, np.asarray([3]))
    _rows, counts2, found2 = ct.read_rows(1, np.asarray([3]))
    assert counts2[0] == 0.0   # zero-count row reads as absent everywhere


def test_cold_tier_fire_ids_is_bounded_to_the_given_set():
    ct = _cold()
    ct.ingest(np.asarray([5, 9]), np.asarray([1, 1]),
              np.asarray([2.0, 3.0], np.float32))
    fields, counts = ct.fire_ids(np.asarray([5]), range(0, 4))
    assert counts.shape == (1,) and counts[0] == 1.0
    assert fields["sum"][0] == pytest.approx(2.0)


def test_cold_tier_purge_below_slice_deletes_history():
    ct = ColdKeyTier(resolve("sum"), 32, purge_granularity=1)
    ct.ingest(np.asarray([1, 1]), np.asarray([2, 20]),
              np.asarray([1.0, 1.0], np.float32))
    ct.purge_below_slice(10)
    _f, counts = ct.fire_ids(np.asarray([1]), range(0, 10))
    assert counts[0] == 0.0
    _f, counts = ct.fire_ids(np.asarray([1]), range(15, 25))
    assert counts[0] == 1.0


def test_cold_tier_corrupt_manifest_is_a_typed_error():
    from flink_tpu.state.cold_tier import _PyStoreFallback

    st = _PyStoreFallback(16)
    with pytest.raises(ColdTierError):
        st.restore("py:!!!not-base64!!!")
    with pytest.raises(ColdTierError):
        st.restore("native-manifest-into-py-store")


def test_cold_tier_restore_adopts_py_snapshot_into_any_store():
    ct = _cold()
    ct.ingest(np.asarray([1]), np.asarray([2]),
              np.asarray([4.0], np.float32))
    snap = ct.snapshot()
    if snap["native"]:
        pytest.skip("native store: py-adoption path not reachable")
    ct2 = _cold()
    ct2.restore(snap)
    _f, counts = ct2.fire_ids(np.asarray([1]), range(0, 4))
    assert counts[0] == 1.0


# ---------------------------------------------------------------------------
# changelog
# ---------------------------------------------------------------------------

def test_changelog_read_entries_range_and_resumed_numbering():
    d = tempfile.mkdtemp()
    log = FsStateChangelog(d, segment_bytes=64)
    for i in range(10):
        log.append(("e", i))
    assert log.offset == 10
    got = log.read_entries(3, 7)
    assert [s for s, _ in got] == [4, 5, 6, 7]
    log2 = FsStateChangelog(d)
    assert log2.offset == 10   # a reopened writer never collides


def test_changelog_trim_above_cuts_the_dead_timeline():
    d = tempfile.mkdtemp()
    log = FsStateChangelog(d, segment_bytes=64)
    for i in range(10):
        log.append(("live" if i < 6 else "orphan", i))
    dropped = log.trim_above(6)
    assert dropped == 4
    assert [e[0] for _s, e in log.read_entries(0)] == ["live"] * 6
    # numbering resumes at the cut: no seq ever collides with, or skips
    # past, the dead timeline
    log.append(("new", 99))
    assert [s for s, _ in log.read_entries(6)] == [7]


def test_changelog_torn_tail_is_skipped_not_fatal():
    d = tempfile.mkdtemp()
    log = FsStateChangelog(d, segment_bytes=1 << 20)
    log.append(("a", 1))
    log.append(("b", 2))
    seg = os.path.join(d, sorted(os.listdir(d))[0])
    with open(seg, "ab") as f:
        f.write((250).to_bytes(4, "big") + b"torn")   # crash mid-append
    assert [e[0] for _s, e in FsStateChangelog(d).read_entries(0)] == \
        ["a", "b"]


def test_changelog_backend_replays_by_sequence_not_position():
    """Regression for the latent orphan-replay bug: entries appended
    AFTER a restored checkpoint (a failed attempt's divergent timeline)
    must never be replayed by a later restore — the old positional
    `entries[:upto]` slice picked the wrong set once orphans interleaved,
    and without the dead-timeline cut a subsequent checkpoint's offsets
    would cover the orphan sequences."""
    d = tempfile.mkdtemp()
    cb = ChangelogKeyedStateBackend(_heap(), FsStateChangelog(d))
    cb.set_current_key("a")
    cb.add("r", 10)
    cp1 = cb.checkpoint()
    cb.add("r", 5)             # orphans-to-be: the attempt that will die
    cb.add("r", 7)

    # restart: restore cp1 and take the OTHER timeline
    r = ChangelogKeyedStateBackend(_heap(), FsStateChangelog(d))
    r.restore(cp1)
    r.set_current_key("a")
    assert r.get("r") == 10    # the orphan adds are not replayed
    r.add("r", 100)            # diverge: this must CUT the orphans
    cp2 = r.checkpoint()

    r2 = ChangelogKeyedStateBackend(_heap(), FsStateChangelog(d))
    r2.restore(cp2)
    r2.set_current_key("a")
    assert r2.get("r") == 110, (
        "the dead timeline's entries leaked into the new checkpoint's "
        "replay range")


# ---------------------------------------------------------------------------
# vocabulary
# ---------------------------------------------------------------------------

def test_vocab_admit_evict_promote_and_id_recycling():
    v = DynamicKeyVocabulary(2)
    r1 = v.observe_batch(np.asarray([10, 20]))
    assert list(r1.ids) == [0, 1] and not r1.demotions
    r2 = v.observe_batch(np.asarray([30]))
    assert r2.demotions and r2.demotions[0][0] in (10, 20)
    evicted_key, evicted_id, cold_id = r2.demotions[0]
    assert list(r2.ids) == [evicted_id]      # the hot id was recycled
    r3 = v.observe_batch(np.asarray([evicted_key]))
    assert r3.promotions and r3.promotions[0][0] == evicted_key
    assert r3.promotions[0][2] == cold_id
    assert v.num_evictions == 2 and v.num_promotions == 1


def test_vocab_pins_batch_touched_keys():
    v = DynamicKeyVocabulary(2)
    r = v.observe_batch(np.asarray([1, 2, 3, 1, 2]))
    # 1 and 2 own the two slots and are pinned; 3 must go cold rather
    # than evict a key this same batch is writing
    assert list(r.ids) == [0, 1, -1, 0, 1]
    assert r.cold_ids[2] >= 0 and not r.demotions


def test_vocab_lru_vs_lfu_victim_choice():
    v = DynamicKeyVocabulary(2, policy="lru")
    v.observe_batch(np.asarray([1, 1, 1]))   # hot by frequency, old
    v.observe_batch(np.asarray([2]))          # recent
    r = v.observe_batch(np.asarray([3]))
    assert r.demotions[0][0] == 1            # lru evicts the oldest touch
    f = DynamicKeyVocabulary(2, policy="lfu")
    f.observe_batch(np.asarray([1, 1, 1]))
    f.observe_batch(np.asarray([2]))
    r = f.observe_batch(np.asarray([3]))
    assert r.demotions[0][0] == 2            # lfu evicts the rare key


def test_vocab_doorkeeper_gates_admission_and_would_evict_projects_it():
    v = DynamicKeyVocabulary(1, admission_min_count=2)
    v.observe_batch(np.asarray([1]))
    r = v.observe_batch(np.asarray([2]))     # first sighting: stays cold
    assert list(r.ids) == [-1] and not r.demotions
    assert not v.would_evict(np.asarray([3]))
    # a key crossing the threshold WITHIN one batch must project as an
    # eviction (the operator flushes on this signal before ids move)
    assert v.would_evict(np.asarray([2]))
    r = v.observe_batch(np.asarray([2]))     # second sighting: admits
    assert r.demotions and r.demotions[0][0] == 1


def test_vocab_snapshot_restore_and_ops_replay_agree():
    v = DynamicKeyVocabulary(3, admission_min_count=1)
    v.drain_ops()
    base = DynamicKeyVocabulary.restore(v.snapshot())
    rng = np.random.default_rng(5)
    for _ in range(20):
        v.observe_batch(rng.integers(0, 12, 6))
    base.apply_ops(v.drain_ops())
    assert base._resident == v._resident
    assert base._cold == v._cold
    assert base.num_evictions == v.num_evictions
    assert base.num_promotions == v.num_promotions
    r = DynamicKeyVocabulary.restore(v.snapshot())
    assert r._resident == v._resident and r._cold == v._cold


# ---------------------------------------------------------------------------
# tiered operator: parity + movement + checkpoints
# ---------------------------------------------------------------------------

def _run_stream(op, *, seed=7, steps=40, n_keys=200, batch=64, start=0,
                collect=None):
    r = np.random.default_rng(seed)
    out = [] if collect is None else collect
    for s in range(steps):
        keys = r.integers(0, n_keys, batch)
        vals = (keys % 5 + 1).astype(np.float32)
        ts = (s * 250 + r.integers(0, 250, batch)).astype(np.int64)
        if s < start:
            continue
        op.process_batch(keys, vals, ts)
        op.process_watermark(s * 250 + 125)
        out.extend(op.drain_output())
    op.process_watermark(MAX_WATERMARK - 1)
    out.extend(op.drain_output())
    return sorted((int(k), int(w.start), float(v)) for k, w, v, _ in out)


@pytest.mark.parametrize("assigner_fn,agg", [
    (lambda: TumblingEventTimeWindows.of(1000), "sum"),
    (lambda: SlidingEventTimeWindows.of(2000, 500), "count"),
    (lambda: TumblingEventTimeWindows.of(1000), "max"),
])
def test_tiered_operator_parity_under_churn(assigner_fn, agg):
    ref = _run_stream(FusedWindowOperator(
        assigner_fn(), agg, key_capacity=1024, superbatch_steps=8))
    op = FusedWindowOperator(
        assigner_fn(), agg, superbatch_steps=8,
        tier=TierConfig(hot_key_capacity=32))
    got = _run_stream(op)
    assert got == ref
    assert op.tier.vocab.num_evictions > 0
    assert op.tier.vocab.num_promotions > 0
    assert op.tier.vocab.resident_count <= 32


def test_tiered_operator_doorkeeper_routes_cold_and_stays_exact():
    ref = _run_stream(FusedWindowOperator(
        TumblingEventTimeWindows.of(1000), "sum", key_capacity=1024,
        superbatch_steps=8))
    op = FusedWindowOperator(
        TumblingEventTimeWindows.of(1000), "sum", superbatch_steps=8,
        tier=TierConfig(hot_key_capacity=32, admission_min_count=3))
    got = _run_stream(op)
    assert got == ref
    assert op.tier.num_cold_records > 0


def _changelog_cfg(d):
    return TierConfig(hot_key_capacity=32, changelog_enabled=True,
                      changelog_dir=d, materialize_interval=3,
                      cold_dir=tempfile.mkdtemp())


def test_tiered_incremental_checkpoint_restores_exactly():
    ref = _run_stream(FusedWindowOperator(
        SlidingEventTimeWindows.of(2000, 500), "sum", key_capacity=1024,
        superbatch_steps=8), steps=40)
    d = tempfile.mkdtemp()
    op = FusedWindowOperator(SlidingEventTimeWindows.of(2000, 500), "sum",
                             superbatch_steps=8, tier=_changelog_cfg(d))
    out = []
    rng = np.random.default_rng(7)
    snap = None
    for s in range(40):
        keys = rng.integers(0, 200, 64)
        vals = (keys % 5 + 1).astype(np.float32)
        ts = (s * 250 + rng.integers(0, 250, 64)).astype(np.int64)
        if s >= 25:   # crash before feeding the remainder
            continue
        op.process_batch(keys, vals, ts)
        op.process_watermark(s * 250 + 125)
        out.extend(op.drain_output())
        if s % 8 == 7:
            snap = op.snapshot()
            out.extend(op.drain_output())
    assert "tier_changelog" in snap
    op2 = FusedWindowOperator(SlidingEventTimeWindows.of(2000, 500), "sum",
                              superbatch_steps=8, tier=_changelog_cfg(d))
    op2.restore(snap)
    got = _run_stream(op2, steps=40, start=24)
    pre = sorted((int(k), int(w.start), float(v)) for k, w, v, _ in out)
    assert sorted(set(pre) | set(got)) == sorted(set(ref))
    # restoring the SAME handle twice (restart loop) stays stable
    op3 = FusedWindowOperator(SlidingEventTimeWindows.of(2000, 500), "sum",
                              superbatch_steps=8, tier=_changelog_cfg(d))
    op3.restore(snap)
    assert _run_stream(op3, steps=40, start=24) == got


def test_tiered_full_snapshot_and_incremental_agree():
    d = tempfile.mkdtemp()
    mk_full = lambda: FusedWindowOperator(   # noqa: E731
        TumblingEventTimeWindows.of(1000), "sum", superbatch_steps=8,
        tier=TierConfig(hot_key_capacity=32))
    op_f = mk_full()
    mk_inc = lambda: FusedWindowOperator(    # noqa: E731
        TumblingEventTimeWindows.of(1000), "sum", superbatch_steps=8,
        tier=_changelog_cfg(d))
    op_i = mk_inc()
    for op in (op_f, op_i):
        rng = np.random.default_rng(9)
        for s in range(16):
            keys = rng.integers(0, 100, 64)
            vals = np.ones(64, np.float32)
            ts = (s * 250 + rng.integers(0, 250, 64)).astype(np.int64)
            op.process_batch(keys, vals, ts)
            op.process_watermark(s * 250 + 125)
            op.drain_output()
    s_f, s_i = op_f.snapshot(), op_i.snapshot()
    op_f.drain_output(), op_i.drain_output()
    r_f, r_i = mk_full(), mk_inc()
    r_f.restore(s_f)
    r_i.restore(s_i)
    assert _run_stream(r_f, seed=11, steps=10, n_keys=100) == \
        _run_stream(r_i, seed=11, steps=10, n_keys=100)


def test_tiered_mesh_parity_and_cross_mesh_restore():
    import jax

    from flink_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("no multi-device mesh on this backend")
    mesh = build_mesh(min(len(jax.devices()), 8))
    ref = _run_stream(FusedWindowOperator(
        SlidingEventTimeWindows.of(2000, 500), "sum", key_capacity=1024,
        superbatch_steps=8))
    op = FusedWindowOperator(SlidingEventTimeWindows.of(2000, 500), "sum",
                             superbatch_steps=8, mesh=mesh,
                             tier=TierConfig(hot_key_capacity=32))
    assert _run_stream(op) == ref
    assert op.mesh_devices() > 1
    assert op.tier.vocab.num_evictions > 0
    # mesh-taken incremental checkpoint restores on a single chip (the
    # canonical-form contract): replay is host-side numpy
    d = tempfile.mkdtemp()
    op_m = FusedWindowOperator(SlidingEventTimeWindows.of(2000, 500),
                               "sum", superbatch_steps=8, mesh=mesh,
                               tier=_changelog_cfg(d))
    out = []
    rng = np.random.default_rng(7)
    snap = None
    for s in range(24):
        keys = rng.integers(0, 200, 64)
        vals = (keys % 5 + 1).astype(np.float32)
        ts = (s * 250 + rng.integers(0, 250, 64)).astype(np.int64)
        op_m.process_batch(keys, vals, ts)
        op_m.process_watermark(s * 250 + 125)
        out.extend(op_m.drain_output())
        if s == 19:
            snap = op_m.snapshot()
            out.extend(op_m.drain_output())
    op_s = FusedWindowOperator(SlidingEventTimeWindows.of(2000, 500),
                               "sum", superbatch_steps=8,
                               tier=_changelog_cfg(d))
    op_s.restore(snap)
    got = _run_stream(op_s, steps=24, start=20)
    ref24 = _run_stream(FusedWindowOperator(
        SlidingEventTimeWindows.of(2000, 500), "sum", key_capacity=1024,
        superbatch_steps=8), steps=24)
    pre = sorted((int(k), int(w.start), float(v)) for k, w, v, _ in out)
    assert sorted(set(pre) | set(got)) == sorted(set(ref24))


def test_tiered_snapshot_refused_by_untired_operator_and_vice_versa():
    op = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "sum",
                             superbatch_steps=8,
                             tier=TierConfig(hot_key_capacity=32))
    op.process_batch(np.asarray([1, 2]), np.asarray([1.0, 1.0], np.float32),
                     np.asarray([100, 200], np.int64))
    snap = op.snapshot()
    plain = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "sum",
                                superbatch_steps=8)
    with pytest.raises(RuntimeError, match="tier"):
        plain.restore(snap)
    # the reverse must fail as loudly: a classic snapshot restored into a
    # tiered operator would route new keys through an EMPTY vocabulary
    # whose recycled dense ids alias the restored rows' old keys
    plain2 = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "sum",
                                 superbatch_steps=8)
    plain2.process_batch(np.asarray([1, 2]),
                         np.asarray([1.0, 1.0], np.float32),
                         np.asarray([100, 200], np.int64))
    classic_snap = plain2.snapshot()
    tiered = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "sum",
                                 superbatch_steps=8,
                                 tier=TierConfig(hot_key_capacity=32))
    with pytest.raises(RuntimeError, match="classic"):
        tiered.restore(classic_snap)


def test_tiered_operator_refuses_traced_prologue_and_gauges_exist():
    from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

    with pytest.raises(ValueError, match="host key dictionary"):
        FusedWindowOperator(
            TumblingEventTimeWindows.of(1000), "count",
            prologue=TracedPrologue(transforms=(), key_fn=lambda c: c),
            tier=TierConfig(hot_key_capacity=32))
    op = FusedWindowOperator(TumblingEventTimeWindows.of(1000), "count",
                             superbatch_steps=8,
                             tier=TierConfig(hot_key_capacity=8))
    _run_stream(op, steps=10, n_keys=50)
    g = op.tier_gauges()
    for key in ("vocabSize", "residentKeys", "evictions", "promotions",
                "spilledBytes", "changelogBytes", "tierHotFillRatio"):
        assert key in g
    assert g["vocabSize"] == 50 and g["residentKeys"] <= 8
    assert op.state_key_count() == 50


# ---------------------------------------------------------------------------
# metric fold + executor wiring
# ---------------------------------------------------------------------------

def test_tier_gauges_fold_sum_across_shards_ratio_means():
    from flink_tpu.runtime.cluster import aggregate_shard_metrics

    agg = aggregate_shard_metrics({
        0: {"job.operator.w.vocabSize": 100, "job.operator.w.evictions": 7,
            "job.operator.w.residentKeys": 16,
            "job.operator.w.promotions": 3,
            "job.operator.w.spilledBytes": 1000,
            "job.operator.w.changelogBytes": 50,
            "job.operator.w.tierHotFillRatio": 0.5},
        1: {"job.operator.w.vocabSize": 40, "job.operator.w.evictions": 5,
            "job.operator.w.residentKeys": 8,
            "job.operator.w.promotions": 1,
            "job.operator.w.spilledBytes": 500,
            "job.operator.w.changelogBytes": 150,
            "job.operator.w.tierHotFillRatio": 1.0},
    })
    # counters/sizes SUM (each shard owns its key range)
    assert agg["job.operator.w.vocabSize"] == 140
    assert agg["job.operator.w.evictions"] == 12
    assert agg["job.operator.w.residentKeys"] == 24
    assert agg["job.operator.w.promotions"] == 4
    assert agg["job.operator.w.spilledBytes"] == 1500
    assert agg["job.operator.w.changelogBytes"] == 200
    # per-shard fraction MEANS (the generic Ratio rule)
    assert agg["job.operator.w.tierHotFillRatio"] == pytest.approx(0.75)


def test_executor_wires_tier_and_device_payload(tmp_path):
    from flink_tpu.api.datastream import StreamExecutionEnvironment
    from flink_tpu.config import (
        Configuration,
        ExecutionOptions,
        StateTierOptions,
    )
    from flink_tpu.connectors.sink import CollectSink
    from flink_tpu.connectors.source import Batch, DataGeneratorSource
    from flink_tpu.core.watermarks import WatermarkStrategy
    from flink_tpu.utils.arrays import obj_array

    def build(tiered):
        from flink_tpu.config import CheckpointingOptions

        config = Configuration()
        config.set(ExecutionOptions.BATCH_SIZE, 200)
        config.set(ExecutionOptions.KEY_CAPACITY, 768)
        if tiered:
            config.set(CheckpointingOptions.INTERVAL_MS, 1)
            config.set(CheckpointingOptions.DIRECTORY, str(tmp_path / "chk"))
        if tiered:
            config.set(StateTierOptions.TIER_ENABLED, True)
            config.set(StateTierOptions.HOT_KEY_CAPACITY, 16)
            config.set(StateTierOptions.CHANGELOG_ENABLED, True)
            config.set(StateTierOptions.CHANGELOG_DIR,
                       str(tmp_path / "changelog"))
            config.set(StateTierOptions.COLD_DIR, str(tmp_path / "cold"))

        def gen(idx):
            values = [(int(i % 64), 1.0, int(i * 10)) for i in idx]
            return Batch(obj_array(values), (idx * 10).astype(np.int64))

        env = StreamExecutionEnvironment(config)
        stream = env.from_source(
            DataGeneratorSource(gen, count=2600, num_splits=8),
            watermark_strategy=WatermarkStrategy.for_monotonous_timestamps())
        sink = CollectSink()
        (stream.key_by(lambda x: x[0])
               .window(TumblingEventTimeWindows.of(1000)).count()
               .sink_to(sink))
        client = env.execute_async("tier-exec")
        client.wait(120)
        return client, sorted((int(k), int(n)) for k, n in sink.results)

    _c, ref = build(False)
    client, got = build(True)
    assert got == ref
    tier = None
    for entry in client._runtime.device_snapshot()["operators"].values():
        if entry.get("tier"):
            tier = entry["tier"]
    assert tier is not None, "tier block missing from /jobs/:id/device"
    assert tier["residentKeys"] <= 16
    assert tier["evictions"] > 0
    assert tier["changelogEnabled"] and tier["changelogBytes"] > 0
