"""Tier-1 full-lint gate with a wall-clock budget (ISSUE-20).

Runs every registered rule individually over the real tree (one shared
ModuleIndex, like the engine), recording per-rule wall clock:

- **Coverage**: exactly 17 rules registered, every one exercised here and
  zero ACTIVE violations per rule against the checked-in baseline (the
  per-rule split means a regression names the rule, not just "lint
  failed").
- **Budget**: the 17-rule run must stay under a pinned multiple of the
  14 pre-EXON rules' time on the same machine/index — the interprocedural
  dataflow layer (summaries + fault fixpoint, shared across the three
  EXON rules via DataflowIndex.shared) must never quietly turn the lint
  gate into the slowest test in tier-1. Failure messages carry the
  per-rule timing table so the offender is named.
- **Stamp**: bench.lint_summary() (the `lint:` block next to `health` in
  every BENCH_*.json) reports the same shape this gate verifies.
"""

import pathlib
import time

import pytest

import flink_tpu
from flink_tpu.lint import Baseline, all_rules

PKG = pathlib.Path(flink_tpu.__file__).parent
BASELINE = PKG.parent / "lint_baseline.json"

#: the 17-rule run may cost at most this multiple of the 14 pre-existing
#: rules' time (measured on the same index in the same process, so the
#: ratio is machine-independent); the floor keeps a near-zero denominator
#: from flaking the assert on very fast machines
BUDGET_MULTIPLE = 3.0
BUDGET_FLOOR_S = 10.0

_PRE_EXISTING = ("ARCH", "CONC", "DEV", "DOC", "WIRE")


@pytest.fixture(scope="module")
def timed_run():
    """One shared index, every rule timed individually."""
    from flink_tpu.lint.index import ModuleIndex

    index = ModuleIndex(PKG)
    times = {}
    found = {}
    for rule in all_rules():
        t0 = time.perf_counter()
        found[rule.id] = list(rule.check(index))
        times[rule.id] = time.perf_counter() - t0
    return times, found


def _timing_table(times):
    return "\n".join(
        f"  {rid}: {t * 1e3:8.1f} ms"
        for rid, t in sorted(times.items(), key=lambda kv: -kv[1]))


def test_registry_holds_exactly_17_rules():
    ids = sorted(r.id for r in all_rules())
    assert len(ids) == 17, ids
    assert [i for i in ids if i.startswith("EXON")] == \
        ["EXON001", "EXON002", "EXON003"]


def test_zero_active_violations_per_rule(timed_run):
    """Every rule individually clean on the tree (baselined debt aside) —
    the per-rule split names the offender directly."""
    _, found = timed_run
    baseline = Baseline.load(BASELINE)
    for rule_id in sorted(found):
        active = [v for v in found[rule_id]
                  if baseline.match(v) is None]
        rendered = "\n".join(v.render() for v in active)
        assert not active, (
            f"{rule_id} has active violations on the tree (fix them or "
            f"baseline with a written justification):\n{rendered}")
    # with every rule's findings matched, no baseline entry may be stale
    stale = [e.fingerprint for e in baseline.stale_entries()]
    assert not stale, f"stale baseline entries: {stale}"


def test_full_run_within_time_budget(timed_run):
    times, _ = timed_run
    pre = sum(t for rid, t in times.items()
              if rid.startswith(_PRE_EXISTING))
    full = sum(times.values())
    budget = max(BUDGET_MULTIPLE * pre, BUDGET_FLOOR_S)
    assert full <= budget, (
        f"full 17-rule lint took {full:.2f}s — over budget "
        f"({BUDGET_MULTIPLE}x the 14 pre-EXON rules' {pre:.2f}s = "
        f"{budget:.2f}s). Per-rule timing (slowest first):\n"
        f"{_timing_table(times)}")


def test_bench_stamp_reports_the_same_verdict():
    """The `lint:` block bench.py stamps into BENCH_*.json next to
    `health` must carry the gate's shape and verdict."""
    import bench

    info = bench.lint_summary()
    assert set(info) == {"modules", "rules", "violations", "analysis_ms"}, (
        f"lint stamp shape drifted (or the run errored): {info}")
    assert info["rules"] == 17
    assert info["violations"] == 0, (
        f"bench stamp sees active violations the gate missed: {info}")
    assert info["modules"] > 100
    assert info["analysis_ms"] > 0
