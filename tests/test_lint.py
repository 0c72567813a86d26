"""Tier-1 gate for the flink_tpu.lint analyzer (ISSUE-5).

Three layers:

1. **The gate** — the full engine over the real ``flink_tpu`` package
   against the checked-in ``lint_baseline.json`` must be clean (exit 0),
   every baseline entry justified and live. This is what keeps future
   PRs' invariants enforced by CI rather than reviewer memory.
2. **Engine mechanics** — CLI exit codes (0/1/2), output formats (text /
   JSON / SARIF-against-golden), ``--write-baseline`` seeding.
3. **Baseline lifecycle round-trip** — add → suppress → remove → fail
   (a stale entry is an error, so fixed debt must leave the ledger).
"""

import json
import pathlib
import shutil
import textwrap

import flink_tpu
from flink_tpu.lint import Baseline, all_rules, run_lint
from flink_tpu.lint.cli import main as lint_main
from flink_tpu.lint.engine import (
    EXIT_BASELINE_ERROR,
    EXIT_CLEAN,
    EXIT_VIOLATIONS,
)

PKG = pathlib.Path(flink_tpu.__file__).parent
REPO = PKG.parent
BASELINE = REPO / "lint_baseline.json"
FIXTURES = pathlib.Path(__file__).parent / "fixtures"
SAMPLE_PKG = FIXTURES / "lint_sample" / "samplepkg"


# ---------------------------------------------------------------------------
# 1. the gate
# ---------------------------------------------------------------------------

def test_flink_tpu_is_lint_clean_against_the_checked_in_baseline():
    baseline = Baseline.load(BASELINE)
    report = run_lint(PKG, baseline=baseline)
    rendered = "\n".join(v.render() for v in report.violations)
    assert not report.violations, (
        "new lint violations (fix them, or baseline WITH a written "
        f"justification in {BASELINE.name}):\n{rendered}"
    )
    assert not report.baseline_errors, "\n".join(report.baseline_errors)
    assert report.exit_code == EXIT_CLEAN
    assert report.modules_scanned > 100     # really scanned the package


def test_every_baseline_entry_is_justified():
    baseline = Baseline.load(BASELINE)
    unjustified = [e.fingerprint for e in baseline.entries if not e.justified]
    assert not unjustified, (
        f"baseline entries without a written justification: {unjustified}"
    )


def test_cli_gate_matches_engine():
    assert lint_main([str(PKG), "--baseline", str(BASELINE)]) == EXIT_CLEAN


def test_the_one_staging_path_is_where_arch003_looks():
    """ARCH003 allows at most one site per section and reads one operator
    file: the real tree must hold exactly one of each, in the pipeline's
    `stage`, and the operator must hand its groups to `process_superbatch`
    — or the rule passes on a tree that lost the path it guards."""
    import re

    from flink_tpu.lint.rules_architecture import (
        ONE_SITE_STAGES,
        WINDOW_OPERATOR,
    )

    for name in ONE_SITE_STAGES:
        sites = [p.relative_to(PKG).as_posix() for p in PKG.rglob("*.py")
                 if re.search(rf'dispatch_stage\([^)]*"{name}"\)',
                              p.read_text())]
        assert sites == ["runtime/fused_window_pipeline.py"], (name, sites)
    op = (PKG / WINDOW_OPERATOR).read_text()
    assert op.count("self.pipe.process_superbatch(") == 1


def test_path_scoped_rules_are_not_vacuous():
    """Every path the rules are configured against must exist in the real
    package — otherwise a rename silently disables the rule and it passes
    forever without checking anything (the old test_layering_rules had
    this guard; the registry migration must not lose it)."""
    from flink_tpu.lint import ModuleIndex
    from flink_tpu.lint.rules_architecture import LAYER_FORBIDDEN
    from flink_tpu.lint.rules_device import CONTROL_PLANE
    from flink_tpu.lint.rules_wire import SerializationFreeDataplaneRule

    index = ModuleIndex(PKG)
    # the history/doctor plane must stay REGISTERED under DEV003's jax
    # ban: both modules consume plain-data snapshots/span dicts, and a
    # module-level jax import would drag backend init into every REST
    # reader and JM schedule tick. A rename (or a dropped tuple entry)
    # would disable the ban silently.
    for rel in ("metrics/history.py", "metrics/doctor.py"):
        assert rel in CONTROL_PLANE, (
            f"{rel} no longer registered in DEV003's CONTROL_PLANE — "
            "the history/doctor plane may not import jax")
        assert index.get(rel) is not None, (
            f"{rel} missing — the history/doctor plane moved and "
            "DEV003's control-plane ban no longer covers it")
    for layer in LAYER_FORBIDDEN:
        assert any(index.in_subtree(layer)), (
            f"layer {layer!r} has no modules — LAYER_FORBIDDEN is stale "
            f"and ARCH001 is vacuous for it")
    # the scheduler layer must stay REGISTERED, not merely existent: a
    # deleted dict entry would leave scheduler/ free to grow runtime
    # imports with every test still green
    assert "scheduler" in LAYER_FORBIDDEN, (
        "scheduler layer unregistered from ARCH001 — the autoscaler may "
        "not import the runtime (rescales flow through injected callables)")
    assert any("runtime" in b for b in LAYER_FORBIDDEN["scheduler"]), (
        "scheduler layer no longer forbids runtime imports")
    assert "metrics" in LAYER_FORBIDDEN and any(
        "scheduler" in b for b in LAYER_FORBIDDEN["metrics"]), (
        "metrics layer no longer forbids importing the scheduler")
    # the fusion planner must stay in graph/ under the graph layer's
    # runtime ban: the DeviceChainPlan is pure data about transformations,
    # and a planner that imports the runtime inverts the translation DAG
    assert "graph" in LAYER_FORBIDDEN and any(
        "runtime" in b for b in LAYER_FORBIDDEN["graph"]), (
        "graph layer no longer forbids runtime imports — the fusion "
        "planner (graph/fusion.py) must not reach into the executor")
    assert index.get("graph/fusion.py") is not None, (
        "graph/fusion.py missing — the whole-graph fusion planner moved "
        "and ARCH001's graph-layer ban no longer covers it")
    # the sharing optimizer must stay in graph/ under the same ban: a
    # SharedWindowPlan is pure data about correlated window siblings the
    # executor consumes — a runtime import here would invert the
    # translation DAG exactly like a fusion-planner one
    assert index.get("graph/window_sharing.py") is not None, (
        "graph/window_sharing.py missing — the Factor-Windows sharing "
        "optimizer moved and ARCH001's graph-layer ban no longer covers "
        "it")
    # the SQL planner must stay REGISTERED with its runtime AND api bans:
    # it emits transformations the executor consumes — an executor (or
    # fluent-api) import here inverts the translation DAG, and a deleted
    # dict entry would let planner/ grow those imports silently
    assert "planner" in LAYER_FORBIDDEN, (
        "planner layer unregistered from ARCH001 — the SQL planner may "
        "not import the runtime or the api")
    assert any("runtime" in b for b in LAYER_FORBIDDEN["planner"]), (
        "planner layer no longer forbids runtime imports")
    assert any(b.endswith(".api") for b in LAYER_FORBIDDEN["planner"]), (
        "planner layer no longer forbids api imports (assigner "
        "construction must stay a function-scoped lazy import)")
    for mod in ("planner/__init__.py", "planner/logical.py",
                "planner/rules.py", "planner/lowering.py"):
        assert index.get(mod) is not None, (
            f"{mod} missing — the SQL planner moved and ARCH001's "
            f"planner-layer bans no longer cover it")
    # the multichip library must stay in parallel/ under the parallel
    # layer's runtime/api ban: the sharded superscan is a kernel/state
    # library the runtime composes (FusedWindowOperator targets it), and
    # a module-level runtime import would invert that DAG
    assert "parallel" in LAYER_FORBIDDEN and any(
        "runtime" in b for b in LAYER_FORBIDDEN["parallel"]), (
        "parallel layer unregistered from ARCH001 (or no longer forbids "
        "runtime imports) — the mesh library may not reach into the "
        "executor")
    for rel in ("parallel/mesh.py", "parallel/sharded_superscan.py"):
        assert index.get(rel) is not None, (
            f"{rel} missing — the multichip SPMD core moved and the "
            "parallel layer's ARCH001 entry no longer covers it")
    # the join subsystem must stay REGISTERED with its runtime/api/table/
    # scheduler bans: the bucket rings and the fused match pipeline are a
    # kernel/state library the runtime's DeviceJoinRunner composes — a
    # module-level runtime (or table) import would invert that DAG, and a
    # deleted dict entry would let joins/ grow those imports silently
    assert "joins" in LAYER_FORBIDDEN, (
        "joins layer unregistered from ARCH001 — the join subsystem may "
        "not import the runtime, api, table, or scheduler")
    for banned in ("runtime", "api", "table", "scheduler"):
        assert any(b.endswith("." + banned)
                   for b in LAYER_FORBIDDEN["joins"]), (
            f"joins layer no longer forbids {banned} imports")
    for rel in ("joins/spec.py", "joins/ring.py", "joins/pipeline.py",
                "joins/sharded.py"):
        assert index.get(rel) is not None, (
            f"{rel} missing — the join subsystem moved and the joins "
            "layer's ARCH001 entry no longer covers it")
    # the skew-adaptive exchange splits across two layers and both must
    # stay under their bans: the routing-table LAYOUT algebra lives in
    # parallel/ (pure numpy, composed by the runtime), while the
    # rebalance POLICY lives in scheduler/ (decides from telemetry, the
    # runtime executes through the capture/restore machinery — the
    # autoscaler's injected-callable pattern)
    assert index.get("parallel/routing.py") is not None, (
        "parallel/routing.py missing — the key-group routing table moved "
        "and the parallel layer's ARCH001 entry no longer covers it")
    assert index.get("scheduler/rebalancer.py") is not None, (
        "scheduler/rebalancer.py missing — the skew rebalancer moved and "
        "the scheduler layer's runtime ban no longer covers it")
    # the million-key state plane must stay in state/ under the state
    # layer's runtime ban: the vocabulary decides placement and the tier
    # manager moves bytes through operator-injected callables — a module
    # that imported the runtime would invert that DAG
    assert any("runtime" in b for b in LAYER_FORBIDDEN["state"]), (
        "state layer no longer forbids runtime imports — vocab.py/"
        "tier_manager.py could silently grow executor dependencies")
    for rel in ("state/vocab.py", "state/tier_manager.py"):
        assert index.get(rel) is not None, (
            f"{rel} missing — the state plane moved and the state "
            "layer's ARCH001 entry no longer covers it")
    # the device-plane observability modules must stay in metrics/ under
    # the metrics layer's runtime ban: compile/key telemetry flows OUTWARD
    # (runtime callers hand in jitted fns and load columns), and a tracker
    # that imported the runtime would invert the metrics DAG
    for rel in ("metrics/device_stats.py", "metrics/key_stats.py"):
        assert index.get(rel) is not None, (
            f"{rel} missing — the device-plane observability core moved "
            "and the metrics layer's runtime-import ban no longer covers "
            "it")
    assert any("runtime" in b for b in LAYER_FORBIDDEN["metrics"]), (
        "metrics layer no longer forbids runtime imports — device_stats/"
        "key_stats could silently grow executor dependencies")
    for rel in CONTROL_PLANE:
        assert index.get(rel) is not None, (
            f"control-plane module {rel} missing — CONTROL_PLANE is stale "
            f"and DEV003 is vacuous for it")
    assert index.get(SerializationFreeDataplaneRule.DATAPLANE) is not None
    assert any(index.in_subtree("checkpoint")), (
        "checkpoint/ has no modules — ARCH002 is vacuous")
    assert index.get("config.py") is not None, "DOC001 is vacuous"
    # CONC005 no-silent-swallow is path-scoped: every configured subtree
    # must exist AND stay configured, or a rename/edit silently frees the
    # runtime/checkpoint planes to grow `except Exception: pass` again
    from flink_tpu.lint.rules_concurrency import SWALLOW_SCOPED_SUBTREES

    assert set(SWALLOW_SCOPED_SUBTREES) >= {"runtime", "checkpoint"}, (
        "CONC005 no longer scopes the runtime/checkpoint subtrees — "
        "silent swallows on the failure-detection planes would pass CI")
    for layer in SWALLOW_SCOPED_SUBTREES:
        assert any(index.in_subtree(layer)), (
            f"CONC005 subtree {layer!r} has no modules — the rule is "
            "vacuous for it")
    # the chaos plane's leaf module must stay where every seam imports it
    # from (security/transport, rpc, dataplane, storage, executor), and
    # the scenario matrix must stay runnable
    for rel in ("chaos/plan.py", "chaos/scenarios.py"):
        assert index.get(rel) is not None, (
            f"{rel} missing — the chaos plane moved and the seams' "
            "module-level hook (and the bench chaos gate) no longer "
            "cover it")


# ---------------------------------------------------------------------------
# 2. engine mechanics
# ---------------------------------------------------------------------------

def _write_violating_pkg(tmp_path) -> pathlib.Path:
    root = tmp_path / "vpkg"
    root.mkdir()
    (root / "__init__.py").touch()
    (root / "w.py").write_text(textwrap.dedent("""
        import threading

        def spawn(fn):
            threading.Thread(target=fn).start()
    """))
    return root


def test_cli_exit_1_on_violations(tmp_path, capsys):
    root = _write_violating_pkg(tmp_path)
    rc = lint_main([str(root), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == EXIT_VIOLATIONS
    assert "CONC004" in out and "vpkg/w.py:5" in out


def test_cli_exit_0_on_clean_package(tmp_path):
    root = tmp_path / "cleanpkg"
    root.mkdir()
    (root / "__init__.py").touch()
    (root / "ok.py").write_text("X = 1\n")
    assert lint_main([str(root), "--no-baseline"]) == EXIT_CLEAN


def test_cli_json_format(tmp_path, capsys):
    root = _write_violating_pkg(tmp_path)
    rc = lint_main([str(root), "--no-baseline", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == EXIT_VIOLATIONS
    assert doc["exit_code"] == EXIT_VIOLATIONS
    assert [v["rule"] for v in doc["violations"]] == ["CONC004"]
    assert doc["violations"][0]["fingerprint"].startswith("CONC004::")


def test_cli_rule_filter_and_list(tmp_path, capsys):
    root = _write_violating_pkg(tmp_path)
    # a filter that excludes the only violation reports clean
    assert lint_main([str(root), "--no-baseline",
                      "--rule", "WIRE001"]) == EXIT_CLEAN
    capsys.readouterr()
    assert lint_main(["--list-rules"]) == EXIT_CLEAN
    out = capsys.readouterr().out
    for rule in all_rules():
        assert rule.id in out


def test_sarif_output_matches_golden(capsys):
    rc = lint_main([str(SAMPLE_PKG), "--no-baseline",
                    "--rule", "CONC004", "--rule", "WIRE001",
                    "--format", "sarif"])
    out = capsys.readouterr().out
    assert rc == EXIT_VIOLATIONS
    golden = (FIXTURES / "lint_expected.sarif").read_text()
    assert json.loads(out) == json.loads(golden), (
        "SARIF output drifted from tests/fixtures/lint_expected.sarif — "
        "if the change is intentional, regenerate the golden with:\n"
        "  python -m flink_tpu.lint tests/fixtures/lint_sample/samplepkg "
        "--no-baseline --rule CONC004 --rule WIRE001 --format sarif "
        "> tests/fixtures/lint_expected.sarif"
    )
    doc = json.loads(out)
    results = doc["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == {"CONC004", "WIRE001"}
    uris = {r["locations"][0]["physicalLocation"]["artifactLocation"]["uri"]
            for r in results}
    assert uris == {"samplepkg/worker.py", "samplepkg/runtime/blob.py"}


def test_sarif_output_validates_against_2_1_0_schema(capsys):
    """The emitted SARIF must satisfy the 2.1.0 schema (vendored subset:
    the official OASIS schema's required/enum/type constraints for every
    object we produce — CI has no network to fetch the full file)."""
    import jsonschema

    rc = lint_main([str(SAMPLE_PKG), "--no-baseline",
                    "--rule", "CONC004", "--rule", "WIRE001",
                    "--format", "sarif"])
    out = capsys.readouterr().out
    assert rc == EXIT_VIOLATIONS
    doc = json.loads(out)
    schema = json.loads(
        (FIXTURES / "sarif-schema-2.1.0.subset.json").read_text())
    jsonschema.validate(doc, schema)          # raises on violation

    # the schema subset must not be vacuous: each of these mutations is
    # illegal under the real 2.1.0 schema and must be rejected here too
    for mutate in (
        lambda d: d.update(version="3.0.0"),
        lambda d: d.pop("runs"),
        lambda d: d["runs"][0].pop("tool"),
        lambda d: d["runs"][0]["tool"]["driver"].pop("name"),
        lambda d: d["runs"][0]["results"][0].pop("message"),
        lambda d: d["runs"][0]["results"][0].update(level="fatal"),
        lambda d: d["runs"][0]["results"][0]["locations"][0]
        ["physicalLocation"]["region"].update(startLine=0),
    ):
        broken = json.loads(out)
        mutate(broken)
        try:
            jsonschema.validate(broken, schema)
        except jsonschema.ValidationError:
            pass
        else:
            raise AssertionError(
                f"schema subset accepted an illegal mutation: {mutate}")


# ---------------------------------------------------------------------------
# 3. baseline lifecycle: add -> suppress -> remove -> fail
# ---------------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    root = _write_violating_pkg(tmp_path)
    bl_path = tmp_path / "lint_baseline.json"

    # (0) violation fails the run
    report = run_lint(root)
    assert report.exit_code == EXIT_VIOLATIONS
    violation = report.violations[0]

    # (1) add WITHOUT justification: suppressed but the run errors (exit 2)
    baseline = Baseline(path=bl_path)
    baseline.add(violation)                      # seeds a TODO justification
    baseline.save()
    report = run_lint(root, baseline=Baseline.load(bl_path))
    assert report.exit_code == EXIT_BASELINE_ERROR
    assert any("justification" in e for e in report.baseline_errors)

    # (2) write the justification: suppressed cleanly (exit 0)
    baseline = Baseline.load(bl_path)
    baseline.entries[0].justification = (
        "fixture thread is short-lived and joined by the test harness")
    baseline.save()
    report = run_lint(root, baseline=Baseline.load(bl_path))
    assert report.exit_code == EXIT_CLEAN
    assert len(report.suppressed) == 1

    # (3) fix the code: the entry goes stale and the run fails again
    (root / "w.py").write_text(textwrap.dedent("""
        import threading

        def spawn(fn):
            threading.Thread(target=fn, daemon=True, name="fix-w").start()
    """))
    report = run_lint(root, baseline=Baseline.load(bl_path))
    assert report.exit_code == EXIT_BASELINE_ERROR
    assert any("stale" in e for e in report.baseline_errors)

    # (4) remove the stale entry: clean again
    baseline = Baseline.load(bl_path)
    baseline.entries = []
    baseline.save()
    report = run_lint(root, baseline=Baseline.load(bl_path))
    assert report.exit_code == EXIT_CLEAN


def test_write_baseline_rejects_no_baseline_combo(tmp_path, capsys):
    """--no-baseline --write-baseline would rebuild the file from empty
    and destroy every human-written justification — refused outright."""
    root = _write_violating_pkg(tmp_path)
    assert lint_main([str(root), "--no-baseline",
                      "--write-baseline"]) == EXIT_BASELINE_ERROR
    assert "mutually exclusive" in capsys.readouterr().err


def test_write_baseline_merges_into_existing(tmp_path, capsys):
    """--write-baseline must preserve already-justified entries."""
    root = _write_violating_pkg(tmp_path)
    (root / "w2.py").write_text(textwrap.dedent("""
        import threading

        def spawn2(fn):
            threading.Thread(target=fn).start()
    """))
    bl_path = tmp_path / "lint_baseline.json"
    report = run_lint(root)
    assert len(report.violations) == 2
    baseline = Baseline(path=bl_path)
    first = next(v for v in report.violations if v.path.endswith("w.py"))
    baseline.add(first, justification="human-written reason")
    baseline.save()

    assert lint_main([str(root), "--baseline", str(bl_path),
                      "--write-baseline"]) == EXIT_CLEAN
    capsys.readouterr()
    doc = json.loads(bl_path.read_text())
    justs = sorted(e["justification"] for e in doc["entries"])
    assert len(doc["entries"]) == 2
    assert justs[0].startswith("TODO")           # the newly-frozen one
    assert justs[1] == "human-written reason"    # preserved, not clobbered


def test_write_baseline_cli_flow(tmp_path, capsys):
    root = _write_violating_pkg(tmp_path)
    bl_path = tmp_path / "lint_baseline.json"

    # seeding writes TODO entries and exits 0 (the freeze itself succeeds)
    assert lint_main([str(root), "--baseline", str(bl_path),
                      "--write-baseline"]) == EXIT_CLEAN
    capsys.readouterr()
    doc = json.loads(bl_path.read_text())
    assert len(doc["entries"]) == 1
    assert doc["entries"][0]["justification"].startswith("TODO")

    # ...but the engine refuses the TODO until a human justifies it
    assert lint_main([str(root), "--baseline",
                      str(bl_path)]) == EXIT_BASELINE_ERROR

    baseline = Baseline.load(bl_path)
    baseline.entries[0].justification = "documented fixture debt"
    baseline.save()
    assert lint_main([str(root), "--baseline", str(bl_path)]) == EXIT_CLEAN


def test_fingerprints_survive_line_churn(tmp_path):
    """Baseline matching is line-independent: prepending code must not
    orphan the entry."""
    root = _write_violating_pkg(tmp_path)
    report = run_lint(root)
    fp_before = report.violations[0].fingerprint
    line_before = report.violations[0].line
    src = (root / "w.py").read_text()
    (root / "w.py").write_text("# a comment\nY = 2\n" + src)
    report = run_lint(root)
    assert report.violations[0].fingerprint == fp_before
    assert report.violations[0].line == line_before + 2  # line moved; fp did not


def _baseline_with_stale_entries(tmp_path) -> pathlib.Path:
    """A baseline holding one live entry, one whose file is gone, and one
    whose rule id was retired."""
    root = _write_violating_pkg(tmp_path)
    bl_path = tmp_path / "lint_baseline.json"
    report = run_lint(root)
    baseline = Baseline(path=bl_path)
    baseline.add(report.violations[0], justification="documented debt")
    baseline.entries.append(type(baseline.entries[0])(
        rule="CONC004", path="vpkg/deleted_module.py", scope="gone",
        symbol="thread@gone", justification="file was deleted in PR 12"))
    baseline.entries.append(type(baseline.entries[0])(
        rule="ZZZZ999", path="vpkg/w.py", scope="spawn",
        symbol="whatever", justification="rule was retired"))
    baseline.save()
    return root


def test_prune_stale_drops_missing_file_and_unknown_rule(tmp_path):
    """Entries whose file no longer exists or whose rule id is unknown
    were previously carried forever (the stale check reports them as
    engine errors against a file nobody can re-lint); prune_stale drops
    exactly those and keeps the live entry."""
    root = _baseline_with_stale_entries(tmp_path)
    baseline = Baseline.load(tmp_path / "lint_baseline.json")
    assert len(baseline) == 3

    pruned = baseline.prune_stale(tmp_path, [r.id for r in all_rules()])
    reasons = sorted(reason for _, reason in pruned)
    assert len(pruned) == 2
    assert any("no longer exists" in r for r in reasons)
    assert any("unknown rule" in r for r in reasons)
    assert len(baseline) == 1
    assert baseline.entries[0].path.endswith("w.py")

    # the pruned baseline still suppresses the live violation
    report = run_lint(root, baseline=baseline)
    assert report.exit_code == EXIT_CLEAN
    assert len(report.suppressed) == 1


def test_cli_prune_baseline_rewrites_the_file(tmp_path, capsys):
    root = _baseline_with_stale_entries(tmp_path)
    bl_path = tmp_path / "lint_baseline.json"

    # without the flag: pruned in memory (run is clean, warning printed)
    # but the file keeps all three entries
    assert lint_main([str(root), "--baseline", str(bl_path)]) == EXIT_CLEAN
    err = capsys.readouterr().err
    assert err.count("pruned stale entry") == 2
    assert len(json.loads(bl_path.read_text())["entries"]) == 3

    # with the flag: the file is rewritten without the stale entries
    assert lint_main([str(root), "--baseline", str(bl_path),
                      "--prune-baseline"]) == EXIT_CLEAN
    err = capsys.readouterr().err
    assert "2 stale entries removed, 1 kept" in err
    doc = json.loads(bl_path.read_text())
    assert len(doc["entries"]) == 1
    assert doc["entries"][0]["path"].endswith("w.py")

    # idempotent: a second prune finds nothing
    assert lint_main([str(root), "--baseline", str(bl_path),
                      "--prune-baseline"]) == EXIT_CLEAN
    assert "0 stale entries removed" in capsys.readouterr().err


def test_prune_baseline_rejects_no_baseline_combo(tmp_path, capsys):
    root = _write_violating_pkg(tmp_path)
    assert lint_main([str(root), "--no-baseline",
                      "--prune-baseline"]) == EXIT_BASELINE_ERROR
    assert "mutually exclusive" in capsys.readouterr().err


def test_rule_filter_skips_stale_check_for_other_rules(tmp_path):
    """A --rule filtered run must not call every other rule's baseline
    entries stale."""
    root = _write_violating_pkg(tmp_path)
    bl_path = tmp_path / "lint_baseline.json"
    baseline = Baseline(path=bl_path)
    report = run_lint(root)
    baseline.add(report.violations[0], justification="documented debt")
    baseline.save()
    from flink_tpu.lint import get_rule

    report = run_lint(root, rules=[get_rule("WIRE001")],
                      baseline=Baseline.load(bl_path))
    assert report.exit_code == EXIT_CLEAN
