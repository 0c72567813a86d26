"""`correct` comes out true on a sound run and false on the control and on
every fault a cell can have. Each case is a process of its own (a planted
fault must not reach the next case through the program's module caches);
CPU backend, rehearsal sizes: a test run can hold it."""

import json
import os
import subprocess
import sys

import pytest

RUNNER = os.path.join(os.path.dirname(__file__), "fault_runner.py")

CELLS = os.path.join(os.path.dirname(__file__), "cells")
TRACED, HOSTKEYED, KEYS64K = (
    "ysb_catchup", "ysb_hostkeyed_catchup", "keys64k_catchup")
# test-only cells: the mesh and the open loop, which no shipped cell drives
LIVE = os.path.join(CELLS, "keys64k_live.json")
MESH = os.path.join(CELLS, "keys64k_mesh4_catchup.json")


def run(fault: str, workload: str):
    proc = subprocess.run([sys.executable, RUNNER, fault, workload],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def failing(out):
    return sorted(k for k, c in out["compared"].items()
                  if (c["limit"].startswith("<=") and c["value"] > int(c["limit"][2:]))
                  or (c["limit"].startswith(">=") and c["value"] < int(c["limit"][2:])))


@pytest.mark.parametrize("workload", [TRACED, HOSTKEYED, KEYS64K, LIVE, MESH])
def test_sound_run_is_correct(workload):
    out = run("none", workload)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    if workload == LIVE:
        assert out["e2e"]["emit_p50_ms"] > 0
    else:
        assert out["e2e"]["events_per_s"] > 0


@pytest.mark.parametrize("workload", [TRACED, HOSTKEYED, KEYS64K, LIVE, MESH])
def test_control_is_not_correct(workload):
    out = run("control", workload)
    assert out["correct"] is False
    assert "cells_wrong" in failing(out)


@pytest.mark.parametrize("workload,fault", [
    (TRACED, "answer_altered"), (HOSTKEYED, "answer_altered"),
    (KEYS64K, "answer_altered"), (LIVE, "answer_altered"),
    (MESH, "answer_altered"),
    (TRACED, "batch_dropped"), (HOSTKEYED, "batch_dropped"),
    (KEYS64K, "batch_dropped"),
    (MESH, "exchange_left_out"),
])
def test_fault_is_not_correct(workload, fault):
    out = run(fault, workload)
    assert out["correct"] is False, out["compared"]
    assert failing(out), out["compared"]
