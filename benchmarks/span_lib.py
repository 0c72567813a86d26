"""What the span readers share. The program's stage clock
(`flink_tpu/metrics/task_io.py`) writes a `flink_tpu.<stage>` span on the
job's thread for every stage it enters, on the profiler's own clock; the
benchmark's reader and sink write `benchmark.poll_batch` /
`benchmark.sink_write` there too.

A span's SELF time is its duration less the part nested `flink_tpu.*` /
`benchmark.*` spans cover. Events the profiler emits by itself
(`np.asarray`, `DevicePut*`, `PjitFunction*`) are no spans of ours: their
time stays with the span that encloses them. So the self times of all spans
and the time under no span (`dark`) partition the job thread's traced window.

A program without the stage clock (the parent of the PR that brought it)
writes no `flink_tpu.*` span: every reader then returns None.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import trace_reduce as tr

PROGRAM = "flink_tpu."
BENCHMARK = "benchmark."


def self_times(ctx: Dict) -> Optional[Dict[str, int]]:
    """{span name: ns of self time inside the traced window} over the job
    thread's `flink_tpu.*` and `benchmark.*` spans; None where the thread
    holds no `flink_tpu.*` span at all."""
    spans = [e for e in tr.job_thread(ctx["trace"])
             if e[0].startswith((PROGRAM, BENCHMARK))]
    if not any(name.startswith(PROGRAM) for name, _a, _b in spans):
        return None
    lo, hi = ctx["trace_window"]
    out: Dict[str, int] = {}
    for name, a, b in tr.innermost_segments(spans):
        part = min(b, hi) - max(a, lo)
        if part > 0:
            out[name] = out.get(name, 0) + part
    return out


def share_pct(ctx: Dict, *stages: str) -> Optional[float]:
    """Self time of the named stages' spans as a share of the traced
    window; None where none of them occurs."""
    times = self_times(ctx)
    if times is None:
        return None
    found = [times[PROGRAM + s] for s in stages if PROGRAM + s in times]
    if not found:
        return None
    lo, hi = ctx["trace_window"]
    return 100.0 * sum(found) / (hi - lo)


def dark_pct(ctx: Dict) -> Optional[float]:
    """Share of the traced window the job's thread spends under no
    `flink_tpu.*` and no `benchmark.*` span."""
    times = self_times(ctx)
    if times is None:
        return None
    lo, hi = ctx["trace_window"]
    return 100.0 * (hi - lo - sum(times.values())) / (hi - lo)
