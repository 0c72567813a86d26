"""What per-layer metric readers share. A reader (`layer_metrics/<name>.py`,
`read(ctx)`) gets the run's spans (the benchmark's own clocks), counters
(`result.metrics`, JAX's compile events) and, in a traced run, the reduced
trace. A reader that finds nothing to read returns None and the harness
leaves the metric out of the line."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import trace_reduce as tr


def fullest(ctx: Dict) -> Optional[str]:
    """The device plane that was busy longest in the traced window."""
    lo, hi = ctx["trace_window"]
    busy = tr.busy_by_device(ctx["trace"], lo, hi)
    return max(busy, key=busy.get) if busy else None


def span_share_pct(ctx: Dict, field: str) -> Optional[float]:
    """Share of the timed window inside one of the benchmark's own spans."""
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * getattr(ctx["state"], field) / ctx["window_s"]


def program(ctx: Dict):
    """(executions, ns) of the window program on the device that ran it
    longest, or None where the trace holds no such program."""
    lo, hi = ctx["trace_window"]
    times = tr.program_times(ctx["trace"], ctx["cfg"]["trace_modules"], lo, hi)
    times = {p: v for p, v in times.items() if v[0]}
    if not times:
        return None
    return max(times.values(), key=lambda v: v[1])
