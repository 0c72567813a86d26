"""What the phase readers share: the window program's time under each of its
`jax.named_scope` phases, on the device's clock.

The program names its phases itself (`flink_tpu/metrics/device_phases.py`:
`prologue`, `exchange`, `ingest`, `fire`, `purge`) and owns the one reader of
a capture's scopes, `device_phases.phase_table`. The reduced trace the other
readers get (`trace_reduce.load_xplane`) keeps an op's name and times and not
its scope, so this library goes back to the capture, which is still on disk
while the readers run: once per run, the fullest device plane only, the
executions of the configuration's `trace_modules` that start inside the
traced window: the executions `superscan_ms` counts, so a cell's phases and
`program_other_ms` sum to its `superscan_ms`.

A reader returns None, like any reader that finds nothing to read
(`layer_lib.py`), where there is no capture on disk (the JSON fixtures), where
the program has no such module (the parent of the PR that brought it) and
where no op of the window program lies under a scope (the Pallas programs:
one custom call).
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import harness, layer_lib

try:
    from flink_tpu.metrics import device_phases
except ImportError:         # a program older than its phase table
    device_phases = None

OTHER = "other"             # the program's time under no scope


def _read(ctx: Dict) -> Optional[Dict[str, float]]:
    if device_phases is None or ctx.get("trace") is None:
        return None
    plane = layer_lib.fullest(ctx)
    try:
        # the run's capture, found as `harness.Tracer.stop` finds it: the
        # newest `.xplane.pb` under TRACE_DIR
        modules = device_phases.phase_table(
            harness.TRACE_DIR, programs=ctx["cfg"]["trace_modules"],
            planes=[plane], window=ctx["trace_window"]).get(plane, {})
    except FileNotFoundError:
        return None
    runs = sum(m["executions"] for m in modules.values())
    per_run: Dict[str, float] = {}
    for m in modules.values():
        for name, ms in list(m["phases"].items()) + [(OTHER, m["other"])]:
            per_run[name] = per_run.get(name, 0.0) + ms / runs
    # nothing under a scope: the table has nothing to say about this program
    return per_run if len(per_run) > 1 else None


def phase_ms(ctx: Dict, name: str) -> Optional[float]:
    """ms per execution of the window program under the scope `name` (OTHER:
    under no scope) inside the traced window, or None."""
    if "phase_ms" not in ctx:
        ctx["phase_ms"] = _read(ctx)
    return (ctx["phase_ms"] or {}).get(name)
