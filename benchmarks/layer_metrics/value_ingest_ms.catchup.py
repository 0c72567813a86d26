"""Device time per dispatch of the window program under the nested scopes of
its `ingest` phase that do the aggregate's VALUE fields' work
(`ingest/hist.value`, `ingest/fold.value`, `ingest/scatter.value`: a sum's
weighted histogram and its fold into the sum ring, a min's scatter), from the
capture's own scopes: the part of `ingest_ms` a value column adds to counting.

`phase_lib` hands on the five phases and not their nested rows, so this
reader goes back to the capture itself, as `phase_lib` does (the same plane,
modules and window: a third reading of the file in a traced run of a cell
that lists this metric; PERF.md section 7, item 1b). None where there is no
capture, where the program is older than its phase table, and where no op
lies under such a scope (a count-only job; a program older than the names).
"""

from benchmarks import harness, layer_lib

try:
    from flink_tpu.metrics import device_phases
except ImportError:         # a program older than its phase table
    device_phases = None

ROWS = ("ingest/hist.value", "ingest/fold.value", "ingest/scatter.value")


def read(ctx):
    if device_phases is None or ctx.get("trace") is None:
        return None
    plane = layer_lib.fullest(ctx)
    try:
        modules = device_phases.phase_table(
            harness.TRACE_DIR, programs=ctx["cfg"]["trace_modules"],
            planes=[plane], window=ctx["trace_window"]).get(plane, {})
    except FileNotFoundError:
        return None
    runs = sum(m["executions"] for m in modules.values())
    found = [m["sub"][row] for m in modules.values() for row in ROWS
             if row in m["sub"]]
    return sum(found) / runs if found and runs else None
