"""Self time of the `flink_tpu.table.*` spans (the SQL result path's host
work: `table.output`, the rows of a fire's kept keys built as the
statement's output rows, one span a fire block) as a share of the traced
window. None where the program writes no such span: a job that runs no SQL
output stage, or a program older than the stage."""

from benchmarks import span_lib


def read(ctx):
    times = span_lib.self_times(ctx)
    if times is None:
        return None
    found = [ns for name, ns in times.items()
             if name.startswith(span_lib.PROGRAM + "table.")]
    if not found:
        return None
    lo, hi = ctx["trace_window"]
    return 100.0 * sum(found) / (hi - lo)
