"""Device idle time under host events other than transfer and readback, as
a share of the traced window (fullest device; gaps attributed to the
innermost event of the job's thread)."""

from benchmarks import layer_lib
from benchmarks import trace_reduce as tr


def read(ctx):
    plane = layer_lib.fullest(ctx)
    if plane is None or not tr.job_thread(ctx["trace"]):
        return None
    lo, hi = ctx["trace_window"]
    by_name, _idle = tr.attribute_gaps(ctx["trace"], plane, lo, hi)
    host = sum(v for k, v in by_name.items() if not tr.TRANSFER.search(k))
    return 100.0 * host / (hi - lo)
