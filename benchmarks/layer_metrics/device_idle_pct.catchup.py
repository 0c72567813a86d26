"""1 - union of device-op intervals over the traced window, on the fullest
device."""

from benchmarks import trace_reduce as tr


def read(ctx):
    lo, hi = ctx["trace_window"]
    busy = tr.busy_by_device(ctx["trace"], lo, hi)
    if not busy:
        return None
    return 100.0 * (1.0 - max(busy.values()) / (hi - lo))
