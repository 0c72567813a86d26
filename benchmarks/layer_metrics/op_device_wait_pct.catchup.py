"""Share of the traced window the job's thread is inside transfer and
readback events (`np.asarray(jax.Array)`, `DevicePut*`)."""

from benchmarks import trace_reduce as tr


def read(ctx):
    lo, hi = ctx["trace_window"]
    if not tr.job_thread(ctx["trace"]):
        return None
    return 100.0 * tr.thread_time_in(ctx["trace"], tr.TRANSFER, lo, hi) / (hi - lo)
