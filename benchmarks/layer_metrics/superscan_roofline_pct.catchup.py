"""Least time for the algorithm's bytes of the traced stretch
(`roofline.py`, over the peak HBM bytes/s of `peaks.json`) divided by the
window program's device time in it. Memory-bound."""

from benchmarks import layer_lib, roofline


def read(ctx):
    prog = layer_lib.program(ctx)
    if prog is None:
        return None
    st = ctx["state"]
    w0, w1 = ctx["trace_wall"]
    events = st.batch * sum(1 for t in st.handed_at if w0 <= t < w1)
    fires = sum(len(ends) for t, _p, _n, ends in ctx["arrivals"] if w0 <= t < w1)
    devices = max(int(ctx["counters"].get("mesh_devices") or 1), 1)
    nbytes = roofline.algorithm_bytes(ctx["cfg"], events, fires, devices)
    if nbytes <= 0:
        return None
    return 100.0 * roofline.least_seconds(nbytes, ctx["peaks"]) / (prog[1] / 1e9)
