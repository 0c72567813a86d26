"""Self time of `flink_tpu.resolve` (the deferred readback of one dispatch,
its `np.asarray` waits included) as a share of the traced window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "resolve")
