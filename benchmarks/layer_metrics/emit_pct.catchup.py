"""Self time of `flink_tpu.emit` (one per fire: the rows of a fired window
built on the host) plus `flink_tpu.drain` (the runner's record array and
the push downstream, less the sink's own span) as a share of the traced
window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "emit", "drain")
