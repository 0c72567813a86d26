"""Self time of `flink_tpu.fire.reduce` (a fire's rows reduced over keys, as
columns, on their way into a window over the whole stream) as a share of the
traced window. None where the program writes no such span: a job with no
such window behind its fused operator, or a program older than the stage."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "fire.reduce")
