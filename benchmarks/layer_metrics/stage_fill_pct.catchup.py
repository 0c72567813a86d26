"""Self time of `flink_tpu.stage.fill` (the host half of staging one
dispatch: the plan and the copy of its batches into the staging arrays) as a
share of the traced window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "stage.fill")
