"""Self time of `flink_tpu.normalize` (the step normaliser's `push` and
`advance`: slices, late records, fire planning) as a share of the traced
window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "normalize")
