"""Share of the traced window the job's thread spends under no
`flink_tpu.*` and no `benchmark.*` span: what the stage clock does not
name yet."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.dark_pct(ctx)
