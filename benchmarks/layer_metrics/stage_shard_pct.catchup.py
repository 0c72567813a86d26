"""Self time of `flink_tpu.stage.shard` (the staged arrays of one dispatch
dealt over the mesh's source shards, nested in `stage.fill`) as a share of
the traced window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "stage.shard")
