"""Self time of `flink_tpu.chain.host` (the host chain's transforms, the key
selector and value function of the host-keyed runner, the column check of
the fused runner) as a share of the traced window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "chain.host")
