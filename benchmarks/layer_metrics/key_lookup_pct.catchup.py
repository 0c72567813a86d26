"""Self time of `flink_tpu.keys.lookup` (the host key dictionary's
`lookup_or_insert` and the ring's `ensure_key_capacity`) as a share of the
traced window. Only host-keyed jobs enter it."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "keys.lookup")
