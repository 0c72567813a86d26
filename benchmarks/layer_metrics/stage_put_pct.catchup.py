"""Self time of `flink_tpu.stage.put` (the `jax.device_put` calls of one
dispatch, `DevicePut*` events included) as a share of the traced window."""

from benchmarks import span_lib


def read(ctx):
    return span_lib.share_pct(ctx, "stage.put")
