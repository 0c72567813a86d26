"""Share of the timed window inside the benchmark's sink (`write_batch`
span, the benchmark's own clock)."""

from benchmarks import layer_lib


def read(ctx):
    return layer_lib.span_share_pct(ctx, "sink_s")
