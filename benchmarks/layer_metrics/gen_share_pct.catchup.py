"""Share of the timed window inside the benchmark's reader (`poll_batch`
span, the benchmark's own clock): nothing is generated there, so about 1 %."""

from benchmarks import layer_lib


def read(ctx):
    return layer_lib.span_share_pct(ctx, "poll_s")
