"""Device time per dispatch of the window program under its `ingest` scope
(records into the slice ring: the step's histogram, its fold into the ring, a
scatter), from the capture's own scopes (`phase_lib`)."""

from benchmarks import phase_lib


def read(ctx):
    return phase_lib.phase_ms(ctx, "ingest")
