"""Device time per dispatch of the window program under its `purge` scope
(expired slices reset), from the capture's own scopes (`phase_lib`)."""

from benchmarks import phase_lib


def read(ctx):
    return phase_lib.phase_ms(ctx, "purge")
