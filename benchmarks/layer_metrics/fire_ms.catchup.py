"""Device time per dispatch of the window program under its `fire` scope (the
due windows read out of the ring into the fire buffer), from the capture's
own scopes (`phase_lib`)."""

from benchmarks import phase_lib


def read(ctx):
    return phase_lib.phase_ms(ctx, "fire")
