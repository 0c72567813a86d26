"""Device time per dispatch of the window program under its `prologue` scope
(the traced chain: its transforms, the key, the value, the key bounds), from
the capture's own scopes (`phase_lib`)."""

from benchmarks import phase_lib


def read(ctx):
    return phase_lib.phase_ms(ctx, "prologue")
