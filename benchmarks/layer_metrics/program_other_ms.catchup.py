"""Device time per dispatch of the window program under none of its scopes:
ops the compiler made outside every scoped op, the step's own lines outside
its `with` blocks, and time in which no op ran (`phase_lib`)."""

from benchmarks import phase_lib


def read(ctx):
    return phase_lib.phase_ms(ctx, phase_lib.OTHER)
