"""Device time of the window program per dispatch (trace; the module names
the configuration's file lists under `trace_modules`)."""

from benchmarks import layer_lib


def read(ctx):
    prog = layer_lib.program(ctx)
    return None if prog is None else prog[1] / prog[0] / 1e6
