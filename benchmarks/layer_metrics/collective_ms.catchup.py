"""Device time inside collective ops (the keyBy all-to-all of the sharded
scan) per dispatch of the window program, on the device where it is largest
(`trace_reduce.collective_times` over the traced window)."""

from benchmarks import mesh_lib


def read(ctx):
    return mesh_lib.collective_ms(ctx, mesh_lib.IN_COLLECTIVES)
