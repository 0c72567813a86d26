"""The part of `collective_ms` during which no other op ran on that device:
what the exchange adds to the program's time, the rest hides behind the
ingest. Never more than `collective_ms`."""

from benchmarks import mesh_lib


def read(ctx):
    return mesh_lib.collective_ms(ctx, mesh_lib.EXPOSED)
