"""Device time per dispatch of the sharded window program under its `exchange`
scope: the keyBy all-to-all of every scan step AND the binning and localising
round it (`collective_ms` is the collectives alone), from the capture's own
scopes (`phase_lib`)."""

from benchmarks import phase_lib


def read(ctx):
    return phase_lib.phase_ms(ctx, "exchange")
