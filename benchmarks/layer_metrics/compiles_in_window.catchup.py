"""Backend compiles and persistent-cache loads that JAX's monitoring events
stamp inside the timed window (the benchmark's own listener and clock). Must
read 0: every program of the window was compiled in set-up."""


def read(ctx):
    return float(ctx["compiles_in_window"])
