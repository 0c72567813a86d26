"""Result rows that reached the benchmark's sink per second of the timed
window."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["state"].rows_in_window / ctx["window_s"]
