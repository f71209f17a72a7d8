"""Median host-clock time of one ``ClassifyEngine.step`` in the window:
admit, predict, and the transfer of the probabilities that ends it."""


def read(ctx):
    return ctx["counts"].get("step_ms_median")
