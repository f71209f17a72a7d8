"""The BMA predictor's share of the chip's bf16 peak: the model operations
of the requests served in the window (one forward pass per bank sample and
request) over window x chips x peak."""
from bench import common


def read(ctx):
    c = ctx["counts"]
    if c["window_s"] <= 0 or c["served_in_window"] == 0:
        return None
    work = common.work_model(ctx["config"])
    flops = work.forward_flops_per_sample(ctx["config"]) * c["samples"]
    return 100.0 * c["served_in_window"] * flops / (
        c["window_s"] * c["chips"] * ctx["peaks"]["bf16_flops"])
