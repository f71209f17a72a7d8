"""The whole round's share of the chips' bf16 peak: the model operations of
the rounds completed (three forward passes a sample; recomputation does not
count) over window x chips x peak."""
from bench import common


def read(ctx):
    c = ctx["counts"]
    if c["window_s"] <= 0 or c["rounds"] == 0:
        return None
    work = common.work_model(ctx["config"])
    flops = work.train_flops_per_round(ctx["config"], ctx["traffic"])
    return 100.0 * c["rounds"] * flops / (
        c["window_s"] * c["chips"] * ctx["peaks"]["bf16_flops"])
