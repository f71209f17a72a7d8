"""The BMA predict program against its roofline: per step, the least time
for reading the whole bank (samples x 4 p bytes) from HBM, or for the
step's operations at the bf16 peak where that is longer, over the device
time of the predict program per step, from the trace."""
import re

from bench import common

PROGRAM = re.compile(r"_predict")


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    names = [n for n in t["module_time"] if PROGRAM.search(n)]
    runs = sum(t["module_count"][n] for n in names)
    dev_s = sum(t["module_time"][n] for n in names)
    if runs <= 0 or dev_s <= 0:
        return None
    cfg, tr, c, pk = ctx["config"], ctx["traffic"], ctx["counts"], ctx["peaks"]
    work = common.work_model(cfg)
    bank_bytes = c["samples"] * 4 * work.params(cfg)
    flops = work.forward_flops_per_sample(cfg) * c["samples"] * tr["slots"]
    ideal = max(bank_bytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * ideal * runs / dev_s
