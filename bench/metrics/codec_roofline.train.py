"""Encode and decode against their roofline: the least time the chip's HBM
needs for the work's ideal bytes (``bench/work/codec.py``) over the summed
device time of the delta-pack and unpack kernels, per device. The bytes
bound it; the kernels do next to no arithmetic."""
import re

from bench import common
from bench.work import codec

KERNELS = re.compile(r"pack", re.IGNORECASE)


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    kernel_s = sum(s for name, s in t["op_time"].items()
                   if KERNELS.search(name))
    if kernel_s <= 0:
        return None
    cfg, tr, c = ctx["config"], ctx["traffic"], ctx["counts"]
    sizes = common.work_model(cfg).leaf_sizes(cfg)
    per_node = codec.ideal_bytes_per_node(sizes, tr["ratio"], tr["block"])
    nodes_per_device = tr["nodes"] / c["chips"]
    ideal_s = (per_node * nodes_per_device * c["rounds"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * ideal_s / kernel_s
