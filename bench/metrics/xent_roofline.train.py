"""The cross-entropy over the tied head against its roofline: the head's
operations in the forward and backward passes (``bench/work/<reference>.py``
``xent_flops_per_round``, 6 d V a token; recomputation not counted) at the
chip's bf16 peak, over the device time under the scope ``xent``. The
operations bound it: the logits need not leave the chip."""
from bench import common, lm_scopes


def read(ctx):
    sec = lm_scopes.scope_seconds(ctx, "xent")
    if not sec:
        return None
    cfg, tr, c = ctx["config"], ctx["traffic"], ctx["counts"]
    flops = common.work_model(cfg).xent_flops_per_round(cfg, tr)
    ideal_s = flops * c["rounds"] / (c["chips"] * ctx["peaks"]["bf16_flops"])
    return 100.0 * ideal_s / sec
