"""The train step's share of the chips' bf16 peak: 3x the forward's
operations over the global batch (bench/work.py) per step, over the
traced window's length per step, all chips counted."""
from bench import work


def read(ctx):
    t, rec, mix = ctx["trace"], ctx["rec"], ctx["mix"]
    steps = rec["work"]["steps"]
    if not steps or t["chips"] == 0:
        return None
    flops = steps * work.train_flops(ctx["conf"], mix["global_batch"],
                                     mix["seq"])
    return 100.0 * flops / t["window_s"] / (
        t["chips"] * ctx["peaks"]["bf16_flops"])
