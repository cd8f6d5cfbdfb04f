"""Share of its roofline of the VRMOM aggregation kernel on the RRS wire
(its Pallas call, ``_agg_2d``): each chip's [W, C/W] shard of the
gradient stack read once and its C/W aggregate written once, at the
gradient dtype bf16 (bench/work.py), over the kernel's device time per
chip. Bound by bytes."""
from bench import work

KERNELS = ("_agg_2d",)


def read(ctx):
    t, mix = ctx["trace"], ctx["mix"]
    steps = ctx["rec"]["work"]["steps"]
    s = t["kernels"]["_agg_2d"]
    if not steps or s <= 0:
        return None
    W = mix["data"]
    nbytes = steps * work.aggregate_bytes(W, work.n_params(ctx["conf"]) // W)
    share, _ = work.roofline_share(0.0, nbytes, s,
                                   ctx["peaks"]["bf16_flops"],
                                   ctx["peaks"]["hbm_bytes_per_s"])
    return share
