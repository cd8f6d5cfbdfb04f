"""Device time of the fused robust aggregate-and-sample kernel (its
Pallas call, ``_tail_3d``) per decode step. The attack and the cast of
the logit stack to f32 before it are not counted."""

KERNELS = ("_tail_3d",)


def read(ctx):
    t, rec = ctx["trace"], ctx["rec"]
    steps = len(rec["work"]["blocks"]) * ctx["mix"]["decode_block"]
    s = t["kernels"]["_tail_3d"]
    return 1e3 * s / steps if steps and s > 0 else None
