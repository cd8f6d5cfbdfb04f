"""Share of its roofline of the fused tail kernel (its Pallas call,
``_tail_3d``): the [m, rows, V] logit stack of the live rows read once
at bf16, the dtype the model emits, plus a token per row
(bench/work.py), over the kernel's device time. Bound by bytes."""
from bench import work

KERNELS = ("_tail_3d",)


def read(ctx):
    s = ctx["trace"]["kernels"]["_tail_3d"]
    steps = work.decode_steps(ctx["rec"]["work"]["blocks"])
    if s <= 0 or not steps:
        return None
    m, V = ctx["mix"]["robust"]["m"], ctx["conf"]["vocab_size"]
    nbytes = sum(work.robust_tail_bytes(m, len(kv), V) for kv in steps)
    share, _ = work.roofline_share(0.0, nbytes, s, ctx["peaks"]["bf16_flops"],
                                   ctx["peaks"]["hbm_bytes_per_s"])
    return share
