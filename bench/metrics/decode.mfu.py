"""The decode loop's share of the chip's bf16 peak: the operations the
delivered tokens require (bench/work.py) over the device time of the
program runs that hold the decode-attention kernel."""
from bench import work

PROGRAMS = ("_decode_grouped",)


def read(ctx):
    s = ctx["trace"]["programs"]["_decode_grouped"]
    steps = work.decode_steps(ctx["rec"]["work"]["blocks"])
    if s <= 0 or not steps:
        return None
    flops = sum(work.decode_flops(ctx["conf"], kv) for kv in steps)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"]
