"""The prefill programs' share of the chip's bf16 peak: the operations
the prompts require (bench/work.py) over the device time of the runs
that hold the flash-attention kernel (``_flash_bh``)."""
from bench import work

PROGRAMS = ("_flash_bh",)


def read(ctx):
    lens = ctx["rec"]["work"]["prefill_lens"]
    s = ctx["trace"]["programs"]["_flash_bh"]
    if not lens or s <= 0:
        return None
    flops = work.prefill_flops(ctx["conf"], lens)
    return 100.0 * flops / s / ctx["peaks"]["bf16_flops"]
