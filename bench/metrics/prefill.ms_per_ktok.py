"""Device time of the prefill programs per thousand prompt tokens
prefilled in the traced window. The prefill programs are the runs that
hold the flash-attention kernel (``_flash_bh``)."""

PROGRAMS = ("_flash_bh",)


def read(ctx):
    lens = ctx["rec"]["work"]["prefill_lens"]
    s = ctx["trace"]["programs"]["_flash_bh"]
    return 1e3 * s / (sum(lens) / 1e3) if lens and s > 0 else None
