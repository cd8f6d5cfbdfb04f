"""Device time of the decode loop per scan step over the traced window.
The decode loop is the program runs that hold the decode-attention
kernel (``_decode_grouped``)."""

PROGRAMS = ("_decode_grouped",)


def read(ctx):
    t, rec = ctx["trace"], ctx["rec"]
    steps = len(rec["work"]["blocks"]) * ctx["mix"]["decode_block"]
    s = t["programs"]["_decode_grouped"]
    return 1e3 * s / steps if steps and s > 0 else None
