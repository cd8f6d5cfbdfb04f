"""Output tokens delivered in the window over the window (host clock)."""


def read(ctx):
    rec = ctx["rec"]
    return rec["tokens"] / rec["elapsed_s"]
