"""Set-up: process start to the start of the window (host clock)."""


def read(ctx):
    return ctx["rec"]["setup_s"]
