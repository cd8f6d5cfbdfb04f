"""p90 over every request completed in the window of its time per
output token after the first, (t_last - t_first) / (n_out - 1)."""
from bench.common import percentile


def read(ctx):
    xs = ctx["rec"]["tpot_s"]
    return 1e3 * percentile(xs, 90) if xs else None
