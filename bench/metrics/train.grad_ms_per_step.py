"""Device time per train step of the ops under the ``train.grad`` scope,
averaged over the chips: each worker's forward and backward (backward
ops carry the forward's path inside ``transpose(...)``) and the
simulated attack on its gradient."""
from bench import program_trace as PT

TRACE = PT.snapshot()   # loaded while the traced run's profile is on disk


def read(ctx):
    return PT.scope_ms_per_step(TRACE, "train.grad",
                                ctx["rec"]["work"]["steps"])
