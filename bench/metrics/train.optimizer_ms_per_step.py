"""Device time per train step of the ops under the ``train.optimizer``
scope, averaged over the chips: the optimizer's update of the sharded
weights and its state."""
from bench import program_trace as PT

TRACE = PT.snapshot()   # loaded while the traced run's profile is on disk


def read(ctx):
    return PT.scope_ms_per_step(TRACE, "train.optimizer",
                                ctx["rec"]["work"]["steps"])
