"""Device time per train step of the ops under the ``rrs.aggregate``
scope, averaged over the chips: the robust aggregation of the
per-worker gradient stack on the RRS wire, its all_to_all
(``rrs.all_to_all``), the VRMOM kernel and the all_gather included."""
from bench import program_trace as PT

TRACE = PT.snapshot()   # loaded while the traced run's profile is on disk


def read(ctx):
    return PT.scope_ms_per_step(TRACE, "rrs.aggregate",
                                ctx["rec"]["work"]["steps"])
