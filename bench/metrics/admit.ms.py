"""Median wall time of the program's ``serve.admit`` spans: one
admission's prefill, slot write and first token, up to the token on the
host."""
import statistics

from bench import program_trace as PT

TRACE = PT.snapshot()   # loaded while the traced run's profile is on disk


def read(ctx):
    xs = [s.dur_ns for s in PT.spans(TRACE, "serve.admit")]
    return 1e-6 * statistics.median(xs) if xs else None
