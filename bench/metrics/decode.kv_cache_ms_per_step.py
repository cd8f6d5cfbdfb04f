"""Device time per decode scan step of the ops under the
``decode.kv_cache`` scope: each layer's per-row cache write and the
relayout of its cache ahead of the decode-attention kernel. The layer
scan's own slicing and write-back of the stacked cache are not under it.
Scan steps: the ``n_steps`` of the program's ``serve.decode_block``
spans."""
from bench import program_trace as PT

TRACE = PT.snapshot()   # loaded while the traced run's profile is on disk


def read(ctx):
    steps = sum(s.args.get("n_steps", 0)
                for s in PT.spans(TRACE, "serve.decode_block"))
    return PT.scope_ms_per_step(TRACE, "decode.kv_cache", steps)
