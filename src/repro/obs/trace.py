"""Profiler spans: name the program's work in ``jax.profiler`` traces.

Two helpers for the two sides of the jit boundary:

* ``trace_span(name, **args)`` — host span
  (``jax.profiler.TraceAnnotation``) on the calling thread's line of
  the trace. Keyword arguments become the event's stats (``uid``,
  ``slot``, ...). Spans nest by containment on one thread. With no
  profiler active a span costs one flag check, so the serving path
  keeps them on.
* ``named_span(name)`` — ``jax.named_scope``: a component of the name
  stack of every op staged under it. It reaches the compiled HLO as
  ``metadata={op_name=".../<name>/..."}``, and the TPU profiler shows
  that path beside each device op. Backward ops keep the forward's
  path inside ``transpose(...)``. It exists only at trace time and
  costs nothing at run time.

Host spans (``serve/scheduler.py``, ``serve/engine.py``):
``serve.step`` (args ``active``, ``queued``) holds one scheduler cycle;
``serve.admit`` (``uid``, ``slot``, ``prompt_len``, ``queue_wait_us``)
one admission, holding ``serve.prefill`` (``prompt_len``),
``serve.write_slot`` (``slot``) and ``serve.first_token``;
``serve.decode_block`` (``n_steps``) the decode loop's dispatch;
``serve.evict`` (``uid``, ``slot``) a retirement; ``serve.wait``
(``what``) each point where the host blocks on a device result.

Device scopes: ``train.grad``, ``rrs.aggregate`` (holding
``rrs.all_to_all``) and ``train.optimizer`` in the train step;
``decode.kv_cache`` around the in-place per-row write into the K/V
pool (and, for a cache length off the decode kernel's kv tile only, the
padded copy of the layer);
``kernels.aggregate``, ``kernels.aggregate_sample``,
``kernels.decode_attention``, ``serve.decode_scan`` and
``consensus.round_loop``.

Each jitted serve program is named by its function, so the profiler's
module line reads ``jit_serve_prefill``, ``jit_serve_first_token``,
``jit_serve_decode_block``, ``jit_serve_stack_flatten`` and
``jit_serve_decode_step``; the train step is ``jit_train_step``.
"""
from __future__ import annotations

import jax

__all__ = ["trace_span", "named_span"]


def trace_span(name: str, **args):
    """Host profiler span; ``args`` show as the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def named_span(name: str):
    """In-trace scope: names the ops staged under it (jax.named_scope)."""
    return jax.named_scope(name)
