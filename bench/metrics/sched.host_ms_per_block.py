"""Host time of the scheduler per decode block: the summed length of the
program's ``serve.step`` spans less the part that the ``serve.wait``
spans inside them cover (the host blocked on a device result), over the
``serve.decode_block`` spans of the traced window."""
from bench import program_trace as PT

TRACE = PT.snapshot()   # loaded while the traced run's profile is on disk


def read(ctx):
    blocks = len(PT.spans(TRACE, "serve.decode_block"))
    if not blocks:
        return None
    return 1e-6 * PT.exclusive_ns(TRACE, "serve.step", "serve.wait") / blocks
