"""Share of its roofline of the decode-attention kernel (its Pallas
call, ``_decode_grouped``): the valid cache of every live row, plus q
and o, at bf16 (bench/work.py), over the kernel's device time. The
kernel is bound by bytes."""
from bench import work

KERNELS = ("_decode_grouped",)


def read(ctx):
    s = ctx["trace"]["kernels"]["_decode_grouped"]
    steps = work.decode_steps(ctx["rec"]["work"]["blocks"])
    if s <= 0 or not steps:
        return None
    conf, pk = ctx["conf"], ctx["peaks"]
    nbytes = sum(work.decode_attention_bytes(conf, kv) for kv in steps)
    flops = sum(work.decode_attention_flops(conf, kv) for kv in steps)
    share, _ = work.roofline_share(flops, nbytes, s, pk["bf16_flops"],
                                   pk["hbm_bytes_per_s"])
    return share
