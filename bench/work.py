"""Operations and bytes that each kernel and each step requires, from
shapes alone: what the algorithm needs, whatever implements it.

Counts follow the usual conventions: 2 operations per multiply-add;
a forward pass costs 2 operations per weight of every matmul per token
(the tied unembedding included) plus causal attention over the valid
context; training costs three times the forward (forward, and the
backward's two products), with no recomputation counted.
"""
from __future__ import annotations

from bench import families


def matmul_params(conf: dict) -> int:
    """Weights that take part in a matmul for each token, by the
    configuration's family."""
    return families.get(conf).matmul_params(conf)


def n_params(conf: dict) -> int:
    """Every weight of the model: what a data-parallel worker's
    gradient holds."""
    return families.get(conf).n_params(conf)


def attn_flops(conf: dict, n_keys_total: int) -> float:
    """QK^T and PV for queries that see ``n_keys_total`` keys in all."""
    L = conf["num_hidden_layers"]
    H, dh = conf["num_attention_heads"], conf["head_dim"]
    return 2.0 * 2.0 * L * H * dh * n_keys_total


def causal_keys(S: int) -> int:
    """Keys seen by all S queries of a causal prompt: S(S+1)/2."""
    return S * (S + 1) // 2


def forward_flops(conf: dict, tokens: int, keys_seen: int) -> float:
    return 2.0 * matmul_params(conf) * tokens + attn_flops(conf, keys_seen)


def prefill_flops(conf: dict, prompt_lens) -> float:
    """Every prompt's causal forward (the logits of every position are
    not required, only the last: one unembedding row per prompt)."""
    V, D = conf["vocab_size"], conf["hidden_size"]
    p = matmul_params(conf) - V * D
    return sum(2.0 * p * S + 2.0 * V * D
               + attn_flops(conf, causal_keys(S)) for S in prompt_lens)


def decode_flops(conf: dict, kv_lens) -> float:
    """Decode steps, one token each, over ``kv_lens`` keys each."""
    return sum(forward_flops(conf, 1, k) for k in kv_lens)


def train_flops(conf: dict, batch: int, seq: int) -> float:
    """One training step: 3x the forward over batch x seq tokens."""
    return 3.0 * batch * forward_flops(conf, seq, causal_keys(seq))


def kv_bytes_per_key(conf: dict, itemsize: int = 2) -> int:
    """K and V of one position over all layers."""
    return (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"]
            * conf["head_dim"] * itemsize)


def decode_attention_bytes(conf: dict, kv_lens, itemsize: int = 2) -> float:
    """The valid cache each decode step reads, plus q and o."""
    L, H, dh = (conf["num_hidden_layers"], conf["num_attention_heads"],
                conf["head_dim"])
    qo = 2 * L * H * dh * itemsize
    return float(sum(k * kv_bytes_per_key(conf, itemsize) + qo
                     for k in kv_lens))


def decode_attention_flops(conf: dict, kv_lens) -> float:
    return attn_flops(conf, sum(kv_lens))


def robust_tail_bytes(m: int, rows: int, vocab: int,
                      itemsize: int = 2) -> float:
    """The [m, rows, V] logit stack read once at the dtype the model
    emits, plus one int32 token per row."""
    return float(m * rows * vocab * itemsize + 4 * rows)


def aggregate_bytes(n_workers: int, n_coords: int,
                    itemsize: int = 2) -> float:
    """The [W, C] gradient stack read once and the [C] aggregate written
    once, at the gradient dtype."""
    return float((n_workers + 1) * n_coords * itemsize)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float):
    """-> (share of the roofline in %, the bound: 'compute' or 'bytes')."""
    t_c, t_b = flops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_c >= t_b else "bytes"
    return 100.0 * max(t_c, t_b) / seconds, bound


def decode_steps(blocks):
    """Per-block rows [(keys written, tokens delivered)] -> one list per
    decode step of the key counts its live rows attend over."""
    steps = []
    for rows in blocks:
        n = max((new for _, new in rows), default=0)
        for j in range(n):
            steps.append([k + j + 1 for k, new in rows if j < new])
    return steps
