"""The Qwen3 dense family (model_type ``qwen3``): the published
config.json mapped to the program's ArchConfig, the weight tree's leaf
shapes, and its weight counts."""
from __future__ import annotations


def arch_config(conf: dict):
    """The program's ArchConfig for a Qwen3 config.json."""
    from repro.configs.base import ArchConfig

    if conf["model_type"] != "qwen3" or conf["hidden_act"] != "silu":
        raise SystemExit(f"bench: no mapping for {conf['model_type']!r}")
    if conf["attention_bias"] or conf["use_sliding_window"]:
        raise SystemExit("bench: attention bias / sliding window unmapped")
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        qk_norm=True, rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=float(conf["rms_norm_eps"]),
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"],
        source=conf["source"])


def shapes(conf: dict) -> dict:
    """Leaf shapes of the dense Qwen3 tree for a config.json."""
    L, D = conf["num_hidden_layers"], conf["hidden_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh, F, V = conf["head_dim"], conf["intermediate_size"], conf["vocab_size"]
    return {
        "embed": (V, D),
        "layers": {
            "norm_attn": (L, D),
            "attn": {"wq": (L, D, H, dh), "wk": (L, D, Hkv, dh),
                     "wv": (L, D, Hkv, dh), "wo": (L, H, dh, D),
                     "q_norm": (L, dh), "k_norm": (L, dh)},
            "norm_ffn": (L, D),
            "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                    "w_down": (L, F, D)},
        },
        "norm_f": (D,),
    }


def matmul_params(conf: dict) -> int:
    """Weights that take part in a matmul for each token: the layers'
    projections and MLP, and the unembedding (tied to the embedding,
    whose lookup is no matmul)."""
    L, D = conf["num_hidden_layers"], conf["hidden_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh, F, V = conf["head_dim"], conf["intermediate_size"], conf["vocab_size"]
    attn = D * H * dh * 2 + D * Hkv * dh * 2
    mlp = 3 * D * F
    return L * (attn + mlp) + V * D


def n_params(conf: dict) -> int:
    """Every leaf of ``shapes``: the matmul weights and the norm scales
    (the tied embedding counted once)."""
    L, D, dh = (conf["num_hidden_layers"], conf["hidden_size"],
                conf["head_dim"])
    return matmul_params(conf) + L * (2 * D + 2 * dh) + D
