"""Unified model API dispatching on cfg.family.

    init(key, cfg)                      -> params
    loss(params, cfg, batch)            -> scalar LM loss
    prefill(params, cfg, batch)         -> (logits [B,S,V], caches)
    init_cache(cfg, batch, max_len)     -> caches (for decode-only entry)
    decode_step(params, cfg, caches, token) -> (logits [B,V], caches)

``window`` semantics: "cfg" uses cfg.sliding_window; an int overrides it
(the long_500k SWA variant for dense archs — DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import hybrid, transformer, whisper

_TRANSFORMER_FAMILIES = ("dense", "moe", "ssm", "vlm")


def init(key, cfg: ArchConfig):
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.init(key, cfg)
    if cfg.family == "hybrid":
        return hybrid.init(key, cfg)
    if cfg.family == "encdec":
        return whisper.init(key, cfg)
    raise ValueError(cfg.family)


def abstract_init(cfg: ArchConfig, seed: int = 0):
    """Shape-only params (no allocation) for the dry-run."""
    return jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(seed))


def loss(params, cfg: ArchConfig, batch, window="cfg"):
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.lm_loss(params, cfg, batch, window=window)
    if cfg.family == "hybrid":
        h, _, aux = hybrid.forward(params, cfg, batch, return_hidden=True)
    elif cfg.family == "encdec":
        h, _, aux = whisper.forward(params, cfg, batch, return_hidden=True)
    else:
        raise ValueError(cfg.family)
    tokens = batch["tokens"]
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
    p_like = {"embed": params["embed"]}
    ce = transformer.chunked_ce(p_like, cfg, h, labels, mask)
    return ce + 0.01 * aux


def prefill(params, cfg: ArchConfig, batch, window="cfg", cache_len=None,
            last_only: bool = False):
    """``last_only``: return logits for the final position only [B, 1, V]
    (the serving path — avoids materializing [B, S, V])."""
    kw = dict(make_cache=True, cache_len=cache_len, return_hidden=True)
    if cfg.family in _TRANSFORMER_FAMILIES:
        h, caches, _ = transformer.forward(params, cfg, batch,
                                           window=window, **kw)
    elif cfg.family == "hybrid":
        h, caches, _ = hybrid.forward(params, cfg, batch, **kw)
    elif cfg.family == "encdec":
        h, caches, _ = whisper.forward(params, cfg, batch, **kw)
    else:
        raise ValueError(cfg.family)
    if last_only:
        h = h[:, -1:]
    if cfg.family in _TRANSFORMER_FAMILIES:
        logits = transformer.unembed(params, cfg, h)
    else:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    return logits, caches


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int, window="cfg"):
    window = cfg.sliding_window if window == "cfg" else window
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.init_cache(cfg, batch_size, max_len, window=window)
    if cfg.family == "hybrid":
        return hybrid.init_cache(cfg, batch_size, max_len, window=window)
    if cfg.family == "encdec":
        return whisper.init_cache(cfg, batch_size, max_len, window=window)
    raise ValueError(cfg.family)


def decode_step(params, cfg: ArchConfig, caches, token, window="cfg"):
    """-> (logits [B, V] in the model's dtype, caches).

    The logits leave behind an optimization barrier: XLA may otherwise
    fuse a consumer into the unembedding and keep the dot's excess f32
    precision there, so argmax, a robust aggregate or an attack would
    each read differently rounded values, and the served tokens would
    depend on what consumes the logits (seen on TPU v5e, not on CPU).
    """
    if cfg.family in _TRANSFORMER_FAMILIES:
        logits, caches = transformer.decode_step(params, cfg, caches, token,
                                                 window=window)
    elif cfg.family == "hybrid":
        logits, caches = hybrid.decode_step(params, cfg, caches, token)
    elif cfg.family == "encdec":
        logits, caches = whisper.decode_step(params, cfg, caches, token)
    else:
        raise ValueError(cfg.family)
    return jax.lax.optimization_barrier(logits), caches


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_param_count(params, cfg: ArchConfig) -> int:
    """MoE-aware active parameter count (for MODEL_FLOPS = 6*N_active*D)."""
    total = param_count(params)
    if cfg.moe is None:
        return total
    m = cfg.moe
    expert_leaves = 0
    layers = params.get("layers", {})
    moe_p = layers.get("moe", None) if isinstance(layers, dict) else None
    if moe_p is not None:
        for name in ("w_gate", "w_up", "w_down"):
            expert_leaves += moe_p[name].size
    inactive = expert_leaves * (1 - m.top_k / m.n_experts)
    return int(total - inactive)
