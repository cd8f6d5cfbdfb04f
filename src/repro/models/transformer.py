"""Decoder-only stack covering the dense / moe / ssm / vlm families.

Layers are homogeneous and scanned (stacked params [L, ...]) so the HLO
stays one-layer-sized; ``cfg.remat`` wraps the scan body in
jax.checkpoint. The hybrid (zamba2) and enc-dec (whisper) families build
on these pieces in hybrid.py / whisper.py.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import attention as A
from . import mamba2 as M
from . import moe as X
from .layers import embed_init, mlp_init, rmsnorm, swiglu


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------

def layer_init(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    p = {}
    if cfg.family == "ssm":
        p["norm_ssm"] = jnp.ones((cfg.d_model,), dt)
        p["ssm"] = M.mamba2_init(key, cfg)
        return p
    k1, k2 = jax.random.split(key)
    p["norm_attn"] = jnp.ones((cfg.d_model,), dt)
    p["attn"] = A.attn_init(k1, cfg)
    p["norm_ffn"] = jnp.ones((cfg.d_model,), dt)
    if cfg.family == "moe":
        p["moe"] = X.moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dt)
    return p


def layer_forward(p, h, cfg, *, positions, window="cfg", make_cache=False,
                  cache_len=None):
    """Full-seq layer. Returns (h, cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        out, cache = M.mamba2_forward(
            p["ssm"], rmsnorm(h, p["norm_ssm"], cfg.norm_eps), cfg,
            return_cache=make_cache)
        return h + out, cache, aux
    attn_out, cache = A.attn_forward(
        p["attn"], rmsnorm(h, p["norm_attn"], cfg.norm_eps), cfg,
        positions=positions, window=window, make_cache=make_cache,
        cache_len=cache_len)
    h = h + attn_out
    hn = rmsnorm(h, p["norm_ffn"], cfg.norm_eps)
    if cfg.family == "moe":
        ffn_out, aux = X.moe_ffn(p["moe"], hn, cfg)
    else:
        ffn_out = swiglu(hn, **p["mlp"])
    return h + ffn_out, cache, aux


def layer_decode(p, h, cfg, cache, *, window="cfg", layer=None):
    """Single-token layer. Returns (h, new_cache, aux). ``layer``: index
    into a layer-stacked KV pool (``attention.attn_decode``)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        out, cache = M.mamba2_decode(
            p["ssm"], rmsnorm(h, p["norm_ssm"], cfg.norm_eps), cfg, cache)
        return h + out, cache, aux
    attn_out, cache = A.attn_decode(
        p["attn"], rmsnorm(h, p["norm_attn"], cfg.norm_eps), cfg, cache,
        window=window, layer=layer)
    h = h + attn_out
    hn = rmsnorm(h, p["norm_ffn"], cfg.norm_eps)
    if cfg.family == "moe":
        ffn_out, aux = X.moe_ffn(p["moe"], hn, cfg)
    else:
        ffn_out = swiglu(hn, **p["mlp"])
    return h + ffn_out, cache, aux


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def init(key, cfg):
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: layer_init(k, cfg))(layer_keys)
    p = {
        "embed": embed_init(ks[1], (cfg.vocab, cfg.d_model), dt),
        "layers": layers,
        "norm_f": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[2], (cfg.d_model, cfg.vocab), dt)
    return p


def _embed_tokens(p, cfg, tokens):
    h = jnp.take(p["embed"], tokens, axis=0)
    return h.astype(jnp.dtype(cfg.compute_dtype))


def embed_inputs(p, cfg, batch):
    """tokens (+ stubbed modality embeddings) -> (h [B,S,D], n_prefix)."""
    h = _embed_tokens(p, cfg, batch["tokens"])
    n_prefix = 0
    if cfg.family == "vlm" and "patches" in batch:
        patches = batch["patches"].astype(h.dtype)
        h = jnp.concatenate([patches, h], axis=1)
        n_prefix = patches.shape[1]
    return h, n_prefix


def unembed(p, cfg, h):
    from .layers import _dot
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    if h.ndim == 3:
        return _dot(h, w)
    return jnp.einsum("...d,dv->...v", h, w)


def forward(p, cfg, batch, *, window="cfg", make_cache=False,
            cache_len=None, return_hidden=False):
    """Train / prefill forward. Returns (logits or hidden, caches)."""
    h, _ = embed_inputs(p, cfg, batch)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    from ..dist import ctx as CTX

    def body(carry, lp):
        h, aux = carry
        h, cache, a = layer_forward(
            lp, h, cfg, positions=positions, window=window,
            make_cache=make_cache, cache_len=cache_len)
        if h.shape[1] >= 8192:
            # Megatron-SP: sequence-shard the residual stream between
            # layers for long sequences (prefill_32k/long_500k) — keeps
            # the scan carry + remat buffers at S/tp per chip. Batch is
            # pinned to the data axes (only the serve path reaches seq
            # >= 8192; train microbatches are shorter).
            h = CTX.constrain(h, ("pod", "data"), "model", None)
        return (h, aux + a), cache

    body_fn = jax.checkpoint(body) if cfg.remat else body
    nb = cfg.remat_block
    if cfg.remat and nb > 1 and cfg.n_layers % nb == 0 and not make_cache:
        # Two-level remat: store only every nb-th layer boundary; the
        # backward recomputes a block then remats per layer within it.
        blocked = jax.tree.map(
            lambda x: x.reshape((cfg.n_layers // nb, nb) + x.shape[1:]),
            p["layers"])

        def block_body(carry, bp):
            out, _ = jax.lax.scan(body_fn, carry, bp)
            return out, None

        (h, aux), _ = jax.lax.scan(jax.checkpoint(block_body),
                                   (h, jnp.zeros((), jnp.float32)), blocked)
        caches = None
    else:
        (h, aux), caches = jax.lax.scan(
            body_fn, (h, jnp.zeros((), jnp.float32)), p["layers"])
    h = rmsnorm(h, p["norm_f"], cfg.norm_eps)
    if return_hidden:
        return h, caches, aux
    return unembed(p, cfg, h), caches, aux


def init_cache(cfg, batch_size: int, max_len: int, window="cfg"):
    window = cfg.sliding_window if window == "cfg" else window
    if cfg.family == "ssm":
        one = M.mamba2_init_cache(cfg, batch_size)
    else:
        one = A.init_cache(cfg, batch_size, max_len, window=window)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), one)


def decode_step(p, cfg, caches, token, *, window="cfg"):
    """One decode step. token: [B] int32. Returns (logits [B,V], caches).

    Attention families carry the K/V pool [L, B, T, Hkv*dh] through the
    layer scan: each layer writes its new rows into the carried pool in
    place and reads its keys from it by index, so no layer's cache is
    sliced out of the stack or written back (DESIGN.md §6/§8). The
    per-layer fill levels and SSM states are small and ride as scan xs.
    """
    h = _embed_tokens(p, cfg, token[:, None])
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        def body(carry, lp_cache):
            h, aux = carry
            lp, cache = lp_cache
            h, new_cache, a = layer_decode(lp, h, cfg, cache, window=window)
            return (h, aux + a), new_cache

        (h, _), new_caches = jax.lax.scan(body, (h, aux0),
                                          (p["layers"], caches))
    else:
        def body(carry, xs):
            h, aux, pool = carry
            lp, layer, pos = xs
            h, pool, a = layer_decode(lp, h, cfg, pool._replace(pos=pos),
                                      window=window, layer=layer)
            return (h, aux + a, pool._replace(pos=None)), pool.pos

        (h, _, pool), pos = jax.lax.scan(
            body, (h, aux0, caches._replace(pos=None)),
            (p["layers"], jnp.arange(cfg.n_layers), caches.pos))
        new_caches = pool._replace(pos=pos)
    h = rmsnorm(h, p["norm_f"], cfg.norm_eps)
    return unembed(p, cfg, h)[:, 0], new_caches


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def chunked_ce(p, cfg, hidden, labels, mask=None):
    """Sequence-chunked cross-entropy: never materializes [B, S, V].

    hidden: [B, S, D]; labels: [B, S] int32; mask: [B, S] float weights.
    """
    B, S, D = hidden.shape
    chunk = min(cfg.loss_chunk, S)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad))) if mask is not None else \
            jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    hc = jnp.moveaxis(hidden.reshape(B, n, chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    def body(acc, inp):
        h, l, m = inp
        logits = unembed(p, cfg, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        loss = jnp.sum((lse - gold) * m)
        return (acc[0] + loss, acc[1] + jnp.sum(m)), None

    # Remat: recompute each chunk's logits in the backward instead of
    # keeping [n_chunks, B, chunk, V] f32 residuals alive.
    body_fn = jax.checkpoint(body) if n > 1 else body
    (tot, cnt), _ = jax.lax.scan(body_fn, (jnp.zeros(()), jnp.zeros(())),
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(p, cfg, batch, *, window="cfg"):
    """Next-token LM loss (+ MoE aux) for one batch of tokens."""
    h, caches, aux = forward(p, cfg, batch, window=window, return_hidden=True)
    tokens = batch["tokens"]
    n_prefix = h.shape[1] - tokens.shape[1]
    h_txt = h[:, n_prefix:] if n_prefix else h
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
    loss = chunked_ce(p, cfg, h_txt, labels, mask)
    return loss + 0.01 * aux
