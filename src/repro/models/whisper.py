"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

The mel-spectrogram + conv feature extractor is a STUB per the brief:
``batch['frames']`` supplies precomputed frame embeddings
[B, n_frames, d_model]. Sinusoidal positions, pre-norm transformer,
no RoPE (cfg.rope=False). Decoder layers: causal self-attn (cached) +
cross-attn over the encoder output (cross K/V precomputed at prefill) +
MLP. Both stacks are scanned.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import attention as A
from .layers import embed_init, mlp_init, rmsnorm, sinusoidal_positions, swiglu


class EncDecCache(NamedTuple):
    self_kv: any   # [L, ...] decoder self-attention caches
    cross_kv: any  # [L, ...] precomputed cross K/V


def _enc_layer_init(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "norm_attn": jnp.ones((cfg.d_model,), dt),
        "attn": A.attn_init(k1, cfg),
        "norm_ffn": jnp.ones((cfg.d_model,), dt),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dt),
    }


def _dec_layer_init(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm_self": jnp.ones((cfg.d_model,), dt),
        "self": A.attn_init(k1, cfg),
        "norm_cross": jnp.ones((cfg.d_model,), dt),
        "cross": A.attn_init(k2, cfg),
        "norm_ffn": jnp.ones((cfg.d_model,), dt),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dt),
    }


def init(key, cfg):
    ks = jax.random.split(key, 3)
    enc_keys = jax.random.split(ks[0], cfg.encoder.n_layers)
    dec_keys = jax.random.split(ks[1], cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "embed": embed_init(ks[2], (cfg.vocab, cfg.d_model), dt),
        "enc_layers": jax.vmap(lambda k: _enc_layer_init(k, cfg))(enc_keys),
        "dec_layers": jax.vmap(lambda k: _dec_layer_init(k, cfg))(dec_keys),
        "norm_enc": jnp.ones((cfg.d_model,), dt),
        "norm_f": jnp.ones((cfg.d_model,), dt),
    }


def encode(p, cfg, frames):
    """frames: [B, F, D] stubbed frontend embeddings -> [B, F, D]."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = frames.astype(dt) + sinusoidal_positions(
        frames.shape[1], cfg.d_model, dt)[None]

    def body(h, lp):
        hn = rmsnorm(h, lp["norm_attn"], cfg.norm_eps)
        out, _ = A.attn_forward(lp["attn"], hn, cfg, positions=None,
                                causal=False, window=None)
        h = h + out
        h = h + swiglu(rmsnorm(h, lp["norm_ffn"], cfg.norm_eps), **lp["mlp"])
        return h, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, _ = jax.lax.scan(body_fn, h, p["enc_layers"])
    return rmsnorm(h, p["norm_enc"], cfg.norm_eps)


def _dec_layer(lp, h, cfg, enc_out, *, make_cache=False, cache_len=None):
    hn = rmsnorm(h, lp["norm_self"], cfg.norm_eps)
    out, self_cache = A.attn_forward(lp["self"], hn, cfg, positions=None,
                                     causal=True, window=None,
                                     make_cache=make_cache,
                                     cache_len=cache_len)
    h = h + out
    hn = rmsnorm(h, lp["norm_cross"], cfg.norm_eps)
    out, _ = A.attn_forward(lp["cross"], hn, cfg, positions=None,
                            causal=False, window=None, kv_x=enc_out)
    h = h + out
    h = h + swiglu(rmsnorm(h, lp["norm_ffn"], cfg.norm_eps), **lp["mlp"])
    cross_cache = A.make_cross_cache(lp["cross"], enc_out, cfg) \
        if make_cache else None
    return h, self_cache, cross_cache


def forward(p, cfg, batch, *, make_cache=False, cache_len=None,
            return_hidden=False):
    enc_out = encode(p, cfg, batch["frames"])
    tokens = batch["tokens"]
    dt = jnp.dtype(cfg.compute_dtype)
    h = jnp.take(p["embed"], tokens, axis=0).astype(dt)
    h = h + sinusoidal_positions(h.shape[1], cfg.d_model, dt)[None]

    def body(h, lp):
        h, sc, cc = _dec_layer(lp, h, cfg, enc_out, make_cache=make_cache,
                               cache_len=cache_len)
        return h, (sc, cc)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, (self_caches, cross_caches) = jax.lax.scan(body_fn, h, p["dec_layers"])
    h = rmsnorm(h, p["norm_f"], cfg.norm_eps)
    caches = EncDecCache(self_caches, cross_caches) if make_cache else None
    aux = jnp.zeros((), jnp.float32)
    if return_hidden:
        return h, caches, aux
    return jnp.einsum("bsd,vd->bsv", h, p["embed"]), caches, aux


def init_cache(cfg, batch_size: int, max_len: int, window=None):
    self1 = A.init_cache(cfg, batch_size, max_len, window=window)
    dtc = jnp.dtype(cfg.compute_dtype)
    F = cfg.encoder.n_frames
    cross1 = A.KVCache(
        k=jnp.zeros((batch_size, F, cfg.n_kv_heads * cfg.head_dim), dtc),
        v=jnp.zeros((batch_size, F, cfg.n_kv_heads * cfg.head_dim), dtc),
        pos=jnp.asarray(F, jnp.int32),
    )
    L = cfg.n_layers
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (L,) + x.shape), t)
    return EncDecCache(self_kv=stack(self1), cross_kv=stack(cross1))


def decode_step(p, cfg, caches: EncDecCache, token):
    dt = jnp.dtype(cfg.compute_dtype)
    h = jnp.take(p["embed"], token[:, None], axis=0).astype(dt)
    # absolute position = self-cache fill level (same for every layer);
    # scalar in the classic path, per-row [B] under the slot cache.
    pos = caches.self_kv.pos[0]
    half = cfg.d_model // 2
    div = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-jnp.log(10000.0) / cfg.d_model) * 2.0)
    ang = pos.astype(jnp.float32)[..., None] * div  # [..., half]
    pe = jnp.zeros(ang.shape[:-1] + (cfg.d_model,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(ang))
    pe = pe.at[..., 1::2].set(jnp.cos(ang[..., : cfg.d_model - half]))
    pe = pe.astype(dt)
    h = h + (pe[:, None] if pos.ndim else pe[None, None])

    def body(h, xs):
        lp, sc, cc = xs
        hn = rmsnorm(h, lp["norm_self"], cfg.norm_eps)
        out, sc_new = A.attn_decode(lp["self"], hn, cfg, sc, window=None)
        h = h + out
        hn = rmsnorm(h, lp["norm_cross"], cfg.norm_eps)
        h = h + A.cross_attn_decode(lp["cross"], hn, cfg, cc)
        h = h + swiglu(rmsnorm(h, lp["norm_ffn"], cfg.norm_eps), **lp["mlp"])
        return h, sc_new

    h, self_new = jax.lax.scan(body, h, (p["dec_layers"], caches.self_kv,
                                         caches.cross_kv))
    h = rmsnorm(h, p["norm_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", h, p["embed"])[:, 0]
    return logits, EncDecCache(self_new, caches.cross_kv)
