"""GQA attention: chunked (flash-style) full-sequence path + cached decode.

Memory design: scores are never materialized at [B, H, S, S]; the query
axis is processed in blocks of ``cfg.attn_chunk`` via lax.scan, keeping
the live buffer at [B, Hkv, Hq/Hkv, blk, T]. GQA is computed grouped
(no repeat of K/V). Sliding-window masking supports Mixtral-style SWA
and the long_500k dense variant; decode uses a ring-buffer cache when a
window is set.

Execution is backend-dispatched (DESIGN.md §8): ``attn_forward`` and
``attn_decode``/``cross_attn_decode`` route through
``models/attn_backend.py``, which sends supported signatures to the
fused Pallas kernels (``kernels/flash_attention`` full-sequence,
``kernels/decode_attention`` single-query grouped-GQA decode) per
``cfg.attn_backend``; the chunked ``mha`` below is the jnp reference
backend and the only implementation of sliding-window masking and the
TP head-padded layout.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs.trace import named_span
from .layers import dense_init, rmsnorm, rope

NEG_INF = -1e30

# KV-cache storage dtypes (cfg.kv_dtype, DESIGN.md §12). Quantization is
# write-side only: Q/K/V are computed in compute_dtype, the cache stores
# the narrow form, and dequantization happens at read time (fused into
# the decode-attention kernel's block loads on the flash backend).
KV_DTYPES = ("float32", "bfloat16", "int8")


class KVCache(NamedTuple):
    # [B, T, Hkv*dh] (T = max_len or window size): a kv head's [T, dh]
    # slab is a plain block of the last two dims, so the decode kernel
    # reads the layer-stacked pool [L, B, T, Hkv*dh] as it rests
    # (DESIGN.md §6/§8)
    k: jnp.ndarray
    v: jnp.ndarray
    pos: jnp.ndarray  # [] int32 — number of tokens already written
    # int8 KV only: per-(row, position) f32 dequant scales [B, T],
    # carried beside the cache exactly like ``pos`` (None otherwise, so
    # unquantized cache trees keep their pre-§12 structure — None is not
    # a pytree leaf and every structural probe/tree.map skips it).
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None


def kv_dtype(cfg):
    """The cache storage dtype: ``cfg.kv_dtype`` or compute_dtype."""
    return jnp.dtype(getattr(cfg, "kv_dtype", None) or cfg.compute_dtype)


def quantize_kv(x, dt):
    """Quantize fresh K/V rows ``[B, S, Hkv, dh]`` for cache storage.

    Returns ``(stored, scale)``: int8 uses a symmetric per-(row,
    position) scale over the [Hkv, dh] tail — each cache position is
    quantized exactly once, at write time, and never requantized — any
    other dtype is a plain cast with ``scale=None``.
    """
    if dt == jnp.int8:
        s = jnp.max(jnp.abs(x), axis=(2, 3)).astype(jnp.float32) / 127.0
        s = jnp.maximum(s, 1e-8)  # all-zero rows (padding) stay zero
        q = jnp.round(x.astype(jnp.float32) / s[:, :, None, None])
        return jnp.clip(q, -127.0, 127.0).astype(jnp.int8), s
    return x.astype(dt), None


def attn_init(key, cfg, d_model=None, cross: bool = False):
    d = d_model or cfg.d_model
    dh = cfg.head_dim
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads, dh), dt, fan_in=d),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, dh), dt, fan_in=d),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, dh), dt, fan_in=d),
        "wo": dense_init(ks[3], (cfg.n_heads, dh, d), dt, fan_in=cfg.n_heads * dh),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _proj(x, w3):
    """[B,S,D] @ [D,H,dh] via the IB-RRS-aware 2-D dot."""
    from .layers import _dot

    D, H, dh = w3.shape
    return _dot(x, w3.reshape(D, H * dh)).reshape(x.shape[:-1] + (H, dh))


def _out_proj(out, wo):
    """[B,S,H,dh] @ [H,dh,D] via the IB-RRS/TP-aware 2-D dot — decode
    shares the sharding/robust-backward contract of ``attn_forward``."""
    from .layers import _dot

    H, dh, D = wo.shape
    return _dot(out.reshape(out.shape[:2] + (H * dh,)), wo.reshape(H * dh, D))


def _qkv(p, x, cfg, positions, kv_x=None):
    kv_x = x if kv_x is None else kv_x
    q = _proj(x, p["wq"])
    k = _proj(kv_x, p["wk"])
    v = _proj(kv_x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def mha(q, k, v, *, causal: bool, window: Optional[int], chunk: int,
        q_offset=0, kv_len: Optional[jnp.ndarray] = None):
    """Chunked multi-head attention, TP-aware.

    q: [B, S, H, dh]; k/v: [B, T, Hkv, dh]. ``q_offset``: absolute
    position of q[0] relative to k[0]. ``kv_len``: optional valid kv
    length (decode with a partially-filled cache) — a scalar, or a
    per-row [B] vector when rows are at different fill levels (the
    slot-cache serving path, DESIGN.md §6). Returns [B, S, H, dh].

    Sharding design (DESIGN.md §5): K/V are repeated to H query heads
    (GQA groups are NOT computed via a reshape of the head axis — a
    reshape of a sharded 16-head axis into [8, 2] forces GSPMD to
    replicate; the repeat keeps a plain head axis that shards cleanly).
    When H doesn't divide the model axis (starcoder2's 36, minitron's
    24), heads are zero-padded up to the next multiple — ~1.3x attention
    flops on those archs, traded for an exact head-sharded layout.
    """
    from ..dist import ctx

    B, S, H, dh = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32)).astype(q.dtype)
    rep = H // Hkv
    if rep > 1:
        # reprolint: disable=RL002 DESIGN §5: TP shards the head axis; a grouped [Hkv, G] reshape of a sharded 16-head axis forces GSPMD replication, so the jnp path repeats pre-shard (flash path stays grouped)
        k = jnp.repeat(k, rep, axis=2)
        # reprolint: disable=RL002 DESIGN §5: same head-sharding constraint as k above
        v = jnp.repeat(v, rep, axis=2)
    tp = ctx.axis_size("model")
    Hp = -(-H // tp) * tp
    if Hp != H:
        padh = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
        q = jnp.pad(q, padh)
        k = jnp.pad(k, padh)
        v = jnp.pad(v, padh)
    if tp > 1:
        q = ctx.constrain(q, ctx.U, ctx.U, "model", None)
        k = ctx.constrain(k, ctx.U, ctx.U, "model", None)
        v = ctx.constrain(v, ctx.U, ctx.U, "model", None)

    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    qp = qp.reshape(B, n_chunks, chunk, Hp, dh)
    kv_pos = jnp.arange(T)

    # Per-row valid-length mask [B, T] (slot cache: rows differ); the
    # scalar case folds into the positional mask below.
    row_valid = None
    if kv_len is not None and getattr(kv_len, "ndim", 0) > 0:
        row_valid = kv_pos[None, :] < kv_len[:, None]

    def body(_, qc_i):
        qc, i = qc_i
        q_pos = q_offset + i * chunk + jnp.arange(chunk)
        s = jnp.einsum("bshd,bthd->bhst", qc * scale, k).astype(jnp.float32)
        mask = jnp.ones((chunk, T), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len is not None and row_valid is None:
            mask &= kv_pos[None, :] < kv_len
        s = jnp.where(mask[None, None], s, NEG_INF)
        if row_valid is not None:
            s = jnp.where(row_valid[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return None, jnp.einsum("bhst,bthd->bshd", p, v)

    # Remat the chunk body: without it, the backward of the chunk scan
    # stacks every chunk's f32 scores/probs ([n_chunks, blk, T] live at
    # once); with it, scores are recomputed per chunk in the backward.
    body_fn = jax.checkpoint(body) if n_chunks > 1 else body
    _, out = jax.lax.scan(
        body_fn, None, (jnp.moveaxis(qp, 1, 0), jnp.arange(n_chunks))
    )
    out = jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * chunk, Hp, dh)
    return out[:, :S, :H]


def attn_forward(p, x, cfg, *, positions, causal=True, window="cfg",
                 kv_x=None, make_cache=False, cache_len=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    Returns (out [B,S,D], cache or None). ``window`` overrides
    cfg.sliding_window when given explicitly.
    """
    from . import attn_backend as AB

    window = cfg.sliding_window if window == "cfg" else window
    q, k, v = _qkv(p, x, cfg, positions, kv_x=kv_x)
    out = AB.full_attention(q, k, v, cfg, causal=causal, window=window)
    out = _out_proj(out, p["wo"])
    cache = None
    if make_cache:
        B, S = k.shape[:2]
        # quantize and fold the heads into the cache layout before the
        # padding to cache length, so only the prompt's rows relayout
        dt = kv_dtype(cfg)
        k, ks = quantize_kv(k, dt)
        v, vs = quantize_kv(v, dt)
        k = k.reshape(B, S, -1)
        v = v.reshape(B, S, -1)
        if window:
            # Ring cache of exactly `window` slots; position p lives at
            # slot p % window so decode can keep writing in ring order.
            w = window
            if S >= w:
                fit = lambda x: jnp.roll(x[:, -w:], S % w, axis=1)
            else:
                fit = lambda x: jnp.pad(
                    x, ((0, 0), (0, w - S)) + ((0, 0),) * (x.ndim - 2))
        else:
            T = cache_len or S
            if T >= S:
                fit = lambda x: jnp.pad(
                    x, ((0, 0), (0, T - S)) + ((0, 0),) * (x.ndim - 2))
            else:
                fit = lambda x: x[:, :T]
        cache = jax.tree.map(fit, KVCache(
            k=k, v=v, pos=None, k_scale=ks, v_scale=vs))._replace(
                pos=jnp.asarray(S, jnp.int32))
    return out, cache


def init_cache(cfg, batch: int, max_len: int, window: Optional[int] = None,
               d_model=None):
    """Empty KV cache. With a window, the cache is a ring of that size."""
    T = min(window, max_len) if window else max_len
    dt = kv_dtype(cfg)
    shape = (batch, T, cfg.n_kv_heads * cfg.head_dim)
    ks = vs = None
    if dt == jnp.int8:
        ks = jnp.zeros((batch, T), jnp.float32)
        vs = jnp.zeros((batch, T), jnp.float32)
    return KVCache(
        k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
        pos=jnp.asarray(0, jnp.int32), k_scale=ks, v_scale=vs,
    )


def attn_decode(p, x1, cfg, cache: KVCache, *, window="cfg", layer=None):
    """Single-token decode. x1: [B, 1, D]. Returns (out [B,1,D], cache).

    ``layer``: with an index, ``cache.k``/``v`` (and int8 scales) are the
    layer-stacked pool [L, B, T, Hkv*dh] carried through the layer scan,
    and this step writes its one new row per slot at (layer, b, pos_b)
    in place and hands the kernel the pool and the index: nothing the
    size of a layer's cache is sliced or copied. Without one, the cache
    is one layer's [B, T, Hkv*dh], run as a pool of one layer.

    ``cache.pos`` (this layer's fill level) may be a scalar (all rows at
    the same fill level — the classic batched path) or a per-row [B]
    vector (slot-cache serving, DESIGN.md §6): each row then writes its
    K/V at its own position and masks to its own valid length.
    """
    if layer is None:
        pooled = jax.tree.map(lambda x: x[None], cache._replace(pos=None))
        out, new = attn_decode(p, x1, cfg, pooled._replace(pos=cache.pos),
                               window=window, layer=0)
        return out, jax.tree.map(lambda x: x[0], new._replace(pos=None)
                                 )._replace(pos=new.pos)
    window = cfg.sliding_window if window == "cfg" else window
    pos = cache.pos
    B = x1.shape[0]
    positions = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (B, 1))
    q, k, v = _qkv(p, x1, cfg, positions)
    T = cache.k.shape[2]
    slot = jnp.mod(pos, T) if window else jnp.minimum(pos, T - 1)
    rows = (layer, jnp.arange(B), jnp.broadcast_to(slot, (B,)))
    kscale, vscale = cache.k_scale, cache.v_scale
    with named_span("decode.kv_cache"):
        # quantize the fresh K/V row once, at write time (no-op cast when
        # the cache dtype matches compute_dtype), and scatter it into the
        # carried pool: one [Hkv*dh] row per slot, in place
        k, ks1 = quantize_kv(k, cache.k.dtype)
        v, vs1 = quantize_kv(v, cache.v.dtype)
        ck = cache.k.at[rows].set(k.reshape(B, -1))
        cv = cache.v.at[rows].set(v.reshape(B, -1))
        if ks1 is not None:
            kscale = kscale.at[rows].set(ks1[:, 0])
            vscale = vscale.at[rows].set(vs1[:, 0])
    # Ring buffer (window set): all T slots valid once pos >= T; slot
    # positions don't matter for masking beyond validity (window == ring
    # size). Linear cache: the first pos+1 slots are valid.
    kv_len = jnp.minimum(pos + 1, T) if window else pos + 1
    from . import attn_backend as AB

    out = AB.decode_attention(q, ck, cv, layer, cfg, kv_len=kv_len,
                              k_scale=kscale, v_scale=vscale)
    out = _out_proj(out, p["wo"])
    return out, KVCache(k=ck, v=cv, pos=pos + 1,
                        k_scale=kscale, v_scale=vscale)


def cross_attn_decode(p, x1, cfg, cross_kv: KVCache):
    """Decode-time cross attention over a fixed encoder cache."""
    from . import attn_backend as AB

    q = _proj(x1, p["wq"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    out = AB.decode_attention(q, cross_kv.k[None], cross_kv.v[None], 0, cfg)
    return _out_proj(out, p["wo"])


def make_cross_cache(p, enc_out, cfg):
    """Precompute K/V over encoder output for decode-time cross attention."""
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"])
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    B, F = k.shape[:2]
    return KVCache(k=k.reshape(B, F, -1), v=v.reshape(B, F, -1),
                   pos=jnp.asarray(F, jnp.int32))
