"""Fused single-query (decode) attention Pallas kernel, GQA-grouped.

The serving hot loop is one query token per sequence attending over the
KV cache. The chunked jnp ``mha`` pays two avoidable memory costs per
decode step: a ``jnp.repeat`` of K/V from Hkv to H heads (4x cache read
traffic at the 4:1 GQA ratios of the assigned archs) and a materialized
f32 ``[B, H, 1, T]`` score tensor. This kernel does neither: GQA is
computed *grouped* — each K/V block is loaded into VMEM once per kv
head and shared by the whole [G = H/Hkv, dh] query group — and the
online-softmax state (m, l, acc) lives in VMEM scratch, so scores never
touch HBM.

The kernel reads the stacked KV pool as it rests (DESIGN.md §6/§8):
K/V are ``[L, B, T, Hkv*dh]`` — layer, slot, position, the kv heads'
features side by side — and the call names the layer, scalar-prefetched
into SMEM like the per-row lengths. A layer's ``[blk_k, dh]`` slab of
one kv head is then a plain block of the last two dims (lane-aligned
for dh in {64, 128}), so no per-layer slice, relayout or write-back of
the cache happens around the kernel. A single layer's cache is a pool
of one layer (``k[None]``, layer 0).

Two layouts of the same online-softmax math (DESIGN.md §8):

* **narrow** (compiled TPU): grid ``(B, Hkv, n_kv_blocks)``, kv axis
  innermost (sequential, accumulating into scratch; the output block is
  written on the last kv step). Blocks are 2-D MXU-shaped: q ``[G,
  dh]``, K/V ``[blk_k, dh]`` at ``(layer, b, j, h)`` of the pool.
* **wide** (interpret mode, host CPU): grid ``(n_batch_blocks,
  n_kv_blocks)`` — kv innermost — with a ``[blk_b, Hkv, G, dh]`` query
  block and ``[blk_b, blk_k, Hkv*dh]`` K/V blocks resident at once,
  grouped einsums over the head axes. ``blk_b`` defaults to the whole
  batch (one batch block): per-grid-step interpreter overhead
  dominates, so one step per ``INTERPRET_BLK_K`` keys amortizes it (à
  la ``vrmom.INTERPRET_TILE``), which is what lets the kernel beat the
  chunked jnp ``mha`` at serving shapes on host CPU too
  (``BENCH_attn.json``).

A cache length that is not a multiple of the kv tile (``kv_tile``) is
the one case that copies: the kernel pads the pool it is handed (scope
``decode.kv_cache``), so the decode policy (``attn_backend``) hands it
the one layer, taken out of the pool, there.

int8 KV caches pass per-(layer, row, position) ``[L, B, T]`` f32 scales
(the kernel takes its layer's ``[B, T]`` rows, a small copy); both
layouts fuse the dequant into the kernel (the cache crosses HBM at 1
byte/element — DESIGN.md §12): the wide layout scales the K/V block on
load, the narrow one scales score and probability columns, which is
the same product regrouped.

Validity masking is per row: ``kv_len`` may be a scalar (classic batched
decode) or a per-row ``[B]`` vector (the slot-cache serving path,
DESIGN.md §6, where every slot sits at its own fill level). The
ring-buffer window cache needs no extra support: decode-with-window
masks by validity only (``kv_len = min(pos+1, T)``, slot order is
irrelevant to softmax — DESIGN.md §6), and tile padding beyond T rides
the same mask. Dispatch policy (which model layers run this vs the
chunked jnp ``mha``) lives in ``models/attn_backend.py``; this module is
the execution entry point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.trace import named_span

NEG_INF = -1e30

DEFAULT_BLK_K = 256     # compiled TPU path: [blk_k, dh] K/V blocks in VMEM
INTERPRET_BLK_K = 4096  # interpret mode: amortize per-grid-step overhead

__all__ = ["decode_attention", "kv_tile", "DEFAULT_BLK_K",
           "INTERPRET_BLK_K"]


def _online_update(s, pv, m_scr, l_scr, acc_scr):
    """One online-softmax accumulation step, shape-generic.

    s: scores [..., blk_k]; ``pv(p)`` contracts the probabilities with
    the value block to acc's shape [..., dh]. Scratch m/l are [...],
    acc is [..., dh].
    """
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv(p)


def _kernel_narrow(layer_ref, len_ref, q_ref, k_ref, v_ref, *refs, scale,
                   blk_k, n_k, has_scale):
    if has_scale:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [G, dh] — the whole query group
    k = k_ref[0, 0].astype(jnp.float32)  # [blk_k, dh] — loaded ONCE per
    v = v_ref[0, 0].astype(jnp.float32)  # kv head, shared by all G rows
    s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
    if has_scale:
        # int8 KV: the per-position dequant scale of key t multiplies
        # score column t, a [1, blk_k] lane row (DESIGN.md §12)
        s = s * ks_ref[0]

    kv_len = len_ref[b]  # scalar-prefetched per-row length (SMEM)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    def pv(p):
        if has_scale:
            p = p * vs_ref[0]  # value t's dequant scale on column t
        return jnp.dot(p, v, preferred_element_type=jnp.float32)

    _online_update(s, pv, m_scr, l_scr, acc_scr)

    @pl.when(ki == n_k - 1)
    def _done():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _kernel_wide(layer_ref, len_ref, q_ref, k_ref, v_ref, *refs, scale, blk_k,
                 n_k, has_scale):
    if has_scale:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    ki = pl.program_id(1)  # kv axis innermost; batch blocks outer

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    B, Hkv, G, dh = q_ref.shape  # B here is the batch block (blk_b rows)
    q = q_ref[...].astype(jnp.float32)                       # [B,Hkv,G,dh]
    k = k_ref[0].astype(jnp.float32).reshape(B, blk_k, Hkv, dh)
    v = v_ref[0].astype(jnp.float32).reshape(B, blk_k, Hkv, dh)
    if has_scale:
        # int8 KV: per-(row, position) dequant fused into the block load
        k = k * ks_ref[...][:, :, None, None]
        v = v * vs_ref[...][:, :, None, None]
    s = jnp.einsum("bhgd,bthd->bhgt", q * scale, k,
                   preferred_element_type=jnp.float32)       # [B,Hkv,G,blk]

    kv_len = len_ref[...]                                    # [B, 1]
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    s = jnp.where(k_pos < kv_len[:, 0][:, None, None, None], s, NEG_INF)
    _online_update(
        s, lambda p: jnp.einsum("bhgt,bthd->bhgd", p, v,
                                preferred_element_type=jnp.float32),
        m_scr, l_scr, acc_scr)

    @pl.when(ki == n_k - 1)
    def _done():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[...] = out.astype(o_ref.dtype)


def _narrow_call(q, k, v, layer, lens, k_scale, v_scale, *, scale, blk_k,
                 n_k, interpret):
    """Narrow layout: grid (B, Hkv, n_kv_blocks), 2-D MXU-shaped blocks,
    kv axis sequential. The layer index and ``lens`` are scalar-prefetched
    into SMEM (a per-row [1, 1] VMEM block would break the (8, 128) block
    rule), and the int8 scales ride as [B, 1, Tk], so each row's
    [1, blk_k] block spans its whole second-minor dim."""
    B, Hkv, G, dh = q.shape
    has_scale = k_scale is not None
    kv_spec = pl.BlockSpec((1, 1, blk_k, dh),
                           lambda b, h, j, layer, lens: (layer[0], b, j, h))
    in_specs = [
        pl.BlockSpec((1, 1, G, dh),
                     lambda b, h, j, layer, lens: (b, h, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = (layer, lens, q, k, v)
    if has_scale:
        in_specs += [pl.BlockSpec((1, 1, blk_k),
                                  lambda b, h, j, layer, lens: (b, 0, j))] * 2
        args += (k_scale[:, None], v_scale[:, None])
    return pl.pallas_call(
        functools.partial(_kernel_narrow, scale=scale, blk_k=blk_k, n_k=n_k,
                          has_scale=has_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, G, dh),
                                   lambda b, h, j, layer, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(*args)


def _wide_call(q, k, v, layer, lens, k_scale, v_scale, *, scale, blk_k, n_k,
               blk_b, interpret):
    """Wide layout: [blk_b, Hkv, G, dh] query block per grid step, batch
    blocks outer, kv axis inner (scratch accumulates per batch block).
    Zero-padded batch rows (lens 0) normalize to 0 and are sliced off."""
    B, Hkv, G, dh = q.shape
    has_scale = k_scale is not None
    blk_b = min(blk_b, B)
    pad_b = (-B) % blk_b
    if pad_b:
        q = jnp.pad(q, ((0, pad_b),) + ((0, 0),) * 3)
        k = jnp.pad(k, ((0, 0), (0, pad_b), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_b), (0, 0), (0, 0)))
        lens = jnp.pad(lens, (0, pad_b))
        if has_scale:
            k_scale = jnp.pad(k_scale, ((0, pad_b), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, pad_b), (0, 0)))
    Bb = B + pad_b
    kv_spec = pl.BlockSpec((1, blk_b, blk_k, Hkv * dh),
                           lambda i, j, layer: (layer[0], i, j, 0))
    in_specs = [
        pl.BlockSpec((blk_b, 1), lambda i, j, layer: (i, 0)),
        pl.BlockSpec((blk_b, Hkv, G, dh), lambda i, j, layer: (i, 0, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = (layer, lens[:, None], q, k, v)
    if has_scale:
        in_specs += [pl.BlockSpec((blk_b, blk_k),
                                  lambda i, j, layer: (i, j))] * 2
        args += (k_scale, v_scale)
    out = pl.pallas_call(
        functools.partial(_kernel_wide, scale=scale, blk_k=blk_k, n_k=n_k,
                          has_scale=has_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bb // blk_b, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((blk_b, Hkv, G, dh),
                                   lambda i, j, layer: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((blk_b, Hkv, G), jnp.float32),
                pltpu.VMEM((blk_b, Hkv, G), jnp.float32),
                pltpu.VMEM((blk_b, Hkv, G, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((Bb, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(*args)
    return out[:B]


@functools.partial(jax.jit,
                   static_argnames=("blk_k", "blk_b", "interpret", "narrow"))
def _decode_grouped(q, k, v, layer, lens, k_scale, v_scale, blk_k, blk_b,
                    interpret, narrow=None):
    """q: [B, Hkv, G, dh]; k/v: the pool [L, B, T, Hkv*dh]; layer: []
    int32; lens: [B] int32; k_scale/v_scale: the layer's [B, T] f32
    int8-dequant scales or None.

    ``narrow`` picks the layout: None means narrow when compiled and
    wide when interpreted (tests pass True to run the compiled layout's
    math in interpret mode)."""
    B, Hkv, G, dh = q.shape
    T = k.shape[2]
    blk_k, pad_k = kv_tile(T, blk_k, interpret)
    if pad_k:
        with named_span("decode.kv_cache"):
            # off the kv tile: pad the pool (one layer, from the decode
            # policy); padded slots fall beyond kv_len <= T and are
            # masked out in-kernel
            padw = ((0, 0), (0, 0), (0, pad_k), (0, 0))
            k = jnp.pad(k, padw)
            v = jnp.pad(v, padw)
            if k_scale is not None:
                k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_k)))
                v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_k)))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    kw = dict(scale=1.0 / (dh ** 0.5), blk_k=blk_k, n_k=(T + pad_k) // blk_k,
              interpret=interpret)
    if narrow if narrow is not None else not interpret:
        return _narrow_call(q, k, v, layer, lens, k_scale, v_scale, **kw)
    return _wide_call(q, k, v, layer, lens, k_scale, v_scale, blk_b=blk_b,
                      **kw)


def _default_interpret():
    return jax.default_backend() != "tpu"


def kv_tile(T, blk_k=None, interpret=None):
    """-> (blk_k, pad): the kv tile the kernel walks a cache of length T
    with (the mode's default when ``blk_k`` is None) and the padding T
    needs to sit on it."""
    if interpret is None:
        interpret = _default_interpret()
    if blk_k is None:
        blk_k = INTERPRET_BLK_K if interpret else DEFAULT_BLK_K
    blk_k = min(blk_k, T)
    return blk_k, (-T) % blk_k


def decode_attention(q, k, v, layer=0, *, kv_len=None, blk_k=None,
                     blk_b=None, interpret=None, k_scale=None, v_scale=None):
    """Fused single-query attention over layer ``layer`` of a KV pool.

    q: [B, 1, H, dh]; k/v: the stacked pool [L, B, T, Hkv*dh] with H
    divisible by Hkv (grouped in-kernel — K/V are never repeated to H);
    one layer's cache is a pool of one layer (``k[None]``, layer 0).
    ``layer``: int or traced [] int32, the layer read; the kernel's
    blocks index the pool there, so nothing is sliced or copied.
    ``kv_len``: valid cache length — None (whole cache), a scalar, or a
    per-row [B] vector (slot-cache serving). Returns [B, 1, H, dh] in
    q's dtype (f32 softmax/accumulation internally).

    ``blk_k=None`` picks the kv tile per mode: a VMEM-sized block when
    compiled, a wide block when interpreted (per-grid-step interpreter
    overhead dominates otherwise — ``BENCH_attn.json``). ``blk_b``
    tiles the *batch* axis of the wide layout (None -> whole batch
    resident per grid step — at serving batches the extra grid steps
    cost more interpreter overhead than the smaller block saves; the
    narrow layout already walks the batch on its grid).

    ``k_scale``/``v_scale``: per-(layer, row, position) [L, B, T] f32
    dequant scales of an int8 pool; the dequant multiply is fused into
    the K/V block loads so the cache crosses HBM at 1 byte/element
    (DESIGN.md §12).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, S, H, dh = q.shape
    if S != 1:
        raise ValueError(f"decode_attention is single-query; got S={S}")
    if k.ndim != 4 or k.shape[1] != B or k.shape[3] % dh:
        raise ValueError(f"k must be a pool [L, {B}, T, Hkv*{dh}]; "
                         f"got {k.shape}")
    T, Hkv = k.shape[2], k.shape[3] // dh
    blk_k, _ = kv_tile(T, blk_k, interpret)
    if H % Hkv:
        raise ValueError(f"H={H} not divisible by Hkv={Hkv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    G = H // Hkv
    # query head h belongs to kv head h // G — the same grouping
    # jnp.repeat(k, G, axis=2) realizes — so the reshape is exact.
    qg = q[:, 0].reshape(B, Hkv, G, dh)
    if kv_len is None:
        lens = jnp.full((B,), T, jnp.int32)
    else:
        kv_len = jnp.asarray(kv_len, jnp.int32)
        lens = jnp.broadcast_to(kv_len, (B,))
    lens = jnp.minimum(lens, T)
    layer = jnp.asarray(layer, jnp.int32)
    if k_scale is not None:
        # the layer's [B, T] scales: a copy of B*T floats, not of the cache
        k_scale, v_scale = (jax.lax.dynamic_index_in_dim(
            jnp.asarray(s, jnp.float32), layer, keepdims=False)
            for s in (k_scale, v_scale))
    with named_span("kernels.decode_attention"):
        out = _decode_grouped(qg, k, v, layer, lens, k_scale, v_scale,
                              int(blk_k), int(blk_b or B), bool(interpret))
    return out.reshape(B, 1, H, dh)
