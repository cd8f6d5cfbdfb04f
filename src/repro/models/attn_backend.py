"""Backend-dispatched attention: one policy site, kernel-selected execution.

Mirror of the §7 Estimator pattern (DESIGN.md §8): model layers never
call a kernel directly — they call :func:`full_attention` /
:func:`decode_attention` here, and the backend carried on the model
config (``ArchConfig.attn_backend``) decides what runs:

* ``"jnp"``   — the chunked ``attention.mha``. Reference semantics, and
  the only backend that implements sliding-window masking and the
  TP head-padding layout.
* ``"flash"`` — the fused Pallas kernels: ``kernels/flash_attention``
  for full-sequence (train / prefill / encoder / cross) attention and
  ``kernels/decode_attention`` for single-query cached decode (GQA
  grouped in-kernel, per-row ``kv_len``). Off-TPU both run in interpret
  mode with wide tiles. Calls the kernels cannot express (sliding
  window, TP > 1 — both are ``mha``-only features — and any call traced
  under a multi-device mesh, where GSPMD partitions the program and
  cannot partition a Mosaic kernel) route to ``mha`` —
  that routing is *policy*, decided here per call signature, unlike the
  silent shape-dependent fallback the flash kernel used to hide inside
  its entry point.
* ``"auto"``  — ``flash`` for decode everywhere (the grouped kernel
  wins on TPU by construction and on host CPU via the wide interpret
  tile — ``BENCH_attn.json``); for full-sequence attention, ``flash``
  on TPU and ``mha`` on host (XLA's fused CPU matmuls beat interpret
  emulation at prefill shapes).

The full-sequence flash path is grad-safe: the kernel has no VJP rule,
so it is wrapped in a ``custom_vjp`` whose backward differentiates the
chunked ``mha`` reference (recompute-in-backward, exactly the remat
trade the chunked path already makes) — ``attn_backend="flash"`` is
valid under ``jax.grad``, not just at inference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BACKENDS = ("auto", "jnp", "flash")

__all__ = ["BACKENDS", "resolve_backend", "full_attention",
           "decode_reads_pool", "decode_attention"]


def _partitioned() -> bool:
    from ..dist import ctx

    return ctx.partitioned()


def resolve_backend(backend: str, *, decode: bool, window=None) -> str:
    """Resolve a config backend to the concrete one a call will run.

    ``window`` is the *positional* sliding-window constraint of the
    call (full-sequence attention only — decode masks by validity, so
    ring-cache decode has no positional window). Kernel-inexpressible
    signatures (window set, or a multi-device mesh active — TP sharding
    included) resolve to ``jnp``.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown attn backend {backend!r}; known: {BACKENDS}")
    if window is not None or _partitioned():
        return "jnp"
    if backend == "auto":
        if decode:
            return "flash"
        return "flash" if jax.default_backend() == "tpu" else "jnp"
    return backend


@functools.lru_cache(maxsize=None)
def _flash_full(causal: bool, chunk: int):
    """Grad-safe full-sequence flash attention for a static signature.

    Forward: the fused kernel. Backward: VJP of the chunked ``mha``
    reference (same math — parity asserted in tests), recomputed from
    the saved q/k/v. Cached per static signature so the custom-vjp
    primitive is built once per config, keeping jit caches stable.
    """
    from ..kernels.flash_attention import flash_attention as _fa

    def _ref(q, k, v):
        from . import attention as A

        return A.mha(q, k, v, causal=causal, window=None, chunk=chunk)

    @jax.custom_vjp
    def f(q, k, v):
        return _fa(q, k, v, causal=causal)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        _, vjp = jax.vjp(_ref, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def full_attention(q, k, v, cfg, *, causal, window, q_offset=0, kv_len=None):
    """Full-sequence attention [B,S,H,dh] x [B,T,Hkv,dh] -> [B,S,H,dh].

    The train / prefill / encoder / cross entry point. ``window`` /
    ``q_offset`` / ``kv_len`` follow ``attention.mha``; signatures the
    flash kernel can't express (window, offset/valid-length masks,
    TP > 1) resolve to the chunked jnp path.
    """
    from . import attention as A

    backend = getattr(cfg, "attn_backend", "auto")
    if q_offset != 0 or kv_len is not None:
        backend = "jnp"
    if resolve_backend(backend, decode=False, window=window) == "flash":
        return _flash_full(bool(causal), cfg.attn_chunk)(q, k, v)
    return A.mha(q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk,
                 q_offset=q_offset, kv_len=kv_len)


def decode_reads_pool(cfg, T: int) -> bool:
    """Whether decode attention over a cache of length ``T`` reads its
    layer straight from the stacked KV pool: when the call resolves to
    the kernel and T sits on the kernel's kv tile. Otherwise the layer
    is copied out of the pool first — the jnp reference always, the
    kernel to pad a cache off its tile. :func:`decode_attention` routes
    by it, and the engine's ``serve.decode_kv_inplace`` gauge reports
    it; like :func:`resolve_backend` it reads the ambient mesh."""
    if resolve_backend(getattr(cfg, "attn_backend", "auto"),
                       decode=True) != "flash":
        return False
    from ..kernels.decode_attention import kv_tile

    return kv_tile(T)[1] == 0


def decode_attention(q, k, v, layer, cfg, *, kv_len=None, k_scale=None,
                     v_scale=None):
    """Single-query cached attention [B,1,H,dh] over layer ``layer`` of
    the KV pool [L,B,T,Hkv*dh] (one layer's cache is a pool of one
    layer: ``k[None]``, layer 0).

    The decode hot loop. ``kv_len``: scalar or per-row [B] valid cache
    length (slot serving); ring caches mask by validity only, so both
    cache geometries take the same kernel (DESIGN.md §6/§8). Where
    :func:`decode_reads_pool` holds, the kernel reads the layer straight
    from the pool; elsewhere the layer is taken out of the pool first (a
    copy, on paths the serve cell does not run).

    ``k_scale``/``v_scale``: per-(layer, row, position) [L, B, T] f32
    dequant scales of an int8 KV pool (DESIGN.md §12). The flash kernel
    fuses the dequant into its K/V block loads; the jnp reference
    dequantizes eagerly before ``mha``. bf16 caches carry no scales —
    both paths already upcast at read.
    """
    from . import attention as A

    B, T, dh = k.shape[1], k.shape[2], q.shape[-1]
    if not decode_reads_pool(cfg, T):
        # the layer, as a pool of one layer
        k, v, k_scale, v_scale = (
            None if x is None else jax.lax.dynamic_slice_in_dim(x, layer, 1)
            for x in (k, v, k_scale, v_scale))
        layer = 0
    backend = getattr(cfg, "attn_backend", "auto")
    if resolve_backend(backend, decode=True) == "flash":
        from ..kernels.decode_attention import decode_attention as _da

        return _da(q, k, v, layer, kv_len=kv_len, k_scale=k_scale,
                   v_scale=v_scale)
    k = k[0].reshape(B, T, -1, dh)
    v = v[0].reshape(B, T, -1, dh)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[0][:, :, None, None]
        v = v.astype(jnp.float32) * v_scale[0][:, :, None, None]
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    return A.mha(q, k, v, causal=False, window=None, chunk=1, kv_len=kv_len)
