"""Backend-parity suite for the attention layer (DESIGN.md §8).

The decode kernel must equal the chunked jnp ``mha`` reference to 1e-5
across GQA ratios, scalar vs per-row ``kv_len``, ring vs linear cache
geometry, odd head counts, and bf16 — and the backend dispatch must be
semantics-free: a model configured with ``attn_backend="flash"`` decodes
token-identically to ``attn_backend="jnp"``, including the replicated
robust serving path under attack.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get as get_arch
from repro.kernels.decode_attention import decode_attention
from repro.models import attn_backend as AB
from repro.models import model as Mo
from repro.models.attention import mha

# ---------------------------------------------------------------- kernel


def _qkv(key, B, H, Hkv, dh, T, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, dh), dtype)
    return q, k, v


def _pool(x):
    """One layer's [B, T, Hkv, dh] K/V (or [B, T] scales) as the
    kernel's pool of one layer [1, B, T, Hkv*dh] ([1, B, T])."""
    return x.reshape((1,) + x.shape[:2] + (-1,)) if x.ndim == 4 else x[None]


def _da(q, k, v, **kw):
    """The kernel on one layer's cache in the mha layout."""
    for s in ("k_scale", "v_scale"):
        if kw.get(s) is not None:
            kw[s] = _pool(kw[s])
    return decode_attention(q, _pool(k), _pool(v), 0, **kw)


# GQA 1:1 and 4:1, plus starcoder2's 36 heads (Hkv=4 -> group of 9)
@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (36, 4)])
@pytest.mark.parametrize("kv_len", ["none", "scalar", "per_row"])
def test_decode_kernel_matches_mha(H, Hkv, kv_len):
    B, dh, T = 3, 32, 100
    q, k, v = _qkv(jax.random.PRNGKey(H * 100 + Hkv), B, H, Hkv, dh, T)
    lens = {"none": None, "scalar": jnp.asarray(37),
            "per_row": jnp.asarray([1, 42, 100])}[kv_len]
    got = _da(q, k, v, kv_len=lens, interpret=True)
    want = mha(q, k, v, causal=False, window=None, chunk=1, kv_len=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blk_k", [16, 64, 4096])
def test_decode_kernel_tile_invariance(blk_k):
    """Wide interpret tile and narrow TPU-style tiles agree (padding
    beyond T rides the same validity mask as kv_len)."""
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 8, 2, 64, 200)
    lens = jnp.asarray([150, 200])
    got = _da(q, k, v, kv_len=lens, blk_k=blk_k, interpret=True)
    want = mha(q, k, v, causal=False, window=None, chunk=1, kv_len=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_kernel_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 8, 2, 64, 128, jnp.bfloat16)
    lens = jnp.asarray([77, 128])
    got = _da(q, k, v, kv_len=lens, interpret=True)
    want = mha(q, k, v, causal=False, window=None, chunk=1, kv_len=lens)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_decode_kernel_rejects_multi_query():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 4, 4, 32, 16)
    with pytest.raises(ValueError, match="single-query"):
        _da(jnp.concatenate([q, q], axis=1), k, v)


# ------------------------------------------------------- model-level decode


def _decode_tokens(cfg, params, tokens, n, cache_len):
    """Greedy decode ``n`` tokens after prefilling ``tokens``."""
    _, caches = Mo.prefill(params, cfg, {"tokens": tokens},
                           cache_len=cache_len)
    tok = tokens[:, -1] * 0  # fixed first decode token
    out = []
    for _ in range(n):
        logits, caches = Mo.decode_step(params, cfg, caches, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x7b",
                                  "whisper-medium"])
def test_flash_backend_token_identity(arch):
    """flash == jnp backends token-for-token through real decode stacks
    (mixtral exercises the ring/window cache, whisper the cross-attn
    decode path)."""
    cfg = get_arch(arch).reduced()
    params = Mo.init(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(3)
    batch = jax.random.randint(key, (2, 12), 0, cfg.vocab)
    toks = {}
    for backend in ("jnp", "flash"):
        c = dataclasses.replace(cfg, attn_backend=backend)
        if cfg.family == "encdec":
            frames = jax.random.normal(
                jax.random.PRNGKey(5),
                (2, cfg.encoder.n_frames, cfg.d_model), jnp.float32)
            _, caches = Mo.prefill(params, c, {"tokens": batch,
                                               "frames": frames},
                                   cache_len=24)
            tok = batch[:, -1] * 0
            out = []
            for _ in range(6):
                logits, caches = Mo.decode_step(params, c, caches, tok)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out.append(tok)
            toks[backend] = jnp.stack(out, axis=1)
        else:
            toks[backend] = _decode_tokens(cfg=c, params=params,
                                           tokens=batch, n=6, cache_len=24)
    np.testing.assert_array_equal(np.asarray(toks["jnp"]),
                                  np.asarray(toks["flash"]))


def test_flash_full_attention_grad():
    """attn_backend='flash' under jax.grad: the custom-VJP wrapper
    differentiates the mha reference, so training configs can carry the
    flash backend. Gradients match the jnp backend closely."""
    cfg = get_arch("qwen3-1.7b").reduced()
    params = Mo.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab)}
    grads = {}
    for backend in ("jnp", "flash"):
        c = dataclasses.replace(cfg, attn_backend=backend)
        grads[backend] = jax.grad(lambda p: Mo.loss(p, c, batch))(params)
    for a, b in zip(jax.tree.leaves(grads["jnp"]),
                    jax.tree.leaves(grads["flash"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-4)


def test_full_flash_forward_matches_mha():
    """Force the full-seq flash path (as on TPU) and compare to mha."""
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 8, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 2, 32))
    got = AB._flash_full(True, 16)(q, k, v)
    want = mha(q, k, v, causal=True, window=None, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_resolve_backend_policy():
    """Window and TP signatures are kernel-inexpressible -> jnp; decode
    auto resolves to flash everywhere; full-seq auto only on TPU."""
    assert AB.resolve_backend("jnp", decode=True) == "jnp"
    assert AB.resolve_backend("flash", decode=True) == "flash"
    assert AB.resolve_backend("flash", decode=False, window=64) == "jnp"
    assert AB.resolve_backend("auto", decode=True) == "flash"
    on_tpu = jax.default_backend() == "tpu"
    assert AB.resolve_backend("auto", decode=False) == (
        "flash" if on_tpu else "jnp")
    with pytest.raises(ValueError, match="unknown attn backend"):
        AB.resolve_backend("cuda", decode=True)


# ----------------------------------------------------- quantized KV cache

from repro.models.attention import quantize_kv


def test_quantize_kv_int8_roundtrip():
    """Symmetric per-(row, position) int8: round-trip error bounded by
    one quantization step of that position's own scale."""
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 16))
    xi, s = quantize_kv(x, jnp.int8)
    assert xi.dtype == jnp.int8 and s.shape == (2, 9) and s.dtype == jnp.float32
    rt = xi.astype(jnp.float32) * s[:, :, None, None]
    step = jnp.max(jnp.abs(x), axis=(2, 3)) / 127.0
    assert float(jnp.max(jnp.abs(rt - x) - step[:, :, None, None])) <= 1e-6


def test_quantize_kv_bf16_cast():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 8))
    xb, s = quantize_kv(x, jnp.bfloat16)
    assert s is None and xb.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(xb, np.float32), np.asarray(x),
                               rtol=1e-2, atol=1e-2)


def test_decode_kernel_int8_fused_dequant():
    """int8 K/V with per-position scales inside the kernel == eager
    dequantize + f32 kernel (the dequant rides the block load)."""
    B, H, Hkv, dh, T = 3, 8, 2, 32, 60
    q, k, v = _qkv(jax.random.PRNGKey(3), B, H, Hkv, dh, T)
    lens = jnp.asarray([13, 60, 41])
    kq, ks = quantize_kv(k, jnp.int8)
    vq, vs = quantize_kv(v, jnp.int8)
    kd = kq.astype(jnp.float32) * ks[:, :, None, None]
    vd = vq.astype(jnp.float32) * vs[:, :, None, None]
    want = mha(q, kd, vd, causal=False, window=None, chunk=1, kv_len=lens)
    got = _da(q, kq, vq, kv_len=lens, interpret=True, k_scale=ks,
              v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # within quantization tolerance of the unquantized attention
    ref = mha(q, k, v, causal=False, window=None, chunk=1, kv_len=lens)
    assert float(jnp.max(jnp.abs(got - ref))) < 0.2


@pytest.mark.parametrize("blk_b", [1, 2, 3, 8])
def test_decode_kernel_batch_tiling(blk_b):
    """blk_b batch blocks (incl. zero-padding B=3 -> blk_b multiples)
    agree with the untiled kernel, with and without scales."""
    B, H, Hkv, dh, T = 3, 4, 2, 32, 48
    q, k, v = _qkv(jax.random.PRNGKey(4), B, H, Hkv, dh, T)
    lens = jnp.asarray([5, 48, 20])
    want = mha(q, k, v, causal=False, window=None, chunk=1, kv_len=lens)
    got = _da(q, k, v, kv_len=lens, interpret=True, blk_b=blk_b, blk_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    kq, ks = quantize_kv(k, jnp.int8)
    vq, vs = quantize_kv(v, jnp.int8)
    got8 = _da(q, kq, vq, kv_len=lens, interpret=True, blk_b=blk_b,
               blk_k=16, k_scale=ks, v_scale=vs)
    base8 = _da(q, kq, vq, kv_len=lens, interpret=True, k_scale=ks,
                v_scale=vs)
    np.testing.assert_allclose(np.asarray(got8), np.asarray(base8),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_decode_kernel_narrow_layout(kv):
    """The compiled (narrow) layout's math, run in interpret mode:
    scalar-prefetched per-row lengths and, for int8, dequant scales on
    score and probability columns instead of on the K/V block."""
    from repro.kernels.decode_attention import _decode_grouped

    B, H, Hkv, dh, T = 3, 8, 2, 32, 40
    q, k, v = _qkv(jax.random.PRNGKey(6), B, H, Hkv, dh, T)
    lens = jnp.asarray([7, 40, 23], jnp.int32)
    ks = vs = None
    if kv == "int8":
        k, ks = quantize_kv(k, jnp.int8)
        v, vs = quantize_kv(v, jnp.int8)
        kd = k.astype(jnp.float32) * ks[:, :, None, None]
        vd = v.astype(jnp.float32) * vs[:, :, None, None]
    else:
        kd, vd = k, v
    want = mha(q, kd, vd, causal=False, window=None, chunk=1, kv_len=lens)
    got = _decode_grouped(q[:, 0].reshape(B, Hkv, H // Hkv, dh), _pool(k),
                          _pool(v), 0, lens, ks, vs, blk_k=16, blk_b=B,
                          interpret=True, narrow=True)
    np.testing.assert_allclose(np.asarray(got.reshape(want.shape)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("narrow", [True, False])
@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
@pytest.mark.parametrize("layer", [0, 2])
def test_decode_kernel_pool_matches_layer_slice(narrow, kv, layer):
    """The kernel reading layer ``layer`` of a 3-layer pool by index
    equals, bit for bit, the same kernel run on that layer sliced out
    (a pool of one layer), in both layouts, bf16 and int8 with scales,
    with per-row lengths from 1 to T."""
    from repro.kernels.decode_attention import _decode_grouped

    L, B, H, Hkv, dh, T = 3, 4, 8, 2, 32, 64
    ks_ = jax.random.split(jax.random.PRNGKey(7 + layer), 3)
    q = jax.random.normal(ks_[0], (B, Hkv, H // Hkv, dh), jnp.bfloat16)
    kf = jax.random.normal(ks_[1], (L, B, T, Hkv, dh), jnp.float32)
    vf = jax.random.normal(ks_[2], (L, B, T, Hkv, dh), jnp.float32)
    lens = jnp.asarray([1, T, 17, 40], jnp.int32)
    if kv == "int8":
        (k, ksc), (v, vsc) = (jax.vmap(lambda x: quantize_kv(x, jnp.int8))(x)
                              for x in (kf, vf))
    else:
        k, v = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        ksc = vsc = None
    k, v = k.reshape(L, B, T, -1), v.reshape(L, B, T, -1)

    def one(x):  # layer `layer` of the pool, as a pool of one layer
        return None if x is None else x[layer][None]

    kw = dict(blk_k=16, blk_b=B, interpret=True, narrow=narrow)
    got = _decode_grouped(q, k, v, jnp.asarray(layer, jnp.int32), lens,
                          *(None if x is None else x[layer]
                            for x in (ksc, vsc)), **kw)
    want = _decode_grouped(q, one(k), one(v), 0, lens,
                           *(None if x is None else x[layer]
                             for x in (ksc, vsc)), **kw)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # the public entry takes the layer's int8 scales from the pool's
    q1 = q.reshape(B, 1, H, dh)
    kw = dict(kv_len=lens, blk_k=16, interpret=True)
    ent = decode_attention(q1, k, v, layer, k_scale=ksc, v_scale=vsc, **kw)
    sliced = decode_attention(q1, one(k), one(v), 0, k_scale=one(ksc),
                              v_scale=one(vsc), **kw)
    np.testing.assert_array_equal(np.asarray(ent, np.float32),
                                  np.asarray(sliced, np.float32))


def test_decode_kernel_scale_validation():
    q, k, v = _qkv(jax.random.PRNGKey(5), 2, 4, 2, 32, 16)
    ks = jnp.ones((2, 16), jnp.float32)
    with pytest.raises(ValueError, match="scale"):
        _da(q, k, v, k_scale=ks, interpret=True)


@pytest.mark.parametrize("kv,tol", [("bfloat16", 2e-2), ("int8", 0.25)])
def test_model_decode_quantized_kv(kv, tol):
    """Model-level: decode logits with a quantized cache stay within
    quantization tolerance, on both attention backends."""
    cfg = get_arch("qwen3-1.7b").reduced()
    cfg = dataclasses.replace(cfg, kv_dtype=kv)
    base = get_arch("qwen3-1.7b").reduced()
    params = Mo.init(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, base.vocab)
    for backend in ("jnp", "flash"):
        c_q = dataclasses.replace(cfg, attn_backend=backend)
        c_f = dataclasses.replace(base, attn_backend=backend)
        lq, cq = Mo.prefill(params, c_q, {"tokens": tokens}, cache_len=20)
        lf, cf = Mo.prefill(params, c_f, {"tokens": tokens}, cache_len=20)
        tok = jnp.argmax(lf[:, -1], -1).astype(jnp.int32)
        lq2, _ = Mo.decode_step(params, c_q, cq, tok)
        lf2, _ = Mo.decode_step(params, c_f, cf, tok)
        err = float(jnp.max(jnp.abs(lq2 - lf2)))
        assert err < tol * max(1.0, float(jnp.max(jnp.abs(lf2)))), (backend,
                                                                    err)
