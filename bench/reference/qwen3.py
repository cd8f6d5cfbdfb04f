"""Plain float32 Qwen3 decoder in jax.numpy, from the published equations.

Nothing here imports the program. Weights come in the layout of
``bench/weights.py``; each layer is cast to float32 as it is used, and
every matmul runs at ``highest`` precision (on a TPU a float32 dot is
otherwise rounded to bfloat16). ``quant="fp8"`` is the control: every
matmul operand rounded to float8_e4m3fn with a scale per row of the
activation and per output column of the weight, as an fp8 path would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _q8(x, axis):
    """Round to float8_e4m3fn with a max-abs scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@jax.custom_vjp
def _fp8_dot(a, b):
    return jnp.matmul(_q8(a, -1), _q8(b, -2), precision="highest")


def _fp8_dot_fwd(a, b):
    return _fp8_dot(a, b), (a, b)


def _fp8_dot_bwd(res, g):
    a, b = res
    gq = _q8(g, -1)
    da = jnp.matmul(gq, _q8(b, -2).swapaxes(-1, -2), precision="highest")
    db = jnp.matmul(_q8(a, -1).swapaxes(-1, -2), _q8(g, -2),
                    precision="highest")
    # a may carry batch dims that b lacks: sum them out of db
    db = db.reshape((-1,) + db.shape[-2:]).sum(0) if db.ndim > b.ndim else db
    return da, db


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def _dot(a, b, quant):
    """a [..., K] @ b [K, N] (b a weight) in float32, or fp8 operands."""
    if quant == "fp8":
        return _fp8_dot(a, b)
    return jnp.matmul(a, b, precision="highest")


def _bdot(eq, a, b, quant):
    """Attention's batched products, fp8 operands in the control."""
    if quant == "fp8":
        a = _q8(a, -1)
        b = _q8(b, -1) if eq.endswith("hqk") else _q8(b, -3)
    return jnp.einsum(eq, a, b, precision="highest")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate halves: x [S, H, dh], pos [S]."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, lp, conf, quant=None):
    """One decoder layer on one sequence x [S, D] (float32)."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh, D = conf["head_dim"], conf["hidden_size"]
    S = x.shape[0]
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    a = lp["attn"]
    h = rmsnorm(x, lp["norm_attn"], eps)
    q = _dot(h, a["wq"].reshape(D, H * dh), quant).reshape(S, H, dh)
    k = _dot(h, a["wk"].reshape(D, Hkv * dh), quant).reshape(S, Hkv, dh)
    v = _dot(h, a["wv"].reshape(D, Hkv * dh), quant).reshape(S, Hkv, dh)
    q = rmsnorm(q, a["q_norm"], eps)
    k = rmsnorm(k, a["k_norm"], eps)
    pos = jnp.arange(S)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    # grouped-query attention: query head i reads key/value head i // G
    G = H // Hkv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = _bdot("qhd,khd->hqk", q, k, quant) / jnp.sqrt(F32(dh))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _bdot("hqk,khd->qhd", p, v, quant).reshape(S, H * dh)
    x = x + _dot(o, a["wo"].reshape(H * dh, D), quant)
    h = rmsnorm(x, lp["norm_ffn"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(_dot(h, m["w_gate"], quant)) * _dot(h, m["w_up"], quant)
    return x + _dot(g, m["w_down"], quant)


def hidden(params, conf, tokens, quant=None, remat=False):
    """Final-normed hidden states [S, D] of one sequence."""
    x = params["embed"][tokens].astype(F32)
    body = functools.partial(layer, conf=conf, quant=quant)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x,
                        params["layers"])
    return rmsnorm(x, params["norm_f"].astype(F32), conf["rms_norm_eps"])


def logits(params, conf, tokens, quant=None):
    """Logits [S, V] float32 of one sequence (tied unembedding)."""
    h = hidden(params, conf, tokens, quant)
    return _dot(h, params["embed"].astype(F32).T, quant)


def seq_loss_sum(params, conf, tokens, quant=None, chunk=512):
    """Sum over positions 0..S-2 of the next-token cross-entropy."""
    h = hidden(params, conf, tokens, quant, remat=True)
    emb_t = params["embed"].astype(F32).T
    S = tokens.shape[0]
    labels = jnp.concatenate([tokens[1:], tokens[:1]])
    w = (jnp.arange(S) < S - 1).astype(F32)

    @jax.checkpoint
    def part(hc, lc, wc):
        lg = _dot(hc, emb_t, quant)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lc[:, None], -1)[:, 0]
        return jnp.sum((lse - gold) * wc)

    tot = 0.0
    for i in range(0, S, chunk):
        tot = tot + part(h[i:i + chunk], labels[i:i + chunk], w[i:i + chunk])
    return tot
