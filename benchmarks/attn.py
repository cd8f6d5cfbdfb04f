"""Decode-attention micro-benchmark: fused kernel vs the jnp ``mha``.

Times the two execution backends of the serving decode hot loop
(DESIGN.md §8) at serving shapes — a single query per row over a KV
cache of T in {256, 1k, 4k} at the assigned archs' 4:1 GQA ratio — on
whatever backend this host has (the Pallas kernel runs in interpret
mode off-TPU: wide-tile config, correctness- and trend-representative).
The jnp row is the chunked ``mha`` exactly as the models run it
(per-row ``kv_len``, f32 scores); the flash row is
``kernels/decode_attention`` through the same jit, reading the cache as
a one-layer pool ``[1, B, T, Hkv*dh]`` (layer 0), the layout the
serving decode loop hands it.

Emits ``BENCH_attn.json``:

    {"B": 8, "H": 32, "Hkv": 8, "dh": 128,
     "us": {"T256": {"jnp": ..., "flash": ...}, ...},
     "speedup_vs_jnp": {"T256": ..., ...}}

  PYTHONPATH=src python -m benchmarks.attn [--batch 8] [--seqs 256,1024,4096]
      [--out BENCH_attn.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention
from repro.models.attention import mha


def _time(fn, *args, iters=5):
    """Best-of-``iters`` wall time after one warm-up (compile) call."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # us


def bench_decode(B=8, H=32, Hkv=8, dh=128, seqs=(256, 1024, 4096), iters=5,
                 out=None):
    rows, us_table = [], {}
    f_jnp = jax.jit(lambda q, k, v, l: mha(q, k, v, causal=False, window=None,
                                           chunk=1, kv_len=l))
    f_flash = jax.jit(lambda q, k, v, l: decode_attention(q, k, v, 0,
                                                           kv_len=l))
    for T in seqs:
        ks = jax.random.split(jax.random.PRNGKey(T), 3)
        q = jax.random.normal(ks[0], (B, 1, H, dh))
        k = jax.random.normal(ks[1], (B, T, Hkv, dh))
        v = jax.random.normal(ks[2], (B, T, Hkv, dh))
        # per-row lengths: the slot-serving signature (rows at different
        # fill levels), not the easier scalar special case
        lens = jnp.linspace(T // 2, T, B).astype(jnp.int32)
        kp, vp = (x.reshape(1, B, T, Hkv * dh) for x in (k, v))
        err = float(jnp.max(jnp.abs(f_jnp(q, k, v, lens)
                                    - f_flash(q, kp, vp, lens))))
        us = {"jnp": _time(f_jnp, q, k, v, lens, iters=iters),
              "flash": _time(f_flash, q, kp, vp, lens, iters=iters)}
        us_table[f"T{T}"] = us
        for backend, t in us.items():
            rows.append((f"attn/decode/{backend}/b{B}xT{T}", t,
                         err if backend == "flash" else 0.0))
    if out:
        result = {
            "B": B, "H": H, "Hkv": Hkv, "dh": dh,
            "backend": jax.default_backend(),
            "us": us_table,
            "speedup_vs_jnp": {
                key: t["jnp"] / t["flash"] for key, t in us_table.items()},
        }
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"# wrote {out}", file=sys.stderr)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8,
                    help="GQA 4:1 by default (llama/starcoder class)")
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seqs", default="256,1024,4096")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="BENCH_attn.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    rows = bench_decode(B=args.batch, H=args.heads, Hkv=args.kv_heads,
                        dh=args.head_dim,
                        seqs=[int(s) for s in args.seqs.split(",")],
                        iters=args.iters, out=args.out)
    for row in rows:
        print(f"{row[0]},{row[1]:.6g},{row[2]:.6g}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
