"""The one traffic generator: turns a mix's data file and a seed into inputs.

A mix (``bench/traffic/<name>.json``) holds only parameters. Every seed
gets the same request sizes, in another order, so that two seeds differ
in order and token ids but not in the amount of work.
The program under test receives only what these functions return.
"""
from __future__ import annotations

import bisect
import json
import math
import statistics
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream...), for any seed >= 0."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def _quantiles(dist: dict, n: int) -> list:
    """n values at the mid-quantiles (i + 0.5) / n of ``dist``."""
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "exponential":
        # the largest-entropy law on lengths with a given mean: the one
        # to take where a source gives the mean alone
        vals = [-dist["mean"] * math.log1p(-q) for q in qs]
    elif dist["dist"] == "lognormal":
        nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
        vals = [math.exp(nd.inv_cdf(q)) for q in qs]
    elif dist["dist"] == "uniform":
        lo, hi = dist["min"], dist["max"]
        vals = [lo + q * (hi - lo + 1) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    out = [int(min(max(math.floor(v), lo), hi)) for v in vals]
    buckets = dist.get("buckets")
    if buckets:
        if max(out) > buckets[-1]:
            raise ValueError(f"length {max(out)} over the largest bucket")
        out = [buckets[bisect.bisect_left(buckets, v)] for v in out]
    return out


def request_sizes(mix: dict) -> list:
    """One stratum of (prompt_len, max_new_tokens): the mid-quantiles of
    both distributions, paired by a fixed permutation, so the pairing
    carries no correlation and is the same for every seed."""
    n = mix["stratum"]
    prompts = _quantiles(mix["prompt"], n)
    outputs = _quantiles(mix["output"], n)
    pairing = np.random.default_rng(0).permutation(n)
    return [(prompts[i], outputs[j]) for i, j in zip(range(n), pairing)]


def serve_requests(mix: dict, vocab: int, seed: int) -> list:
    """-> list of (prompt int32 [S], max_new_tokens): ``pool_requests``
    requests, stratum after stratum, each stratum the same sizes in the
    seed's order. Any run of consecutive requests then holds nearly the
    same mix of sizes, whatever the seed."""
    sizes = request_sizes(mix)
    n = len(sizes)
    order_rng, tok_rng = rng_for(seed, 0), rng_for(seed, 1)
    out = []
    for _ in range(mix["pool_requests"] // n):
        for i in order_rng.permutation(n):
            p, m = sizes[i]
            out.append((tok_rng.integers(0, vocab, size=(p,),
                                         dtype=np.int32), m))
    return out


def lm_batch(mix: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """Tokens [global_batch, seq] int32 of a noisy integer AR process.

    Each row starts at a random id and walks with a random drift plus
    noise, so next-token loss is learnable and no two rows are alike.
    """
    b, s = mix["global_batch"], mix["seq"]
    lm = mix["lm"]
    rng = rng_for(seed, 2, step)
    drift = rng.integers(lm["drift_min"], lm["drift_max"], size=(b, 1))
    start = rng.integers(0, vocab, size=(b, 1))
    noise = rng.integers(0, lm["noise_max"], size=(b, s))
    toks = (start + drift * np.arange(s)[None, :] + noise) % vocab
    return toks.astype(np.int32)
