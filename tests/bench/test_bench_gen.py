"""The traffic generator: deterministic in the seed, and the same work
for every seed."""
import collections

import numpy as np
import pytest

import tiny
from bench import gen

SEEDS = [0, 7, 2**31 + 11, 2**33 + 5]


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_requests_deterministic(seed):
    mix = gen.load_mix("decode-heavy.robust-m8")
    a = gen.serve_requests(mix, 151936, seed)
    b = gen.serve_requests(mix, 151936, seed)
    assert len(a) == len(b) == mix["pool_requests"]
    for (pa, na), (pb, nb) in zip(a, b):
        assert na == nb and np.array_equal(pa, pb)


def test_serve_sizes_are_one_multiset_in_seed_orders():
    mix = gen.load_mix("decode-heavy.robust-m8")
    sizes = [collections.Counter((p.shape[0], n) for p, n in
                                 gen.serve_requests(mix, 1000, s))
             for s in SEEDS]
    assert all(s == sizes[0] for s in sizes)
    orders = [[(p.shape[0], n) for p, n in gen.serve_requests(mix, 1000, s)]
              for s in SEEDS[:2]]
    assert orders[0] != orders[1]
    lens = {p for p, _ in sizes[0]}
    assert lens <= set(mix["prompt"]["buckets"])
    outs = [n for _, n in sizes[0].elements()]
    assert min(outs) >= mix["output"]["min"]
    assert max(outs) <= mix["output"]["max"]
    assert max(p for p, _ in sizes[0]) + max(outs) + mix["decode_block"] \
        - 1 <= mix["max_len"]


@pytest.mark.parametrize("seed", SEEDS)
def test_lm_batch_deterministic_rows_differ(seed):
    mix = tiny.train_mix()
    a = gen.lm_batch(mix, 512, seed, 3)
    assert np.array_equal(a, gen.lm_batch(mix, 512, seed, 3))
    assert not np.array_equal(a, gen.lm_batch(mix, 512, seed, 4))
    assert a.shape == (mix["global_batch"], mix["seq"])
    assert len({r.tobytes() for r in a}) == a.shape[0]
    assert a.min() >= 0 and a.max() < 512


@pytest.mark.parametrize("dist, centre", [
    ({"dist": "exponential", "mean": 300.0}, "mean"),
    ({"dist": "lognormal", "median": 256, "sigma": 0.8}, "median"),
    ({"dist": "uniform", "min": 128, "max": 512}, "median")])
def test_length_laws_at_their_mid_quantiles(dist, centre):
    vals = gen._quantiles(dist, 64)
    assert vals == sorted(vals) and len(vals) == 64
    if centre == "mean":
        assert abs(np.mean(vals) - dist["mean"]) < 0.02 * dist["mean"]
    elif dist["dist"] == "lognormal":
        assert vals[31] <= dist["median"] <= vals[32] + 1
    else:
        assert min(vals) >= dist["min"] and max(vals) <= dist["max"]
        assert vals[31] <= (dist["min"] + dist["max"]) / 2 <= vals[32] + 1


def test_length_over_the_largest_bucket_is_refused():
    with pytest.raises(ValueError, match="largest bucket"):
        gen._quantiles({"dist": "exponential", "mean": 300.0,
                        "buckets": [128, 256]}, 16)
