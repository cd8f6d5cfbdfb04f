"""Required operations and bytes against hand counts at a small shape."""
import math

import jax
import pytest

import tiny
from bench import common, families, work


def test_matmul_params_by_hand():
    c = tiny.conf()
    # per layer: q 64x64, k 64x32, v 64x32, o 64x64, mlp 3x64x128
    layer = 64 * 64 + 64 * 32 + 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert work.matmul_params(c) == 2 * layer + 512 * 64


def test_decode_and_attention_counts_by_hand():
    c = tiny.conf()
    # one token over 10 keys: 2 per weight, and QK + PV: 2*2*L*H*dh*keys
    assert work.forward_flops(c, 1, 10) == (2 * work.matmul_params(c)
                                            + 4 * 2 * 4 * 16 * 10)
    # K and V of a key over 2 layers, 2 heads of 16, bf16: 2*2*2*16*2
    assert work.kv_bytes_per_key(c) == 256
    qo = 2 * 2 * 4 * 16 * 2
    assert work.decode_attention_bytes(c, [10, 3]) == 13 * 256 + 2 * qo


def test_prefill_train_and_roofline_by_hand():
    c = tiny.conf()
    p = work.matmul_params(c) - 512 * 64
    want = 2 * p * 8 + 2 * 512 * 64 + 4 * 2 * 4 * 16 * 36
    assert work.prefill_flops(c, [8]) == want
    assert work.train_flops(c, 2, 8) == 3 * 2 * work.forward_flops(c, 8, 36)
    assert work.robust_tail_bytes(8, 3, 100) == 8 * 3 * 100 * 2 + 12
    assert work.aggregate_bytes(4, 10) == 5 * 10 * 2
    share, bound = work.roofline_share(1e12, 1e9, 1.0, 1e12, 1e10)
    assert (share, bound) == (100.0, "compute")
    share, bound = work.roofline_share(0.0, 5e9, 1.0, 1e12, 1e10)
    assert share == pytest.approx(50.0) and bound == "bytes"


def test_decode_steps_expand_blocks():
    # a row with 5 keys written that delivers 3 tokens, one that delivers 1
    steps = work.decode_steps([[(5, 3), (9, 1)]])
    assert steps == [[6, 10], [7], [8]]


def test_percentile_is_nearest_rank_over_all():
    xs = list(range(1, 101))
    assert common.percentile(xs, 90) == 90
    assert common.percentile([5.0], 90) == 5.0
    assert common.percentile([3, 1, 2], 50) == 2



def test_n_params_counts_every_weight_leaf():
    c = tiny.conf()
    leaves = jax.tree.leaves(families.get(c).shapes(c),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert work.n_params(c) == sum(math.prod(s) for s in leaves)


def test_unknown_family_is_refused():
    c = dict(tiny.conf(), model_type="no_such_family")
    with pytest.raises(SystemExit, match="no_such_family"):
        work.matmul_params(c)


class _Dev:
    def __init__(self, in_use):
        self.in_use = in_use

    def memory_stats(self):
        return {"bytes_in_use": self.in_use, "peak_bytes_in_use": self.in_use}


def test_program_peak_adds_outputs_and_temporaries_to_what_is_in_use():
    x = jax.numpy.ones((256, 256), jax.numpy.float32)
    fn = jax.jit(lambda a: (a @ a).sum(0))
    ma = fn.lower(x).compile().memory_analysis()
    want = (1000 + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes)
    assert common.program_peak([_Dev(1000), _Dev(10)], fn, x) == want
    assert want >= 1000 + 256 * 4


def test_program_peak_is_zero_where_nothing_is_reported():
    assert common.program_peak([_Dev(5)], lambda a: a, 1.0) == 0
