"""The main path's Pallas kernels compile for a TPU v5e chip, at real widths.

No chip is needed: the TPU compiler compiles for a described (not
attached) v5e chip and refuses what Mosaic would refuse on the device —
blocks off the (8, 128) tiling, layouts that disagree with XLA's, ops
Mosaic cannot lower, VMEM overuse. Interpret-mode tests cannot see
these. Each compiled program must hold the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.vrmom import aggregate_pallas, aggregate_sample_pallas

VOCAB = 151936                          # qwen3-1.7b
DECODE = dict(B=8, T=4096, Hkv=8, G=2, dh=128)  # qwen3-1.7b decode shape


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_with_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("method,m", [("vrmom", 4), ("vrmom", 8),
                                      ("vrmom", 64), ("median", 8),
                                      ("trimmed_mean", 8), ("mean", 8)])
def test_aggregate_compiles(one_chip, method, m):
    x = _sds(one_chip, (m, 2 ** 20), jnp.float32)
    _compiles_with_kernel(
        lambda x: aggregate_pallas(x, method, beta=0.25, interpret=False), x)


@pytest.mark.parametrize("top_k", [0, 40])
@pytest.mark.parametrize("b", [1, 8])
def test_fused_tail_compiles(one_chip, top_k, b):
    x = _sds(one_chip, (8, b, VOCAB), jnp.float32)
    _compiles_with_kernel(
        lambda x: aggregate_sample_pallas(x, "vrmom", top_k=top_k,
                                          interpret=False), x)


@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_decode_attention_compiles(one_chip, kv):
    B, T, Hkv, G, dh = (DECODE[k] for k in ("B", "T", "Hkv", "G", "dh"))
    q = _sds(one_chip, (B, 1, Hkv * G, dh), jnp.bfloat16)
    k = _sds(one_chip, (B, T, Hkv, dh), jnp.dtype(kv))
    lens = _sds(one_chip, (B,), jnp.int32)
    if kv == "int8":
        s = _sds(one_chip, (B, T), jnp.float32)
        _compiles_with_kernel(
            lambda q, k, v, l, sk, sv: decode_attention(
                q, k, v, kv_len=l, k_scale=sk, v_scale=sv, interpret=False),
            q, k, k, lens, s, s)
    else:
        _compiles_with_kernel(
            lambda q, k, v, l: decode_attention(q, k, v, kv_len=l,
                                                interpret=False),
            q, k, k, lens)


@pytest.mark.parametrize("S", [2048, 512, 100])
def test_flash_forward_compiles(one_chip, S):
    q = _sds(one_chip, (1, S, 16, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, S, 8, 128), jnp.bfloat16)
    _compiles_with_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv)
