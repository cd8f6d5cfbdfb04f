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
import re

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
    """The kernel reads a traced layer of a two-layer pool."""
    B, T, Hkv, G, dh = (DECODE[k] for k in ("B", "T", "Hkv", "G", "dh"))
    q = _sds(one_chip, (B, 1, Hkv * G, dh), jnp.bfloat16)
    k = _sds(one_chip, (2, B, T, Hkv * dh), jnp.dtype(kv))
    layer = _sds(one_chip, (), jnp.int32)
    lens = _sds(one_chip, (B,), jnp.int32)
    if kv == "int8":
        s = _sds(one_chip, (2, B, T), jnp.float32)
        _compiles_with_kernel(
            lambda q, k, v, i, l, sk, sv: decode_attention(
                q, k, v, i, kv_len=l, k_scale=sk, v_scale=sv,
                interpret=False),
            q, k, k, layer, lens, s, s)
    else:
        _compiles_with_kernel(
            lambda q, k, v, i, l: decode_attention(q, k, v, i, kv_len=l,
                                                   interpret=False),
            q, k, k, layer, lens)


@pytest.mark.parametrize("S", [2048, 512, 100])
def test_flash_forward_compiles(one_chip, S):
    q = _sds(one_chip, (1, S, 16, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, S, 8, 128), jnp.bfloat16)
    _compiles_with_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv)


_HLO_INST = re.compile(
    r"^\s*(ROOT )?%(\S+) = (.*?) ([\w-]+)\(([^)]*)\)")
_HLO_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_HLO_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _hlo_ops(text):
    """Optimized HLO text -> {name: (elements, opcode, operands, op_name,
    root)} for every instruction, fused computations included.
    ``elements`` is the largest array of the result (a tuple's largest
    element); ``root`` is, for a fusion, the root instruction of the
    computation it calls."""
    ops, roots, calls = {}, {}, {}
    comp = None
    for line in text.splitlines():
        c = _HLO_COMP.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _HLO_INST.match(line)
        if not m:
            continue
        is_root, name, result, opcode, operands = m.groups()
        n = 0
        for dims in _HLO_ARRAY.findall(result):
            size = 1
            for d in filter(None, dims.split(",")):
                size *= int(d)
            n = max(n, size)
        if is_root:
            roots[comp] = name
        called = re.search(r"calls=%([\w.-]+)", line)
        if called:
            calls[name] = called.group(1)
        op_name = re.search(r'op_name="([^"]*)"', line)
        ops[name] = [n, opcode, re.findall(r"%([\w.-]+)", operands),
                     op_name.group(1) if op_name else "", None]
    for name, comp in calls.items():
        ops[name][4] = roots.get(comp)
    return ops


def test_decode_loop_reads_pool_in_place(one_chip, monkeypatch):
    """An 8-step scan of ``decode_step`` at qwen3-1.7b widths (2 layers,
    16 slots of 2048 keys, bf16) compiled with the kernel: inside the
    loops no op whose result (or a tuple's largest element) is as large
    as one layer's cache, save loop plumbing that moves no data and a
    write of one row per slot (the in-place scatter, bare or as the root
    of a fusion): no slice, copy, relayout, transpose or async copy. The
    entry copy of a pool the caller does not donate lies outside the
    loops."""
    import dataclasses
    import sys

    from repro.configs import get as get_arch
    from repro.models import attn_backend as AB
    from repro.models import model as M
    from repro.serve.cache import vectorize_pos

    monkeypatch.setattr(sys.modules["repro.kernels.decode_attention"],
                        "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_arch("qwen3-1.7b"), n_layers=2)
    B, T = 16, 2048
    Hkv, dh = cfg.n_kv_heads, cfg.head_dim
    assert AB.decode_reads_pool(cfg, T)

    def sds(tree):
        return jax.tree.map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = sds(jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg)))
    caches = sds(jax.eval_shape(
        lambda: vectorize_pos(M.init_cache(cfg, B, T), B)))

    def loop(params, caches, tok):
        def body(carry, _):
            caches, tok = carry
            logits, caches = M.decode_step(params, cfg, caches, tok)
            return (caches, jnp.argmax(logits, -1).astype(jnp.int32)), None

        return jax.lax.scan(body, (caches, tok), None, length=8)[0]

    text = jax.jit(loop).lower(params, caches,
                               _sds(one_chip, (B,), jnp.int32)
                               ).compile().as_text()
    assert "tpu_custom_call" in text
    layer_cache, row = B * T * Hkv * dh, B * Hkv * dh
    ops = _hlo_ops(text)

    def row_write(name):  # a write of at most one row per slot
        n, opcode, operands, _, root = ops[name]
        if opcode == "fusion":
            return root is not None and row_write(root)
        if opcode not in ("dynamic-update-slice", "scatter"):
            return False
        upd = operands[1 if opcode == "dynamic-update-slice" else 2]
        return upd in ops and ops[upd][0] <= row

    # ops that move no data: loop plumbing and aliasing views
    plumbing = ("parameter", "get-tuple-element", "tuple", "while",
                "bitcast")
    bad = [(name, opcode, n, op_name)
           for name, (n, opcode, _, op_name, _) in ops.items()
           if "while" in op_name and n >= layer_cache
           and opcode not in plumbing and not row_write(name)]
    assert not bad, bad
