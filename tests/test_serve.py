"""repro.serve: slot cache, fused decode loop, continuous batching,
Byzantine-robust replicated decoding."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get as get_arch
from repro.models import attn_backend as AB
from repro.models import model as Mo
from repro.serve import (Request, RobustDecodeConfig, Sampling, Scheduler,
                         ServeEngine, replica_mask, robust_logits)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dense():
    cfg = get_arch("qwen3-1.7b").reduced()
    params = Mo.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt_batch(cfg, B, S, seed=1):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                         cfg.vocab)}


# ---------------------------------------------------------------------------
# Engine: scanned decode loop
# ---------------------------------------------------------------------------

def test_scanned_loop_matches_python_loop(dense):
    """The fused lax.scan decode must be token-identical to per-step
    Python dispatch (greedy)."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=48)
    batch = _prompt_batch(cfg, B=4, S=16)
    scan = eng.generate(batch, 12)
    loop = eng.generate_python_loop(batch, 12)
    assert scan.shape == (4, 12)
    np.testing.assert_array_equal(np.asarray(scan), np.asarray(loop))


def test_sampling_modes(dense):
    """Temperature / top-k sampling produce in-vocab tokens and differ
    across keys; top-k=1 degenerates to greedy."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=40)
    batch = _prompt_batch(cfg, B=2, S=8)
    t = eng.generate(batch, 8, sampling=Sampling("temperature", 1.5),
                     key=jax.random.PRNGKey(3))
    assert bool(jnp.all((t >= 0) & (t < cfg.vocab)))
    t2 = eng.generate(batch, 8, sampling=Sampling("temperature", 1.5),
                      key=jax.random.PRNGKey(4))
    assert not bool(jnp.all(t == t2))  # different keys, different draws
    k1 = eng.generate(batch, 8, sampling=Sampling("top_k", 1.0, top_k=1),
                      key=jax.random.PRNGKey(5))
    greedy = eng.generate(batch, 8)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))


# ---------------------------------------------------------------------------
# Scheduler: continuous batching (satellite coverage)
# ---------------------------------------------------------------------------

def test_scheduler_variable_length_admission(dense):
    """Variable-length prompts through the pool must match per-request
    solo decode exactly (per-slot lengths isolate the rows)."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=64, n_slots=3)
    sched = Scheduler(eng, decode_block=4)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab, size=(n,)) for n in (5, 17, 11)]
    uids = [sched.submit(Request(tokens=p, max_new_tokens=7))
            for p in prompts]
    done = sched.run()
    assert sorted(done) == sorted(uids)
    for u, p in zip(uids, prompts):
        solo = eng.generate({"tokens": jnp.asarray(p)[None]}, 7)
        assert done[u].tokens == list(map(int, solo[0]))
        assert done[u].finished_by == "length"


def test_scheduler_slot_reuse_after_retirement(dense):
    """A slot freed by a short request must be reused mid-decode by a
    queued one, without disturbing the still-running slots."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=64, n_slots=2)
    sched = Scheduler(eng, decode_block=2)
    rs = np.random.RandomState(1)
    short = Request(tokens=rs.randint(0, cfg.vocab, size=(6,)),
                    max_new_tokens=2)
    long = Request(tokens=rs.randint(0, cfg.vocab, size=(9,)),
                   max_new_tokens=12)
    late = Request(tokens=rs.randint(0, cfg.vocab, size=(4,)),
                   max_new_tokens=8)
    uids = [sched.submit(r) for r in (short, long, late)]
    # only 2 slots: `late` waits until `short` retires, then decodes
    # alongside `long`, which must be unaffected.
    done = sched.run()
    assert sorted(done) == sorted(uids)
    for u, r in zip(uids, (short, long, late)):
        assert len(done[u].tokens) == r.max_new_tokens
        solo = eng.generate({"tokens": jnp.asarray(r.tokens)[None]},
                            r.max_new_tokens)
        assert done[u].tokens == list(map(int, solo[0]))


def test_scheduler_queue_starvation(dense):
    """More requests than slots: FIFO admission drains the whole queue."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=48, n_slots=2)
    sched = Scheduler(eng, decode_block=3)
    rs = np.random.RandomState(2)
    uids = [sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(4 + i,)),
                                 max_new_tokens=3))
            for i in range(7)]
    done = sched.run()
    assert sorted(done) == sorted(uids)
    assert all(len(done[u].tokens) == 3 for u in uids)


def test_scheduler_rejects_oversized_requests(dense):
    """A request whose prompt + budget cannot fit a slot is rejected
    onto completed (not crashed, not silently cache-corrupted), and the
    queue behind it still drains."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=24, n_slots=1)
    sched = Scheduler(eng, decode_block=2)
    rs = np.random.RandomState(4)
    big = sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(40,)),
                               max_new_tokens=4))
    tight = sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(10,)),
                                 max_new_tokens=20))  # 10+20+1 > 24
    ok = sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(10,)),
                              max_new_tokens=4))
    done = sched.run()
    assert done[big].finished_by == "rejected" and done[big].tokens == []
    assert done[tight].finished_by == "rejected"
    assert done[ok].finished_by == "length" and len(done[ok].tokens) == 4


def test_engine_capacity_check(dense):
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=24)
    batch = _prompt_batch(cfg, B=1, S=20)
    with pytest.raises(ValueError, match="cache slots"):
        eng.generate(batch, 10)


def test_scheduler_eos_trims_overshoot(dense):
    """EOS mid-block stops the sequence; overshoot tokens are trimmed."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=48, n_slots=1)
    # find the token greedy decode emits at step 2, use it as "EOS"
    probe = eng.generate(_prompt_batch(cfg, B=1, S=8, seed=9), 8)
    eos = int(probe[0, 2])
    sched = Scheduler(eng, decode_block=8)
    tokens = np.asarray(_prompt_batch(cfg, B=1, S=8, seed=9)["tokens"][0])
    uid = sched.submit(Request(tokens=tokens, max_new_tokens=8, eos_id=eos))
    done = sched.run()
    assert done[uid].finished_by == "eos"
    assert done[uid].tokens == list(map(int, probe[0, :3]))


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-7b",
                                  "whisper-medium"])
def test_pool_decode_other_families(arch):
    """Slot pool + per-slot positions across cache layouts (SSM state,
    hybrid grouped stacks, enc-dec cross caches)."""
    cfg = get_arch(arch).reduced()
    params = Mo.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_len=40, n_slots=2)
    sched = Scheduler(eng, decode_block=2)
    rs = np.random.RandomState(3)
    reqs = []
    for i in range(3):
        extras = None
        if cfg.family == "encdec":
            extras = {"frames": rs.randn(cfg.encoder.n_frames,
                                         cfg.d_model).astype(np.float32)}
        reqs.append(Request(tokens=rs.randint(0, cfg.vocab, size=(5 + 3 * i,)),
                            max_new_tokens=4, extras=extras))
    uids = [sched.submit(r) for r in reqs]
    done = sched.run()
    for u, r in zip(uids, reqs):
        batch = {"tokens": jnp.asarray(r.tokens)[None]}
        if r.extras:
            batch.update({k: jnp.asarray(v)[None]
                          for k, v in r.extras.items()})
        solo = eng.generate(batch, 4)
        assert done[u].tokens == list(map(int, solo[0]))


# ---------------------------------------------------------------------------
# Robust replicated decoding (acceptance criterion)
# ---------------------------------------------------------------------------

def test_replica_mask_counts():
    mask = replica_mask(8, 0.25)
    assert int(mask.sum()) == 2 and not bool(mask[0])
    with pytest.raises(ValueError):
        replica_mask(8, 0.5)  # 4/8 corrupted: no honest majority


@pytest.mark.parametrize("attack", ["signflip", "gaussian"])
@pytest.mark.parametrize("aggregator", ["vrmom", "median", "trimmed_mean"])
def test_robust_decode_token_identical_under_attack(dense, attack,
                                                    aggregator):
    """floor(alpha*m)=2 of m=8 replicas corrupted: greedy replicated
    decode must be token-identical to single-replica decode."""
    cfg, params = dense
    batch = _prompt_batch(cfg, B=2, S=12)
    plain = ServeEngine(cfg, params, max_len=40).generate(batch, 10)
    kw = dict(K=8) if aggregator == "vrmom" else {}
    reng = ServeEngine(cfg, params, max_len=40,
                       robust=RobustDecodeConfig(
                           m=8, estimator=aggregator, attack=attack,
                           alpha=0.25, **kw))
    robust = reng.generate(batch, 10, key=jax.random.PRNGKey(11))
    np.testing.assert_array_equal(np.asarray(robust), np.asarray(plain))


def test_robust_flash_backend_token_identical_under_attack(dense):
    """Fused end-to-end decode (kernel attention + kernel aggregation,
    DESIGN.md §8): attn_backend='flash' with m=8 replicated decode under
    signflip must still be token-identical to plain single-replica
    decode — the backend changes execution, never tokens."""
    cfg, params = dense
    batch = _prompt_batch(cfg, B=2, S=12)
    plain = ServeEngine(cfg, params, max_len=40,
                        attn_backend="jnp").generate(batch, 10)
    reng = ServeEngine(cfg, params, max_len=40, attn_backend="flash",
                       robust=RobustDecodeConfig(m=8, estimator="vrmom", K=8,
                                                 attack="signflip",
                                                 alpha=0.25))
    robust = reng.generate(batch, 10, key=jax.random.PRNGKey(11))
    np.testing.assert_array_equal(np.asarray(robust), np.asarray(plain))


def test_mean_aggregation_breaks_under_attack(dense):
    """Control: non-robust mean aggregation is corrupted by an attack
    the robust aggregators survive (omniscient: the corrupted rows drag
    the mean to a huge negative multiple of the honest logits)."""
    cfg, params = dense
    batch = _prompt_batch(cfg, B=2, S=12)
    plain = ServeEngine(cfg, params, max_len=40).generate(batch, 10)
    meng = ServeEngine(cfg, params, max_len=40,
                       robust=RobustDecodeConfig(m=8, estimator="mean",
                                                 attack="omniscient",
                                                 alpha=0.25))
    mean_toks = meng.generate(batch, 10, key=jax.random.PRNGKey(11))
    assert not bool(jnp.all(mean_toks == plain))


def test_robust_pool_decode_token_identical_under_attack(dense):
    """Continuous batching + replicated decode: the pool path flattens
    replicas into the slot dim per decode block (and restores them for
    admit/evict) — completions must still match plain solo decode under
    attack, across mid-decode admissions."""
    cfg, params = dense
    plain = ServeEngine(cfg, params, max_len=64, n_slots=2)
    reng = ServeEngine(cfg, params, max_len=64, n_slots=2,
                       robust=RobustDecodeConfig(m=4, estimator="vrmom", K=8,
                                                 attack="signflip",
                                                 alpha=0.25))
    sched = Scheduler(reng, decode_block=3)
    rs = np.random.RandomState(7)
    reqs = [Request(tokens=rs.randint(0, cfg.vocab, size=(5 + 2 * i,)),
                    max_new_tokens=6) for i in range(3)]
    uids = [sched.submit(r) for r in reqs]
    done = sched.run()
    assert sorted(done) == sorted(uids)
    for u, r in zip(uids, reqs):
        solo = plain.generate({"tokens": jnp.asarray(r.tokens)[None]}, 6)
        assert done[u].tokens == list(map(int, solo[0]))


def test_flatten_unflatten_replicas_roundtrip(dense):
    """flatten_replicas is a bijection on replica-stacked cache trees."""
    from repro.serve.robust import (flatten_replicas, stack_replicas,
                                    unflatten_replicas)
    from repro.serve import cache as C

    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=32, n_slots=3)
    dims = C.slot_dims(eng._pool_caches)
    caches = eng._pool_caches(3)
    rep = stack_replicas(caches, 4)
    flat = flatten_replicas(rep, dims, 4)
    back = unflatten_replicas(flat, dims, 4)
    for a, b in zip(jax.tree.leaves(rep), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_robust_logits_exactness():
    """With identical honest rows, the aggregate IS the honest row
    bit-exactly (degenerate-scale guard makes VRMOM the median)."""
    key = jax.random.PRNGKey(0)
    honest = jax.random.normal(key, (3, 32))
    stacked = jnp.broadcast_to(honest[None], (8,) + honest.shape)
    rcfg = RobustDecodeConfig(m=8, estimator="vrmom", K=8,
                              attack="gaussian", alpha=0.25)
    agg = robust_logits(stacked, rcfg, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(agg), np.asarray(honest))


# ---------------------------------------------------------------------------
# Sharded pool smoke (cache_specs plug-in), subprocess with 8 devices
# ---------------------------------------------------------------------------

def test_pool_specs_shard_and_decode():
    """Pool sharded via serve.cache.pool_specs on a (4 data, 2 model)
    mesh decodes token-identically to the unsharded pool."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get as get_arch
from repro.dist import ctx as CTX, sharding as S
from repro.models import attn_backend as AB
from repro.models import model as Mo
from repro.serve import Request, Scheduler, ServeEngine
from repro.serve import cache as C

cfg = get_arch("qwen3-1.7b").reduced()
params = Mo.init(jax.random.PRNGKey(0), cfg)
eng = ServeEngine(cfg, params, max_len=32, n_slots=4)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab)}
want = np.stack([np.asarray(
    eng.generate({"tokens": batch["tokens"][i:i+1]}, 6))[0]
    for i in range(4)])

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
pool = eng.make_pool()
specs = C.pool_specs(cfg, pool, mesh, batch_axes=("data",))
named = S.to_named(mesh, specs)
pool = jax.tree.map(lambda s, x: jax.device_put(x, s), named, pool,
                    is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding))
for slot in range(4):
    pool, tok = eng.admit(pool, slot, {"tokens": batch["tokens"][slot:slot+1]})
    assert tok == int(want[slot, 0]), (slot, tok, want[slot, 0])
cur = np.asarray(want[:, 0], np.int32)
with CTX.mesh_context(mesh):
    pool, toks = eng.decode_pool(pool, cur, 5)
got = np.concatenate([cur[:, None], np.asarray(toks).T], axis=1)
np.testing.assert_array_equal(got, want)
print("SHARDED-POOL-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "SHARDED-POOL-OK" in r.stdout


# ---------------------------------------------------------------------------
# Fused robust-decode tail (DESIGN.md §12)
# ---------------------------------------------------------------------------

from repro.core.estimator import Estimator
from repro.serve import robust as Ro


@pytest.mark.parametrize("m", [4, 8])
@pytest.mark.parametrize("method", ["median", "mom", "trimmed_mean",
                                    "vrmom"])
@pytest.mark.parametrize("alpha,attack", [(0.0, "none"), (0.25, "signflip"),
                                          (0.25, "gaussian")])
def test_fused_robust_sample_greedy_identity(dense, m, method, alpha, attack):
    """robust_sample with fuse_tail on/off: greedy tokens bit-identical
    across every estimator x replica count x attack cell (logit level —
    the model forward is shared, so this isolates the tail)."""
    cfg, _ = dense
    est = Estimator(method=method,
                    beta=0.25 if method == "trimmed_mean" else 0.1)
    logits_r = 4.0 * jax.random.normal(
        jax.random.PRNGKey(m), (m, 3, cfg.vocab), jnp.float32)
    akey, skey = jax.random.split(jax.random.PRNGKey(2))
    sc = Sampling()  # greedy
    tok_f = Ro.robust_sample(
        logits_r, RobustDecodeConfig(m=m, alpha=alpha, attack=attack,
                                     estimator=est, fuse_tail=True),
        akey, skey, sc)
    tok_u = Ro.robust_sample(
        logits_r, RobustDecodeConfig(m=m, alpha=alpha, attack=attack,
                                     estimator=est, fuse_tail=False),
        akey, skey, sc)
    np.testing.assert_array_equal(np.asarray(tok_f), np.asarray(tok_u))


def test_fused_engine_greedy_identity(dense):
    """End-to-end: fused vs unfused engines emit identical greedy tokens
    through prefill + the scanned decode loop under attack."""
    cfg, params = dense
    batch = _prompt_batch(cfg, B=4, S=12)
    toks = {}
    for fused in (True, False):
        eng = ServeEngine(cfg, params, max_len=32, robust=RobustDecodeConfig(
            m=8, alpha=0.25, attack="signflip", estimator="vrmom",
            fuse_tail=fused))
        toks[fused] = np.asarray(eng.generate(batch, 8,
                                              key=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(toks[True], toks[False])


def test_fused_topk_sampling_distribution(dense):
    """Fused top-k tail samples from the same distribution as the
    unfused path: over many keys, per-position token histograms agree
    within sampling noise (the kernels share values but draw through
    differently-shaped gumbel tensors, so tokens differ per-key)."""
    cfg, params = dense
    logits_r = 4.0 * jax.random.normal(jax.random.PRNGKey(0),
                                       (4, 2, cfg.vocab), jnp.float32)
    sc = Sampling("top_k", temperature=1.0, top_k=5)
    # 256 iid draws per original batch row by tiling the batch axis:
    # the sampling epilogue draws per-row gumbels, so tiled rows are
    # independent repeats of the same two distributions.
    reps = 256
    big = jnp.tile(logits_r, (1, reps, 1))  # [4, reps*2, V]
    draws = {}
    for fused in (True, False):
        rcfg = RobustDecodeConfig(m=4, alpha=0.0, attack="none",
                                  estimator="vrmom", fuse_tail=fused)
        akey, skey = jax.random.split(jax.random.PRNGKey(1))
        draws[fused] = np.asarray(
            Ro.robust_sample(big, rcfg, akey, skey, sc)).reshape(reps, 2)
    # same support, against the aggregate rcfg actually builds
    # (__post_init__ pins VRMOM's K, so a bare Estimator would differ)
    agg = Ro.robust_logits(logits_r, rcfg)
    top5 = np.asarray(jax.lax.top_k(agg, 5)[1])
    for d in draws.values():
        for b in range(2):
            assert set(np.unique(d[:, b])) <= set(top5[b])
    # distributions agree: total-variation distance over the top-5
    # support within Monte-Carlo noise for 256 draws
    for b in range(2):
        pf = np.array([(draws[True][:, b] == t).mean() for t in top5[b]])
        pu = np.array([(draws[False][:, b] == t).mean() for t in top5[b]])
        assert 0.5 * np.abs(pf - pu).sum() < 0.15, (b, pf, pu)


def test_deterministic_loop_skips_key_split(dense):
    """Greedy + attack='none' decode consumes no randomness: any key
    yields the same tokens (the per-step threefry split is elided)."""
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=32,
                      robust=RobustDecodeConfig(m=4, estimator="vrmom"))
    batch = _prompt_batch(cfg, B=2, S=8)
    a = np.asarray(eng.generate(batch, 8, key=jax.random.PRNGKey(0)))
    b = np.asarray(eng.generate(batch, 8, key=jax.random.PRNGKey(99)))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Quantized KV cache in the serve path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["bfloat16", "int8"])
def test_engine_quantized_kv_token_identity(dense, kv):
    """Greedy tokens survive KV quantization on a short horizon (the
    reduced model's logit margins dwarf bf16/int8 rounding)."""
    cfg, params = dense
    batch = _prompt_batch(cfg, B=4, S=10)
    ref_t = np.asarray(ServeEngine(cfg, params, max_len=24)
                       .generate(batch, 6))
    got = np.asarray(ServeEngine(cfg, params, max_len=24, kv_dtype=kv)
                     .generate(batch, 6))
    assert (ref_t == got).mean() > 0.9, kv


def test_pool_decode_quantized_kv(dense):
    """Continuous batching at bf16 KV: scheduler completes mixed-length
    requests with the same tokens as the f32 pool."""
    cfg, params = dense

    def run(kv):
        eng = ServeEngine(cfg, params, max_len=24, n_slots=3, kv_dtype=kv)
        sched = Scheduler(eng, sampling=Sampling())
        batch = _prompt_batch(cfg, B=3, S=10)
        for i in range(3):
            sched.submit(Request(tokens=np.asarray(batch["tokens"][i][:6 + i]),
                                 max_new_tokens=5))
        return {rid: np.asarray(r.tokens) for rid, r in sched.run().items()}

    ref_t, got = run(None), run("bfloat16")
    assert sorted(ref_t) == sorted(got)
    same = [np.array_equal(ref_t[r], got[r]) for r in ref_t]
    assert np.mean(same) >= 2 / 3, same


def test_kv_bytes_per_slot_gauge(dense):
    """serve.kv_bytes_per_slot reports the quantization win: bf16 halves
    and int8 (data + f32 scales) cuts ~4x the f32 per-slot bytes."""
    from repro.obs import MetricsRegistry
    cfg, params = dense
    g = {}
    for kv in (None, "bfloat16", "int8"):
        reg = MetricsRegistry()
        ServeEngine(cfg, params, max_len=32, kv_dtype=kv, obs=reg)
        g[kv] = reg.snapshot()["gauges"]["serve.kv_bytes_per_slot"]
    assert g[None] > g["bfloat16"] > g["int8"] > 0
    assert abs(g["bfloat16"] / g[None] - 0.5) < 0.05
    assert g["int8"] < 0.35 * g[None]


def test_robust_engine_quantized_kv(dense):
    """Replica-stacked pool slots carry quantized KV too: the
    replicated emulation's per-slot bytes scale by m, the shared one's
    don't, and both decode the same tokens over a bf16 cache."""
    from repro.obs import MetricsRegistry
    cfg, params = dense
    toks, gauges = {}, {}
    for shared in (True, False):
        reg = MetricsRegistry()
        eng = ServeEngine(cfg, params, max_len=24, kv_dtype="bfloat16",
                          robust=RobustDecodeConfig(
                              m=4, alpha=0.25, attack="signflip",
                              estimator="vrmom",
                              share_replica_compute=shared),
                          obs=reg)
        batch = _prompt_batch(cfg, B=2, S=8)
        toks[shared] = np.asarray(eng.generate(batch, 6,
                                               key=jax.random.PRNGKey(0)))
        gauges[shared] = reg.snapshot()["gauges"]["serve.kv_bytes_per_slot"]
    np.testing.assert_array_equal(toks[True], toks[False])
    assert gauges[False] == 4 * gauges[True]


@pytest.mark.parametrize("alpha,attack", [(0.0, "none"), (0.25, "signflip"),
                                          (0.25, "gaussian")])
def test_shared_replica_compute_token_identity(dense, alpha, attack):
    """The shared-compute emulation's equivalence claim: one forward
    broadcast into the wire stack decodes bit-identically to executing
    every replica's forward, across attacks (the attack corrupts the
    logit stack, never replica state)."""
    cfg, params = dense
    batch = _prompt_batch(cfg, B=3, S=10)
    toks = {}
    for shared in (True, False):
        eng = ServeEngine(cfg, params, max_len=24, robust=RobustDecodeConfig(
            m=8, alpha=alpha, attack=attack, estimator="vrmom",
            share_replica_compute=shared))
        toks[shared] = np.asarray(eng.generate(batch, 8,
                                               key=jax.random.PRNGKey(4)))
    np.testing.assert_array_equal(toks[True], toks[False])


def test_shared_replica_compute_pool_identity(dense):
    """Same equivalence through the scheduler pool path: plain-shaped
    robust slots decode the tokens the [m, ...]-stacked pool does."""
    cfg, params = dense

    def run(shared):
        eng = ServeEngine(cfg, params, max_len=24, n_slots=2,
                          robust=RobustDecodeConfig(
                              m=4, alpha=0.25, attack="signflip",
                              estimator="vrmom",
                              share_replica_compute=shared))
        sched = Scheduler(eng, decode_block=3)
        batch = _prompt_batch(cfg, B=2, S=10)
        uids = [sched.submit(Request(tokens=np.asarray(batch["tokens"][i]),
                                     max_new_tokens=5)) for i in range(2)]
        done = sched.run()
        return {u: done[u].tokens for u in uids}

    a, b = run(True), run(False)
    assert a == b


# ---------------------------------------------------------------------------
# The decode loop reads and writes the stacked KV pool in place
# ---------------------------------------------------------------------------

def _per_layer_decode_step(params, cfg, caches, tok):
    """Reference decode step: each layer's cache sliced out of the stack,
    decoded as one layer's cache, and stacked back."""
    from repro.models import transformer as Tr
    from repro.models.layers import rmsnorm

    h = Tr._embed_tokens(params, cfg, tok[:, None])
    new = []
    for l in range(cfg.n_layers):
        lp, c = jax.tree.map(lambda x: x[l], (params["layers"], caches))
        h, c, _ = Tr.layer_decode(lp, h, cfg, c)
        new.append(c)
    h = rmsnorm(h, params["norm_f"], cfg.norm_eps)
    return (Tr.unembed(params, cfg, h)[:, 0],
            jax.tree.map(lambda *x: jnp.stack(x), *new))


def test_decode_pool_in_place_matches_per_layer_reference(dense):
    """Admissions and an eviction leave the slots at different fill
    levels; the pool decode loop (kernel reading the pool in place) emits
    the jnp-backend engine's tokens, and its pool, layer by layer, is the
    one a per-layer reference decode writes."""
    import dataclasses

    cfg, params = dense
    assert cfg.n_layers == 2
    ref_cfg = dataclasses.replace(cfg, attn_backend="jnp")
    ref_step = jax.jit(lambda c, t: _per_layer_decode_step(params, ref_cfg,
                                                           c, t))
    engines = {b: ServeEngine(cfg, params, max_len=32, n_slots=3,
                              attn_backend=b) for b in ("auto", "jnp")}
    assert AB.decode_reads_pool(engines["auto"].cfg, 32)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, cfg.vocab, size=(n,)) for n in (5, 9, 3, 6)]
    # (admit {slot: prompt}, evict [slots], decode n steps)
    plan = [({0: 0, 1: 1}, [], 3), ({0: 2, 2: 3}, [0], 4)]
    out = {}
    for name in ("auto", "jnp", "reference"):
        eng = engines["jnp" if name == "reference" else name]
        pool, cur, toks = eng.make_pool(), np.zeros(3, np.int32), []
        for admits, evicts, n in plan:
            for s in evicts:
                pool = eng.evict(pool, s)
            for s, i in admits.items():
                pool, cur[s] = eng.admit(pool, s, {
                    "tokens": jnp.asarray(prompts[i])[None]})
            if name == "reference":
                caches, t = pool.caches, jnp.asarray(cur)
                for _ in range(n):
                    logits, caches = ref_step(caches, t)
                    t = jnp.argmax(logits, -1).astype(jnp.int32)
                    toks.append(np.asarray(t))
                pool = pool._replace(caches=caches)
            else:
                pool, blk = eng.decode_pool(pool, cur, n)
                toks += list(np.asarray(blk))
            cur = np.array(toks[-1], np.int32)
        out[name] = (np.stack(toks), pool.caches)
    got_toks, got = out["auto"]
    for name in ("jnp", "reference"):
        np.testing.assert_array_equal(got_toks, out[name][0])
    want = out["reference"][1]
    np.testing.assert_array_equal(np.asarray(got.pos), np.asarray(want.pos))
    assert got.k.shape == (cfg.n_layers, 3, 32,
                           cfg.n_kv_heads * cfg.head_dim)
    for l in range(cfg.n_layers):
        for a, b in ((got.k, want.k), (got.v, want.v)):
            np.testing.assert_allclose(np.asarray(a[l]), np.asarray(b[l]),
                                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,window,flag", [
    ("auto", "cfg", 1.0), ("jnp", "cfg", 0.0), ("auto", 20, 1.0)])
def test_decode_kv_inplace_gauge(dense, backend, window, flag):
    """serve.decode_kv_inplace: 1 when the decode kernel reads the pool
    in place (a ring cache too), 0 on the jnp reference's per-layer
    copies."""
    from repro.obs import MetricsRegistry

    cfg, params = dense
    reg = MetricsRegistry()
    ServeEngine(cfg, params, max_len=32, window=window, attn_backend=backend,
                obs=reg)
    assert reg.snapshot()["gauges"]["serve.decode_kv_inplace"] == flag
