"""Smoke test of the system's main path on a TPU, at published widths.

Default phase (one chip):

* **Serve.** ``qwen3-1.7b`` at its published widths (28 layers, d_model
  2048, vocab 151936, bf16 params made from a seed) through
  ``ServeEngine`` + ``Scheduler``: 8 slots, 8 seeded requests with
  prompt lengths in {128, 512}, 32 greedy tokens each, robust decode
  over m=8 VRMOM replicas with the fused aggregate+sample tail and a
  ``signflip`` attack on floor(0.25 * 8) = 2 replicas. The served tokens
  must equal those of the same engine with no attack and of plain m=1
  decode (honest replicas are identical rows, so the robust aggregate is
  exactly the honest logits).
* **Kernels.** ``Estimator(...).apply`` on an ``[8, 2048*6144]`` f32
  stack against ``kernels.ref`` at 2e-5; the fused top-k tail against
  ``jax.lax.top_k``; ``decode_attention`` (bf16 and int8 caches) at the
  qwen3-1.7b decode shape against the ``jnp`` attention backend.
* Every checked program must contain ``tpu_custom_call``: no interpret
  or jnp path stood in for a kernel.

``--chips 4`` runs only the ``launch/train.py`` path on a (data=4,
model=1) mesh: qwen3-1.7b at published widths cut to ``TRAIN_LAYERS``
layers, ``stacked-rrs`` VRMOM with one Byzantine (gaussian) worker. The
first step's gradient stack, aggregated by ``stacked-rrs`` and by
``stacked-auto``, must agree at 2e-5 (the RRS program must hold the
kernel), and 5 steps must give finite, non-increasing loss.

Run from the repository root, one process per chip set:

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # four chips

Without a TPU it exits non-zero and prints no result. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SERVE_ARCH = "qwen3-1.7b"
N_SLOTS = 8
PROMPT_LENS = (128, 512)
N_REQUESTS = 8
NEW_TOKENS = 32
DECODE_BLOCK = 8
MAX_LEN = 768          # >= 512 + 32 + DECODE_BLOCK; a multiple of the
                       # decode kernel's 256-key block, so no cache pad
REPLICAS = 8
ALPHA = 0.25
AGG_SHAPE = (8, 2048 * 6144)   # one qwen3-1.7b MLP matrix per worker
DECODE_SHAPE = dict(B=8, T=4096, Hkv=8, G=2, dh=128)
# both attention outputs are bf16 (8 significant bits): rounded from
# nearly equal f32 values they may differ by one bf16 ULP, 2^-7 * |x|;
# 1e-2 absolute + 1e-2 relative covers that at every magnitude
ATTN_TOL = 1e-2
AGG_TOL = 2e-5
# depth cut of the 4-chip phase (widths published). Compiled for v5e, the
# step program needs per chip 12.31 GiB at 14 layers, 15.50 at 15, 13.68
# at 16 and 17.42 at 28, and the initial params and optimizer state (2.37
# GiB at 14 layers, 2.48 at 15, 2.60 at 16) stay held beside it: of these
# only 14 layers (14.68 GiB) fits the 15.75 GiB HBM
TRAIN_LAYERS = 14
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, found "
                         f"{len(devs)}")
    return devs


def program_text(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text()


def assert_kernel(text: str, what: str) -> None:
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: compiled program has no "
                             f"tpu_custom_call (a non-kernel path ran)")


# ---------------------------------------------------------------- serving

def make_requests(vocab: int, seed: int = SEED, lens=PROMPT_LENS,
                  n: int = N_REQUESTS):
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = rng.permutation(np.resize(np.asarray(lens), n))
    return [rng.integers(0, vocab, size=(int(L),), dtype=np.int32)
            for L in lengths]


def serve(engine, prompts, new_tokens: int, decode_block: int):
    """All prompts through a fresh Scheduler; -> (tokens per request,
    wall seconds). The scheduler reads every block back to the host, so
    the wall time covers the device work."""
    from repro.serve import Request, Scheduler

    sched = Scheduler(engine, decode_block=decode_block)
    for p in prompts:
        sched.submit(Request(tokens=p, max_new_tokens=new_tokens))
    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    toks = [done[uid].tokens for uid in sorted(done)]
    if any(done[uid].finished_by != "length" for uid in done):
        raise AssertionError("a request did not run to its token budget")
    return toks, dt, sched


def serve_phase(cfg, params, *, prompts, new_tokens=NEW_TOKENS,
                decode_block=DECODE_BLOCK, n_slots=N_SLOTS, max_len=MAX_LEN,
                replicas=REPLICAS, alpha=ALPHA, expect_kernel=True):
    import jax
    import jax.numpy as jnp

    from repro.serve import GREEDY, RobustDecodeConfig, ServeEngine

    arms = {
        "robust_signflip": RobustDecodeConfig(
            m=replicas, estimator="vrmom", attack="signflip", alpha=alpha),
        "robust_clean": RobustDecodeConfig(m=replicas, estimator="vrmom"),
        "plain_m1": None,
    }
    served = {}
    for name, rcfg in arms.items():
        engine = ServeEngine(cfg, params, max_len=max_len, n_slots=n_slots,
                             robust=rcfg)
        cold, t_cold, _ = serve(engine, prompts, new_tokens, decode_block)
        warm, t_warm, sched = serve(engine, prompts, new_tokens,
                                    decode_block)
        if warm != cold:
            raise AssertionError(f"{name}: warm tokens differ from cold")
        n_tok = sum(len(t) for t in warm)
        log(f"serve {name}: {len(prompts)} requests, {n_tok} tokens; "
            f"cold run {t_cold:.3f} s (compile included), warm run "
            f"{t_warm:.3f} s = {n_tok / t_warm:.1f} tok/s "
            f"(prefill included)")
        served[name] = warm
        if rcfg is not None and rcfg.attack != "none" and expect_kernel:
            loop = engine._decode_loop_fn(decode_block, GREEDY, pool=True)
            text = loop.lower(engine.params, sched.pool.caches,
                              jnp.zeros((n_slots,), jnp.int32),
                              jax.random.PRNGKey(0)).compile().as_text()
            assert_kernel(text, "robust decode loop")
            log("serve: robust decode loop program holds "
                f"{text.count('tpu_custom_call')} tpu_custom_call sites")
    base = served["plain_m1"]
    bad = {name: [i for i, (a, b) in enumerate(zip(served[name], base))
                  if a != b]
           for name in ("robust_signflip", "robust_clean")}
    if any(bad.values()):
        raise AssertionError(f"requests whose tokens differ from plain m=1 "
                             f"decode: {bad}")
    log("serve: robust m=8 signflip tokens == unattacked m=8 == plain m=1")


# ---------------------------------------------------------------- kernels

def kernel_phase(*, agg_shape=AGG_SHAPE, decode_shape=DECODE_SHAPE,
                 vocab=151936, expect_kernel=True):
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get as get_arch
    from repro.core.estimator import Estimator
    from repro.kernels import ref
    from repro.models import attn_backend as AB
    from repro.models.attention import quantize_kv

    check = assert_kernel if expect_kernel else (lambda text, what: None)
    key = jax.random.PRNGKey(SEED)
    x = jax.jit(lambda k: 4.0 * jax.random.normal(k, agg_shape) + 1.5)(key)
    refs = {"vrmom": ref.ref_vrmom, "median": ref.ref_mom,
            "trimmed_mean": lambda a: ref.ref_trimmed_mean(a, beta=0.25)}
    for method, want_fn in refs.items():
        est = Estimator(method=method, beta=0.25)
        got = jax.jit(est.apply)(x)
        want = jax.jit(want_fn)(x)
        err = float(jnp.max(jnp.abs(got - want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=AGG_TOL, atol=AGG_TOL)
        check(program_text(est.apply, x), f"Estimator({method}).apply")
        log(f"kernel aggregate {method} {list(agg_shape)}: max |err| vs "
            f"kernels.ref = {err:.3g} (tol {AGG_TOL})")

    # fused aggregate + top-k tail on a [m, B, V] logit stack
    m, b = 8, 8
    logits = jax.jit(lambda k: 4.0 * jax.random.normal(k, (m, b, vocab)))(
        jax.random.fold_in(key, 1))
    est = Estimator(method="vrmom")
    agg, topv, topi = jax.jit(
        lambda a: est.apply_sample(a, top_k=40))(logits)
    want_v, want_i = jax.lax.top_k(agg, 40)
    if not (bool(jnp.all(topi == want_i)) and bool(jnp.all(topv == want_v))):
        raise AssertionError("fused top-k tail differs from lax.top_k")
    check(program_text(lambda a: est.apply_sample(a, top_k=40), logits),
          "fused top-k tail")
    log(f"kernel fused vrmom+top-40 tail [{m}, {b}, {vocab}]: equals "
        f"lax.top_k of its aggregate")

    # decode attention vs the jnp backend at the qwen3-1.7b decode shape
    B, T, Hkv, G, dh = (decode_shape[k] for k in ("B", "T", "Hkv", "G",
                                                   "dh"))
    cfg = get_arch(SERVE_ARCH)
    flash = dataclasses.replace(cfg, attn_backend="flash")
    plain = dataclasses.replace(cfg, attn_backend="jnp")
    ks = jax.random.split(jax.random.fold_in(key, 2), 4)
    q = jax.random.normal(ks[0], (B, 1, Hkv * G, dh), jnp.bfloat16)
    kf = jax.random.normal(ks[1], (B, T, Hkv, dh), jnp.float32)
    vf = jax.random.normal(ks[2], (B, T, Hkv, dh), jnp.float32)
    lens = jax.random.randint(ks[3], (B,), 1, T + 1)
    caches = {"bf16": (kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16),
                       None, None)}
    k8, s_k = quantize_kv(kf, jnp.int8)
    v8, s_v = quantize_kv(vf, jnp.int8)
    caches["int8"] = (k8, v8, s_k, s_v)
    for name, (k, v, sk, sv) in caches.items():
        # the cache as the second layer of a two-layer pool
        # [2, B, T, Hkv*dh] (scales [2, B, T]), read by layer index
        k, v = (jnp.stack([x[::-1], x]).reshape(2, B, T, Hkv * dh)
                for x in (k, v))
        if sk is not None:
            sk, sv = jnp.stack([sk[::-1], sk]), jnp.stack([sv[::-1], sv])

        def run(c, q, k, v, lens, sk, sv):
            return AB.decode_attention(q, k, v, 1, c, kv_len=lens,
                                       k_scale=sk, v_scale=sv)

        got = jax.jit(functools.partial(run, flash))(q, k, v, lens, sk, sv)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(functools.partial(run, plain))(q, k, v, lens, sk,
                                                          sv)
        got32 = np.asarray(got, np.float32)
        want32 = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got32 - want32)))
        np.testing.assert_allclose(got32, want32, rtol=ATTN_TOL,
                                   atol=ATTN_TOL)
        check(program_text(functools.partial(run, flash), q, k, v, lens, sk,
                           sv), f"decode_attention {name}")
        log(f"kernel decode_attention {name} B={B} T={T} Hkv={Hkv} G={G} "
            f"dh={dh}: max |err| vs jnp backend = {err:.3g} "
            f"(tol {ATTN_TOL})")


# ---------------------------------------------------------------- training

def train_phase(n_chips: int, *, arch=SERVE_ARCH, layers=TRAIN_LAYERS,
                reduced=False, steps=5, batch=8, seq=256, lr=1e-4,
                expect_kernel=True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import train as T

    argv = ["--arch", arch, "--layers", str(layers), "--data", str(n_chips),
            "--model", "1", "--aggregator", "vrmom", "--mode", "stacked-rrs",
            # one Byzantine worker: make_train_step attacks
            # int(frac * (workers - 1)) of them
            "--byzantine", str(1.0 / (n_chips - 1)), "--attack", "gaussian",
            "--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
            "--lr", str(lr), "--log-every", "1"]
    if reduced:
        argv.append("--reduced")
    args = T.parse_args(argv)
    t0 = time.perf_counter()
    run = T.build(args)
    auto = T.make_setup(args, run.cfg, run.mesh, mode="stacked-auto")
    b0, k0 = run.batch(0, args), jax.random.PRNGKey(0)
    # one first-step gradient stack (Byzantine row included) through both
    # wires: two separately compiled backward passes may round their
    # bf16 gradients differently, which would compare the compiler and
    # not the wire
    loss0, grads = jax.jit(run.setup.worker_grads_fn)(run.params, b0, k0)
    agg_r = jax.jit(run.setup.aggregate_fn)(grads)
    agg_a = jax.jit(auto.aggregate_fn)(grads)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(agg_r), jax.tree.leaves(agg_a)):
        a32 = np.asarray(a.astype(jnp.float32))
        b32 = np.asarray(b.astype(jnp.float32))
        np.testing.assert_allclose(a32, b32, rtol=AGG_TOL, atol=AGG_TOL)
        worst = max(worst, float(np.max(np.abs(a32 - b32))))
    del agg_r, agg_a
    if expect_kernel:
        assert_kernel(program_text(run.setup.aggregate_fn, grads),
                      "stacked-rrs aggregation")
    del grads
    log(f"train: first-step aggregate stacked-rrs vs stacked-auto: max "
        f"|diff| = {worst:.3g} over {len(jax.tree.leaves(run.params))} "
        f"leaves (tol {AGG_TOL}); loss {float(loss0):.6f}; set-up "
        f"{time.perf_counter() - t0:.1f} s")
    # five steps on the first batch: loss on a fixed batch must descend
    params, opt_state, losses = run.params, run.opt_state, []
    for i in range(steps):
        t1 = time.perf_counter()
        params, opt_state, loss = run.step(params, opt_state, b0,
                                           jax.random.PRNGKey(i))
        losses.append(float(loss))
        log(f"train step {i}: loss {losses[-1]:.6f} "
            f"({time.perf_counter() - t1:.3f} s)")
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if any(b > a for a, b in zip(losses, losses[1:])):
        raise AssertionError(f"loss increased: {losses}")
    log(f"train: {steps} steps, losses {losses}")


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-chip training phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs = require_tpu(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        train_phase(4)
    else:
        from repro.configs import get as get_arch
        from repro.models import model as M

        cfg = get_arch(SERVE_ARCH)
        params = jax.block_until_ready(jax.jit(M.init, static_argnums=1)(
            jax.random.PRNGKey(SEED), cfg))
        log(f"{cfg.name}: {M.param_count(params) / 1e9:.3f}B params "
            f"({cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
            f"{cfg.vocab}, {cfg.param_dtype}) initialised in "
            f"{time.perf_counter() - t0:.1f} s")
        serve_phase(cfg, params, prompts=make_requests(cfg.vocab))
        del params
        kernel_phase()
    stats = devs[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on "
        f"{devs[0].device_kind}; total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
