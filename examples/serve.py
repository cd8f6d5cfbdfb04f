"""Serving example: thin CLI over the ``repro.serve`` engine.

Runs any assigned architecture at its published widths (``--reduced``
for the smoke-scale variant) on the local devices, prefills a batch of
prompts and decodes continuations in one fused
scan dispatch. Compile time is reported separately from steady-state
throughput (the first call of each jitted program pays tracing + XLA
compilation; timing it together with decode used to overstate the
per-token cost by orders of magnitude).

All timings go through ``repro.obs`` (DESIGN.md §11) under the same
metric names ``benchmarks/serve.py`` records — ``serve.compile_s``,
``serve.ttft_s``, ``serve.decode_step_s`` — and ``--metrics-out FILE``
appends the registry snapshot as telemetry JSONL for
``scripts/metrics_dump.py``.

  PYTHONPATH=src python examples/serve.py --reduced --arch mixtral-8x7b --tokens 16
  PYTHONPATH=src python examples/serve.py --reduced --robust --attack signflip
  PYTHONPATH=src python examples/serve.py --reduced --scheduler --requests 6
  PYTHONPATH=src python examples/serve.py --scheduler --slots 8   # on a chip
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get as get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import JsonlSink, MetricsRegistry
from repro.obs.metrics import now
from repro.serve import (GREEDY, Request, RobustDecodeConfig, Sampling,
                         Scheduler, ServeEngine)
from repro.models import model as M


def build_batch(cfg, batch, prompt_len):
    out = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)}
    if cfg.family == "encdec":
        out["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.encoder.n_frames, cfg.d_model), jnp.float32)
    elif cfg.family == "vlm":
        out["patches"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.vision.n_patches, cfg.d_model), jnp.float32)
    return out


def run_batch(engine, cfg, args, sampling, reg):
    batch = build_batch(cfg, args.batch, args.prompt_len)

    # compile + first call — a gauge, not a histogram: one value per run
    with reg.timer("serve.compile_s", kind="gauge"):
        gen = jax.block_until_ready(engine.generate(batch, args.tokens,
                                                    sampling=sampling))
    t_cold = reg.gauges["serve.compile_s"]

    # TTFT: prefill + first sampled token (everything is warm now)
    t0 = now()
    jax.block_until_ready(engine.generate(batch, 1, sampling=sampling))
    ttft = now() - t0
    reg.observe("serve.ttft_s", ttft)

    t0 = now()
    gen = jax.block_until_ready(engine.generate(batch, args.tokens,
                                                sampling=sampling))
    t_warm = now() - t0
    tok_s = args.tokens * args.batch / max(t_warm, 1e-9)
    # steady-state per-token decode cost: the warm call minus its
    # prefill/first-token part, over the scanned tokens
    reg.observe("serve.decode_step_s",
                max(t_warm - ttft, 0.0) / max(args.tokens - 1, 1))

    print(f"{cfg.name}: {args.batch}x{args.prompt_len} prompt, "
          f"{args.tokens} new tokens/seq")
    print(f"  compile+first call: {t_cold:.2f}s   "
          f"steady-state: {t_warm:.3f}s ({tok_s:.1f} tok/s)   "
          f"ttft: {ttft * 1e3:.1f}ms")
    print("  generated ids[0]:", list(map(int, gen[0])))
    assert bool(jnp.all(gen >= 0)) and bool(jnp.all(gen < cfg.vocab))


def run_scheduler(engine, cfg, args, sampling, reg):
    sched = Scheduler(engine, decode_block=args.decode_block,
                      sampling=sampling)
    rs = np.random.RandomState(0)
    for i in range(args.requests):
        extras = None
        if cfg.family == "encdec":
            extras = {"frames": rs.randn(cfg.encoder.n_frames,
                                         cfg.d_model).astype(np.float32)}
        elif cfg.family == "vlm":
            extras = {"patches": rs.randn(cfg.vision.n_patches,
                                          cfg.d_model).astype(np.float32)}
        sched.submit(Request(
            tokens=rs.randint(0, cfg.vocab,
                              size=(args.prompt_len + 2 * i,)),
            max_new_tokens=args.tokens, extras=extras))
    t0 = now()
    done = sched.run()
    dt = now() - t0
    n_tok = sum(len(c.tokens) for c in done.values())
    print(f"{cfg.name}: {args.requests} requests through "
          f"{engine.n_slots} slots (block={args.decode_block}) in {dt:.2f}s "
          f"— {n_tok} tokens (incl. compile)")
    for uid in sorted(done):
        c = done[uid]
        print(f"  req {uid}: prompt {len(c.prompt)} -> {len(c.tokens)} "
              f"tokens ({c.finished_by})")
    # the scheduler recorded admit/retire counters + TTFT / decode-step
    # histograms into the engine's registry as it ran (DESIGN.md §11)
    snap = reg.snapshot()
    cnt = snap["counters"]
    h = reg.histograms.get("serve.decode_step_s")
    extra = (f"  decode_step p50={h.percentile(50) * 1e3:.2f}ms "
             f"p95={h.percentile(95) * 1e3:.2f}ms" if h and h.count else "")
    print(f"  obs: admitted={cnt.get('serve.admitted', 0):.0f} "
          f"retired={cnt.get('serve.retired', 0):.0f} "
          f"rejected={cnt.get('serve.rejected', 0):.0f} "
          f"tokens_out={cnt.get('serve.tokens_out', 0):.0f}\n" + extra)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the smoke-scale variant of the arch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--scheduler", action="store_true",
                    help="continuous-batching demo instead of one batch")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--decode-block", type=int, default=4)
    ap.add_argument("--robust", action="store_true",
                    help="replicated Byzantine-robust decode")
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--aggregator", default="vrmom")
    ap.add_argument("--attack", default="none",
                    help="fault injection: none|signflip|gaussian|...")
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--attn-backend", default=None,
                    choices=("auto", "jnp", "flash"),
                    help="attention backend override (DESIGN.md §8)")
    ap.add_argument("--metrics-out", default=None,
                    help="append the obs registry snapshot to this "
                         "telemetry JSONL (obs.sinks wire format)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = jax.jit(M.init, static_argnums=1)(jax.random.PRNGKey(0), cfg)

    sampling = GREEDY
    if args.top_k:
        # temperature 0 means "greedy" on the CLI; within top-k it
        # degenerates to plain top-k at temperature 1.
        sampling = Sampling("top_k", args.temperature or 1.0, args.top_k)
    elif args.temperature > 0:
        sampling = Sampling("temperature", args.temperature)

    robust = None
    if args.robust:
        robust = RobustDecodeConfig(m=args.replicas,
                                    estimator=args.aggregator,
                                    attack=args.attack, alpha=args.alpha)
        print(f"robust decode: m={args.replicas} {args.aggregator}, "
              f"attack={args.attack} alpha={args.alpha}")

    reg = MetricsRegistry()
    max_len = args.prompt_len + 2 * args.requests + args.tokens + 8
    engine = ServeEngine(cfg, params, max_len=max_len, n_slots=args.slots,
                         robust=robust, attn_backend=args.attn_backend,
                         obs=reg)
    if args.scheduler:
        run_scheduler(engine, cfg, args, sampling, reg)
    else:
        run_batch(engine, cfg, args, sampling, reg)
    if args.metrics_out:
        with JsonlSink(args.metrics_out) as sink:
            sink.write_registry(reg, source="examples.serve", arch=cfg.name,
                                robust=bool(robust),
                                mode="scheduler" if args.scheduler
                                else "batch")
        print(f"metrics appended to {args.metrics_out}")


if __name__ == "__main__":
    main()
