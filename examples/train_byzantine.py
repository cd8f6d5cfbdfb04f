"""End-to-end driver: train a ~100M-param qwen3-family model for a few
hundred steps on 8 (host) devices with Byzantine workers, comparing
VRMOM aggregation against the vanilla mean.

  PYTHONPATH=src python examples/train_byzantine.py \
      [--steps 200] [--dmodel 512] [--layers 8] [--attack omniscient]

The script sets up its own 8 host devices; run it directly (not under a
process that already initialized jax).
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import dataclasses

import jax
import numpy as np

import repro.optim as O
from repro.configs import get as get_arch
from repro.data import lm_batch, shard_batch
from repro.dist import sharding as S
from repro.dist.faults import FaultPlan
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.obs import JsonlSink, MetricsRegistry
from repro.obs.metrics import now
from repro.train.step import make_train_step


def build_cfg(d_model, layers, vocab=8192):
    base = get_arch("qwen3-1.7b")
    return dataclasses.replace(
        base, name=f"qwen3-{d_model}d{layers}L", d_model=d_model,
        n_layers=layers, n_heads=8, n_kv_heads=4, d_head=d_model // 8,
        d_ff=4 * d_model, vocab=vocab, param_dtype="float32",
        compute_dtype="float32", attn_chunk=128, loss_chunk=256, remat=False)


def run(cfg, mesh, *, steps, aggregator, byz, attack, seq, batch, lr, log,
        reg=None, reduce_backend="rrs", dropout=0.0):
    """``reg``: optional obs.MetricsRegistry — builds the step with
    ``with_diag=True`` and records the per-worker suspicion diagnostics
    (alpha-hat, suspected count, pre/post gradient norms) plus step time
    and loss after each step. The diag aux rides the same jitted step —
    no extra dispatches.

    ``reduce_backend="consensus"``: aggregate through the decentralized
    consensus wire (DESIGN.md §13) instead of the coordinator RRS,
    optionally with ``dropout`` message loss injected each round; the
    consensus aux (rounds, quorum, dropped messages) lands in ``reg``.
    """
    with_diag = reg is not None
    consensus = reduce_backend == "consensus"
    kw = {}
    if consensus:
        kw["reduce_backend"] = "consensus"
        if dropout:
            kw["fault_plan"] = FaultPlan(dropout=dropout)
    setup = make_train_step(cfg, mesh, estimator=aggregator,
                            mode="stacked-rrs" if aggregator != "mean"
                            else "mean",
                            byzantine_frac=byz, attack=attack, lr=lr,
                            microbatch=1, with_diag=with_diag, **kw)
    opt = O.get(cfg.optimizer, lr=lr)
    params = M.init(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, S.to_named(mesh, setup.params_specs))
    opt_state = jax.jit(opt.init)(params)
    step = jax.jit(setup.step_fn)
    # Adaptive estimators (auto_gm / vrmom_adaptive): the census/EMA
    # state is an explicit jit carry through the step (DESIGN.md §14).
    adaptive = setup.init_state is not None
    agg_state = setup.init_state() if adaptive else None
    losses = []
    t0 = now()
    for i in range(steps):
        b = shard_batch(lm_batch(cfg, i, batch, seq), mesh, setup.batch_axes)
        ts = now()
        if adaptive:
            out = step(params, opt_state, b, jax.random.PRNGKey(i),
                       agg_state)
        else:
            out = step(params, opt_state, b, jax.random.PRNGKey(i))
        params, opt_state, loss = out[:3]
        rest = list(out[3:])
        if adaptive:
            agg_state = rest.pop(0)
        caux = rest.pop(0) if consensus else None
        diag = rest.pop(0) if with_diag else None
        losses.append(float(loss))  # blocks: device work for step i done
        if caux is not None and reg is not None:
            reg.observe("consensus.rounds", float(caux.rounds_to_eps))
            reg.counter("dist.messages_dropped",
                        float(caux.messages_dropped))
            reg.gauge("dist.quorum", float(caux.quorum))
        if with_diag:
            reg.observe("train.step_s", now() - ts)
            reg.gauge("train.loss", losses[-1])
            reg.gauge("agg.alpha_hat", float(diag.alpha_hat))
            reg.gauge("agg.suspected_workers",
                      float(np.asarray(diag.suspected).sum()))
            reg.gauge("agg.grad_norm_pre",
                      float(np.asarray(diag.pre_norms).mean()))
            reg.gauge("agg.grad_norm_post", float(diag.post_norm))
            if adaptive:
                reg.gauge("agg.worker_weight_min",
                          float(np.asarray(agg_state.weights).min()))
        if i % log == 0 or i == steps - 1:
            diag_note = ""
            if with_diag:
                diag_note = (f" alpha_hat={reg.gauges['agg.alpha_hat']:.3f}"
                             f" suspected="
                             f"{reg.gauges['agg.suspected_workers']:.0f}")
            print(f"  [{aggregator:6s} byz={byz:.2f}] step {i:4d} "
                  f"loss {losses[-1]:.4f} ({(now()-t0)/(i+1):.2f}s/it)"
                  + diag_note)
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dmodel", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192,
                    help="vocab size; the consensus wire is O(n^2 * "
                         "params) per round, so CI smoke runs shrink "
                         "this")
    ap.add_argument("--seq", type=int, default=128)
    # 8 sequences per worker: median-based aggregation needs each
    # worker's mean gradient to concentrate (the paper's n >> 1 per
    # machine). At 2 seqs/worker the coordinate-wise median of 4 noisy
    # means is too attenuated to descend.
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--byzantine", type=float, default=0.4)
    # (0.4 of 3 non-master workers floors to 1 Byzantine on the default
    #  4x2 host mesh; the paper uses floor(alpha*m) the same way)
    ap.add_argument("--attack", default="omniscient")
    ap.add_argument("--estimator", default="vrmom",
                    help="robust-arm aggregator: vrmom, median, "
                         "trimmed_mean, or an adaptive one (auto_gm, "
                         "vrmom_adaptive — DESIGN.md §14)")
    ap.add_argument("--reduce-backend", default="rrs",
                    choices=("rrs", "consensus"),
                    help="gradient aggregation wire: coordinator RRS or "
                         "decentralized approximate consensus (§13)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-round message-loss probability injected "
                         "into the consensus wire (consensus backend "
                         "only)")
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--metrics-out", default=None,
                    help="append the obs registry snapshot to this "
                         "telemetry JSONL (obs.sinks wire format)")
    args = ap.parse_args()

    n = len(jax.devices())
    consensus = args.reduce_backend == "consensus"
    if consensus:
        # Consensus validity needs n_workers > 5f: put every device on
        # the worker axis (8 > 5), and keep the Byzantine count at 1
        # (f = 1) — floor(0.15 * 7) = 1.
        mesh = make_mesh((n, 1), ("data", "model"))
        if int(args.byzantine * (n - 1)) > 1:
            print(f"consensus backend: clamping --byzantine "
                  f"{args.byzantine} -> 0.15 (n={n} workers supports "
                  f"f=1)")
            args.byzantine = 0.15
    else:
        mesh = make_mesh((max(n // 2, 1), min(2, n)), ("data", "model"))
    cfg = build_cfg(args.dmodel, args.layers, vocab=args.vocab)
    n_params = sum(x.size for x in jax.tree.leaves(M.abstract_init(cfg)))
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params, mesh "
          f"{dict(mesh.shape)}, attack={args.attack}, "
          f"backend={args.reduce_backend}"
          + (f", dropout={args.dropout}" if args.dropout else ""))

    common = dict(steps=args.steps, attack=args.attack, seq=args.seq,
                  batch=args.batch, lr=args.lr, log=args.log_every,
                  reduce_backend=args.reduce_backend, dropout=args.dropout)
    reg = MetricsRegistry()
    est_name = args.estimator
    print(f"== clean baseline ({est_name}, no Byzantine) ==")
    l_clean = run(cfg, mesh, aggregator=est_name, byz=0.0, **common)
    print(f"== {est_name} under {args.byzantine:.0%} Byzantine "
          f"(with diagnostics) ==")
    l_vr = run(cfg, mesh, aggregator=est_name, byz=args.byzantine,
               reg=reg, **common)
    print(f"== mean under {args.byzantine:.0%} Byzantine ==")
    # The mean arm stays on the plain (non-consensus) reduce on purpose:
    # under the consensus wire even est="mean" gets f-trimmed per round,
    # which would blunt the divergence this contrast demonstrates.
    l_mean = run(cfg, mesh, aggregator="mean", byz=args.byzantine,
                 **{**common, "reduce_backend": "rrs", "dropout": 0.0})
    if args.metrics_out:
        with JsonlSink(args.metrics_out) as sink:
            sink.write_registry(reg, source="examples.train_byzantine",
                                arch=cfg.name, attack=args.attack,
                                byzantine=args.byzantine)
        print(f"metrics appended to {args.metrics_out}")

    print("\nfinal losses: clean-%s %.4f | byz-%s %.4f | byz-mean %s"
          % (est_name, l_clean[-1], est_name, l_vr[-1],
             f"{l_mean[-1]:.4f}" if np.isfinite(l_mean[-1]) else "diverged"))
    assert l_clean[-1] < l_clean[0], "clean robust training should progress"
    # Under attack the robust run is guaranteed *stable* (bounded near
    # its start — descent needs longer horizons than a demo run).
    assert l_vr[-1] < l_vr[0] + 0.5, \
        f"{est_name} should stay stable under attack"
    if args.attack in ("alie", "ipm", "mimic"):
        # Stealth/omniscient-adaptive attacks: the payload sits inside
        # (alie, mimic) or scales with (ipm) the honest statistics, so
        # the mean arm degrades by per-step bias rather than diverging —
        # only finiteness is guaranteed at demo scale.
        assert np.isfinite(l_mean[-1]), \
            f"mean should stay finite under {args.attack}"
    else:
        # Loud attacks (omniscient/signflip/gaussian): the mean run
        # must diverge away from the robust one.
        assert (not np.isfinite(l_mean[-1])) or l_mean[-1] > l_vr[-1] + 1.0, \
            "mean aggregation should diverge where the robust arm holds"


if __name__ == "__main__":
    main()
