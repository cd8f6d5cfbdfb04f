"""Consensus-vs-RRS backend comparison + fault degradation (DESIGN.md §13).

Two experiments, one committed artifact (``BENCH_dist.json``):

1. **Backend comparison** on an 8-worker host mesh: wall time per
   jitted aggregation call and analytic wire bytes per worker for the
   centralized RRS backend (reduce-scatter + all-gather: ~2*C*4*(W-1)/W
   bytes) against the decentralized consensus backend (p_end rounds of
   all-to-all broadcast: rounds*(W-1)*C*4 bytes). The decentralization
   premium is explicit: consensus buys no-coordinator fault tolerance
   with O(rounds * W) wire traffic, never for free.

2. **Degradation curve** (host emulation, n = 8, f = 1): for each
   attack in {alie, omniscient} at alpha = 0.125 with a persistent
   (pinned) adversary, sweep message dropout and record the consensus
   error against the same cell's zero-dropout decision, rounds-to-eps,
   and the quorum gauge. This is the committed graceful-degradation
   evidence: error grows smoothly with loss rate and the quorum gauge
   reports the shrinking reception set — no cliffs, no NaNs.

  PYTHONPATH=src python -m benchmarks.dist [--smoke] [--out BENCH_dist.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# 8 host devices for the mesh comparison; must precede the jax import.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

if __package__ in (None, ""):
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import attacks as A
from repro.dist import robust_reduce as RR
from repro.dist.consensus import ConsensusConfig, aggregate_stacked_consensus, \
    consensus_aggregate
from repro.dist.faults import FaultPlan
from repro.launch.mesh import make_mesh

N_WORKERS = 8
DROPOUTS = (0.0, 0.05, 0.1, 0.2, 0.3, 0.5)
ATTACKS = ("alie", "omniscient")
N_BYZ = 1      # 1 Byzantine row out of 8 (alpha = 0.125) -> f = 1
ALPHA = N_BYZ / N_WORKERS


def _timed(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def backend_comparison(C=1 << 16, iters=20):
    """Jitted wall time + analytic bytes for both backends, same wire."""
    mesh = make_mesh((N_WORKERS, 1), ("data", "model"))
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (N_WORKERS, C))}
    gp = {"w": jax.device_put(g["w"],
                              NamedSharding(mesh, P("data", None)))}
    cfg = ConsensusConfig(f=1).validate(N_WORKERS)
    rounds = cfg.phases(None)

    rrs = jax.jit(lambda x: RR.aggregate_stacked_rrs(
        x, mesh, ("data",), "vrmom"))
    cons = jax.jit(lambda x: aggregate_stacked_consensus(
        x, mesh, ("data",), "vrmom", config=cfg))

    t_rrs = _timed(rrs, gp, iters=iters)
    t_cons = _timed(cons, gp, iters=iters)
    out_c, aux = cons(gp)
    out_r = rrs(gp)
    maxdiff = float(jnp.max(jnp.abs(out_c["w"] - out_r["w"])))

    bytes_rrs = 2 * C * 4 * (N_WORKERS - 1) / N_WORKERS
    bytes_cons = rounds * (N_WORKERS - 1) * C * 4
    return {
        "workers": N_WORKERS, "coords": C, "estimator": "vrmom",
        "rrs": {"seconds_per_call": t_rrs,
                "bytes_per_worker": bytes_rrs, "rounds": 1},
        "consensus": {"seconds_per_call": t_cons,
                      "bytes_per_worker": bytes_cons, "rounds": rounds,
                      "rounds_run": int(aux.rounds_run),
                      "rounds_to_eps": int(aux.rounds_to_eps)},
        "fault_free_maxdiff_vs_rrs": maxdiff,
        "wire_overhead_x": bytes_cons / bytes_rrs,
    }


def degradation_curve(C=512, seeds=8):
    """Emulated n=8 consensus under a pinned adversary x dropout sweep."""
    n = N_WORKERS
    cfg = ConsensusConfig(f=1, trim="midpoint").validate(n)
    # Direct mask: exactly N_BYZ of the n peers (byzantine_mask floors
    # alpha*(n-1), which would round 1/8 down to zero attackers).
    mask = jnp.arange(n) >= n - N_BYZ

    def cell(attack, dropout, seed):
        kv, ka, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
        v = jax.random.normal(kv, (n, C))
        v_att = A.REGISTRY[attack](ka, v, mask)
        plan = FaultPlan(dropout=dropout).validate(n)
        got, aux = consensus_aggregate(v_att, "vrmom", config=cfg,
                                       plan=plan, key=kc, pin_mask=mask)
        ref, _ = consensus_aggregate(v_att, "vrmom", config=cfg,
                                     key=kc, pin_mask=mask)
        honest = jnp.mean(v[~mask], axis=0)
        return (float(jnp.max(jnp.abs(got - ref))),
                float(jnp.max(jnp.abs(got - honest))),
                int(aux.rounds_to_eps), float(aux.quorum),
                bool(aux.quorum_lost), int(aux.messages_dropped))

    rows = []
    for attack in ATTACKS:
        for dropout in DROPOUTS:
            res = [cell(attack, dropout, s) for s in range(seeds)]
            err, err_h, r2e, quorum, lost, dropped = zip(*res)
            rows.append({
                "attack": attack, "alpha": ALPHA, "dropout": dropout,
                "err_vs_no_dropout": float(np.mean(err)),
                "err_max": float(np.max(err)),
                "err_vs_honest_mean": float(np.mean(err_h)),
                "rounds_to_eps_mean": float(np.mean(r2e)),
                "quorum_mean": float(np.mean(quorum)),
                "quorum_lost_frac": float(np.mean(lost)),
                "messages_dropped_mean": float(np.mean(dropped)),
            })
            print(f"degrade {attack:10s} dropout={dropout:.2f} "
                  f"err={rows[-1]['err_vs_no_dropout']:.4f} "
                  f"err_honest={rows[-1]['err_vs_honest_mean']:.4f} "
                  f"rounds={rows[-1]['rounds_to_eps_mean']:.1f} "
                  f"quorum={rows[-1]['quorum_mean']:.3f}", flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny wire + few seeds for CI")
    ap.add_argument("--out", default=None, help="write JSON here")
    args = ap.parse_args(argv)

    C, iters, seeds = ((1 << 12, 3, 2) if args.smoke else (1 << 16, 20, 8))

    t0 = time.perf_counter()
    print("backend comparison (8-worker host mesh)...", flush=True)
    comp = backend_comparison(C=C, iters=iters)
    print(f"  rrs       {comp['rrs']['seconds_per_call']*1e3:8.2f} ms/call  "
          f"{comp['rrs']['bytes_per_worker']/1e6:.2f} MB/worker")
    print(f"  consensus {comp['consensus']['seconds_per_call']*1e3:8.2f} "
          f"ms/call  {comp['consensus']['bytes_per_worker']/1e6:.2f} "
          f"MB/worker  ({comp['consensus']['rounds']} rounds)")
    print(f"  fault-free maxdiff vs RRS: "
          f"{comp['fault_free_maxdiff_vs_rrs']:.2e}")

    curve = degradation_curve(C=min(C, 512), seeds=seeds)

    # Committed guarantees: fault-free equivalence is exact, and at 10%
    # loss the decision error stays small while quorum never collapses.
    at10 = [r for r in curve if r["dropout"] == 0.1]
    acceptance = {
        "fault_free_matches_rrs": comp["fault_free_maxdiff_vs_rrs"] == 0.0,
        "dropout10_err_max": max(r["err_max"] for r in at10),
        "dropout10_no_quorum_loss": all(r["quorum_lost_frac"] == 0.0
                                        for r in at10),
        "pass": (comp["fault_free_maxdiff_vs_rrs"] == 0.0
                 and all(r["quorum_lost_frac"] == 0.0 for r in at10)
                 and max(r["err_max"] for r in at10) < 2.0),
    }
    print(f"acceptance: {'PASS' if acceptance['pass'] else 'FAIL'} "
          f"(err@10%={acceptance['dropout10_err_max']:.3f})")

    out = {
        "settings": {"workers": N_WORKERS, "f": 1, "alpha": ALPHA,
                     "estimator": "vrmom", "coords_timing": C,
                     "smoke": bool(args.smoke),
                     "total_seconds": round(time.perf_counter() - t0, 1)},
        "backend_comparison": comp,
        "degradation": curve,
        "acceptance": acceptance,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.out}")
    return out


if __name__ == "__main__":
    main()
