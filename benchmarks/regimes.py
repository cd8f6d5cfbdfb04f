"""Regime matrix: adaptive adversaries vs adaptive aggregation
(DESIGN.md §14).

Drives the attack x estimator x alpha grid through three production
wires and commits the result as ``BENCH_regimes.json``:

* **coverage** — the Monte-Carlo CI harness (``repro.infer.coverage``).
  Fixed arms model the analyst who assumes a clean fleet
  (``assumed_alpha=0.0``: no contamination inflation); adaptive arms
  plug in the *census-estimated* ``alpha_hat``
  (``repro.core.adaptive.estimate_alpha``) — nobody is told the true
  alpha. The stealth attacks (alie/ipm) are exactly the regimes where
  the fixed arms' uninflated CIs lose coverage while the census keeps
  the adaptive arms honest.
* **serve** — the m=8 replicated greedy-decode tail
  (``repro.serve.robust.robust_sample``): fraction of served tokens
  differing from the honest decode.
* **train** — the sharded Byzantine train step on a reduced qwen3
  model: loss stability under attack, with the adaptive arms threading
  their ``AdaptiveState`` carry.

The ``acceptance`` block is the committed tentpole claim: at alie or
ipm with alpha=0.2 BOTH fixed arms (vrmom, median) fail the coverage
gate (< 0.9) while BOTH adaptive arms (vrmom_adaptive, auto_gm) pass
it, and the fault-free adaptive estimators are bit-identical to their
fixed baselines.

  PYTHONPATH=src python -m benchmarks.regimes [--smoke] [--reps 96]
      [--out BENCH_regimes.json] [--no-mesh]

Importable without jax at module top: ``scripts/check_docs.py`` reads
the grid constants below to verify the DESIGN.md §14 regime table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

# The regime grid (single source of truth for the DESIGN.md §14 table).
ATTACKS = ("gaussian", "signflip", "wrong_value", "alie", "ipm", "mimic")
ESTIMATOR_CELLS = ("median", "vrmom", "vrmom_adaptive", "trimmed_mean",
                   "auto_gm", "mean")
ALPHAS = (0.0, 0.1, 0.2)

FIXED_ARMS = ("vrmom", "median")         # acceptance: these fail the gate
ADAPTIVE_ARMS = ("vrmom_adaptive", "auto_gm")  # ... while these pass it
SERVE_ALPHA = 0.25
LEVEL = 0.95
COVERAGE_GATE = 0.90
K = 10
TRAIN_ATTACKS = ("ipm", "wrong_value")
TRAIN_ARMS = ("vrmom", "auto_gm", "mean")


def _estimator(name, K_=K, backend=None):
    from repro.core.estimator import Estimator

    kw = {"backend": backend} if backend else {}
    if name == "trimmed_mean":
        # beta must cover the worst grid alpha; the default 0.1 would
        # trim less than the contamination at alpha=0.2.
        return Estimator(method="trimmed_mean", beta=0.25, **kw)
    if name in ("vrmom", "vrmom_adaptive"):
        return Estimator(method=name, K=K_, **kw)
    return Estimator(method=name, **kw)


def _census_alpha_hat(attack, alpha, m_workers):
    """The adaptive arms' assumed contamination: census an attacked
    stack (the duplicate/loudness structure is attack-determined, not
    data-determined), exactly 0.0 for the clean regime."""
    import jax

    from repro.core import adaptive as AD
    from repro.core import attacks as A

    if alpha == 0.0 or attack == "none":
        return 0.0
    v = jax.random.normal(jax.random.PRNGKey(0), (m_workers + 1, 64)) + 1.0
    mask = A.byzantine_mask(m_workers + 1, alpha)
    v_att = A.REGISTRY[attack](jax.random.PRNGKey(1), v, mask)
    return float(AD.estimate_alpha(v_att, axis=0))


def run_coverage_wire(attacks, alphas, arms, reps, mesh=None, *,
                      m_workers=100, verbose=True):
    from repro.infer.coverage import coverage_run

    rows = {}
    cells = [("none", 0.0, arm) for arm in arms if 0.0 in alphas]
    cells += [(attack, alpha, arm) for attack in attacks
              for alpha in alphas if alpha > 0.0 for arm in arms]
    for attack, alpha, arm in cells:
        assumed = (_census_alpha_hat(attack, alpha, m_workers)
                   if arm in ADAPTIVE_ARMS else 0.0)
        cell_reps = reps
        if mesh is not None:
            w = int(mesh.shape["data"])
            cell_reps = max(w, cell_reps - cell_reps % w)
        t0 = time.perf_counter()
        cell = coverage_run(
            model="linear", attack=attack, alpha=alpha,
            # jnp backend: the coverage scan's remainder batch can be
            # zero-length, which the interpret-mode pallas kernel rejects
            # (and rcsl's own string coercion already pins jnp here).
            estimator=_estimator(arm, backend="jnp"),
            reps=cell_reps, N_per_machine=100,
            m_workers=m_workers, p=5, rounds=4, level=LEVEL, batch_size=12,
            mesh=mesh, assumed_alpha=assumed)
        s = cell.summary()
        s["assumed_alpha"] = round(assumed, 4)
        s["seconds"] = round(time.perf_counter() - t0, 2)
        name = f"coverage/{attack}/a{alpha}/{arm}"
        rows[name] = s
        if verbose:
            print(f"{name:42s} coverage={s['coverage']:.3f} "
                  f"width={s['mean_width']:.4f} assumed={assumed:.3f} "
                  f"({s['seconds']:.1f}s)", flush=True)
    return rows


def run_serve_wire(attacks, arms, verbose=True):
    """m=8 replica greedy decode: honest replicas are bit-identical, so
    a robust arm must serve the exact honest tokens under every attack
    at alpha=0.25."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import RobustDecodeConfig, Sampling
    from repro.serve import robust as Ro

    B, V, m = 16, 128, 8
    honest = jax.random.normal(jax.random.PRNGKey(3), (B, V))
    logits_r = jnp.broadcast_to(honest[None], (m, B, V))
    want = np.asarray(jnp.argmax(honest, axis=-1))
    sc = Sampling(method="greedy")
    rows = {}
    for attack in attacks:
        for arm in arms:
            rcfg = RobustDecodeConfig(m=m, estimator=_estimator(arm, K_=8),
                                      attack=attack, alpha=SERVE_ALPHA)
            tok = np.asarray(Ro.robust_sample(
                logits_r, rcfg, jax.random.PRNGKey(7),
                jax.random.PRNGKey(0), sc))
            corr = float((tok != want).mean())
            name = f"serve/{attack}/a{SERVE_ALPHA}/{arm}"
            rows[name] = {"token_corruption": corr, "tokens": int(B)}
            if verbose:
                print(f"{name:42s} token_corruption={corr:.3f}", flush=True)
    return rows


def run_train_wire(attacks, arms, steps, verbose=True):
    """Reduced-model Byzantine descent: robust arms must stay stable
    where the mean degrades; adaptive arms thread their state carry."""
    import jax
    import numpy as np

    import repro.optim as O
    from repro.configs import get as get_arch
    from repro.data import lm_batch, shard_batch
    from repro.dist import sharding as S
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.train.step import make_train_step

    n = len(jax.devices())
    mesh = make_mesh((max(n // 2, 1), min(2, n)), ("data", "model"))
    cfg = get_arch("qwen3-1.7b").reduced()
    rows = {}
    for attack in attacks:
        for arm in arms:
            t0 = time.perf_counter()
            setup = make_train_step(
                cfg, mesh, estimator=_estimator(arm),
                mode="mean" if arm == "mean" else "stacked-rrs",
                byzantine_frac=0.4, attack=attack, lr=1e-2, microbatch=1)
            adaptive = setup.init_state is not None
            state = setup.init_state() if adaptive else None
            opt = O.get(cfg.optimizer, lr=1e-2)
            params = M.init(jax.random.PRNGKey(0), cfg)
            params = jax.device_put(params,
                                    S.to_named(mesh, setup.params_specs))
            opt_state = jax.jit(opt.init)(params)
            step = jax.jit(setup.step_fn)
            losses = []
            for i in range(steps):
                b = shard_batch(lm_batch(cfg, i, 8, 32), mesh,
                                setup.batch_axes)
                if adaptive:
                    out = step(params, opt_state, b, jax.random.PRNGKey(i),
                               state)
                    params, opt_state, loss, state = out[:4]
                else:
                    out = step(params, opt_state, b, jax.random.PRNGKey(i))
                    params, opt_state, loss = out[:3]
                losses.append(float(loss))
            finite = bool(np.isfinite(losses[-1]))
            row = {
                "loss_first": losses[0], "loss_last": losses[-1],
                "finite": finite,
                "stable": finite and losses[-1] < losses[0] + 0.5,
                "seconds": round(time.perf_counter() - t0, 2),
            }
            if adaptive:
                row["alpha_hat"] = float(state.alpha_hat)
                row["worker_weight_min"] = float(state.weights.min())
            name = f"train/{attack}/a0.4/{arm}"
            rows[name] = row
            if verbose:
                print(f"{name:42s} loss {losses[0]:.3f}->{losses[-1]:.3f} "
                      f"stable={row['stable']} ({row['seconds']:.1f}s)",
                      flush=True)
    return rows


def bit_identity_record():
    """The zero-cost-adaptivity acceptance half: on honest data the
    adaptive estimators are bit-identical to their fixed baselines and
    the census is exactly silent."""
    import jax
    import numpy as np

    from repro.core import adaptive as AD
    # reprolint: disable=RL001 oracle: honest bit-identity compares auto_gm against raw weiszfeld below the Estimator layer
    from repro.core import aggregators as AG
    from repro.core.vrmom import vrmom

    v = jax.random.normal(jax.random.PRNGKey(5), (41, 40)) + 1.0
    gm = np.array_equal(np.asarray(AD.auto_gm(v, axis=0)),
                        np.asarray(AG.geometric_median(v, axis=0)))
    vr = np.array_equal(np.asarray(AD.vrmom_adaptive(v, K=K, axis=0)),
                        np.asarray(vrmom(v, K=K, axis=0)))
    return {
        "auto_gm_eq_geometric_median": bool(gm),
        "vrmom_adaptive_eq_vrmom": bool(vr),
        "honest_alpha_hat_zero":
            float(AD.estimate_alpha(v, axis=0)) == 0.0,
    }


def acceptance(rows, identity):
    """>= 1 stealth regime at alpha=0.2 where BOTH fixed arms fail the
    coverage gate and BOTH adaptive arms pass it, plus exact honest-
    regime bit identity."""
    regimes = {}
    for attack in ("alie", "ipm"):
        cov = {arm: rows.get(f"coverage/{attack}/a0.2/{arm}", {})
               .get("coverage") for arm in FIXED_ARMS + ADAPTIVE_ARMS}
        if any(c is None for c in cov.values()):
            continue
        regimes[attack] = {
            "coverage": cov,
            "fixed_fail": all(cov[a] < COVERAGE_GATE for a in FIXED_ARMS),
            "adaptive_pass": all(cov[a] >= COVERAGE_GATE
                                 for a in ADAPTIVE_ARMS),
        }
    gate = any(r["fixed_fail"] and r["adaptive_pass"]
               for r in regimes.values())
    ident = all(identity.values())
    return {
        "criterion": "at alie or ipm (alpha=0.2) fixed arms "
                     f"{FIXED_ARMS} have coverage < {COVERAGE_GATE} while "
                     f"adaptive arms {ADAPTIVE_ARMS} reach >= "
                     f"{COVERAGE_GATE}; fault-free adaptive estimators "
                     "bit-identical to fixed baselines",
        "regimes": regimes,
        "bit_identity": identity,
        "pass": bool(gate and ident),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=96,
                    help="replications per coverage cell")
    ap.add_argument("--steps", type=int, default=6,
                    help="train-wire steps per cell")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI: alpha=0.2 only, stealth "
                         "attacks, 16 reps, one train cell")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--no-mesh", action="store_true",
                    help="ignore local devices, run single-device")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.mesh import make_mesh

    mesh = None
    n_dev = len(jax.devices())
    if not args.no_mesh and n_dev > 1:
        mesh = make_mesh((n_dev,), ("data",))
        print(f"sharding coverage replications over {n_dev} devices")

    if args.smoke:
        attacks, alphas, reps = ("alie", "ipm"), (0.0, 0.2), 16
        train_attacks, train_arms = ("ipm",), ("auto_gm", "mean")
        serve_attacks = ATTACKS
    else:
        attacks, alphas, reps = ATTACKS, ALPHAS, args.reps
        train_attacks, train_arms = TRAIN_ATTACKS, TRAIN_ARMS
        serve_attacks = ATTACKS

    t0 = time.perf_counter()
    rows = {}
    rows.update(run_coverage_wire(attacks, alphas, ESTIMATOR_CELLS, reps,
                                  mesh=mesh))
    rows.update(run_serve_wire(serve_attacks, ESTIMATOR_CELLS))
    rows.update(run_train_wire(train_attacks, train_arms, args.steps))
    identity = bit_identity_record()
    total_s = time.perf_counter() - t0

    out = {
        "settings": {
            "level": LEVEL, "reps": reps, "m_workers": 100, "p": 5,
            "K": K, "serve_alpha": SERVE_ALPHA,
            "coverage_gate": COVERAGE_GATE, "devices": n_dev,
            "smoke": bool(args.smoke),
            "total_seconds": round(total_s, 1),
        },
        "rows": rows,
        "acceptance": acceptance(rows, identity),
    }
    acc = out["acceptance"]
    print(f"acceptance: {'PASS' if acc['pass'] else 'FAIL'} "
          f"(bit_identity={identity})")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.out}")
    return out


if __name__ == "__main__":
    main()
