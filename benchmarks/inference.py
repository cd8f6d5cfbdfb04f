"""Coverage/width tables for the plug-in inference layer (DESIGN.md §9).

Reproduces the statistical-guarantee side of the paper's Section 4: for
every (model, attack, Byzantine fraction, aggregator) cell, run a
fully-compiled Monte-Carlo coverage experiment
(``repro.infer.coverage_run`` — ``lax.map``-batched replications, no
per-rep Python dispatch; shard_map-sharded over the local device mesh
when one is available) and record empirical coverage of the nominal-95%
sandwich CIs, mean CI width, and point-estimate RMSE.

Emits ``BENCH_inference.json``:

    {"settings": {...},
     "rows": {"linear/gaussian/a0.1/vrmom": {"coverage": 0.96, ...}, ...},
     "acceptance": {"cell": "linear/gaussian/a0.1/vrmom",
                    "coverage": ..., "nominal": 0.95, "pass": true}}

The ``acceptance`` block is the repo's committed guarantee: empirical
coverage of VRMOM-RCSL on the linear model under the paper's Gaussian
attack at alpha = 0.1 stays within 3 points of the nominal 95%.

  PYTHONPATH=src python -m benchmarks.inference [--smoke] [--reps 200]
      [--out BENCH_inference.json]
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

import jax

from repro.infer import coverage_run
from repro.launch.mesh import make_mesh

ATTACKS = ("gaussian", "signflip", "wrong_value")
ALPHAS = (0.05, 0.1, 0.2)
LEVEL = 0.95
# Logistic needs more per-machine data for the Newton solve's asymptotics.
N_PER_MACHINE = {"linear": 200, "logistic": 400}
ACCEPTANCE_CELL = "linear/gaussian/a0.1/vrmom"
ACCEPTANCE_TOL = 0.03


def _cells(models, attacks, alphas, aggregators):
    """The benchmark grid: one clean cell per (model, aggregator), then
    the full attack x alpha cross."""
    for model in models:
        for agg in aggregators:
            yield model, "none", 0.0, agg
            for attack in attacks:
                for alpha in alphas:
                    yield model, attack, alpha, agg


def run_grid(models, attacks, alphas, aggregators, reps, mesh=None,
             verbose=True):
    rows = {}
    for model, attack, alpha, agg in _cells(models, attacks, alphas,
                                            aggregators):
        # Logistic Newton solves make each rep ~2x a linear rep; the
        # coverage estimate tolerates fewer of them.
        cell_reps = reps if model == "linear" else max(reps // 2, 8)
        n = N_PER_MACHINE[model]
        if mesh is not None:
            w = int(mesh.shape["data"])
            cell_reps = max(w, cell_reps - cell_reps % w)
        t0 = time.perf_counter()
        cell = coverage_run(
            model=model, attack=attack, alpha=alpha, estimator=agg,
            reps=cell_reps, N_per_machine=n, m_workers=100, p=5, rounds=6,
            level=LEVEL, batch_size=12, mesh=mesh)
        s = cell.summary()
        s["seconds"] = round(time.perf_counter() - t0, 2)
        name = f"{model}/{attack}/a{alpha}/{agg}"
        rows[name] = s
        if verbose:
            print(f"{name:38s} coverage={s['coverage']:.3f} "
                  f"width={s['mean_width']:.4f} rmse={s['rmse']:.4f} "
                  f"({s['seconds']:.1f}s)", flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=200,
                    help="replications per linear cell (logistic uses half)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid + reps for CI (one attack, two alphas)")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--no-mesh", action="store_true",
                    help="ignore local devices, run single-device")
    args = ap.parse_args(argv)

    mesh = None
    n_dev = len(jax.devices())
    if not args.no_mesh and n_dev > 1:
        mesh = make_mesh((n_dev,), ("data",))
        print(f"sharding replications over {n_dev} local devices")

    if args.smoke:
        models, attacks, alphas = ("linear", "logistic"), ("gaussian",), (0.1,)
        aggregators, reps = ("vrmom",), min(args.reps, 24)
    else:
        models, attacks, alphas = ("linear", "logistic"), ATTACKS, ALPHAS
        aggregators, reps = ("vrmom", "median"), args.reps

    t0 = time.perf_counter()
    rows = run_grid(models, attacks, alphas, aggregators, reps, mesh=mesh)
    total_s = time.perf_counter() - t0

    out = {
        "settings": {
            "level": LEVEL, "reps_linear": reps, "m_workers": 100, "p": 5,
            "K": 10, "rounds": 6, "N_per_machine": N_PER_MACHINE,
            "devices": n_dev, "smoke": bool(args.smoke),
            "total_seconds": round(total_s, 1),
        },
        "rows": rows,
    }
    acc_row = rows.get(ACCEPTANCE_CELL)
    if acc_row is not None:
        out["acceptance"] = {
            "criterion": f"empirical coverage within {ACCEPTANCE_TOL:.0%} of "
                         f"nominal {LEVEL:.0%} for VRMOM-RCSL, linear model, "
                         f"gaussian attack, alpha=0.1",
            "cell": ACCEPTANCE_CELL,
            "coverage": acc_row["coverage"],
            "nominal": LEVEL,
            "pass": abs(acc_row["coverage"] - LEVEL) <= ACCEPTANCE_TOL,
        }
        print(f"acceptance [{ACCEPTANCE_CELL}]: "
              f"coverage={acc_row['coverage']:.3f} vs nominal {LEVEL} -> "
              f"{'PASS' if out['acceptance']['pass'] else 'FAIL'}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.out}")
    return out


if __name__ == "__main__":
    main()
