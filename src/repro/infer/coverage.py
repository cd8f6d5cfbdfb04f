"""Vectorized Monte-Carlo coverage harness for the plug-in CIs.

Reproduces the statistical-guarantee side of the paper's Section 4: for
a (model, attack, Byzantine-fraction, aggregator) cell, run ``reps``
full replications — simulate sharded data, run RCSL under attack,
compute plug-in CIs under the *same* attack on the reported statistics
(``repro.infer.sandwich``), and record whether each coordinate of
theta* landed inside its interval — then report empirical coverage,
mean CI width, and RMSE.

The whole cell is ONE compiled program (DESIGN.md §9): replications are
``jax.lax.map``-batched (an inner ``vmap`` over ``batch_size`` reps per
scan step — vectorized work, bounded memory, zero per-rep Python
dispatch), and with a mesh they are additionally ``shard_map``-sharded
over the worker axis, each device running its own ``reps / W`` slice of
keys with no cross-device communication until the host-side summary.

``benchmarks/inference.py`` drives this over the paper grid and commits
``BENCH_inference.json``; ``tests/test_infer.py`` runs a small-rep cell
and checks coverage against the nominal level.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import rcsl as R
from ..core.estimator import Estimator
from .sandwich import infer

__all__ = ["CoverageCell", "coverage_run"]


class CoverageCell(NamedTuple):
    """Raw per-replication outcomes of one coverage cell.

    covered: ``[reps, p]`` bool — theta*_l inside [lower_l, upper_l].
    width:   ``[reps, p]`` CI widths.
    err:     ``[reps, p]`` estimation errors theta_hat - theta*.
    """

    covered: jnp.ndarray
    width: jnp.ndarray
    err: jnp.ndarray

    def summary(self) -> dict:
        """Host-side scalars for tables / BENCH_inference.json."""
        return {
            "coverage": float(jnp.mean(self.covered)),
            "coverage_per_coord": [float(c)
                                   for c in jnp.mean(self.covered, axis=0)],
            "mean_width": float(jnp.mean(self.width)),
            "rmse": float(jnp.sqrt(jnp.mean(self.err ** 2))),
            "reps": int(self.covered.shape[0]),
        }


def coverage_run(
    model: str = "linear",
    attack: str = "gaussian",
    alpha: float = 0.1,
    estimator: Union[str, Estimator] = "vrmom",
    K: int = 10,
    level: float = 0.95,
    reps: int = 200,
    N_per_machine: int = 200,
    m_workers: int = 100,
    p: int = 5,
    rounds: int = 6,
    mu_x: float = 0.0,
    labelflip: bool = False,
    simultaneous: bool = False,
    seed: int = 0,
    batch_size: int = 16,
    mesh=None,
    rep_axis: str = "data",
    reduce_backend: str = "direct",
    consensus=None,
    fault_plan=None,
    assumed_alpha: Optional[float] = None,
) -> CoverageCell:
    """Run one fully-compiled coverage cell; see module docstring.

    ``mesh``/``rep_axis``: when given (and the axis is non-trivial) the
    replication axis is shard_map-sharded over it — ``reps`` must be
    divisible by the axis size. Without a mesh the same program runs on
    one device.

    ``reduce_backend="consensus"`` runs every RCSL round's aggregation
    through the peer-to-peer consensus emulation (DESIGN.md §13) with
    the given ``dist.consensus.ConsensusConfig`` / ``dist.faults.
    FaultPlan`` — the statistical cell under the decentralized wire,
    optionally with message loss and crashes injected inside each
    replication.

    ``assumed_alpha``: the contamination fraction the *analyst* plugs
    into the CI inflation, independent of the true ``alpha`` driving
    the attack (``infer``'s regime-matrix knob, DESIGN.md §14).
    ``None`` keeps the legacy oracle behavior (assume the truth).
    """
    theta_star = R.paper_theta_star(p)
    problem = (R.LinearRegressionProblem() if model == "linear"
               else R.LogisticRegressionProblem())

    def one_rep(key):
        kd, kr, ks = jax.random.split(key, 3)
        shards = R.make_shards(kd, N_per_machine=N_per_machine,
                               m_workers=m_workers, p=p,
                               theta_star=theta_star, model=model, mu_x=mu_x)
        theta_hat, _ = R.rcsl(problem, shards, kr, alpha=alpha, attack=attack,
                              aggregator=estimator, K=K, rounds=rounds,
                              labelflip=labelflip,
                              reduce_backend=reduce_backend,
                              consensus=consensus, fault_plan=fault_plan)
        shards_rep, stat_attack = shards, attack
        if labelflip:
            # Label-flip Byzantine machines report *honest* statistics
            # computed on flipped-label data (paper 4.2.2) — model that
            # by flipping their shard labels before machine_stats. The
            # flipped shards ARE the Byzantine reports, so no registry
            # attack is layered on top (rcsl's labelflip branch ignores
            # `attack` for the same reason).
            mask = R.attacks.byzantine_mask(m_workers + 1, alpha)
            shards_rep = R.Shards(
                X=shards.X,
                Y=jnp.where(mask[:, None], 1.0 - shards.Y, shards.Y))
            stat_attack = "none"
        res = infer(problem, shards_rep, theta_hat, estimator=estimator, K=K,
                    level=level, simultaneous=simultaneous,
                    alpha=alpha, attack=stat_attack, key=ks,
                    assumed_alpha=assumed_alpha)
        covered = jnp.logical_and(res.ci.lower <= theta_star,
                                  theta_star <= res.ci.upper)
        return covered, res.ci.upper - res.ci.lower, theta_hat - theta_star

    def run_keys(keys):
        return jax.lax.map(one_rep, keys, batch_size=batch_size)

    keys = jax.random.split(jax.random.PRNGKey(seed), reps)
    if mesh is not None and int(mesh.shape[rep_axis]) > 1:
        W = int(mesh.shape[rep_axis])
        if reps % W:
            raise ValueError(f"reps={reps} not divisible by the {W}-way "
                             f"mesh axis {rep_axis!r}")
        spec = P(rep_axis)
        keys = jax.device_put(keys, NamedSharding(mesh, spec))
        # Independent replications: each shard maps its own key slice;
        # no collectives — the rep axis is embarrassingly parallel.
        run = jax.shard_map(run_keys, mesh=mesh,
                        in_specs=spec, out_specs=(spec, spec, spec),
                        check_vma=False)
        covered, width, err = jax.jit(run)(keys)
    else:
        covered, width, err = jax.jit(run_keys)(keys)
    return CoverageCell(covered=covered, width=width, err=err)
