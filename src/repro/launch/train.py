"""Training launcher.

Runs real (allocating) robust training on whatever devices exist: a
TPU host's chips, or host CPU devices for local runs (the production
mesh on a pod). For the 512-device compile-only path use dryrun.py.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --layers 4 --steps 5 --data 4 --model 1 --aggregator vrmom \
      --byzantine 0.34

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 50 --data 4 --model 2 --aggregator vrmom --byzantine 0.25

``parse_args``, ``build`` and ``make_setup`` give callers in Python
(``chip_smoke.py --chips 4``) the launcher's own path.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import NamedTuple

import jax

from repro import optim as O
from repro.checkpoint import save as ckpt_save
from repro.configs import get as get_arch
from repro.data import lm_batch, shard_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.dist import sharding as S
from repro.models import model as M
from repro.core.estimator import Estimator
from repro.obs.metrics import now
from repro.train.step import make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers (0 = published "
                         "depth); widths stay as published")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--data", type=int, default=0,
                    help="data mesh axis (0 = all devices)")
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--aggregator", default="vrmom",
                    choices=["vrmom", "mom", "trimmed_mean", "mean"])
    ap.add_argument("--mode", default="stacked-rrs")
    ap.add_argument("--K", type=int, default=10)
    ap.add_argument("--beta", type=float, default=None,
                    help="trimmed_mean trim fraction per end (default: "
                         "0.1, raised to 1/workers when 0.1 trims no rows)")
    ap.add_argument("--byzantine", type=float, default=0.0)
    ap.add_argument("--attack", default="gaussian")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


class Run(NamedTuple):
    cfg: object
    mesh: object
    setup: object
    params: object
    opt_state: object
    step: object

    def batch(self, i: int, args):
        return shard_batch(lm_batch(self.cfg, i, args.batch, args.seq),
                           self.mesh, self.setup.batch_axes)


def make_setup(args, cfg, mesh, mode=None):
    """The train step for ``args`` on ``mesh``; ``mode`` overrides
    ``args.mode`` (another aggregation wire over the same params)."""
    n_workers = mesh.shape["data"]  # worker axes = ("data",) on this mesh
    beta = args.beta if args.beta is not None else max(0.1, 1.0 / n_workers)
    return make_train_step(
        cfg, mesh,
        estimator=Estimator(method=args.aggregator, K=args.K, beta=beta),
        mode=mode or args.mode, lr=args.lr, byzantine_frac=args.byzantine,
        attack=args.attack)


def build(args) -> Run:
    """Mesh, config, step and initial state for ``args``."""
    n_dev = len(jax.devices())
    data = args.data or max(n_dev // args.model, 1)
    mesh = make_mesh((data, args.model), ("data", "model"))
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, name=f"{cfg.name}-{args.layers}l",
                                  n_layers=args.layers)
    setup = make_setup(args, cfg, mesh)
    optimizer = O.get(cfg.optimizer, lr=args.lr)
    params = jax.jit(M.init, static_argnums=1,
                     out_shardings=S.to_named(mesh, setup.params_specs))(
        jax.random.PRNGKey(0), cfg)
    opt_state = jax.jit(optimizer.init, out_shardings=S.to_named(
        mesh, setup.opt_specs))(params)
    return Run(cfg, mesh, setup, params, opt_state, jax.jit(setup.step_fn))


def train(args):
    """The training loop."""
    run = build(args)
    params, opt_state = run.params, run.opt_state
    n_params = M.param_count(params)
    print(f"arch={run.cfg.name} params={n_params/1e6:.1f}M "
          f"mesh={dict(run.mesh.shape)} workers={run.setup.n_workers} "
          f"aggregator={args.aggregator} mode={args.mode} "
          f"byzantine={args.byzantine} attack={args.attack}")

    t0 = now()
    for i in range(args.steps):
        params, opt_state, loss = run.step(params, opt_state,
                                           run.batch(i, args),
                                           jax.random.PRNGKey(i))
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = now() - t0
            print(f"step {i:4d} loss {float(loss):.4f} "
                  f"({dt/(i+1):.2f} s/step)")
    if args.checkpoint:
        ckpt_save(args.checkpoint, {"params": params, "opt": opt_state})
        print("checkpoint saved to", args.checkpoint)


def main(argv=None):
    enable_compile_cache()
    train(parse_args(argv))


if __name__ == "__main__":
    main()
