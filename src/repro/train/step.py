"""Step builders: Byzantine-robust train_step + prefill/decode serve steps.

``make_train_step`` wires the paper's technique into the training loop:
per-worker gradients (vmap over the worker axis = data mesh axes),
optional simulated Byzantine corruption, robust aggregation
(repro.dist.robust_reduce), optimizer update. Everything jit-compatible
and fully sharded; the returned callable carries .in_shardings /
.out_shardings for jit/lower.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..core import attacks as atk
from ..core.estimator import Estimator
from ..dist import ctx as CTX
from ..dist import robust_reduce as RR
from ..dist import sharding as S
from ..models import model as M
from ..obs.trace import named_span
from .. import optim as O


@dataclasses.dataclass(frozen=True)
class TrainSetup:
    step_fn: Callable
    # Stacked modes, split at the wire (e.g. to feed one gradient stack
    # to two aggregation modes): (params, batch, key) -> (loss, per-worker
    # grads [W, ...] after the attack), and (grads) -> the mode's aggregate
    # (rrs, auto and mean modes).
    worker_grads_fn: Callable
    aggregate_fn: Callable
    params_specs: object
    opt_specs: object
    batch_axes: tuple
    worker_axes: tuple
    n_workers: int
    # Adaptive estimators only (est.adaptive): zero-arg callable building
    # the initial core.adaptive.AdaptiveState carry; the step then takes
    # it as a trailing arg and returns the updated state after the loss
    # (RL211: adaptive state is an explicit carry, never Python state).
    init_state: Optional[Callable] = None




def make_train_step(
    cfg: ArchConfig,
    mesh,
    *,
    estimator=Estimator(),  # Estimator spec or method name (coerced)
    mode: str = "stacked-rrs",  # stacked-rrs | stacked-auto | mean | inloop
    optimizer=None,
    lr: float = 1e-3,
    byzantine_frac: float = 0.0,
    attack: str = "gaussian",
    global_batch: Optional[int] = None,
    microbatch: Optional[int] = None,
    with_diag: bool = False,
    reduce_backend: str = "rrs",
    consensus=None,
    fault_plan=None,
    weights_beta: float = 0.5,
    momentum: float = 0.0,
) -> TrainSetup:
    """``estimator``: a ``core.estimator.Estimator`` (or method name) —
    the single aggregation spec threaded to every robust-reduction mode.
    ``microbatch``: gradient-accumulation steps per worker (None = auto:
    one-sequence microbatches when seq_len >= 2048 — keeps remat-stored
    layer boundaries at one sequence/chip, see EXPERIMENTS.md §Perf).
    ``with_diag``: the step additionally returns an
    ``obs.diag.AggDiagnostics`` aux (per-worker suspicion scores,
    alpha-hat, pre/post norms) — static-shape arrays riding the same jit,
    so enabling it changes the step signature but adds no host sync.
    ``reduce_backend``: ``"rrs"`` keeps the coordinator-style modes as
    selected by ``mode``; ``"consensus"`` reroutes the stacked wire
    through peer-to-peer approximate consensus (DESIGN.md §13), with
    ``consensus`` (a ``dist.consensus.ConsensusConfig``; default derives
    ``f`` from ``byzantine_frac``) and ``fault_plan`` (a
    ``dist.faults.FaultPlan`` of injected dropout/crashes/stragglers).
    In consensus mode the step always returns a
    ``dist.consensus.ConsensusAux`` after the loss — the step signature
    becomes ``(params, opt, loss, caux[, diag])``.
    Adaptive estimators (``est.adaptive``, DESIGN.md §14) reroute the
    stacked wire through ``aggregate_stacked_adaptive``: the step takes
    an ``AdaptiveState`` as a trailing argument (build it with
    ``TrainSetup.init_state()``) and returns the new state after the
    loss — ``(params, opt, loss, agg_state[, diag])``. ``weights_beta``
    / ``momentum`` are the adaptive EMA knobs (ignored otherwise)."""
    est = Estimator.coerce(estimator)
    if with_diag and mode == "inloop":
        raise ValueError(
            "with_diag is unavailable in inloop mode: IB-RRS aggregates "
            "inside the backward pass and the per-worker gradient stack "
            "never materializes to diagnose. Use mode='stacked-rrs'.")
    worker_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_workers = 1
    for a in worker_axes:
        n_workers *= mesh.shape[a]
    batch_axes = worker_axes
    optimizer = optimizer or O.get(cfg.optimizer, lr=lr)

    if reduce_backend not in ("rrs", "consensus"):
        raise ValueError(f"unknown reduce_backend {reduce_backend!r}; "
                         "known: ('rrs', 'consensus')")
    if reduce_backend == "consensus":
        from ..dist.consensus import ConsensusConfig

        if mode == "inloop":
            raise ValueError(
                "reduce_backend='consensus' needs the materialized "
                "stacked wire; inloop (IB-RRS) aggregates inside the "
                "backward pass. Use a stacked mode.")
        mode = "stacked-consensus"
        if consensus is None:
            n_byz_hint = int(byzantine_frac * (n_workers - 1))
            consensus = ConsensusConfig(f=max(n_byz_hint, 1))
        if n_workers > 1:
            consensus.validate(n_workers)  # fail at build, not at trace

    if est.adaptive:
        if mode == "inloop":
            raise ValueError(
                "adaptive estimators need the materialized stacked wire; "
                "inloop (IB-RRS) aggregates inside the backward pass. "
                "Use a stacked mode.")
        if mode == "stacked-consensus":
            raise ValueError(
                "adaptive estimators are unavailable on the consensus "
                "backend: peer rounds exchange coordinate slices, never "
                "complete worker rows (DESIGN.md §13). Use "
                "reduce_backend='rrs'.")
        mode = "stacked-adaptive"

    params_shapes = M.abstract_init(cfg)
    params_specs = S.param_specs(params_shapes, mesh)
    opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
    opt_specs = S.opt_state_specs(opt_shapes, params_shapes, params_specs)

    init_state = None
    if est.adaptive:
        # The adaptive wire ravels every leaf, so the census dimension is
        # the total parameter count.
        wire_dim = sum(math.prod(l.shape)
                       for l in jax.tree.leaves(params_shapes))
        init_state = lambda: est.init_adaptive_state(n_workers, wire_dim)

    n_byz = int(byzantine_frac * (n_workers - 1))
    mask = jnp.arange(n_workers) >= (n_workers - n_byz)
    attack_fn = atk.get(attack)

    def loss_fn(p, b):
        return M.loss(p, cfg, b)

    def _micro_for(batch_w):
        if microbatch is not None:
            return microbatch
        tokens = batch_w["tokens"]
        per_worker, seq = tokens.shape[1], tokens.shape[2]
        return per_worker if seq >= 2048 else 1

    def worker_grad(params, b):
        """Per-worker loss+grad with gradient accumulation over
        1/micro-sized slices of the worker's batch (f32 accumulator)."""
        micro = _micro_for_static[0]
        if micro <= 1:
            return jax.value_and_grad(loss_fn)(params, b)
        bm = jax.tree.map(
            lambda x: x.reshape((micro, x.shape[0] // micro) + x.shape[1:]),
            b)
        acc0 = (jnp.zeros(()),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))

        def mb(acc, bi):
            l, g = jax.value_and_grad(loss_fn)(params, bi)
            return (acc[0] + l,
                    jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32),
                                 acc[1], g)), None

        (l, g), _ = jax.lax.scan(mb, acc0, bm)
        g = jax.tree.map(lambda x, p: (x / micro).astype(p.dtype), g, params)
        return l / micro, g

    _micro_for_static = [1]

    def stacked_specs():
        return S.stacked_grad_specs(params_specs, worker_axes, mesh,
                                    shapes=params_shapes)

    def worker_grads(params, batch, key):
        """Stacked modes: -> (mean loss, per-worker grads [W, ...] after
        the attack, consensus key or None)."""
        # split the global batch into per-worker microbatches
        def split(x):
            b = x.shape[0]
            return x.reshape((n_workers, b // n_workers) + x.shape[1:])

        batch_w = jax.tree.map(split, batch)
        _micro_for_static[0] = _micro_for(batch_w)
        # spmd_axis_name pins every batched intermediate's worker
        # dim to the data axes — without it XLA materializes
        # worker-replicated activations in the backward pass.
        losses, grads = jax.vmap(
            worker_grad, in_axes=(None, 0),
            spmd_axis_name=worker_axes,
        )(params, batch_w)
        grads = jax.lax.with_sharding_constraint(
            grads, S.to_named(mesh, stacked_specs()))
        k_cons = None
        if mode == "stacked-consensus":
            key, k_cons = jax.random.split(key)
        if n_byz:
            grads = jax.tree.map(lambda g: attack_fn(key, g, mask), grads)
        return jnp.mean(losses), grads, k_cons

    def aggregate_stack(grads, k_cons=None, agg_state=None):
        """The mode's robust aggregation of a per-worker grad stack."""
        if mode == "stacked-consensus":
            return RR.aggregate(grads, mesh, worker_axes, mode=mode,
                                est=est, specs=stacked_specs(),
                                with_diag=with_diag, consensus=consensus,
                                plan=fault_plan, key=k_cons,
                                pin_mask=mask if n_byz else None)
        if mode == "stacked-adaptive":
            return RR.aggregate_stacked_adaptive(
                grads, agg_state, est, with_diag=with_diag,
                weights_beta=weights_beta, momentum=momentum)
        return RR.aggregate(grads, mesh, worker_axes, mode=mode, est=est,
                            specs=stacked_specs(), with_diag=with_diag)

    def robust_grad(params, batch, key, agg_state=None):
      """-> (loss, aggregate, new_state, caux, diag): everything up to
      the optimizer update."""
      with CTX.mesh_context(mesh):
          if mode == "inloop":
              # IB-RRS: global backward; heavy matmul grads are robust-
              # reduced inside the bwd pass via robust_dot. Gradient
              # accumulation over batch slices bounds activation memory
              # (the aggregate of per-micro VRMOMs stays robust: each
              # micro-step aggregation already bounds Byzantine influence).
              B = batch["tokens"].shape[0]
              seq = batch["tokens"].shape[1]
              micro = microbatch if microbatch is not None else (
                  max(B // n_workers, 1) if seq >= 2048 else 1)
              if B % max(n_workers, 1):
                  raise ValueError(
                      f"inloop global batch {B} must be divisible by "
                      f"the {n_workers} workers")
              per_worker = B // max(n_workers, 1)
              if micro > 1 and per_worker % micro:
                  raise ValueError(
                      f"inloop microbatch={micro} must divide the "
                      f"per-worker batch {per_worker}")
              with RR.robust_backward(mesh, worker_axes, est):
                  if micro > 1:
                      # STRIDED split: every micro-slice must contain an
                      # equal worker-major block from each physical worker,
                      # or robust_dot's per-worker grouping inside the
                      # backward stops corresponding to workers and a
                      # single Byzantine worker owns whole micro-steps.
                      def split_micro(x):
                          b = x.shape[0]
                          x = x.reshape((n_workers, micro,
                                         b // (n_workers * micro))
                                        + x.shape[1:])
                          x = jnp.swapaxes(x, 0, 1)
                          return x.reshape((micro, b // micro) + x.shape[3:])

                      bm = jax.tree.map(split_micro, batch)
                      acc0 = (jnp.zeros(()),
                              jax.tree.map(lambda p: jnp.zeros(
                                  p.shape, jnp.float32), params))

                      def mb(acc, bi):
                          l, g = jax.value_and_grad(loss_fn)(params, bi)
                          g = jax.lax.with_sharding_constraint(
                              g, S.to_named(mesh, params_specs))
                          return (acc[0] + l, jax.tree.map(
                              lambda a, gg: a + gg.astype(jnp.float32),
                              acc[1], g)), None

                      (loss, grads), _ = jax.lax.scan(mb, acc0, bm)
                      loss = loss / micro
                      grads = jax.tree.map(
                          lambda x, p: (x / micro).astype(p.dtype),
                          grads, params)
                  else:
                      loss, grads = jax.value_and_grad(loss_fn)(params, batch)
              agg = grads
          else:
              with named_span("train.grad"):
                  loss, grads, k_cons = worker_grads(params, batch, key)
              with named_span("rrs.aggregate"):
                  agg = aggregate_stack(grads, k_cons, agg_state)
          diag = caux = new_state = None
          if mode == "stacked-consensus":
              if with_diag:
                  agg, caux, diag = agg
              else:
                  agg, caux = agg
          elif mode == "stacked-adaptive":
              if with_diag:
                  agg, new_state, diag = agg
              else:
                  agg, new_state = agg
          elif with_diag:
              agg, diag = agg
          agg = jax.lax.with_sharding_constraint(
              agg, S.to_named(mesh, params_specs))
          return loss, agg, new_state, caux, diag

    def worker_grads_fn(params, batch, key):
        with CTX.mesh_context(mesh):
            loss, grads, _ = worker_grads(params, batch, key)
        return loss, grads

    def aggregate_fn(grads):
        with CTX.mesh_context(mesh):
            return aggregate_stack(grads)

    def train_step(params, opt_state, batch, key, agg_state=None):
      loss, agg, new_state, caux, diag = robust_grad(params, batch, key,
                                                     agg_state)
      with CTX.mesh_context(mesh):
          with named_span("train.optimizer"):
              new_params, new_opt = optimizer.update(agg, opt_state, params)
          new_params = jax.lax.with_sharding_constraint(
              new_params, S.to_named(mesh, params_specs))
          out = (new_params, new_opt, loss)
          if new_state is not None:
              out = out + (new_state,)
          if caux is not None:
              out = out + (caux,)
          if with_diag:
              out = out + (diag,)
          return out

    return TrainSetup(
        step_fn=train_step,
        worker_grads_fn=worker_grads_fn,
        aggregate_fn=aggregate_fn,
        params_specs=params_specs,
        opt_specs=opt_specs,
        batch_axes=batch_axes,
        worker_axes=worker_axes,
        n_workers=n_workers,
        init_state=init_state,
    )


def make_serve_steps(cfg: ArchConfig, mesh, *, shape, window="cfg"):
    """Returns (prefill_fn, decode_fn, cache_spec_fn) with spec helpers."""
    batch_axes = S.batch_axes_for(mesh, shape.global_batch)

    def prefill_fn(params, batch):
        with CTX.mesh_context(mesh):
            logits, caches = M.prefill(params, cfg, batch, window=window,
                                       cache_len=shape.seq_len,
                                       last_only=True)
            return logits, caches

    def decode_fn(params, caches, token):
        with CTX.mesh_context(mesh):
            return M.decode_step(params, cfg, caches, token, window=window)

    def cache_shapes():
        return jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len,
                                 window=window))

    def specs():
        cs = S.cache_specs(cfg, cache_shapes(), mesh, batch_axes,
                           global_batch=shape.global_batch)
        return cs

    return prefill_fn, decode_fn, cache_shapes, specs, batch_axes
