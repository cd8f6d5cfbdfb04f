"""Decentralized approximate consensus over the mesh worker axes.

The coordinator-free alternative to Robust-Reduce-Scatter (DESIGN.md
§13): instead of one all_to_all + one all_gather with a designated
owner per coordinate chunk, every worker is a peer. Each round a
worker broadcasts its current value vector, f-trims whatever arrives,
and moves to the trimmed aggregate; after a *static* number of rounds

    ``p_end = ceil(log(eps / K) / log(1/2))``

(the JACM86 phase bound for convergence factor 1/2 per round, with
``K = init_range`` the assumed bound on the initial spread) every
honest worker holds the same value to within ``eps``. Validity
requires ``n > 5f`` — refused at trace time, mirroring
``robust_dot``'s divisibility refusal — and each round proceeds on any
``n - f`` received values (the quorum), so the iteration tolerates
message dropout, stragglers serving stale values, and permanent
crashes injected by a :class:`repro.dist.faults.FaultPlan`.

Two executions of the same round semantics:

* ``consensus_iterate`` / ``consensus_aggregate`` — mesh-free jit
  emulation on a local ``[n, C]`` stack (every receiver's view is
  materialized, ``O(n^2 C)`` on the fault path). The numerical oracle,
  and the backend for `infer/coverage` cells and small-n callers.
* ``aggregate_stacked_consensus`` — the shard_map backend: same
  stacked-gradient wire and sharding specs as ``aggregate_stacked_rrs``
  (leaves ``[n_workers, *param]``, dim 0 on the worker axes, model
  axis partitioning coordinates), one ``all_gather`` per round inside
  a ``lax.fori_loop`` with the static ``p_end`` bound.

Fault-free with ``trim="mean"``, a round *is* one §7 ``Estimator``
aggregate of the gathered stack — every peer computes the identical
value, the iteration is idempotent from round 1 on, and the output
equals ``aggregate_stacked_auto``/``_rrs`` exactly. Under faults the
per-receiver reception masks differ, so rounds run the masked f-trim
(``sort`` + windowed mean or midpoint) instead; receivers below
quorum hold their previous value, and quorum loss is *reported* (aux
flag + ``dist.quorum`` gauge), never a NaN.

Adversary model: attacks from ``core/attacks`` corrupt the initial
stack (static adversary); passing the Byzantine mask as ``pin_mask``
upgrades them to *persistent* senders that re-broadcast their corrupt
payload every round — the regime the ``n > 5f`` bound is for.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.estimator import Estimator
from ..obs.trace import named_span
from .faults import FaultPlan

__all__ = [
    "ConsensusConfig",
    "ConsensusAux",
    "consensus_iterate",
    "consensus_aggregate",
    "aggregate_stacked_consensus",
]

EstimatorLike = Union[str, Estimator]

# Missing-message sentinel: sorts after any real payload but stays a
# normal float (no inf arithmetic anywhere near the trim windows), and
# is far above every attack payload in the zoo (|omniscient| ~ 1e10).
_MISSING = jnp.float32(3.0e38)

TRIM_MODES = ("mean", "midpoint")


class ConsensusConfig(NamedTuple):
    """Static spec of the consensus iteration (hashable, keys jit).

    ``f``          — Byzantine peers tolerated; drives both the
                     per-round trim width and the ``n - f`` quorum.
    ``eps``        — target agreement diameter.
    ``init_range`` — ``K``: assumed bound on the initial honest spread
                     (enters only through the log in ``p_end``).
    ``trim``       — per-round update: ``"mean"`` (trimmed mean; the
                     §7 Estimator fault-free) or ``"midpoint"``
                     (JACM86 trimmed midpoint).
    ``max_rounds`` — optional hard cap on ``p_end``.
    """
    f: int = 1
    eps: float = 1e-4
    init_range: float = 64.0
    trim: str = "mean"
    max_rounds: Optional[int] = None

    def validate(self, n: int) -> "ConsensusConfig":
        """Trace-time validity: approximate consensus under Byzantine
        peers *and* message loss requires ``n > 5f`` (JACM86). ``n``
        and ``f`` are static, so — like ``robust_dot``'s divisibility
        guard — an invalid deployment refuses to trace rather than
        silently losing the convergence guarantee."""
        if self.trim not in TRIM_MODES:
            raise ValueError(
                f"unknown trim mode {self.trim!r}; known: {TRIM_MODES}")
        if self.f < 0:
            raise ValueError(f"f must be >= 0, got {self.f}")
        if n <= 5 * self.f:
            raise ValueError(
                f"consensus validity needs n > 5f: n={n} peers cannot "
                f"tolerate f={self.f} Byzantine faults (need n >= "
                f"{5 * self.f + 1} or f <= {(n - 1) // 5})")
        if not 0.0 < self.eps < self.init_range:
            raise ValueError(
                f"need 0 < eps < init_range, got eps={self.eps}, "
                f"init_range={self.init_range}")
        return self

    def phases(self, plan: Optional[FaultPlan] = None) -> int:
        """Static round bound ``p_end = ceil(log(eps/K)/log(1/2))``.

        Receivers below quorum hold their value instead of updating,
        so with message dropout the bound is doubled — at the 10%
        dropout / n=8 operating point the per-round update probability
        stays well above 1/2, leaving margin to spare. Staleness adds
        its window on top. ``max_rounds`` caps the result.
        """
        p = max(1, math.ceil(math.log(self.eps / self.init_range)
                             / math.log(0.5)))
        if plan is not None:
            if plan.dropout > 0.0:
                p *= 2
            if plan.n_stragglers:
                p += int(plan.stale_rounds)
        if self.max_rounds is not None:
            p = min(p, int(self.max_rounds))
        return p


class ConsensusAux(NamedTuple):
    """Fixed-shape jit aux outputs of one consensus aggregate.

    Drained host-side into the §11 metrics (``consensus.rounds``
    histogram, ``dist.messages_dropped`` counter, ``dist.quorum``
    gauge); every field is a scalar array so the pytree rides any jit
    boundary unchanged.
    """
    rounds_run: jax.Array        # [] int32 — static phase bound executed
    rounds_to_eps: jax.Array     # [] int32 — first round with honest
    #                                 spread <= eps (rounds_run if never)
    spread: jax.Array            # [] f32  — final honest-alive spread
    quorum: jax.Array            # [] f32  — fraction of (round, alive
    #                                 receiver) slots meeting n-f quorum
    quorum_lost: jax.Array       # [] bool — no alive receiver met quorum
    #                                 in the final round
    messages_dropped: jax.Array  # [] int32 — alive->alive messages lost


# ---------------------------------------------------------------------------
# round primitives (shared by the emulation and the shard_map backend)
# ---------------------------------------------------------------------------

def _masked_trim(vals, recv, f: int, trim: str):
    """f-trimmed aggregate of the received subset of ``vals``.

    ``vals``: [n, C]; ``recv``: [n] bool. Missing rows are replaced by
    the ``_MISSING`` sentinel so they sort to the top; the trim window
    ``[f, n_recv - f)`` then only ever touches real payloads. Returns
    [C]; always finite (empty windows fall back to 0 — callers gate on
    quorum before trusting the value).
    """
    n = vals.shape[0]
    vm = jnp.where(recv[:, None], vals, _MISSING)
    srt = jnp.sort(vm, axis=0)
    n_recv = jnp.sum(recv.astype(jnp.int32))
    idx = jnp.arange(n, dtype=jnp.int32)[:, None]
    if trim == "midpoint":
        lo_i = jnp.clip(f, 0, jnp.maximum(n_recv - 1, 0))
        hi_i = jnp.clip(n_recv - 1 - f, lo_i, n - 1)
        lo = jnp.sum(jnp.where(idx == lo_i, srt, 0.0), axis=0)
        hi = jnp.sum(jnp.where(idx == hi_i, srt, 0.0), axis=0)
        return jnp.where(n_recv > 0, 0.5 * (lo + hi), 0.0)
    keep = (idx >= f) & (idx < n_recv - f)
    denom = jnp.maximum(n_recv - 2 * f, 1).astype(jnp.float32)
    return jnp.sum(jnp.where(keep, srt, 0.0), axis=0) / denom


def _spread(vals, mask):
    """[] f32 — max over coordinates of (max - min) over ``mask`` rows
    of ``vals`` [n, C]; 0 when fewer than two rows are selected."""
    m = mask[:, None]
    hi = jnp.max(jnp.where(m, vals, -_MISSING), axis=0)
    lo = jnp.min(jnp.where(m, vals, _MISSING), axis=0)
    sp = jnp.max(hi - lo)
    return jnp.where(jnp.sum(mask) >= 2, sp, 0.0)


def _rounds_to_eps(spreads, final_spread, eps, p_end: int):
    """First round index whose *entering* honest spread is <= eps
    (spreads[p] is measured on the values entering round p, so index p
    means "converged after p rounds"); ``p_end`` if only the final
    values — or nothing — made it."""
    conv = jnp.concatenate([spreads, final_spread[None]]) <= eps
    return jnp.where(jnp.any(conv), jnp.argmax(conv),
                     p_end).astype(jnp.int32)


class _RoundView(NamedTuple):
    """Per-round fault state, computed identically on every shard from
    the (replicated) plan + key: reception matrix, liveness, quorum."""
    recv: jax.Array      # [n, n] bool — recv[i, j]: i received j
    alive: jax.Array     # [n] bool
    q_ok: jax.Array      # [n] bool — receiver met the n-f quorum
    dropped: jax.Array   # [] int32 — alive->alive messages lost


def _round_view(plan: FaultPlan, key, n: int, p, quorum: int) -> _RoundView:
    recv = plan.recv_matrix(key, n, p)
    alive = ~plan.crashed_at(n, p)
    q_ok = jnp.sum(recv, axis=1) >= quorum
    expected = (alive[:, None] & alive[None, :]) & ~jnp.eye(n, dtype=bool)
    dropped = jnp.sum(expected & ~recv).astype(jnp.int32)
    return _RoundView(recv, alive, q_ok, dropped)


def _prep(stack_n: int, est: EstimatorLike, config, plan, key):
    """Shared argument normalization + trace-time validation."""
    est = Estimator.coerce(est).require_coordinatewise(
        "consensus rounds (dist.consensus)")
    config = (config if config is not None else ConsensusConfig())
    if not isinstance(config, ConsensusConfig):
        raise TypeError(f"expected ConsensusConfig, got {type(config)!r}")
    config.validate(stack_n)
    plan = (plan if plan is not None else FaultPlan()).validate(stack_n)
    if key is None:
        key = jax.random.PRNGKey(0)
    return est, config, plan, key


# ---------------------------------------------------------------------------
# mesh-free emulation
# ---------------------------------------------------------------------------

def consensus_iterate(stack, est: EstimatorLike = "vrmom", *,
                      config: Optional[ConsensusConfig] = None,
                      plan: Optional[FaultPlan] = None,
                      key=None, pin_mask=None
                      ) -> Tuple[jax.Array, ConsensusAux]:
    """Run the full consensus iteration on a local ``[n, C]`` stack.

    Returns ``(finals, aux)`` where ``finals`` [n, C] holds every
    peer's value after ``p_end`` rounds. ``pin_mask`` [n] bool marks
    persistent Byzantine senders (they re-broadcast their initial —
    already attack-corrupted — row every round and never update).
    Jit/vmap-pure; the fault path materializes every receiver's view
    (``O(n^2 C)`` work per round).
    """
    n, _C = stack.shape
    est, config, plan, key = _prep(n, est, config, plan, key)
    f, trim, eps = config.f, config.trim, config.eps
    p_end = config.phases(plan)
    quorum = n - f
    v0 = stack.astype(jnp.float32)
    strag = plan.straggler_mask(n)
    k = int(plan.stale_rounds) if plan.n_stragglers else 0
    pin = None if pin_mask is None else jnp.asarray(pin_mask)
    hist0 = (jnp.broadcast_to(v0, (k,) + v0.shape) if k
             else jnp.zeros((0,) + v0.shape, jnp.float32))
    fixed = None
    if plan.trivial and trim == "mean" and pin is None:
        # Round 1 leaves every row at the Estimator output; each later
        # round aggregates n identical rows, which is that value again
        # in exact arithmetic. Reuse it: a float mean re-summed over n
        # equal rows can move by an ULP.
        fixed = jnp.broadcast_to(est.apply(v0, axis=0)[None], v0.shape)

    def body(p, carry):
        v, hist, spreads, dropped, q_sum, _last_q = carry
        sent = jnp.where(strag[:, None], hist[k - 1], v) if k else v
        if pin is not None:
            sent = jnp.where(pin[:, None], v0, sent)
        rv = _round_view(plan, key, n, p, quorum)
        honest = rv.alive if pin is None else rv.alive & ~pin
        if fixed is not None:
            new = fixed
        elif plan.trivial and trim == "mean":
            new = jnp.broadcast_to(est.apply(sent, axis=0)[None], v.shape)
        else:
            new = jax.vmap(
                lambda r: _masked_trim(sent, r, f, trim))(rv.recv)
        upd = (rv.q_ok & rv.alive)[:, None]
        v_new = jnp.where(upd, new, v)
        hist_new = (jnp.concatenate([v_new[None], hist[:k - 1]]) if k > 1
                    else (v_new[None] if k else hist))
        spreads = spreads.at[p].set(_spread(sent, honest))
        dropped = dropped + rv.dropped
        n_alive = jnp.maximum(jnp.sum(rv.alive), 1)
        q_sum = q_sum + jnp.sum(rv.q_ok & rv.alive) / n_alive
        return v_new, hist_new, spreads, dropped, q_sum, jnp.any(
            rv.q_ok & rv.alive)

    init = (v0, hist0, jnp.zeros((p_end,), jnp.float32),
            jnp.int32(0), jnp.float32(0.0), jnp.bool_(True))
    with named_span("consensus.round_loop"):
        finals, _, spreads, dropped, q_sum, last_q = jax.lax.fori_loop(
            0, p_end, body, init)
    if pin is not None:
        finals = jnp.where(pin[:, None], v0, finals)
    alive_end = ~plan.crashed_at(n, p_end)
    honest_end = alive_end if pin is None else alive_end & ~pin
    aux = ConsensusAux(
        rounds_run=jnp.int32(p_end),
        rounds_to_eps=_rounds_to_eps(
            spreads, _spread(finals, honest_end), eps, p_end),
        spread=_spread(finals, honest_end),
        quorum=q_sum / jnp.float32(p_end),
        quorum_lost=~last_q,
        messages_dropped=dropped,
    )
    return finals, aux


def consensus_aggregate(stack, est: EstimatorLike = "vrmom", *,
                        config: Optional[ConsensusConfig] = None,
                        plan: Optional[FaultPlan] = None,
                        key=None, pin_mask=None
                        ) -> Tuple[jax.Array, ConsensusAux]:
    """``[n, C] -> ([C], ConsensusAux)``: iterate, then decide.

    The decision is the f-trimmed aggregate over the still-alive
    peers' final values — robust to up to ``f`` persistent Byzantine
    rows, finite (never NaN) even below quorum. Fault-free with
    ``trim="mean"`` every final row is the identical Estimator output,
    and that value is returned exactly.
    """
    n, _C = stack.shape
    est_c, config_c, plan_c, key = _prep(n, est, config, plan, key)
    finals, aux = consensus_iterate(stack, est_c, config=config_c,
                                    plan=plan_c, key=key, pin_mask=pin_mask)
    if plan_c.trivial and config_c.trim == "mean" and pin_mask is None:
        return finals[0], aux
    alive_end = ~plan_c.crashed_at(n, config_c.phases(plan_c))
    out = _masked_trim(finals, alive_end, config_c.f, config_c.trim)
    return out, aux


# ---------------------------------------------------------------------------
# shard_map backend — the RRS-wire drop-in
# ---------------------------------------------------------------------------

def aggregate_stacked_consensus(grads, mesh, worker_axes,
                                est: EstimatorLike = "vrmom", *,
                                config: Optional[ConsensusConfig] = None,
                                plan: Optional[FaultPlan] = None,
                                key=None, pin_mask=None, specs=None):
    """Peer-to-peer consensus aggregate of a stacked-gradient pytree.

    Drop-in for ``aggregate_stacked_rrs``: same wire (leaves
    ``[n_workers, *param]``, dim 0 sharded over ``worker_axes``,
    ``specs`` overriding the canonical layout), same output pytree with
    the worker dim removed — plus a :class:`ConsensusAux`, always:
    returns ``(pytree, aux)``. No worker owns any coordinate; each
    round is one ``all_gather`` of every peer's wire vector followed by
    the per-receiver f-trim, ``p_end`` rounds under a static
    ``fori_loop``. Non-worker mesh axes partition coordinates exactly
    as in RRS (each model shard converges on its own slice; aux spread
    is ``pmax``-ed across them).

    The leading dim of every leaf must equal the worker count — unlike
    RRS there is no meaningful reshape fallback for a mismatched stack.
    """
    from .robust_reduce import (_canonical_stacked_spec, _n_workers,
                                aggregate_stacked_auto)

    worker_axes = tuple(worker_axes)
    nw = _n_workers(mesh, worker_axes)
    if nw <= 1:
        # A one-peer mesh has nothing to disagree about: emulate with
        # f=0 (f>0 could never satisfy n > 5f at n=1).
        cfg1 = config if config is not None else ConsensusConfig()
        if isinstance(cfg1, ConsensusConfig) and cfg1.f != 0:
            cfg1 = cfg1._replace(f=0)
        return aggregate_stacked_auto(
            grads, est, reduce_backend="consensus", consensus=cfg1,
            plan=plan, key=key, pin_mask=pin_mask)
    est, config, plan, key = _prep(nw, est, config, plan, key)
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    f, trim, eps = config.f, config.trim, config.eps
    p_end = config.phases(plan)
    quorum = nw - f
    k = int(plan.stale_rounds) if plan.n_stragglers else 0
    has_pin = pin_mask is not None

    leaves, treedef = jax.tree.flatten(grads)
    for l in leaves:
        if l.shape[0] != nw:
            raise ValueError(
                f"consensus wire: leaf {l.shape} must lead with the "
                f"{nw} workers of axes {worker_axes}")
    if specs is not None:
        in_specs = jax.tree.leaves(specs,
                                   is_leaf=lambda x: isinstance(x, P))
    else:
        in_specs = [_canonical_stacked_spec(l.shape, mesh, worker_axes)
                    for l in leaves]
    leaves = [jax.lax.with_sharding_constraint(l, NamedSharding(mesh, s))
              for l, s in zip(leaves, in_specs)]
    out_specs = [P(*s[1:]) for s in in_specs]
    other_axes = tuple(a for a in mesh.axis_names if a not in worker_axes)
    pin_arg = (jnp.zeros((nw,), bool) if pin_mask is None
               else jnp.asarray(pin_mask))
    aux_specs = ConsensusAux(*([P()] * len(ConsensusAux._fields)))

    def local_consensus(key_arg, pin, *blocks):
        w_loc = blocks[0].shape[0]
        if w_loc != 1:
            raise ValueError(
                f"consensus wire: specs leave {w_loc} worker rows on one "
                f"shard; the worker dim must be fully sharded over "
                f"{worker_axes}")
        flat = jnp.concatenate(
            [b.reshape(w_loc, -1).astype(jnp.float32) for b in blocks],
            axis=1)
        rank = 0
        for a in worker_axes:
            rank = rank * int(mesh.shape[a]) + jax.lax.axis_index(a)
        strag = plan.straggler_mask(nw)
        v0 = flat[0]

        def exchange(sent):
            return jax.lax.all_gather(sent, worker_axes, axis=0,
                                      tiled=False).reshape(nw, -1)

        # fault-free mean rounds after the first are an exact fixed
        # point: reuse round 1's value (see consensus_iterate)
        fixed = (est.apply(exchange(v0), axis=0)
                 if plan.trivial and trim == "mean" and not has_pin
                 else None)

        def body(p, carry):
            v, hist, spreads, dropped, q_sum, _last_q = carry
            sent = jnp.where(strag[rank], hist[k - 1], v) if k else v
            if has_pin:
                sent = jnp.where(pin[rank], v0, sent)
            allv = exchange(sent)
            rv = _round_view(plan, key_arg, nw, p, quorum)
            honest = rv.alive & ~pin if has_pin else rv.alive
            if fixed is not None:
                new = fixed
            elif plan.trivial and trim == "mean":
                new = est.apply(allv, axis=0)
            else:
                new = _masked_trim(allv, rv.recv[rank], f, trim)
            upd = rv.q_ok[rank] & rv.alive[rank]
            v_new = jnp.where(upd, new, v)
            hist_new = (jnp.concatenate([v_new[None], hist[:k - 1]])
                        if k > 1 else (v_new[None] if k else hist))
            spreads = spreads.at[p].set(_spread(allv, honest))
            dropped = dropped + rv.dropped
            n_alive = jnp.maximum(jnp.sum(rv.alive), 1)
            q_sum = q_sum + jnp.sum(rv.q_ok & rv.alive) / n_alive
            return (v_new, hist_new, spreads, dropped, q_sum,
                    jnp.any(rv.q_ok & rv.alive))

        hist0 = (jnp.broadcast_to(v0, (k,) + v0.shape) if k
                 else jnp.zeros((0,) + v0.shape, jnp.float32))
        init = (v0, hist0, jnp.zeros((p_end,), jnp.float32),
                jnp.int32(0), jnp.float32(0.0), jnp.bool_(True))
        with named_span("consensus.round_loop"):
            v_fin, _, spreads, dropped, q_sum, last_q = jax.lax.fori_loop(
                0, p_end, body, init)

        if has_pin:
            v_fin = jnp.where(pin[rank], v0, v_fin)
        finals = exchange(v_fin)
        alive_end = ~plan.crashed_at(nw, p_end)
        honest_end = alive_end & ~pin if has_pin else alive_end
        if plan.trivial and trim == "mean" and not has_pin:
            wire = finals[0]
        else:
            wire = _masked_trim(finals, alive_end, f, trim)
        final_spread = _spread(finals, honest_end)
        if other_axes:  # model shards each watched their own slice
            spreads = jax.lax.pmax(spreads, other_axes)
            final_spread = jax.lax.pmax(final_spread, other_axes)
        aux = ConsensusAux(
            rounds_run=jnp.int32(p_end),
            rounds_to_eps=_rounds_to_eps(spreads, final_spread, eps, p_end),
            spread=final_spread,
            quorum=q_sum / jnp.float32(p_end),
            quorum_lost=~last_q,
            messages_dropped=dropped,
        )
        outs, off = [], 0
        for b in blocks:
            size = b.size // w_loc
            outs.append(wire[off:off + size]
                        .reshape(b.shape[1:]).astype(b.dtype))
            off += size
        return tuple(outs) + (aux,)

    results = jax.shard_map(
        local_consensus, mesh=mesh,
        in_specs=(P(None), P(None)) + tuple(in_specs),
        out_specs=tuple(out_specs) + (aux_specs,),
        check_vma=False)(key, pin_arg, *leaves)
    agg_leaves, aux = results[:-1], results[-1]
    return jax.tree.unflatten(treedef, agg_leaves), aux
