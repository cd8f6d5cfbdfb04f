"""Layer-2 abstract trace auditor (RL201–RL211, DESIGN.md §10).

Drives the public entry points through ``jax.eval_shape`` /
``jax.make_jaxpr`` — no array is ever materialized, no kernel executed —
and verifies the invariants the AST layer cannot see: wire shapes and
dtypes, the §9 upper-triangle wire length, the coordinatewise gate, the
worker-divisibility guards, and recompile stability of the static specs.

Entry points audited (ISSUE acceptance: ≥ 6):

1. ``dist.robust_reduce.aggregate_stacked_rrs``       (RL201, RL204)
2. ``dist.robust_reduce.aggregate_symmetric_stacked`` (RL202)
3. ``dist.robust_reduce.robust_dot``/``robust_backward`` (RL205)
4. ``train.step.make_train_step``                     (RL206, RL205)
5. ``serve.engine.ServeEngine`` prefill + decode loop (RL207, RL204)
6. ``infer.sandwich.infer`` (sandwich CI path)        (RL208)
7. ``dist.consensus.aggregate_stacked_consensus``     (RL210)
8. ``core.adaptive`` init_state/apply_adaptive carry  (RL211)
9. every static spec: Estimator / ConsensusConfig /
   FaultPlan / ArchConfig / RobustDecodeConfig /
   Sampling                                           (RL209)

The recompile guard (RL209) is the one check that *runs* a jitted
function — a scalar-add wrapper with the spec as its static argument,
called twice with equal-valued-but-freshly-constructed specs, counting
Python traces. That is the only way to observe the jit cache key; the
wrapper's cost is one scalar add.

Mesh-dependent checks report ``status="skip"`` when fewer than 2
devices are visible (the CLI's ``--host-devices N`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
imports).
"""
from __future__ import annotations

import traceback
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from .findings import AuditResult

__all__ = ["run_audit", "recompile_stability", "divisibility_audit",
           "consensus_validity_audit"]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _result(check_id: str, entry: str, fn: Callable[[], str]) -> AuditResult:
    """Run one check body; it returns the ok-detail or raises."""
    try:
        return AuditResult(check_id, entry, "ok", fn())
    except _Skip as s:
        return AuditResult(check_id, entry, "skip", str(s))
    except Exception as e:  # noqa: BLE001 — every failure is a finding
        detail = f"{type(e).__name__}: {e}"
        if not str(e):
            detail = traceback.format_exc(limit=3)
        return AuditResult(check_id, entry, "fail", detail)


class _Skip(Exception):
    pass


def _mesh1d():
    nd = jax.device_count()
    if nd < 2:
        raise _Skip(f"needs >= 2 devices for a worker mesh, have {nd} "
                    f"(run the CLI with --host-devices 8)")
    from ..launch.mesh import make_mesh

    return make_mesh((nd,), ("data",)), nd


def _expect_raises(thunk, exc, must_contain: str, what: str) -> None:
    try:
        thunk()
    except exc as e:
        if must_contain not in str(e):
            raise AssertionError(
                f"{what}: raised {type(e).__name__} but the message "
                f"{str(e)!r} does not mention {must_contain!r}")
        return
    raise AssertionError(f"{what}: expected {exc.__name__}, nothing raised")


# ---------------------------------------------------------------------------
# RL201 — RRS wire shapes/dtypes
# ---------------------------------------------------------------------------

def _check_rrs_wire() -> List[AuditResult]:
    def body():
        from ..core.estimator import Estimator
        from ..dist.robust_reduce import aggregate_stacked_rrs

        mesh, nw = _mesh1d()
        est = Estimator(method="vrmom", K=3)
        # deliberately wire-unfriendly sizes: total coords 4*6+5 = 29,
        # coprime with any nw >= 2, so the zero-pad path is exercised.
        grads = {"w": _sds((nw, 4, 6), jnp.bfloat16),
                 "b": _sds((nw, 5), jnp.float32)}
        out = jax.eval_shape(
            lambda g: aggregate_stacked_rrs(g, mesh, ("data",), est), grads)
        assert out["w"].shape == (4, 6), out["w"].shape
        assert out["b"].shape == (5,), out["b"].shape
        assert out["w"].dtype == jnp.bfloat16, (
            f"bf16 leaf upcast to {out['w'].dtype} on the wire")
        assert out["b"].dtype == jnp.float32, out["b"].dtype
        return (f"[{nw}, ...] pytree -> worker dim removed, dtypes "
                f"preserved (bf16 stays bf16) across the padded f32 wire")

    return [_result("RL201", "dist.aggregate_stacked_rrs", body)]


# ---------------------------------------------------------------------------
# RL202 — §9 upper-triangle wire length
# ---------------------------------------------------------------------------

def _check_symmetric_wire() -> List[AuditResult]:
    def body():
        from ..core.estimator import Estimator
        from ..dist.robust_reduce import aggregate_symmetric_stacked

        W, p = 5, 7
        tri = p * (p + 1) // 2
        est = Estimator(method="vrmom", K=3)
        closed = jax.make_jaxpr(
            lambda m: aggregate_symmetric_stacked(m, est))(
                _sds((W, p, p), jnp.bfloat16))
        out_aval = closed.out_avals[0]
        assert out_aval.shape == (p, p), out_aval.shape
        assert out_aval.dtype == jnp.bfloat16, (
            f"symmetric aggregate upcast to {out_aval.dtype}")
        # the wire aval [W, p(p+1)/2] must appear in the jaxpr — and the
        # full [W, p*p] square must NOT be what rides the estimator.
        shapes = set()
        for eqn in closed.jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "shape", None):
                    shapes.add(tuple(aval.shape))
        assert (W, tri) in shapes, (
            f"no [W={W}, p(p+1)/2={tri}] wire aval in the jaxpr; "
            f"saw {sorted(shapes)}")
        return (f"[{W}, {p}, {p}] stack rides a [{W}, {tri}] "
                f"upper-triangle wire; output [{p}, {p}] {out_aval.dtype}")

    return [_result("RL202", "dist.aggregate_symmetric_stacked", body)]


# ---------------------------------------------------------------------------
# RL203 — coordinatewise gate
# ---------------------------------------------------------------------------

def _check_coordinatewise_gate() -> List[AuditResult]:
    def body():
        from ..core.estimator import Estimator
        from ..dist.robust_reduce import aggregate_stacked_auto
        from ..serve.robust import RobustDecodeConfig

        g = {"w": _sds((8, 12), jnp.float32)}
        for method in ("geometric_median", "krum"):
            _expect_raises(
                lambda m=method: jax.eval_shape(
                    lambda x: aggregate_stacked_auto(x, m), g),
                ValueError, "whole-vector",
                f"aggregate_stacked_auto({method!r})")
            _expect_raises(
                lambda m=method: RobustDecodeConfig(m=8, estimator=m),
                ValueError, "whole-vector",
                f"RobustDecodeConfig(estimator={method!r})")
        _expect_raises(
            lambda: Estimator(method="trimmed_mean", beta=0.05).validate(8),
            ValueError, "degrade",
            "trimmed_mean beta=0.05 at m=8 (trims 0 rows)")
        return ("GM/Krum rejected on the RRS wire and the replicated "
                "decode path; degenerate trimmed_mean rejected at "
                "validate()")

    return [_result("RL203", "Estimator.require_coordinatewise", body)]


# ---------------------------------------------------------------------------
# RL204 — wire dtype discipline
# ---------------------------------------------------------------------------

def _check_wire_dtype() -> List[AuditResult]:
    def body():
        from ..dist.robust_reduce import aggregate_stacked_auto
        from ..serve.robust import RobustDecodeConfig, robust_logits

        out = jax.eval_shape(
            lambda g: aggregate_stacked_auto(g, "vrmom"),
            {"w": _sds((8, 33), jnp.bfloat16)})
        assert out["w"].dtype == jnp.bfloat16, (
            f"bf16 gradient stack silently upcast to {out['w'].dtype}")
        rcfg = RobustDecodeConfig(m=4, estimator="median")
        logits = jax.eval_shape(
            lambda lr: robust_logits(lr, rcfg, jax.random.PRNGKey(0)),
            _sds((4, 2, 64), jnp.bfloat16))
        assert logits.shape == (2, 64), logits.shape
        assert logits.dtype == jnp.float32, (
            f"robust decode logits must be f32, got {logits.dtype}")
        return ("stacked aggregation returns the input dtype (bf16 in, "
                "bf16 out); robust decode logits are exactly f32")

    return [_result("RL204", "dist/serve wire dtypes", body)]


# ---------------------------------------------------------------------------
# RL205 — worker-divisibility guards
# ---------------------------------------------------------------------------

def _check_divisibility_guard() -> List[AuditResult]:
    def body():
        from ..dist.robust_reduce import robust_backward, robust_dot

        mesh, nw = _mesh1d()

        def loss(x, w):
            return jnp.sum(robust_dot(x, w))

        def grad_with_batch(B):
            with robust_backward(mesh, ("data",), "median"):
                return jax.eval_shape(
                    jax.grad(loss, argnums=1),
                    _sds((B, 2, 4), jnp.float32), _sds((4, 3), jnp.float32))

        _expect_raises(lambda: grad_with_batch(nw + 1),
                       ValueError, "not divisible",
                       f"robust_dot with B={nw + 1}, nw={nw}")
        dw = grad_with_batch(2 * nw)
        assert dw.shape == (4, 3), dw.shape
        return (f"B={nw + 1} refused at trace time; B={2 * nw} traces "
                f"with dW [4, 3] robustly aggregated over {nw} workers")

    return [_result("RL205", "dist.robust_dot / robust_backward", body)]


# ---------------------------------------------------------------------------
# RL206 — train step traces abstractly
# ---------------------------------------------------------------------------

def _audit_cfg():
    from ..configs import get
    return get("qwen3-1.7b").reduced()


def _check_train_step() -> List[AuditResult]:
    def body():
        from .. import optim as O
        from ..models import model as M
        from ..train.step import make_train_step

        mesh, nw = _mesh1d()
        cfg = _audit_cfg()
        setup = make_train_step(cfg, mesh, estimator="vrmom",
                                mode="stacked-rrs")
        assert setup.n_workers == nw, (setup.n_workers, nw)
        params = M.abstract_init(cfg)
        opt_state = jax.eval_shape(O.get(cfg.optimizer, lr=1e-3).init,
                                   params)
        batch = {"tokens": _sds((2 * nw, 32), jnp.int32)}
        p2, _, loss = jax.eval_shape(setup.step_fn, params, opt_state,
                                     batch, jax.random.PRNGKey(0))
        in_leaves = jax.tree.leaves(params)
        out_leaves = jax.tree.leaves(p2)
        assert len(in_leaves) == len(out_leaves)
        for a, b in zip(in_leaves, out_leaves):
            assert a.shape == b.shape and a.dtype == b.dtype, (a, b)
        assert loss.shape == (), loss.shape
        # inloop guard: indivisible global batch refused at trace time
        inloop = make_train_step(cfg, mesh, estimator="median",
                                 mode="inloop")
        _expect_raises(
            lambda: jax.eval_shape(
                inloop.step_fn, params, opt_state,
                {"tokens": _sds((nw + 1, 32), jnp.int32)},
                jax.random.PRNGKey(0)),
            ValueError, "divisible",
            f"inloop train step with batch {nw + 1} on {nw} workers")
        return (f"stacked-rrs step traces end-to-end on {nw} workers "
                f"(param/opt shapes stable, scalar loss); inloop refuses "
                f"an indivisible batch at trace time")

    return [_result("RL206", "train.make_train_step", body)]


# ---------------------------------------------------------------------------
# RL207 — serve prefill/decode + cache round-trip
# ---------------------------------------------------------------------------

def _check_serve_engine() -> List[AuditResult]:
    def body():
        from ..models import model as M
        from ..serve.engine import GREEDY, ServeEngine
        from ..serve.robust import RobustDecodeConfig

        cfg = _audit_cfg()
        params = M.abstract_init(cfg)
        engine = ServeEngine(cfg, params, max_len=48, n_slots=2,
                             robust=RobustDecodeConfig(m=2,
                                                       estimator="median"))
        logits, _ = jax.eval_shape(engine._prefill_fn(), params,
                                   {"tokens": _sds((2, 8), jnp.int32)})
        assert logits.shape == (2, cfg.vocab), logits.shape

        pool = jax.eval_shape(engine.make_pool)
        loop = engine._decode_loop_fn(3, GREEDY, pool=True)
        toks, caches_out = jax.eval_shape(
            loop, params, pool.caches, _sds((2,), jnp.int32),
            jax.random.PRNGKey(0))
        assert toks.shape == (3, 2), toks.shape
        assert toks.dtype == jnp.int32, toks.dtype
        in_l, in_def = jax.tree.flatten(pool.caches)
        out_l, out_def = jax.tree.flatten(caches_out)
        assert in_def == out_def, "cache tree structure changed in-loop"
        for a, b in zip(in_l, out_l):
            assert a.shape == b.shape and a.dtype == b.dtype, (
                f"cache leaf {a.shape}/{a.dtype} -> {b.shape}/{b.dtype}: "
                f"the stacked<->flat replica round-trip is not lossless")
        return ("prefill logits [B, V]; 3-step robust pool decode traces "
                "with a bit-identical cache tree (replica "
                "stacked<->flat round-trip lossless)")

    return [_result("RL207", "serve.ServeEngine prefill/decode", body)]


# ---------------------------------------------------------------------------
# RL208 — sandwich CI path
# ---------------------------------------------------------------------------

def _check_sandwich() -> List[AuditResult]:
    def body():
        from ..core.rcsl import LinearRegressionProblem, Shards
        from ..infer.sandwich import infer

        m, n, p = 4, 16, 3
        shards = Shards(X=_sds((m + 1, n, p), jnp.float32),
                        Y=_sds((m + 1, n), jnp.float32))
        res = jax.eval_shape(
            lambda s, t: infer(LinearRegressionProblem(), s, t,
                               estimator="vrmom", K=3),
            shards, _sds((p,), jnp.float32))
        assert res.ci.lower.shape == (p,), res.ci.lower.shape
        assert res.ci.upper.shape == (p,), res.ci.upper.shape
        assert res.cov.shape == (p, p), res.cov.shape
        assert res.H.shape == (p, p), res.H.shape
        assert res.Sigma.shape == (p, p), res.Sigma.shape
        return (f"machine stats -> robust moments -> Theorem-4 sandwich "
                f"traces abstractly: [p]={p} intervals, [p, p] covariance")

    return [_result("RL208", "infer.sandwich.infer", body)]


# ---------------------------------------------------------------------------
# RL210 — consensus wire shapes + n > 5f refusal
# ---------------------------------------------------------------------------

def _check_consensus() -> List[AuditResult]:
    def body():
        from ..core.estimator import Estimator
        from ..dist.consensus import (ConsensusAux, ConsensusConfig,
                                      aggregate_stacked_consensus)
        from ..dist.faults import FaultPlan

        mesh, nw = _mesh1d()
        est = Estimator(method="vrmom", K=3)
        f_ok = max((nw - 1) // 5, 0)
        grads = {"w": _sds((nw, 4, 6), jnp.bfloat16),
                 "b": _sds((nw, 5), jnp.float32)}
        for plan in (None, FaultPlan(dropout=0.25, n_crashed=1,
                                     crash_round=1)):
            out, aux = jax.eval_shape(
                lambda g: aggregate_stacked_consensus(
                    g, mesh, ("data",), est,
                    config=ConsensusConfig(f=f_ok, max_rounds=4),
                    plan=plan, key=jax.random.PRNGKey(0)),
                grads)
            assert out["w"].shape == (4, 6), out["w"].shape
            assert out["b"].shape == (5,), out["b"].shape
            assert out["w"].dtype == jnp.bfloat16, (
                f"bf16 leaf upcast to {out['w'].dtype} through the "
                f"round loop")
            assert out["b"].dtype == jnp.float32, out["b"].dtype
            assert isinstance(aux, ConsensusAux), type(aux)
            for name, leaf in zip(aux._fields, aux):
                assert leaf.shape == (), (
                    f"aux field {name} is not a scalar: {leaf.shape}")
        _expect_raises(
            lambda: jax.eval_shape(
                lambda g: aggregate_stacked_consensus(
                    g, mesh, ("data",), est,
                    config=ConsensusConfig(f=nw)),
                grads),
            ValueError, "n > 5f",
            f"consensus with f={nw} on {nw} peers")
        return (f"[{nw}, ...] pytree -> worker dim removed, dtypes "
                f"preserved through the static round loop (fault-free "
                f"and faulty plans); f={nw} refused at trace time")

    return [_result("RL210", "dist.aggregate_stacked_consensus", body)]


# ---------------------------------------------------------------------------
# RL211 — adaptive aggregation state is an explicit jit-pure carry
# ---------------------------------------------------------------------------

_IMMUTABLE = (type(None), bool, int, float, complex, str, bytes,
              tuple, frozenset)


def _check_adaptive_carry() -> List[AuditResult]:
    def body():
        from ..core import adaptive as AD
        from ..core.estimator import Estimator

        # 1. no mutable module-level state: every non-callable global
        # of repro.core.adaptive must be an immutable constant — a
        # module-level list/dict/array would leak state across steps
        # and silently break the jit-pure carry contract.
        mutable = []
        for gname, val in vars(AD).items():
            if gname.startswith("_") or callable(val):
                continue
            if type(val).__name__ == "module":
                continue
            if type(val).__module__ == "__future__":
                continue  # the `annotations` feature flag
            if not isinstance(val, _IMMUTABLE):
                mutable.append(f"{gname}: {type(val).__name__}")
        assert not mutable, (
            f"mutable module-level state in repro.core.adaptive: "
            f"{mutable}")

        # 2. init/apply round-trip under eval_shape: the carry's pytree
        # structure, shapes, and dtypes must be a fixed point, so the
        # train-step scan can thread it without retracing.
        nw, dim = 9, 40
        for method in ("auto_gm", "vrmom_adaptive"):
            est = Estimator(method=method, K=4)
            state = est.init_adaptive_state(nw, dim)
            out, new_state = jax.eval_shape(
                lambda x, s, e=est: e.apply_adaptive(x, s),
                _sds((nw, dim), jnp.float32),
                jax.tree.map(lambda l: _sds(l.shape, l.dtype), state))
            assert out.shape == (dim,), (method, out.shape)
            assert out.dtype == jnp.float32, (method, out.dtype)
            old_s = [(l.shape, jnp.dtype(l.dtype))
                     for l in jax.tree.leaves(state)]
            new_s = [(l.shape, jnp.dtype(l.dtype))
                     for l in jax.tree.leaves(new_state)]
            assert old_s == new_s, (
                f"{method}: carry is not a fixed point — "
                f"{old_s} -> {new_s}")

        # 3. non-adaptive estimators must refuse to mint a carry.
        _expect_raises(
            lambda: Estimator(method="vrmom", K=4)
            .init_adaptive_state(nw, dim),
            ValueError, "adaptive",
            "init_adaptive_state on a fixed-K estimator")
        return ("auto_gm/vrmom_adaptive carry round-trips with fixed "
                "shapes+dtypes; module globals immutable; fixed-K "
                "estimators refuse a carry")

    return [_result("RL211", "core.adaptive carry", body)]


# ---------------------------------------------------------------------------
# RL209 — recompile stability (public helper + the spec sweep)
# ---------------------------------------------------------------------------

def recompile_stability(name: str, factory: Callable[[], object],
                        ) -> AuditResult:
    """Verify a static-spec factory is jit-cache stable.

    ``factory()`` must build a *fresh* spec each call. The spec is used
    as ``static_argnums=0`` of a scalar-add jit; calling with two fresh
    equal specs must trace exactly once. Also checks ``hash(a) ==
    hash(b)`` and ``a == b`` directly, so a failure names the drift.
    """
    def body():
        a, b = factory(), factory()
        assert a is not b, (
            f"{name}: factory returned the same object twice — the "
            f"check needs freshly constructed specs")
        assert a == b, f"{name}: two fresh equal-valued specs are != "
        assert hash(a) == hash(b), (
            f"{name}: equal specs hash differently "
            f"({hash(a)} vs {hash(b)}) — every jit call retraces")
        traces = [0]

        def f(spec, x):
            traces[0] += 1
            return x + 1.0

        jf = jax.jit(f, static_argnums=0)
        x = jnp.zeros(())
        jf(a, x)
        jf(b, x)
        assert traces[0] == 1, (
            f"{name}: second call with a fresh equal spec retraced "
            f"(traces={traces[0]}) — jit cache key is unstable")
        return "two fresh equal specs -> one trace (cache key stable)"

    return _result("RL209", name, body)


def _check_recompile() -> List[AuditResult]:
    from ..configs.base import ArchConfig
    from ..core.estimator import Estimator
    from ..serve.engine import Sampling
    from ..serve.robust import RobustDecodeConfig

    from ..dist.consensus import ConsensusConfig
    from ..dist.faults import FaultPlan

    specs = [
        ("core.Estimator",
         lambda: Estimator(method="vrmom", K=4, backend="pallas")),
        ("core.Estimator[adaptive]",
         lambda: Estimator(method="auto_gm")),
        ("dist.ConsensusConfig",
         lambda: ConsensusConfig(f=1, eps=1e-3, trim="midpoint")),
        ("dist.FaultPlan",
         lambda: FaultPlan(dropout=0.1, n_crashed=1, crash_round=2)),
        ("configs.ArchConfig",
         lambda: ArchConfig(name="audit", family="dense", n_layers=1,
                            d_model=32, n_heads=2, n_kv_heads=1,
                            d_ff=64, vocab=64)),
        ("serve.RobustDecodeConfig",
         lambda: RobustDecodeConfig(m=4, estimator="median")),
        ("serve.Sampling",
         lambda: Sampling(method="top_k", temperature=0.7, top_k=5)),
    ]
    return [recompile_stability(name, fac) for name, fac in specs]


# ---------------------------------------------------------------------------
# public helper for config-level divisibility audits (used by tests)
# ---------------------------------------------------------------------------

def divisibility_audit(name: str, batch: int, n_workers: int) -> AuditResult:
    """Flag a config whose global batch the worker count cannot divide —
    the static precondition RL205 verifies the runtime guards enforce."""
    def body():
        if n_workers > 1 and batch % n_workers:
            raise AssertionError(
                f"global batch {batch} is not divisible by {n_workers} "
                f"workers: per-worker grouping breaks and the robust "
                f"guarantee does not apply")
        return f"batch {batch} / {n_workers} workers divides evenly"

    return _result("RL205", name, body)


def consensus_validity_audit(name: str, n: int, f: int) -> AuditResult:
    """Flag a consensus deployment outside the ``n > 5f`` validity
    region — the static precondition RL210 verifies the runtime
    refusal enforces. Mesh-free (pure arithmetic on the config), so
    configs can be audited before any device exists."""
    def body():
        from ..dist.consensus import ConsensusConfig

        if n <= 5 * f:
            raise AssertionError(
                f"n={n} peers with f={f} Byzantine faults violates "
                f"n > 5f: approximate consensus loses both validity "
                f"and convergence (need n >= {5 * f + 1})")
        ConsensusConfig(f=f).validate(n)
        return f"n={n}, f={f} satisfies n > 5f (margin {n - 5 * f})"

    return _result("RL210", name, body)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_audit() -> List[AuditResult]:
    """Run every RL2xx check; never raises — failures are results."""
    results: List[AuditResult] = []
    results += _check_rrs_wire()
    results += _check_symmetric_wire()
    results += _check_coordinatewise_gate()
    results += _check_wire_dtype()
    results += _check_divisibility_guard()
    results += _check_train_step()
    results += _check_serve_engine()
    results += _check_sandwich()
    results += _check_consensus()
    results += _check_adaptive_carry()
    results += _check_recompile()
    return results
