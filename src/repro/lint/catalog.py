"""Catalog of every reprolint rule and audit check (DESIGN.md §10).

Pure data, no imports beyond the stdlib: ``scripts/check_docs.py``
imports this module to verify the DESIGN.md §10 rule-ID table stays in
sync with the registered rules, and it must be able to do so in an
environment without jax. Layer-1 AST rules (RL0xx) are implemented in
:mod:`repro.lint.rules`; layer-2 trace-auditor checks (RL2xx) in
:mod:`repro.lint.auditor`. RL000 is the meta-rule guarding the waiver
mechanism itself.

Each entry records the invariant the rule protects and where that
invariant was established (DESIGN section / PR in CHANGES.md), so a
finding always points back at the design decision it enforces.
"""
from __future__ import annotations

from typing import NamedTuple

__all__ = ["RuleInfo", "AST_RULES", "AUDIT_CHECKS", "ALL_IDS", "info"]


class RuleInfo(NamedTuple):
    id: str
    name: str
    invariant: str
    established: str  # DESIGN section / PR that created the invariant


AST_RULES = (
    RuleInfo(
        "RL000", "suppression-without-reason",
        "Every `# reprolint: disable=RLxxx` waiver must carry a reason; "
        "an unexplained suppression is itself a finding.",
        "this PR (§10)"),
    RuleInfo(
        "RL001", "direct-aggregation-bypass",
        "All robust aggregation routes through the hashable "
        "core.estimator.Estimator dispatch: no direct jnp.median/"
        "quantile/percentile and no core.aggregators access at call "
        "sites outside the estimator layer itself.",
        "DESIGN §7 (PR 3)"),
    RuleInfo(
        "RL002", "kv-head-repeat",
        "GQA K/V tensors are never jnp.repeat-ed to the query-head "
        "count in models/ or kernels/ — grouped compute keeps K/V "
        "cache traffic at Hkv, not H.",
        "DESIGN §8 (PR 4)"),
    RuleInfo(
        "RL003", "trace-unsafe-python",
        "No Python `if`/`while` branching and no int()/float()/bool() "
        "casts on values that flow in as traced parameters of a jitted "
        "function (shape/ndim/dtype/size reads are static and exempt).",
        "DESIGN §1-§2 (jit discipline)"),
    RuleInfo(
        "RL004", "unhashable-static",
        "Config-like specs (\\*Config/\\*Spec/Estimator/Sampling/"
        "\\*Setup) that flow into jit static args must be hashable: "
        "dataclasses frozen=True, no list/dict/set-typed fields.",
        "DESIGN §7 (PR 3); runtime backstop this PR"),
    RuleInfo(
        "RL005", "impure-index-map",
        "Pallas BlockSpec index maps are pure arithmetic functions of "
        "the grid indices and reads of their own scalar-prefetch "
        "arguments: no calls, attribute reads, or subscripts of captured "
        "state.",
        "DESIGN §7-§8 kernel discipline"),
    RuleInfo(
        "RL006", "unmasked-padded-load",
        "A Pallas kernel whose wrapper zero/inf-pads its operands to "
        "tile boundaries must mask validity in-kernel (jnp.where / "
        "broadcasted_iota), per the flash/decode-attention mask "
        "discipline.",
        "DESIGN §8 (PR 4 pad_k fix)"),
    RuleInfo(
        "RL007", "wall-clock-outside-obs",
        "Library code under src/repro/ never reads the wall clock "
        "directly (time.time/perf_counter/monotonic/...): timings "
        "route through repro.obs.metrics.now() so they land in the "
        "metrics registry instead of ad-hoc prints; the obs layer is "
        "the single allowed call site.",
        "DESIGN §11 (this PR)"),
)

AUDIT_CHECKS = (
    RuleInfo(
        "RL201", "rrs-wire-shapes",
        "aggregate_stacked_rrs preserves every leaf's shape (minus the "
        "worker dim) and dtype across the padded f32 wire, for every "
        "worker count the mesh supports.",
        "DESIGN §3 (PR 1)"),
    RuleInfo(
        "RL202", "symmetric-triangle-wire",
        "aggregate_symmetric_stacked puts exactly p(p+1)/2 upper-"
        "triangle coordinates on the wire and returns a [p, p] matrix "
        "of the input dtype.",
        "DESIGN §9 (PR 5)"),
    RuleInfo(
        "RL203", "coordinatewise-gate",
        "Whole-vector estimators (geometric_median, Krum) are rejected "
        "at trace time on every chunked/RRS/serve wire, and degenerate "
        "trimmed_mean specs raise instead of silently meaning mean.",
        "DESIGN §7 (PR 3)"),
    RuleInfo(
        "RL204", "wire-dtype-discipline",
        "Robust aggregation of a bf16 gradient stack returns bf16 "
        "(f32 internally, no silent upcast of the output); robust "
        "decode logits are exactly f32.",
        "DESIGN §3/§6"),
    RuleInfo(
        "RL205", "worker-divisibility-guard",
        "robust_dot and the inloop train step refuse (at trace time) "
        "batches the worker count does not divide, instead of "
        "degrading to a non-robust grouping.",
        "DESIGN §2 (PR 1)"),
    RuleInfo(
        "RL206", "train-step-traces",
        "make_train_step's step function traces abstractly end-to-end "
        "(params/opt-state/loss shapes stable) on the config matrix.",
        "DESIGN §1 (PR 1)"),
    RuleInfo(
        "RL207", "serve-cache-roundtrip",
        "ServeEngine prefill and the scanned (robust) decode loop "
        "trace abstractly, and the pool cache tree returns with "
        "bit-identical structure/shapes/dtypes (the stacked<->flat "
        "replica layout round-trip is lossless).",
        "DESIGN §6-§7 (PR 2/3)"),
    RuleInfo(
        "RL208", "sandwich-ci-shapes",
        "The plug-in sandwich CI path (machine stats -> robust moments "
        "-> Theorem-4 factor -> intervals) traces abstractly with "
        "[p]-shaped intervals and [p, p] covariance.",
        "DESIGN §9 (PR 5)"),
    RuleInfo(
        "RL209", "recompile-stability",
        "Calling a jitted entry point twice with equal-valued but "
        "freshly constructed static configs (Estimator, ArchConfig, "
        "RobustDecodeConfig, Sampling) traces exactly once: hash/eq "
        "drift in a spec would silently retrace per call.",
        "DESIGN §7 (PR 3); guard this PR"),
    RuleInfo(
        "RL210", "consensus-wire",
        "aggregate_stacked_consensus preserves every leaf's shape and "
        "dtype through the static round loop (fault-free and faulty "
        "plans, scalar aux), and refuses n <= 5f configurations at "
        "trace time — outside that region approximate consensus loses "
        "validity.",
        "DESIGN §13 (PR 9)"),
    RuleInfo(
        "RL211", "adaptive-state-carry",
        "The adaptive aggregation state (per-worker weights, momentum, "
        "alpha_hat) is an explicit jit-pure carry: init_state/apply "
        "round-trip under eval_shape with fixed shapes and dtypes, "
        "repro.core.adaptive holds no mutable module-level state, and "
        "non-adaptive estimators refuse to mint a carry.",
        "DESIGN §14 (PR 10)"),
)

ALL_IDS = tuple(r.id for r in AST_RULES + AUDIT_CHECKS)

_BY_ID = {r.id: r for r in AST_RULES + AUDIT_CHECKS}


def info(rule_id: str) -> RuleInfo:
    return _BY_ID[rule_id]
