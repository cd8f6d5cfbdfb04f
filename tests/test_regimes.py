"""Regime matrix: adaptive adversaries vs adaptive aggregation
(DESIGN.md §14).

The grid is {gaussian, signflip, wrong_value, alie, ipm, mimic} x
{median, vrmom, vrmom_adaptive, trimmed_mean, auto_gm} x alpha — every
robust arm must stay bounded in every regime while the mean control is
dragged by the loud attacks, and the *adaptive* arms must additionally
(a) estimate alpha online (the census), (b) recover the Byzantine
ranking where the §11 MAD-z suspicion is blind (S3), and (c) stay
bit-identical to their fixed baselines on honest data — adaptivity must
cost exactly nothing when there is nothing to adapt to.

The same matrix is driven through the production wires: the serve
m-replica token wire (greedy tokens identical to the honest decode),
the coverage harness (``assumed_alpha`` regime knob), and the sharded
train step (explicit ``AdaptiveState`` carry) in an 8-device
subprocess. ``benchmarks/regimes.py`` runs the full committed grid;
these tests pin the mechanisms.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptive as AD
# reprolint: disable=RL001 oracle: bit-identity tests compare adaptive arms against raw weiszfeld below the Estimator layer
from repro.core import aggregators as AG
from repro.core import attacks as A
from repro.core.estimator import Estimator
from repro.obs import diag as OD

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ATTACKS = ("gaussian", "signflip", "wrong_value", "alie", "ipm", "mimic")
ROBUST_ARMS = {
    "median": Estimator(method="median"),
    "vrmom": Estimator(method="vrmom", K=10),
    "vrmom_adaptive": Estimator(method="vrmom_adaptive", K=10),
    "trimmed_mean": Estimator(method="trimmed_mean", beta=0.25),
    "auto_gm": Estimator(method="auto_gm"),
}

W, C = 41, 40


MU = 2.0  # nonzero truth: a zero-mean truth would make signflip a
# near-no-op and ipm's payload vanish; mu=2 keeps signflip decisively
# loud (its payload sits at -mu, 2*mu from the center) for the S3
# exact-detection half.


def _stack(key=0):
    v = jax.random.normal(jax.random.PRNGKey(key), (W, C))
    return v + MU


def _attacked(attack, alpha, key=0):
    v = _stack(key)
    mask = A.byzantine_mask(W, alpha)
    return A.REGISTRY[attack](jax.random.PRNGKey(100 + key), v, mask), mask


def _err(agg):
    return float(jnp.linalg.norm(agg.astype(jnp.float32) - MU))


# ------------------------------------------------------ estimator-level matrix

@pytest.mark.parametrize("alpha", (0.1, 0.2))
@pytest.mark.parametrize("attack", ATTACKS)
def test_matrix_robust_arms_bounded(attack, alpha):
    """Every robust arm stays within a few honest standard errors of
    the truth, in every regime of the matrix."""
    v_att, _ = _attacked(attack, alpha)
    for name, est in ROBUST_ARMS.items():
        err = _err(est.apply(v_att, axis=0))
        assert err < 3.5, (attack, alpha, name, err)


@pytest.mark.parametrize("attack", ("signflip", "ipm", "wrong_value"))
def test_matrix_adaptive_beats_fixed_k(attack):
    """The tentpole contrast: at alpha=0.2 the fixed-K vrmom keeps its
    honest-regime K (its correction term amplifies the contamination
    drag), while the adaptive arms census the stack and either impute +
    drop K (vrmom_adaptive) or downweight (auto_gm) — strictly smaller
    error on the same attacked stack."""
    v_att, _ = _attacked(attack, 0.2)
    err_fixed = _err(ROBUST_ARMS["vrmom"].apply(v_att, axis=0))
    for name in ("vrmom_adaptive", "auto_gm"):
        err = _err(ROBUST_ARMS[name].apply(v_att, axis=0))
        assert err < err_fixed, (attack, name, err, err_fixed)


@pytest.mark.parametrize("attack", ("gaussian", "wrong_value"))
def test_matrix_mean_control_diverges(attack):
    """The contrast column: the unprotected mean is dragged far past
    every robust arm by the loud attacks at alpha=0.2."""
    v_att, _ = _attacked(attack, 0.2)
    err_mean = _err(jnp.mean(v_att, axis=0))
    worst_robust = max(_err(est.apply(v_att, axis=0))
                       for est in ROBUST_ARMS.values())
    assert err_mean > 2.0 * worst_robust + 1.0, (attack, err_mean)


@pytest.mark.parametrize("attack", ATTACKS)
def test_census_estimates_alpha_online(attack):
    """``estimate_alpha`` lands near the true contamination for every
    attack in the matrix — including the coordinated stealth attacks
    the §11 z-score alone cannot see (their identical payload rows trip
    the duplicate-multiplicity census instead)."""
    v_att, mask = _attacked(attack, 0.2)
    true_alpha = float(jnp.mean(mask.astype(jnp.float32)))
    a_hat = float(AD.estimate_alpha(v_att, axis=0))
    assert abs(a_hat - true_alpha) <= 0.1, (attack, a_hat, true_alpha)


def test_estimate_alpha_honest_is_exactly_zero():
    v = _stack()
    assert float(AD.estimate_alpha(v, axis=0)) == 0.0
    assert np.all(np.asarray(AD.worker_weights(v, axis=0)) == 1.0)


# ------------------------------------------------- honest-regime bit identity

def test_auto_gm_honest_bit_identical_to_geometric_median():
    v = _stack(key=3)
    np.testing.assert_array_equal(
        np.asarray(AD.auto_gm(v, axis=0)),
        np.asarray(AG.geometric_median(v, axis=0)))
    np.testing.assert_array_equal(
        np.asarray(Estimator(method="auto_gm").apply(v, axis=0)),
        np.asarray(AG.geometric_median(v, axis=0)))


def test_vrmom_adaptive_honest_bit_identical_to_vrmom():
    from repro.core.vrmom import vrmom

    v = _stack(key=4)
    np.testing.assert_array_equal(
        np.asarray(AD.vrmom_adaptive(v, K=10, axis=0)),
        np.asarray(vrmom(v, K=10, axis=0)))
    # Same-backend comparison: the adaptive tier runs on the jnp
    # backend, so the bit-identity claim is against the jnp vrmom (the
    # auto-resolved pallas kernel differs from jnp by 1 ulp on a few
    # coordinates, orthogonal to adaptivity).
    np.testing.assert_array_equal(
        np.asarray(Estimator(method="vrmom_adaptive", K=10).apply(v, axis=0)),
        np.asarray(Estimator(method="vrmom", K=10,
                             backend="jnp").apply(v, axis=0)))


def test_stateful_honest_bit_identical_and_state_fixed():
    """Unit weights are a fixed point of the EMA and momentum=0 is an
    exact passthrough: the stateful adaptive apply on honest stacks is
    bit-identical to the stateless one, for every step."""
    est = Estimator(method="auto_gm")
    state = est.init_adaptive_state(W, C)
    for k in range(3):
        v = _stack(key=10 + k)
        out, state = est.apply_adaptive(v, state, axis=0)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(est.apply(v, axis=0)))
        assert np.all(np.asarray(state.weights) == 1.0)
        assert float(state.alpha_hat) == 0.0
        assert int(state.step) == k + 1


def test_k_ladder_select():
    assert AD.k_ladder(10) == (10, 5, 1)
    assert AD.k_ladder(1) == (1,)
    assert float(AD.select_k(jnp.float32(0.0), 10)) == 10.0
    assert float(AD.select_k(jnp.float32(0.1), 10)) == 5.0
    assert float(AD.select_k(jnp.float32(0.3), 10)) == 1.0


def test_census_constants_match_obs_diag():
    """§11 parity: the census and the telemetry suspicion machinery use
    the same z-score convention — they must never drift apart."""
    assert AD.Z_THRESH == OD._Z_THRESH
    assert AD.REL_FLOOR == OD._REL_FLOOR


# ---------------------------------------------- S3: suspicion degradation

@pytest.mark.parametrize("attack", ("gaussian", "signflip"))
def test_mad_z_suspicion_exact_on_loud_attacks(attack):
    """The §11 MAD-z census alone identifies loud attackers exactly at
    alpha=0.25: suspected == the true Byzantine mask."""
    v_att, mask = _attacked(attack, 0.25)
    # reprolint: disable=RL001 diagnose() takes a precomputed center; raw median is the documented §11 pairing
    d = OD.diagnose(v_att, jnp.median(v_att, axis=0))
    np.testing.assert_array_equal(np.asarray(d.suspected), np.asarray(mask))


@pytest.mark.parametrize("attack", ("alie", "mimic"))
def test_mad_z_suspicion_blind_to_stealth_attacks(attack):
    """The degradation half of S3: the same MAD-z census flags NOTHING
    under alie/mimic at alpha=0.25 — the payloads sit inside the honest
    deviation spread."""
    v_att, _ = _attacked(attack, 0.25)
    # reprolint: disable=RL001 diagnose() takes a precomputed center; raw median is the documented §11 pairing
    d = OD.diagnose(v_att, jnp.median(v_att, axis=0))
    assert not bool(jnp.any(d.suspected)), attack


def test_auto_gm_weights_recover_stealth_ranking():
    """The recovery half of S3: auto_gm's census weights rank the
    stealth attackers below every honest worker (alie), or confine them
    to the lowest-weight duplicate cluster (mimic, where the mimicked
    victim is indistinguishable collateral by construction)."""
    v_att, mask = _attacked("alie", 0.25)
    w = np.asarray(AD.worker_weights(v_att, axis=0))
    m = np.asarray(mask)
    assert w[m].max() < w[~m].min(), (w[m].max(), w[~m].min())

    v_att, mask = _attacked("mimic", 0.25)
    w = np.asarray(AD.worker_weights(v_att, axis=0))
    m = np.asarray(mask)
    n_byz = int(m.sum())
    lowest = np.argsort(w)[: n_byz + 1]
    assert set(np.where(m)[0]).issubset(set(lowest))


# ------------------------------------------------------------ serve wire

@pytest.mark.parametrize("method", ("vrmom", "vrmom_adaptive", "auto_gm",
                                    "median"))
def test_serve_token_identity_under_attack(method):
    """m=8 replica wire at alpha=0.25 under the gaussian attack: every
    robust arm (fixed and adaptive) serves greedy tokens identical to
    the honest decode; the mean control serves corrupted tokens."""
    from repro.serve import RobustDecodeConfig, Sampling
    from repro.serve import robust as Ro

    B, V, m = 4, 64, 8
    honest = jax.random.normal(jax.random.PRNGKey(21), (B, V))
    logits_r = jnp.broadcast_to(honest[None], (m, B, V))
    want = np.asarray(jnp.argmax(honest, axis=-1).astype(jnp.int32))
    sc = Sampling(method="greedy")
    skey = jax.random.PRNGKey(0)

    rcfg = RobustDecodeConfig(m=m, estimator=method, K=8,
                              attack="gaussian", alpha=0.25)
    tok = Ro.robust_sample(logits_r, rcfg, jax.random.PRNGKey(5), skey, sc)
    np.testing.assert_array_equal(np.asarray(tok), want, err_msg=method)

    mcfg = RobustDecodeConfig(m=m, estimator="mean",
                              attack="gaussian", alpha=0.25)
    tok_mean = Ro.robust_sample(logits_r, mcfg, jax.random.PRNGKey(5),
                                skey, sc)
    assert np.any(np.asarray(tok_mean) != want), "control not corrupted"


# ----------------------------------------------------------- coverage wire

def test_coverage_assumed_alpha_narrows_ci():
    """The regime-matrix knob: an analyst assuming alpha=0 gets strictly
    narrower CIs than the oracle that inflates for the true alpha=0.2 —
    the width deficit is exactly what the fixed arms lose coverage to in
    BENCH_regimes.json."""
    from repro.infer.coverage import coverage_run

    kw = dict(model="linear", attack="alie", alpha=0.2, estimator="vrmom",
              K=5, reps=8, N_per_machine=100, m_workers=20, p=3, rounds=3,
              batch_size=4, seed=7)
    w_naive = float(jnp.mean(coverage_run(assumed_alpha=0.0, **kw).width))
    w_oracle = float(jnp.mean(coverage_run(assumed_alpha=0.2, **kw).width))
    assert w_naive < w_oracle, (w_naive, w_oracle)


def test_coverage_wire_accepts_adaptive_estimator():
    from repro.infer.coverage import coverage_run

    cell = coverage_run(model="linear", attack="alie", alpha=0.2,
                        estimator="auto_gm", reps=8, N_per_machine=100,
                        m_workers=20, p=3, rounds=3, batch_size=4, seed=7)
    s = cell.summary()
    assert np.isfinite(s["rmse"])
    assert s["coverage"] >= 0.5, s


# ---------------------------------------------------------- dist/train wire

def test_stacked_adaptive_wire_honest_matches_stateless():
    from repro.dist import robust_reduce as RR

    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 4, 6)) + 1.0,
         "b": jax.random.normal(jax.random.PRNGKey(1), (8, 5)) + 1.0}
    est = Estimator(method="auto_gm")
    dim = sum(x.size // 8 for x in g.values())
    out, state = RR.aggregate_stacked_adaptive(
        g, est.init_adaptive_state(8, dim), est)
    direct = RR.aggregate_stacked_auto(g, est)
    for k in g:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(direct[k]))
    assert np.all(np.asarray(state.weights) == 1.0)
    assert float(state.alpha_hat) == 0.0


def test_train_step_adaptive_state_carry_8dev():
    """Sharded train step with an adaptive estimator: the AdaptiveState
    rides the jitted step as an explicit carry (RL211), the loss stays
    finite under ipm, and the honest-regime state stays at the unit
    fixed point bit-exactly."""
    script = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get as get_arch
from repro.data import lm_batch, shard_batch
from repro.dist import sharding as S
from repro.models import model as M
from repro.train.step import make_train_step
import repro.optim as O

from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
cfg = get_arch("qwen3-1.7b").reduced()
setup = make_train_step(cfg, mesh, estimator="auto_gm",
                        byzantine_frac=0.15, attack="ipm", lr=1e-2,
                        microbatch=1)
assert setup.init_state is not None
st = setup.init_state()
assert st.weights.shape == (8,)
opt = O.get(cfg.optimizer, lr=1e-2)
params = M.init(jax.random.PRNGKey(0), cfg)
p = jax.device_put(params, S.to_named(mesh, setup.params_specs))
os_ = jax.jit(opt.init)(p)
step = jax.jit(setup.step_fn)
for i in range(3):
    b = shard_batch(lm_batch(cfg, i, 8, 32), mesh, setup.batch_axes)
    p, os_, loss, st = step(p, os_, b, jax.random.PRNGKey(i), st)
    assert np.isfinite(float(loss))
assert int(st.step) == 3
print("ADAPTIVE-STEP-OK")

setup_h = make_train_step(cfg, mesh, estimator="vrmom_adaptive",
                          byzantine_frac=0.0, attack="gaussian", lr=1e-2,
                          microbatch=1)
sth = setup_h.init_state()
b = shard_batch(lm_batch(cfg, 0, 8, 32), mesh, setup_h.batch_axes)
p2, os2, l2, sth = jax.jit(setup_h.step_fn)(p, os_, b,
                                            jax.random.PRNGKey(0), sth)
assert float(sth.weights.min()) == 1.0 and float(sth.alpha_hat) == 0.0
print("HONEST-STATE-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ADAPTIVE-STEP-OK" in r.stdout and "HONEST-STATE-OK" in r.stdout
