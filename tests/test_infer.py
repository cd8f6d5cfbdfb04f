"""Tests for the plug-in inference layer (repro.infer, DESIGN.md §9)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks, rcsl as R, vrmom as V
from repro.core.estimator import Estimator
from repro.dist.robust_reduce import aggregate_symmetric_stacked
from repro.launch.mesh import make_mesh
from repro.infer import (bvn_cdf, confidence_intervals,
                         contamination_inflation, corrupt_stats, cov_factor,
                         coverage_run, infer, machine_stats, mom_cov_factor,
                         robust_moments, sandwich_cov, vrmom_cov_factor)


# ---------------------------------------------------------------------------
# The jittable Theorem-4 machinery vs its host-side numpy oracles
# ---------------------------------------------------------------------------


def test_bvn_cdf_matches_host_quadrature():
    cases = [(0.5, -0.3, 0.6), (0.0, 0.0, 0.3), (1.2, 1.2, -0.8),
             (-1.0, 2.0, 0.95), (0.3, -0.7, 0.0)]
    for a, b, rho in cases:
        host = V._phi2_cdf_grid(a, b, rho)
        assert float(bvn_cdf(a, b, rho)) == pytest.approx(host, abs=2e-4)


def test_bvn_cdf_special_values():
    from jax.scipy.special import ndtr

    # independence: P = Phi(a) Phi(b)
    got = float(bvn_cdf(0.7, -0.2, 0.0))
    assert got == pytest.approx(float(ndtr(0.7) * ndtr(-0.2)), abs=1e-6)
    # the arcsine law at the origin
    rho = 0.37
    assert float(bvn_cdf(0.0, 0.0, rho)) == pytest.approx(
        0.25 + math.asin(rho) / (2 * math.pi), abs=1e-6)
    # perfect correlation collapses to the marginals (hit by every
    # correlation-matrix diagonal)
    assert float(bvn_cdf(0.7, 1.5, 1.0)) == pytest.approx(
        float(ndtr(0.7)), abs=1e-6)
    assert float(bvn_cdf(0.5, -0.5, -1.0)) == pytest.approx(
        float(ndtr(0.5) + ndtr(-0.5) - 1.0), abs=1e-6)


def test_vrmom_cov_factor_matches_host_oracle():
    rng = np.random.default_rng(0)
    A = rng.standard_normal((3, 3))
    Sigma = A @ A.T + 0.5 * np.eye(3)
    C_host = V.vrmom_asymptotic_cov(Sigma, K=10)
    C = np.asarray(vrmom_cov_factor(jnp.asarray(Sigma), K=10))
    np.testing.assert_allclose(C, C_host, rtol=2e-3, atol=1e-4)
    # diagonal recovers the 1-D theory: C_ll = sigma_K^2 Sigma_ll
    np.testing.assert_allclose(np.diag(C), V.sigma_k_sq(10) * np.diag(Sigma),
                               rtol=1e-4)


def test_mom_cov_factor_closed_form():
    rng = np.random.default_rng(1)
    A = rng.standard_normal((3, 3))
    Sigma = A @ A.T + 0.5 * np.eye(3)
    C_host = V.mom_asymptotic_cov(Sigma)
    C = np.asarray(mom_cov_factor(jnp.asarray(Sigma)))
    np.testing.assert_allclose(C, C_host, rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(np.diag(C), (math.pi / 2) * np.diag(Sigma),
                               rtol=1e-5)


def test_cov_factor_dispatch_and_rejection():
    Sigma = jnp.eye(2)
    np.testing.assert_allclose(
        np.asarray(cov_factor(Sigma, Estimator(method="mean"))),
        np.eye(2), atol=1e-7)
    assert float(cov_factor(Sigma, Estimator(method="median"))[0, 0]) == \
        pytest.approx(math.pi / 2, rel=1e-5)
    # trimmed_mean carries the winsorized-IF scaling (>= 1 on the
    # diagonal — trimming always costs efficiency at the Gaussian)
    tm = np.asarray(cov_factor(Sigma, Estimator(method="trimmed_mean",
                                                beta=0.2)))
    assert tm[0, 0] > 1.0
    # whole-vector selectors have no normality theory in the paper
    with pytest.raises(ValueError, match="no asymptotic-normality"):
        cov_factor(Sigma, Estimator(method="geometric_median"))


def test_contamination_inflation():
    assert contamination_inflation(0.0) == 1.0
    assert contamination_inflation(0.0, "median") == 1.0
    # exact rank-offset result for the median
    assert contamination_inflation(0.1, "median") == pytest.approx(
        1.0 / 0.81, rel=1e-9)
    # VRMOM pays more than MOM for contamination (its correction term
    # has its own garbage influence), and inflation grows with alpha
    assert contamination_inflation(0.1) > contamination_inflation(0.1, "median")
    assert contamination_inflation(0.2) > contamination_inflation(0.1) > 1.0
    with pytest.raises(ValueError):
        contamination_inflation(0.5)


# ---------------------------------------------------------------------------
# Symmetric-stack aggregation (dist wire format)
# ---------------------------------------------------------------------------


def test_aggregate_symmetric_stacked_exact_and_robust():
    key = jax.random.PRNGKey(0)
    W, p = 15, 4
    A = jax.random.normal(key, (W, p, p))
    mats = A + jnp.swapaxes(A, -1, -2)  # symmetric stack
    out = aggregate_symmetric_stacked(mats, "median")
    # exactly symmetric, and equal to per-coordinate aggregation
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out.T))
    full = Estimator(method="median", backend="jnp").apply(mats, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), atol=1e-6)
    # corrupted rows cannot move the median aggregate far
    bad = mats.at[-7:].set(1e6)
    out_bad = aggregate_symmetric_stacked(bad, "median")
    assert float(jnp.max(jnp.abs(out_bad - out))) < 5.0


def test_aggregate_symmetric_stacked_rejects_bad_inputs():
    with pytest.raises(ValueError, match="symmetric stack"):
        aggregate_symmetric_stacked(jnp.zeros((5, 3, 4)), "median")
    with pytest.raises(ValueError, match="whole-vector"):
        aggregate_symmetric_stacked(jnp.zeros((5, 3, 3)), "krum")


def test_wrong_value_attack():
    v = jnp.zeros((6, 3))
    mask = attacks.byzantine_mask(6, 0.4)  # 2 corrupted rows
    out = attacks.get("wrong_value")(jax.random.PRNGKey(0), v, mask)
    np.testing.assert_allclose(np.asarray(out[:4]), 0.0)
    np.testing.assert_allclose(np.asarray(out[4:]), 100.0)


# ---------------------------------------------------------------------------
# Sandwich covariance against textbook theory
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lin_setup():
    p = 4
    theta_star = R.paper_theta_star(p)
    shards = R.make_shards(jax.random.PRNGKey(0), N_per_machine=400,
                           m_workers=40, p=p, theta_star=theta_star,
                           model="linear")
    prob = R.LinearRegressionProblem()
    theta_hat, _ = R.rcsl(prob, shards, jax.random.PRNGKey(1), rounds=5)
    return prob, shards, theta_star, theta_hat


def test_sandwich_matches_ols_theory(lin_setup):
    """With mean aggregation the sandwich collapses to the OLS covariance
    sigma^2 Sigma_x^{-1} (H = 2 Sigma, Sigma_g = 4 sigma^2 Sigma)."""
    prob, shards, theta_star, theta_hat = lin_setup
    stats = machine_stats(prob, theta_hat, shards)
    H, Sig = robust_moments(stats, "mean")
    Xi = sandwich_cov(H, Sig, "mean")
    p = theta_star.shape[0]
    idx = jnp.arange(p)
    Sigma_x = 0.5 ** jnp.abs(idx[:, None] - idx[None, :])  # make_shards rho
    Xi_theory = jnp.linalg.inv(Sigma_x)  # noise_std = 1
    np.testing.assert_allclose(np.asarray(Xi), np.asarray(Xi_theory),
                               rtol=0.2, atol=0.05)


def test_vrmom_interval_efficiency(lin_setup):
    """VRMOM CIs are narrower than MOM CIs on the same data (Theorem 1's
    efficiency gain surfacing in interval width), wider than mean CIs."""
    prob, shards, theta_star, theta_hat = lin_setup
    widths = {}
    for est in ("mean", "vrmom", "median"):
        res = infer(prob, shards, theta_hat, estimator=est)
        widths[est] = float(jnp.mean(res.ci.upper - res.ci.lower))
    assert widths["mean"] < widths["vrmom"] < widths["median"]
    # the asymptotic ratio is sqrt(sigma_K^2 / (pi/2)) ~ 0.82 at K=10;
    # at m=41 machines the two plug-in Sigma_hats differ too, so only
    # bracket it (the coverage benchmark pins the calibrated behaviour)
    assert 0.6 < widths["vrmom"] / widths["median"] < 0.92


def test_ci_width_shrinks_like_sqrt_n():
    p = 3
    theta_star = R.paper_theta_star(p)
    prob = R.LinearRegressionProblem()
    widths = []
    for n in (200, 800):  # 4x the data -> half the width
        shards = R.make_shards(jax.random.PRNGKey(2), N_per_machine=n,
                               m_workers=30, p=p, theta_star=theta_star,
                               model="linear")
        theta_hat, _ = R.rcsl(prob, shards, jax.random.PRNGKey(3), rounds=5)
        res = infer(prob, shards, theta_hat)
        widths.append(float(jnp.mean(res.ci.upper - res.ci.lower)))
    assert widths[0] / widths[1] == pytest.approx(2.0, rel=0.1)


def test_ci_width_grows_with_level_and_alpha(lin_setup):
    prob, shards, theta_star, theta_hat = lin_setup
    w = {lvl: float(jnp.mean(
        (r := infer(prob, shards, theta_hat, level=lvl)).ci.upper
        - r.ci.lower)) for lvl in (0.8, 0.95, 0.99)}
    assert w[0.8] < w[0.95] < w[0.99]
    # assumed Byzantine fraction widens the interval (finite-alpha
    # contamination inflation), deterministically
    wa = {a: float(jnp.mean(
        (r := infer(prob, shards, theta_hat, alpha=a)).ci.upper
        - r.ci.lower)) for a in (0.0, 0.1, 0.2)}
    assert wa[0.0] < wa[0.1] < wa[0.2]
    assert wa[0.1] / wa[0.0] == pytest.approx(
        math.sqrt(contamination_inflation(0.1)), rel=1e-4)


def test_simultaneous_wider_than_pointwise(lin_setup):
    prob, shards, theta_star, theta_hat = lin_setup
    res_pt = infer(prob, shards, theta_hat)
    res_si = infer(prob, shards, theta_hat, simultaneous=True)
    assert bool(jnp.all(res_si.ci.lower < res_pt.ci.lower))
    assert bool(jnp.all(res_si.ci.upper > res_pt.ci.upper))


def test_ci_attack_invariance(lin_setup):
    """floor(alpha*m) machines reporting garbage statistics must not move
    the robustly-aggregated CI: same centre, nearly the same width as
    the honestly-computed CI at the same assumed alpha."""
    prob, shards, theta_star, theta_hat = lin_setup
    clean = infer(prob, shards, theta_hat, alpha=0.2)  # attack='none'
    for attack in ("gaussian", "signflip", "wrong_value"):
        res = infer(prob, shards, theta_hat, alpha=0.2, attack=attack,
                    key=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(res.theta),
                                      np.asarray(clean.theta))
        ratio = np.asarray(res.ci.se / clean.ci.se)
        assert np.all(ratio > 0.75) and np.all(ratio < 1.35), (attack, ratio)
    # a non-robust aggregate is destroyed by the same corruption: the
    # mean-aggregated H/Sigma absorb the garbage rows (H can even lose
    # positive-definiteness), so the resulting "CI" deviates wildly
    # where the robust one stayed put
    honest_mean = infer(prob, shards, theta_hat, estimator="mean")
    broken = infer(prob, shards, theta_hat, estimator="mean", alpha=0.2,
                   attack="gaussian", key=jax.random.PRNGKey(7))
    log_dev = np.abs(np.log(np.asarray(broken.ci.se)
                            / np.asarray(honest_mean.ci.se)))
    assert float(log_dev.max()) > math.log(1.5)


def test_infer_jits_and_matches_eager(lin_setup):
    prob, shards, theta_hat = lin_setup[0], lin_setup[1], lin_setup[3]
    eager = infer(prob, shards, theta_hat, alpha=0.1, attack="gaussian",
                  key=jax.random.PRNGKey(9))
    jitted = jax.jit(lambda s, t, k: infer(prob, s, t, alpha=0.1,
                                           attack="gaussian", key=k))(
        shards, theta_hat, jax.random.PRNGKey(9))
    np.testing.assert_allclose(np.asarray(eager.ci.lower),
                               np.asarray(jitted.ci.lower), rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(eager.cov),
                               np.asarray(jitted.cov), rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Coverage harness
# ---------------------------------------------------------------------------


def test_coverage_close_to_nominal_small_rep():
    """Empirical coverage of the 95% CIs under the paper's Gaussian
    attack at alpha=0.1 — a small-rep version of the committed
    BENCH_inference.json acceptance cell (binomial noise at 40 reps
    demands loose bounds; the benchmark tightens them at 200)."""
    s = coverage_run(model="linear", attack="gaussian", alpha=0.1,
                     estimator="vrmom", reps=40, N_per_machine=200,
                     m_workers=100, p=5, rounds=6, level=0.95,
                     batch_size=10).summary()
    assert 0.85 <= s["coverage"] <= 1.0
    assert np.isfinite(s["mean_width"]) and s["mean_width"] > 0
    assert s["rmse"] < 0.05


def test_coverage_outputs_shapes():
    cell = coverage_run(model="linear", attack="none", alpha=0.0,
                        estimator="vrmom", reps=6, N_per_machine=100,
                        m_workers=20, p=3, rounds=3, batch_size=3)
    assert cell.covered.shape == (6, 3)
    assert cell.width.shape == (6, 3)
    assert cell.covered.dtype == jnp.bool_
    s = cell.summary()
    assert s["reps"] == 6 and len(s["coverage_per_coord"]) == 3


def test_coverage_rejects_indivisible_mesh_reps():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >1 device")
    mesh = make_mesh((len(devs),), ("data",))
    with pytest.raises(ValueError, match="not divisible"):
        coverage_run(reps=len(devs) + 1, mesh=mesh)
