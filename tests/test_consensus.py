"""Tests for the decentralized consensus backend (DESIGN.md §13).

Single-device tests exercise the emulation path (``consensus_iterate``
/ ``consensus_aggregate`` on a host [n, C] stack); the shard_map wire
and the consensus train step run in an 8-device SUBPROCESS via the same
``_run`` harness as tests/test_distributed.py.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks as A
from repro.dist import robust_reduce as RR
from repro.dist.consensus import (ConsensusConfig, consensus_aggregate,
                                  consensus_iterate)
from repro.dist.faults import FaultPlan

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def _stack(n=8, C=37, key=0):
    return jax.random.normal(jax.random.PRNGKey(key), (n, C))


# ---------------------------------------------------------------- emulation

@pytest.mark.parametrize("est", ["vrmom", "median", "mean"])
def test_fault_free_matches_direct_aggregation(est):
    """No faults, trim='mean', no pin: the consensus value is EXACTLY
    the direct robust aggregate (round 1 is idempotent)."""
    v = _stack()
    cfg = ConsensusConfig(f=1).validate(v.shape[0])
    got, aux = consensus_aggregate(v, est, config=cfg)
    want = RR.aggregate_stacked_auto({"g": v}, est)["g"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not bool(aux.quorum_lost)
    assert float(aux.spread) <= cfg.eps


def test_refuses_n_le_5f():
    v = _stack(n=8)
    with pytest.raises(ValueError, match="n > 5f"):
        consensus_aggregate(v, "vrmom", config=ConsensusConfig(f=2))
    # boundary: n = 5f exactly is still invalid
    with pytest.raises(ValueError, match="n > 5f"):
        ConsensusConfig(f=1).validate(5)
    ConsensusConfig(f=1).validate(6)  # minimal valid population


def test_convergence_under_dropout_and_byzantine_pin():
    """10% message loss + a persistent Byzantine sender: honest values
    still contract to eps, and the aux telemetry is coherent."""
    n = 8
    v = _stack(n=n)
    mask = jnp.arange(n) >= n - 1              # last row Byzantine
    assert int(mask.sum()) == 1
    v_att = A.omniscient(jax.random.PRNGKey(3), v, mask)
    cfg = ConsensusConfig(f=1, trim="midpoint").validate(n)
    plan = FaultPlan(dropout=0.1).validate(n)
    finals, aux = consensus_iterate(v_att, "vrmom", config=cfg, plan=plan,
                                    key=jax.random.PRNGKey(9), pin_mask=mask)
    assert np.isfinite(np.asarray(finals)).all()
    assert float(aux.spread) <= cfg.eps
    assert int(aux.rounds_to_eps) <= int(aux.rounds_run)
    assert int(aux.messages_dropped) > 0
    assert 0.0 < float(aux.quorum) <= 1.0
    assert not bool(aux.quorum_lost)
    # honest finals agree with each other and stay near the honest cloud
    honest = np.asarray(finals)[: n - 1]
    assert np.abs(honest - honest[0]).max() <= cfg.eps
    ref = np.asarray(v)[: n - 1].mean(0)
    assert np.abs(honest[0] - ref).max() < 3.0


def test_crash_within_quorum_converges():
    n, v = 8, _stack()
    cfg = ConsensusConfig(f=1).validate(n)
    plan = FaultPlan(n_crashed=1, crash_round=1).validate(n)
    got, aux = consensus_aggregate(v, "vrmom", config=cfg, plan=plan,
                                   key=jax.random.PRNGKey(1))
    assert np.isfinite(np.asarray(got)).all()
    assert not bool(aux.quorum_lost)
    assert float(aux.spread) <= cfg.eps


def test_quorum_loss_flags_not_nan():
    """Crashes beyond n - f: the backend degrades gracefully — finite
    output, quorum gauge collapses, quorum_lost flag raised. Never NaN."""
    n, v = 8, _stack()
    cfg = ConsensusConfig(f=1).validate(n)
    plan = FaultPlan(n_crashed=3, crash_round=0).validate(n)
    got, aux = consensus_aggregate(v, "vrmom", config=cfg, plan=plan,
                                   key=jax.random.PRNGKey(2))
    assert np.isfinite(np.asarray(got)).all(), "quorum loss must not NaN"
    assert bool(aux.quorum_lost)
    assert float(aux.quorum) < 0.5
    assert np.isfinite(float(aux.spread))


def test_stragglers_converge():
    n, v = 8, _stack()
    cfg = ConsensusConfig(f=1).validate(n)
    plan = FaultPlan(n_stragglers=2, stale_rounds=2).validate(n)
    _, aux = consensus_aggregate(v, "vrmom", config=cfg, plan=plan,
                                 key=jax.random.PRNGKey(4))
    assert float(aux.spread) <= cfg.eps
    assert not bool(aux.quorum_lost)


@pytest.mark.parametrize("attack", ["ipm", "mimic"])
def test_omniscient_pin_composition_stays_bounded(attack):
    """S4 (DESIGN.md §14): omniscient attack payloads re-broadcast every
    round by the *pinned* Byzantine rows compose with the consensus
    trim: the honest consensus value stays inside the honest cloud and
    quorum holds — for both a loud payload (ipm at eps=100) and a
    legitimate-looking one (mimic)."""
    n = 16
    v = _stack(n=n, key=5)
    mask = jnp.arange(n) >= n - 3            # 3 pinned Byzantine, 16 > 5*3
    if attack == "ipm":
        v_att = A.ipm(jax.random.PRNGKey(8), v, mask, eps=100.0)
    else:
        v_att = A.mimic(jax.random.PRNGKey(8), v, mask)
    cfg = ConsensusConfig(f=3).validate(n)
    got, aux = consensus_aggregate(v_att, "vrmom", config=cfg,
                                   key=jax.random.PRNGKey(12), pin_mask=mask)
    assert np.isfinite(np.asarray(got)).all()
    assert not bool(aux.quorum_lost)
    assert float(aux.spread) <= cfg.eps
    ref = np.asarray(v)[: n - 3].mean(0)     # honest reference
    assert np.abs(np.asarray(got) - ref).max() < 3.0


def test_omniscient_pin_mean_control_diverges():
    """The contrast cell for S4: the same pinned ipm payload through an
    untrimmed mean consensus (f=0) drags the value far from the honest
    cloud — robust trimming, not the consensus rounds, is what bounds
    the error above."""
    n = 16
    v = _stack(n=n, key=5)
    mask = jnp.arange(n) >= n - 3
    v_att = A.ipm(jax.random.PRNGKey(8), v, mask, eps=100.0)
    ref = np.asarray(v)[: n - 3].mean(0)
    robust, _ = consensus_aggregate(
        v_att, "vrmom", config=ConsensusConfig(f=3).validate(n),
        key=jax.random.PRNGKey(12), pin_mask=mask)
    control, _ = consensus_aggregate(
        v_att, "mean", config=ConsensusConfig(f=0).validate(n),
        key=jax.random.PRNGKey(12), pin_mask=mask)
    err_r = np.linalg.norm(np.asarray(robust) - ref)
    err_c = np.linalg.norm(np.asarray(control) - ref)
    assert err_c > 5.0 * err_r + 1.0, (err_c, err_r)


def test_aux_fields_are_scalars():
    v = _stack()
    _, aux = consensus_aggregate(v, "vrmom",
                                 config=ConsensusConfig(f=1).validate(8))
    for name, val in aux._asdict().items():
        assert jnp.shape(val) == (), (name, jnp.shape(val))


def test_auto_consensus_backend_roundtrip():
    """aggregate_stacked_auto(reduce_backend='consensus') flattens a
    pytree onto one wire and returns leaves with original shape/dtype,
    matching the direct backend fault-free."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 4, 6)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (8, 5))
              .astype(jnp.bfloat16)}
    cfg = ConsensusConfig(f=1).validate(8)
    out, aux = RR.aggregate_stacked_auto(g, "vrmom",
                                         reduce_backend="consensus",
                                         consensus=cfg)
    direct = RR.aggregate_stacked_auto(g, "vrmom")
    for k in g:
        assert out[k].shape == g[k].shape[1:]
        assert out[k].dtype == g[k].dtype
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(direct["w"]), rtol=1e-6, atol=1e-6)
    assert not bool(aux.quorum_lost)


# ------------------------------------------------------- 8-device subprocess

def test_shard_map_consensus_matches_rrs_and_emulation():
    """On a real 8-device mesh: fault-free consensus == RRS exactly,
    and the faulty shard_map wire is bit-identical to the emulation
    (same key -> same recv matrices -> same trajectory)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist import robust_reduce as RR
from repro.dist.consensus import (ConsensusConfig, aggregate_stacked_consensus,
                                  consensus_aggregate)
from repro.dist.faults import FaultPlan
from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
g = {"w": jax.random.normal(jax.random.PRNGKey(2), (8, 12, 8)),
     "b": jax.random.normal(jax.random.PRNGKey(3), (8, 7))}
sh = {"w": NamedSharding(mesh, P("data", None, "model")),
      "b": NamedSharding(mesh, P("data", None))}
gp = jax.tree.map(jax.device_put, g, sh)
cfg = ConsensusConfig(f=1).validate(8)

out, aux = jax.jit(lambda x: aggregate_stacked_consensus(
    x, mesh, ("data",), "vrmom", config=cfg))(gp)
rrs = jax.jit(lambda x: RR.aggregate_stacked_rrs(
    x, mesh, ("data",), "vrmom"))(gp)
for k in g:
    np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(rrs[k]))
assert not bool(aux.quorum_lost)
print("CONS-EQ-RRS")

# faulty wire vs emulation, bit for bit (values and aux)
plan = FaultPlan(dropout=0.2, n_crashed=1, crash_round=1).validate(8)
key = jax.random.PRNGKey(11)
out_f, aux_f = jax.jit(lambda x: aggregate_stacked_consensus(
    x, mesh, ("data",), "vrmom", config=cfg, plan=plan, key=key))(gp)
wire = jnp.concatenate([g["w"].reshape(8, -1), g["b"].reshape(8, -1)], axis=1)
want, aux_e = consensus_aggregate(wire, "vrmom", config=cfg, plan=plan,
                                  key=key)
got = jnp.concatenate([out_f["w"].reshape(-1), out_f["b"].reshape(-1)])
np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
for name in aux_e._fields:
    np.testing.assert_array_equal(np.asarray(getattr(aux_f, name)),
                                  np.asarray(getattr(aux_e, name)), err_msg=name)
print("CONS-EQ-EMU")
""")
    assert "CONS-EQ-RRS" in out and "CONS-EQ-EMU" in out


def test_train_step_consensus_under_attack_and_dropout():
    """End-to-end sharded training with the consensus backend: ALIE
    attacker + 10% dropout + a mid-run crash stays finite and learns."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get as get_arch
from repro.data import lm_batch, shard_batch
from repro.models import model as M
from repro.train.step import make_train_step
import repro.optim as O
from repro.dist import sharding as S
from repro.dist.consensus import ConsensusConfig
from repro.dist.faults import FaultPlan

from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
cfg = get_arch("qwen3-1.7b").reduced()
plan = FaultPlan(dropout=0.1, n_crashed=1, crash_round=2)
setup = make_train_step(cfg, mesh, estimator="vrmom",
                        reduce_backend="consensus",
                        consensus=ConsensusConfig(f=1),
                        fault_plan=plan,
                        byzantine_frac=0.15, attack="alie", lr=1e-2)
# 0.15 * 7 floors to exactly one Byzantine worker; 0.125 would floor to
# zero and silently test nothing.
assert int(0.15 * (8 - 1)) == 1
assert setup.n_workers == 8
opt = O.get(cfg.optimizer, lr=1e-2)
params = M.init(jax.random.PRNGKey(0), cfg)
p = jax.device_put(params, S.to_named(mesh, setup.params_specs))
st = jax.jit(opt.init)(p)
step = jax.jit(setup.step_fn)
losses = []
for i in range(6):
    b = shard_batch(lm_batch(cfg, i, 8, 32), mesh, setup.batch_axes)
    p, st, loss, caux = step(p, st, b, jax.random.PRNGKey(i))
    losses.append(float(loss))
    assert np.isfinite(losses[-1])
    assert not bool(caux.quorum_lost)
    assert int(caux.rounds_run) >= 1
assert losses[-1] < losses[0], losses
print("CONS-TRAIN-OK", losses[0], losses[-1])
""", timeout=1800)
    assert "CONS-TRAIN-OK" in out


def test_coverage_cell_under_consensus():
    """Statistical cell (rcsl + sandwich CI) through the consensus wire
    with dropout: coverage stays near nominal."""
    out = _run("""
import numpy as np
from repro.infer.coverage import coverage_run
from repro.dist.consensus import ConsensusConfig
from repro.dist.faults import FaultPlan
cell = coverage_run(model="linear", attack="alie", alpha=0.1,
                    estimator="vrmom", K=5, reps=16, N_per_machine=100,
                    m_workers=20, p=3, rounds=4, batch_size=8,
                    reduce_backend="consensus",
                    consensus=ConsensusConfig(f=2),
                    fault_plan=FaultPlan(dropout=0.1))
s = cell.summary()
assert np.isfinite(s["rmse"])
assert s["coverage"] >= 0.6, s
print("CONS-COVERAGE-OK", s["coverage"])
""", devices=1, timeout=1200)
    assert "CONS-COVERAGE-OK" in out
