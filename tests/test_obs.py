"""repro.obs telemetry layer: fixed-edge histogram convention (jit
counts == host bisect), suspicion-score diagnostics ranking Byzantine
workers, serve-path disagreement drain (tokens bit-identical with
telemetry on), scheduler metrics, sinks round-trip, and the stdlib-only
import guarantee of the non-jax half."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get as get_arch
from repro.core import attacks as ATK
from repro.core.estimator import Estimator
from repro.models import model as Mo
from repro.obs import (Histogram, JsonlSink, MetricsRegistry, catalog,
                       merge_records, prometheus_text, read_jsonl)
from repro.obs.diag import (diagnose, histogram_counts, replica_disagreement,
                            tree_diagnose)
from repro.serve import (Request, RobustDecodeConfig, Scheduler, ServeEngine,
                         replica_mask, robust_logits)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dense():
    cfg = get_arch("qwen3-1.7b").reduced()
    params = Mo.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt_batch(cfg, B, S, seed=1):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                         cfg.vocab)}


# ---------------------------------------------------------------------------
# Histogram mechanics (host side)
# ---------------------------------------------------------------------------

def test_histogram_record_and_percentiles():
    h = Histogram((1.0, 2.0, 5.0, 10.0))
    vals = [0.5, 1.5, 1.5, 3.0, 7.0, 20.0]
    h.record_many(vals)
    assert h.count == len(vals)
    assert h.min == 0.5 and h.max == 20.0
    assert abs(h.mean - np.mean(vals)) < 1e-12
    # percentiles are monotone and bracketed by the observed extremes
    ps = [h.percentile(q) for q in (1, 25, 50, 75, 99)]
    assert ps == sorted(ps)
    assert h.min <= ps[0] and ps[-1] <= h.max
    # the median sample (3.0) lives in bucket (2, 5]
    assert 2.0 <= h.percentile(50) <= 5.0


def test_histogram_edges_must_increase():
    with pytest.raises(ValueError):
        Histogram((1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))


def test_histogram_snapshot_merge_roundtrip():
    a = Histogram((1.0, 10.0))
    b = Histogram((1.0, 10.0))
    a.record_many([0.5, 5.0])
    b.record_many([20.0, 5.0])
    c = Histogram.from_snapshot(a.snapshot())
    c.merge(b)
    both = Histogram((1.0, 10.0))
    both.record_many([0.5, 5.0, 20.0, 5.0])
    assert c.snapshot() == both.snapshot()
    with pytest.raises(ValueError):
        a.merge(Histogram((1.0, 2.0)))


# ---------------------------------------------------------------------------
# Fixed-edge bucket convention: jit counts == host bisect
# ---------------------------------------------------------------------------

def test_histogram_counts_matches_host_convention():
    """``diag.histogram_counts`` (searchsorted left) and the host
    ``Histogram`` (bisect_left) must bucket identically — including
    values landing exactly on an edge — so jit counts drain losslessly."""
    edges = (0.0, 0.25, 0.5, 1.0)
    vals = [-1.0, 0.0, 0.1, 0.25, 0.3, 0.5, 0.75, 1.0, 2.0]
    dev = jax.jit(histogram_counts, static_argnums=1)(
        jnp.asarray(vals, jnp.float32), edges)
    host = Histogram(edges)
    host.record_many(vals)
    assert [int(c) for c in dev] == host.counts
    # merge_counts reproduces the host-recorded histogram exactly
    drained = Histogram(edges)
    drained.merge_counts([int(c) for c in dev], float(np.sum(vals)),
                         len(vals))
    assert drained.counts == host.counts
    assert drained.count == host.count
    assert abs(drained.sum - host.sum) < 1e-6


def test_merge_counts_length_mismatch_raises():
    h = Histogram((1.0, 2.0))
    with pytest.raises(ValueError):
        h.merge_counts([1, 2], 3.0, 2)  # needs len(edges) + 1 == 3


# ---------------------------------------------------------------------------
# Registry + catalog
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_timer():
    reg = MetricsRegistry()
    reg.counter("serve.admitted")
    reg.counter("serve.admitted", 2)
    reg.gauge("serve.queue_depth", 5)
    with reg.timer("serve.ttft_s"):
        pass
    with reg.timer("serve.compile_s", kind="gauge"):
        pass
    assert reg.counters["serve.admitted"] == 3
    assert reg.gauges["serve.queue_depth"] == 5.0
    assert reg.histograms["serve.ttft_s"].count == 1
    assert reg.gauges["serve.compile_s"] >= 0.0
    # histogram edges come from the catalog entry for the name
    assert reg.histograms["serve.ttft_s"].edges == catalog.LATENCY_EDGES_S
    assert (reg.histogram("serve.replica_disagreement").edges
            == catalog.FRACTION_EDGES)


def test_catalog_registered_names():
    names = {m.name for m in catalog.METRICS}
    assert len(names) == len(catalog.METRICS)  # no duplicates
    for m in catalog.METRICS:
        assert m.kind in ("counter", "gauge", "histogram")
        assert (m.edges is not None) == (m.kind == "histogram")
    # every name the serve/train/launch paths record is registered
    for n in ("serve.ttft_s", "serve.decode_step_s", "serve.admitted",
              "serve.replica_disagreement", "agg.alpha_hat", "train.step_s",
              "launch.compile_flops"):
        assert n in names, n


def test_obs_stdlib_half_imports_without_jax():
    """catalog/metrics/sinks must work in a jax-less interpreter (docs
    CI): block jax imports and exercise the whole host-side path."""
    script = """
import sys

class _Block:
    def find_module(self, name, path=None):
        if name == "jax" or name.startswith("jax."):
            return self
    def load_module(self, name):
        raise ImportError(f"blocked: {name}")

sys.meta_path.insert(0, _Block())
import repro.obs as obs
reg = obs.MetricsRegistry()
reg.counter("serve.admitted")
reg.observe("serve.ttft_s", 0.01)
text = obs.prometheus_text(reg.snapshot())
assert "serve_admitted_total 1" in text
assert "serve_ttft_s_count 1" in text
assert "jax" not in sys.modules
print("NO-JAX-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "NO-JAX-OK" in r.stdout


# ---------------------------------------------------------------------------
# Sinks: JSONL -> merge -> Prometheus
# ---------------------------------------------------------------------------

def test_sinks_jsonl_prometheus_roundtrip(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = MetricsRegistry()
    reg.counter("serve.admitted", 2)
    reg.gauge("agg.alpha_hat", 0.25)
    reg.observe("serve.ttft_s", 0.05)
    with JsonlSink(path) as sink:
        sink.write_registry(reg, source="test", arch="x")
        sink.write_registry(reg)  # second record: counters/hists add up
    recs = read_jsonl(path)
    assert len(recs) == 2 and recs[0]["kind"] == "metrics"
    assert recs[0]["meta"] == {"source": "test", "arch": "x"}
    summary = merge_records(recs)
    assert summary["counters"]["serve.admitted"] == 4
    assert summary["gauges"]["agg.alpha_hat"] == 0.25
    assert summary["histograms"]["serve.ttft_s"]["count"] == 2
    text = prometheus_text(summary)
    # the TYPE line names the sample family (_total) — classic format
    assert "# TYPE serve_admitted_total counter" in text
    assert "serve_admitted_total 4" in text
    assert "agg_alpha_hat 0.25" in text
    assert 'serve_ttft_s_bucket{le="+Inf"} 2' in text
    assert "serve_ttft_s_count 2" in text


def test_metrics_dump_cli(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = MetricsRegistry()
    reg.counter("serve.retired", 3)
    reg.histogram("serve.decode_step_s").record_many([0.01, 0.02, 0.04])
    with JsonlSink(path) as sink:
        sink.write_registry(reg)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "metrics_dump.py"),
         path, "--format", "prometheus"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "serve_retired_total 3" in r.stdout
    assert "serve_decode_step_s_count 3" in r.stdout
    assert "serve_decode_step_s_p95" in r.stdout  # synthetic percentile
    # json format round-trips through the merge schema
    r2 = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "metrics_dump.py"),
         path, "--format", "json", "--no-percentiles"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r2.returncode == 0, r2.stderr
    summary = json.loads(r2.stdout)
    assert summary["counters"]["serve.retired"] == 3
    # missing file -> exit 2
    r3 = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "metrics_dump.py"),
         str(tmp_path / "nope.jsonl")],
        capture_output=True, text=True, env=env, timeout=120)
    assert r3.returncode == 2


def test_metrics_dump_percentile_values_percent_scale(tmp_path):
    """The synthetic _p50/_p95/_p99 gauges take q in PERCENT: on a
    skewed distribution (90% fast, 10% slow) recorded through the dump
    path, p50 must land in the fast mass and p95/p99 in the slow tail —
    a fraction-scale call (0.95) would return ~the minimum."""
    path = str(tmp_path / "metrics.jsonl")
    reg = MetricsRegistry()
    vals = [0.001] * 90 + [0.5] * 10
    reg.histogram("serve.decode_step_s").record_many(vals)
    with JsonlSink(path) as sink:
        sink.write_registry(reg)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "metrics_dump.py"),
         path, "--format", "json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    g = json.loads(r.stdout)["gauges"]
    p50 = g["serve.decode_step_s_p50"]
    p95 = g["serve.decode_step_s_p95"]
    p99 = g["serve.decode_step_s_p99"]
    assert p50 <= p95 <= p99
    assert p50 < 0.01, p50    # median sits in the fast mass
    assert p95 >= 0.4, p95    # tail percentiles reach the slow samples
    assert p99 <= max(vals)


# ---------------------------------------------------------------------------
# Suspicion diagnostics: corrupted workers dominate the ranking
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("attack", ["signflip", "wrong_value"])
@pytest.mark.parametrize("alpha", [0.125, 0.25])
def test_suspicion_ranks_byzantine_workers(backend, attack, alpha):
    """floor(alpha*m) corrupted rows must take exactly the top suspicion
    scores, and the robust-z mask must flag exactly them."""
    m, d = 8, 64
    key = jax.random.PRNGKey(0)
    base = jax.random.normal(key, (d,))
    noise = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (m, d))
    honest = base[None] + noise
    mask = replica_mask(m, alpha)
    n_byz = int(np.sum(np.asarray(mask)))
    assert n_byz == int(alpha * m) >= 1
    x = ATK.get(attack)(jax.random.PRNGKey(2), honest, mask)
    est = Estimator(method="vrmom", backend=backend)
    agg, diag = jax.jit(est.apply_with_diag)(x)
    # the aggregate is bit-identical to the diag-less apply
    np.testing.assert_array_equal(np.asarray(agg),
                                  np.asarray(jax.jit(est.apply)(x)))
    scores = np.asarray(diag.scores)
    top = set(np.argsort(scores)[-n_byz:])
    assert top == set(np.flatnonzero(np.asarray(mask))), scores
    np.testing.assert_array_equal(np.asarray(diag.suspected),
                                  np.asarray(mask))
    assert abs(float(diag.alpha_hat) - n_byz / m) < 1e-6
    assert diag.pre_norms.shape == (m,) and diag.post_norm.shape == ()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_suspicion_all_false_when_honest(backend):
    """alpha = 0: a noisy all-honest stack must produce an all-false
    mask and alpha_hat == 0 (the relative floor absorbs the jitter)."""
    m, d = 8, 64
    base = jax.random.normal(jax.random.PRNGKey(0), (d,))
    x = base[None] + 0.01 * jax.random.normal(jax.random.PRNGKey(1), (m, d))
    est = Estimator(method="vrmom", backend=backend)
    _, diag = est.apply_with_diag(x)
    assert not np.asarray(diag.suspected).any()
    assert float(diag.alpha_hat) == 0.0


def test_suspicion_identical_rows_zero_scores():
    """The serve regime — deterministic replicas, identical rows — must
    give exact-zero scores, never float-jitter accusations."""
    x = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(0), (32,)),
                         (6, 32))
    _, diag = Estimator(method="median", backend="jnp").apply_with_diag(x)
    assert np.asarray(diag.scores).max() == 0.0
    assert not np.asarray(diag.suspected).any()


def test_tree_diagnose_matches_flat():
    """Pytree diagnostics accumulate per-leaf second moments; the result
    must equal ``diagnose`` on the concatenated flat stack."""
    w = 6
    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    tree = {"a": jax.random.normal(ka, (w, 4, 5)),
            "b": jax.random.normal(kb, (w, 7))}
    flat = jnp.concatenate([tree["a"].reshape(w, -1),
                            tree["b"].reshape(w, -1)], axis=1)
    agg_tree = jax.tree.map(lambda g: jnp.mean(g, axis=0), tree)
    agg_flat = jnp.mean(flat, axis=0)
    dt = tree_diagnose(tree, agg_tree)
    df = diagnose(flat, agg_flat)
    for a, b in zip(dt, df):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_with_diag_does_not_retrace():
    """apply_with_diag under jit: one trace serves every same-shape call
    (the diag aux is a pure function of the traced stack)."""
    est = Estimator(method="vrmom", backend="jnp")
    traces = []

    @jax.jit
    def f(x):
        traces.append(1)
        return est.apply_with_diag(x)

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    f(x)
    f(x + 1.0)
    assert len(traces) == 1


# ---------------------------------------------------------------------------
# Replica disagreement (serve wire signal)
# ---------------------------------------------------------------------------

def test_replica_disagreement_counts_argmax_mismatch():
    # m=4, B=2, V=3: replica 3 votes elsewhere for sequence 0 only
    agg = jnp.asarray([[9.0, 0.0, 0.0], [0.0, 9.0, 0.0]])
    logits_r = jnp.broadcast_to(agg[None], (4, 2, 3)).copy()
    logits_r = logits_r.at[3, 0].set(jnp.asarray([0.0, 0.0, 9.0]))
    rates = replica_disagreement(logits_r, agg)
    np.testing.assert_allclose(np.asarray(rates), [0.25, 0.0], atol=1e-7)


def test_robust_logits_with_diag_matches_alpha():
    """signflip at alpha=0.25, m=8 over identical honest logits: served
    logits unchanged vs the diag-less path, disagreement exactly 2/8."""
    m, B, V = 8, 3, 16
    rcfg = RobustDecodeConfig(m=m, estimator="median", attack="signflip",
                              alpha=0.25)
    honest = jax.random.normal(jax.random.PRNGKey(0), (B, V))
    stack = jnp.broadcast_to(honest[None], (m, B, V))
    key = jax.random.PRNGKey(1)
    agg0 = robust_logits(stack, rcfg, key)
    agg1, dis = robust_logits(stack, rcfg, key, with_diag=True)
    np.testing.assert_array_equal(np.asarray(agg0), np.asarray(agg1))
    # honest majority holds: served argmax == honest argmax
    np.testing.assert_array_equal(np.asarray(jnp.argmax(agg1, -1)),
                                  np.asarray(jnp.argmax(honest, -1)))
    np.testing.assert_allclose(np.asarray(dis), np.full((B,), 0.25),
                               atol=1e-7)


# ---------------------------------------------------------------------------
# Engine + scheduler integration
# ---------------------------------------------------------------------------

def test_engine_obs_tokens_bit_identical_and_drain(dense):
    """Telemetry on vs off: same compiled semantics (bit-identical
    tokens), and the disagreement histogram drains one counts vector per
    dispatch with exact count and the attack's disagreement rate."""
    cfg, params = dense
    rcfg = RobustDecodeConfig(m=4, estimator="median", attack="signflip",
                              alpha=0.25)
    batch = _prompt_batch(cfg, B=2, S=8)
    off = ServeEngine(cfg, params, max_len=32, robust=rcfg)
    reg = MetricsRegistry()
    on = ServeEngine(cfg, params, max_len=32, robust=rcfg, obs=reg)
    t_off = off.generate(batch, 6)
    t_on = on.generate(batch, 6)
    np.testing.assert_array_equal(np.asarray(t_off), np.asarray(t_on))
    h = reg.histograms["serve.replica_disagreement"]
    assert h.count == (6 - 1) * 2  # scanned tokens x batch
    # 1 of 4 replicas signflipped -> disagreement exactly 1/4 per token
    assert abs(h.mean - 0.25) < 1e-6
    # same shapes again: no new compiled programs, histogram accumulates
    n_fns = len(on._fns)
    on.generate(batch, 6)
    assert len(on._fns) == n_fns
    assert h.count == 2 * (6 - 1) * 2


def test_decode_pool_diag_masks_inactive_slots(dense):
    """Pool-path disagreement drain counts ACTIVE slots only: inactive
    slots decode stale/garbage caches and their rates must not dilute
    the per-request Byzantine signal (count = n_steps * n_active, and
    the mean stays exactly the attack's disagreement rate)."""
    cfg, params = dense
    rcfg = RobustDecodeConfig(m=4, estimator="median", attack="signflip",
                              alpha=0.25)
    reg = MetricsRegistry()
    eng = ServeEngine(cfg, params, max_len=32, n_slots=3, robust=rcfg,
                      obs=reg)
    pool = eng.make_pool()
    pool, first = eng.admit(pool, 0, _prompt_batch(cfg, B=1, S=8))
    n_steps = 4
    pool, _ = eng.decode_pool(pool, np.asarray([first, 0, 0], np.int32),
                              n_steps)
    h = reg.histograms["serve.replica_disagreement"]
    assert h.count == n_steps * 1, h.count  # 1 active of 3 slots
    # 1 of 4 replicas signflipped -> disagreement exactly 1/4 per token
    assert abs(h.mean - 0.25) < 1e-6, h.mean


def test_engine_without_robust_records_nothing(dense):
    """obs without a robust config: the plain decode loop carries no
    diag aux (nothing to disagree about) and stays 2-output."""
    cfg, params = dense
    reg = MetricsRegistry()
    eng = ServeEngine(cfg, params, max_len=32, obs=reg)
    eng.generate(_prompt_batch(cfg, B=2, S=8), 6)
    assert "serve.replica_disagreement" not in reg.histograms


def test_scheduler_records_serve_metrics(dense):
    cfg, params = dense
    reg = MetricsRegistry()
    eng = ServeEngine(cfg, params, max_len=48, n_slots=2, obs=reg)
    sched = Scheduler(eng, decode_block=3)
    rs = np.random.RandomState(0)
    uids = [sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(6,)),
                                 max_new_tokens=4)) for _ in range(3)]
    # cannot fit: prompt + budget + block overshoot > max_len
    big = sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(40,)),
                               max_new_tokens=16))
    done = sched.run()
    assert sorted(done) == sorted(uids + [big])
    assert done[big].finished_by == "rejected"
    c = reg.counters
    assert c["serve.admitted"] == 3
    assert c["serve.retired"] == 3
    assert c["serve.rejected"] == 1
    assert c["serve.tokens_out"] == sum(len(done[u].tokens) for u in uids)
    # first admission at the (6,) prompt shape compiles the prefill
    # program, so it lands in serve.compile_s, not the TTFT histogram
    assert reg.histograms["serve.ttft_s"].count == 2
    assert reg.gauges["serve.compile_s"] > 0.0
    assert reg.histograms["serve.decode_step_s"].count >= 1
    assert reg.gauges["serve.queue_depth"] == 0.0  # last cycle: drained
    assert "serve.slots_active" in reg.gauges


# ---------------------------------------------------------------------------
# Train-path diagnostics (8 host devices, subprocess)
# ---------------------------------------------------------------------------

def _run(script, devices=8, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_train_step_with_diag_flags_byzantine_worker():
    """Sharded train step with with_diag=True: the wrong_value worker
    must top the suspicion ranking; diagnostics ride the jitted step as
    static-shape aux, and the loss matches the diag-less step exactly.
    inloop mode has no stacked gradient to diagnose and must refuse."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get as get_arch
from repro.data import lm_batch, shard_batch
from repro.models import model as M
from repro.train.step import make_train_step
import repro.optim as O
from repro.dist import sharding as S

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_arch("qwen3-1.7b").reduced()
params = M.init(jax.random.PRNGKey(0), cfg)

def run(with_diag):
    setup = make_train_step(cfg, mesh, estimator="vrmom", mode="stacked-rrs",
                            byzantine_frac=0.4, attack="wrong_value",
                            lr=1e-2, with_diag=with_diag)
    opt = O.get(cfg.optimizer, lr=1e-2)
    p = jax.device_put(params, S.to_named(mesh, setup.params_specs))
    st = jax.jit(opt.init)(p)
    step = jax.jit(setup.step_fn)
    diag = None
    for i in range(2):
        b = shard_batch(lm_batch(cfg, i, 8, 32), mesh, setup.batch_axes)
        if with_diag:
            p, st, loss, diag = step(p, st, b, jax.random.PRNGKey(i))
        else:
            p, st, loss = step(p, st, b, jax.random.PRNGKey(i))
    return float(loss), diag

loss_plain, _ = run(False)
loss_diag, diag = run(True)
assert loss_plain == loss_diag, (loss_plain, loss_diag)
scores = np.asarray(diag.scores)
assert scores.shape == (4,)
# 0.4 of 3 non-master workers -> 1 Byzantine (the last worker), whose
# wrong_value gradient dominates the deviation ranking
assert int(np.argmax(scores)) == 3, scores
assert bool(np.asarray(diag.suspected)[3])
assert not np.asarray(diag.suspected)[:3].any()
assert abs(float(diag.alpha_hat) - 0.25) < 1e-6
assert np.isfinite(np.asarray(diag.pre_norms)).all()
assert np.isfinite(float(diag.post_norm))

try:
    make_train_step(cfg, mesh, mode="inloop", with_diag=True)
except ValueError as e:
    assert "inloop" in str(e)
else:
    raise AssertionError("inloop + with_diag must refuse")
print("OBS-TRAIN-OK", loss_diag)
""", timeout=1800)
    assert "OBS-TRAIN-OK" in out


def test_rrs_aggregate_with_diag_matches_plain():
    """aggregate(..., with_diag=True) over the RRS wire: the aggregate
    matches the diag-less call bit-for-bit and the diagnostics flag the
    corrupted row of a signflip-attacked stacked pytree."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist import robust_reduce as RR
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
g = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 6, 16)) + 2.0}
g["w"] = g["w"].at[3].multiply(-1.0)  # worker 3 signflips on the wire
sh = {"w": NamedSharding(mesh, P("data", None, "model"))}
gp = jax.tree.map(jax.device_put, g, sh)
plain = jax.jit(lambda x: RR.aggregate(x, mesh, ("data",)))(gp)
agg, diag = jax.jit(
    lambda x: RR.aggregate(x, mesh, ("data",), with_diag=True))(gp)
np.testing.assert_array_equal(np.asarray(plain["w"]), np.asarray(agg["w"]))
scores = np.asarray(diag.scores)
assert int(np.argmax(scores)) == 3, scores
assert bool(np.asarray(diag.suspected)[3])
print("RRS-DIAG-OK")
""")
    assert "RRS-DIAG-OK" in out
