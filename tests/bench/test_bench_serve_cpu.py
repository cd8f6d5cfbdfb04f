"""The serve cell end to end on a tiny configuration on the CPU (Pallas
kernels interpreted), and its check failing under each fault the cell
can have."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax

import tiny
from bench import common
from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
CELL = "serve.decode-heavy.robust-m8"


def run_cell(mix=None, trace=False, seconds=2.0):
    man = R.load_manifest()
    _, _, e2e, per_layer = R.cell_of(man, CELL)
    mix = mix or tiny.serve_mix()
    mix["check"]["max_logit_gap"] = 0.05   # the tiny model's limit
    return R.measure(tiny.conf(), mix, seed=2**33 + 5, seconds=seconds,
                     trace=trace, devs=jax.devices()[:1],
                     metrics=per_layer if trace else e2e,
                     t_proc=time.perf_counter())


def test_serve_cell_runs_end_to_end_and_is_correct(capsys):
    result, checks = run_cell()
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    m = result["metrics"]
    assert set(m) == {"tok_s", "tpot_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    common.emit(result, checks)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert "max_logit_gap" in last["checks"]
    assert err.strip().splitlines()[-1].startswith("check max_logit_gap")


def test_serve_fault_altered_token(monkeypatch):
    from repro.serve import engine as E

    orig = E.ServeEngine.decode_pool

    def altered(self, pool, cur_tok, n_steps, **kw):
        pool, toks = orig(self, pool, cur_tok, n_steps, **kw)
        v = self.cfg.vocab
        return pool, toks.at[n_steps // 2].set(
            (toks[n_steps // 2] + v // 2) % v)

    monkeypatch.setattr(E.ServeEngine, "decode_pool", altered)
    result, checks = run_cell()
    assert not result["correct"], checks


def test_serve_fault_state_unchanged(monkeypatch):
    from repro.models import model as M

    orig = M.decode_step

    def frozen(params, cfg, caches, token, window="cfg"):
        logits, _ = orig(params, cfg, caches, token, window=window)
        return logits, caches

    monkeypatch.setattr(M, "decode_step", frozen)
    result, checks = run_cell()
    assert not result["correct"], checks


def test_run_exits_nonzero_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr


def test_serve_control_fp8_is_not_correct():
    """The control: the reference at fp8 in the program's place, judged
    by the same gap on the same served requests."""
    from bench.drivers import serve as D

    mix = tiny.serve_mix()
    conf = tiny.conf()
    rec, cfg, params, finished = D.serve_window(
        conf, mix, 11, 2.0, False, jax.devices()[:1], time.perf_counter(),
        None)
    reqs = D.sample(finished, 100, 11)   # every finished request
    with jax.default_matmul_precision("highest"):
        gap, n = D.reference_gaps(params, conf, mix, reqs)
        cgap, _ = D.reference_gaps(params, conf, mix, reqs, quant="fp8")
    assert n > 0
    assert gap <= 0.05 < cgap, (gap, cgap)
