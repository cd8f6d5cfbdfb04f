"""The train cell on a tiny configuration over four CPU devices, in a
process of its own: correct when sound, and not correct under each
fault the cell can have (state unchanged, half the batch left out, the
exchange between chips left out, one honest worker's gradient taken
from the wrong rows) or with the fp8 control in the program's place."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent

SCRIPT = r'''
import json, os, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2], sys.argv[3]]
import jax
import tiny
from bench.drivers import train as D
conf, mix = tiny.conf("qwen3-1.7b-14l"), tiny.train_mix()
# the tiny model's limits: sound runs read about 3e-4, 3e-3 and 6e-3
mix["check"].update(loss_gap=2e-3, grad_norm_gap=0.05, update_norm_gap=0.012)
devs = jax.devices()[:4]
built = D.build(conf, mix, devs)
setup, step = built["setup"], built["step"]
W = mix["data"]
fn = setup.step_fn


def half(p, o, b, k):
    n = b["tokens"].shape[0] // 2
    return fn(p, o, {"tokens": b["tokens"][:n]}, k)


def wrong_shard(p, o, b, k):
    # honest worker 0 computes its gradient on worker 1's rows
    t = b["tokens"]
    n = t.shape[0] // W
    return fn(p, o, {"tokens": t.at[:n].set(t[n:2 * n])}, k)


def frozen(p, o, b, k):
    return p, o, fn(p, o, b, k)[2]


def run(kind, step):
    b = dict(built, step=step)
    rec, cfg, prog = D.train_window(conf, mix, 5, 0.5, False, devs,
                                    time.perf_counter(), None, built=b)
    return prog


ref = D.reference_readings(conf, mix, 5, devs)
out = {}
for kind, s in [("sound", step), ("frozen", jax.jit(frozen)),
                ("half_batch", jax.jit(half)),
                ("one_worker_shard", jax.jit(wrong_shard))]:
    out[kind] = D.compare(run(kind, s), ref)
import repro.dist.robust_reduce as RR
real = jax.lax.all_to_all
RR.jax.lax.all_to_all = lambda x, *a, **k: x.reshape(W, -1)
try:
    built = D.build(conf, mix, devs)
    out["no_exchange"] = D.compare(run("no_exchange", built["step"]), ref)
finally:
    RR.jax.lax.all_to_all = real
out["control_fp8"] = D.compare(
    D.reference_readings(conf, mix, 5, devs, quant="fp8"), ref)
lim = mix["check"]
print(json.dumps({k: {n: (v, v <= lim[n]) for n, v in g.items()}
                  for k, g in out.items()}))
'''


@pytest.fixture(scope="module")
def readings():
    root = HERE.parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(root),
                        str(root / "src"), str(HERE)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(readings):
    assert all(ok for _, ok in readings["sound"].values()), readings


@pytest.mark.parametrize("kind", ["frozen", "half_batch", "no_exchange",
                                  "one_worker_shard", "control_fp8"])
def test_fault_or_control_is_not_correct(readings, kind):
    assert not all(ok for _, ok in readings[kind].values()), readings[kind]
