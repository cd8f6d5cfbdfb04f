"""Train driver: the jitted Byzantine-robust step of ``repro.launch.train``.

Set-up builds the step that ``launch.train.make_setup`` makes for the
mix's arguments, makes the weights from the seed on the mesh, and
drives that same step through the first ``check.steps`` steps: the
first call compiles. It reads the first aggregated gradient from the
optimizer's first moment after step 1 (m = (1 - b1) g) and the change
of the weights after the last of them. The window then goes on stepping
the same object, with a batch made on the host each step as a loader
would, the next batch made while the device runs the current step.

After the window the program's state is freed and the plain reference
(``bench/reference/train.py``) follows the same first steps.
"""
from __future__ import annotations

import contextlib
import gc
import statistics
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import common, gen, weights
from ..reference.train import Reference

TRACE_SECONDS = 6.0
# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: its change is not compared
QUIET_LEAF = 1e-3


def launch_args(conf, mix):
    """The launcher's own command line for the mix (its ``--arch`` is
    only a label here: ``make_setup`` takes the configuration)."""
    W = mix["data"]
    return ["--arch", conf["name"], "--data", str(W),
            "--model", str(mix["model"]), "--aggregator", mix["aggregator"],
            "--mode", mix["mode"], "--K", str(mix["K"]),
            # make_train_step attacks int(frac * (W - 1)) workers
            "--byzantine", repr(mix["byzantine_workers"] / (W - 1)),
            "--attack", mix["attack"], "--lr", repr(mix["optimizer"]["lr"]),
            "--batch", str(mix["global_batch"]), "--seq", str(mix["seq"])]


def _norms(tree, scale=1.0):
    return jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        * scale, t))(tree)


def _diff_norms(a, b):
    return jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))(a, b)


def build(conf, mix, devs):
    """The launcher's step for the mix on a mesh of ``devs``."""
    from repro.dist import sharding as S
    from repro.launch import train as T
    from repro.launch.mesh import make_mesh

    cfg = common.arch_config(conf)
    W = mix["data"]
    mesh = make_mesh((W, mix["model"]), ("data", "model"),
                     devices=devs[:W * mix["model"]])
    args = T.parse_args(launch_args(conf, mix))
    setup = T.make_setup(args, cfg, mesh)
    return {"cfg": cfg, "mesh": mesh, "args": args, "setup": setup,
            "step": jax.jit(setup.step_fn),
            "param_sh": S.to_named(mesh, setup.params_specs),
            "opt_sh": S.to_named(mesh, setup.opt_specs)}


def train_window(conf, mix, seed, seconds, trace, devs, t_proc, trace_dir,
                 built=None):
    """Set-up and window; -> (record, config, program readings).
    ``built`` reuses the step of an earlier ``build`` (several seeds in
    one process)."""
    from repro import optim as O
    from repro.models import model as M

    b = built or build(conf, mix, devs)
    cfg, mesh, setup, step = b["cfg"], b["mesh"], b["setup"], b["step"]
    if mix["optimizer"]["name"] != cfg.optimizer:
        raise SystemExit(f"bench: the reference follows adamw, the program "
                         f"runs {cfg.optimizer!r}")
    optimizer = O.get(cfg.optimizer, lr=b["args"].lr)
    params = weights.make(conf, seed, out_shardings=b["param_sh"])
    weights.check_layout(params, M.abstract_init(cfg))
    opt_state = jax.jit(optimizer.init, out_shardings=b["opt_sh"])(params)
    bsh = NamedSharding(mesh, P(setup.batch_axes, None))

    def batch(i):
        return {"tokens": jax.device_put(gen.lm_batch(mix, cfg.vocab, seed,
                                                      i), bsh)}

    def key(i):
        return weights.key_for(seed, 1000 + i)

    b1 = mix["optimizer"]["b1"]
    n_check = mix["check"]["steps"]
    p0, losses, g_norms = params, [], None
    for i in range(n_check):
        params, opt_state, loss = step(params, opt_state, batch(i), key(i))
        losses.append(float(loss))
        if i == 0:
            g_norms = jax.tree.map(float, _norms(opt_state["m"],
                                                 1.0 / (1.0 - b1)))
    change = jax.tree.map(float, _diff_norms(params, p0))
    del p0
    nxt = batch(n_check)
    jax.block_until_ready((params, opt_state, nxt))

    counter = common.CompileCounter().install()
    n_steps, i = 0, n_check
    t0 = time.perf_counter()
    setup_s = t0 - t_proc
    tr_end = None

    def span(name):
        if trace and tr_end is None:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    if trace:
        jax.profiler.start_trace(str(trace_dir))
    t_end = t0 + seconds
    window_losses = []
    with counter:
        t = t0
        while t < t_end:
            with span("host.dispatch"):
                params, opt_state, loss = step(params, opt_state, nxt,
                                               key(i))
            i += 1
            with span("host.batch"):
                nxt = batch(i)
            with span("host.wait"):
                window_losses.append(float(loss))
            n_steps += 1
            t = time.perf_counter()
            if trace and tr_end is None and (t - t0 >= min(TRACE_SECONDS,
                                                           seconds)):
                jax.profiler.stop_trace()
                tr_end, traced = t, n_steps
    if trace and tr_end is None:
        jax.profiler.stop_trace()
        tr_end, traced = t, n_steps
    elapsed = t - t0
    rec = {"setup_s": setup_s, "elapsed_s": elapsed, "steps": n_steps,
           "tokens": n_steps * mix["global_batch"] * mix["seq"],
           "compiles_in_window": counter.n, "attempted": n_steps,
           "failed": sum(1 for x in window_losses if x != x)}
    # the buffers' peak, or the step's own peak where the compiler
    # counts more (its temporaries)
    rec["memory_peak_bytes"] = max(
        common.memory_peak(devs),
        common.program_peak(devs, step, params, opt_state, nxt, key(i)))
    if trace:
        rec["trace_window_s"] = tr_end - t0
        rec["work"] = {"steps": traced}
    del params, opt_state, nxt, step, setup, b
    gc.collect()
    return rec, cfg, {"losses": losses, "grad_norms": g_norms,
                      "change_norms": change}


def reference_readings(conf, mix, seed, devs, quant=None, faults=()):
    """The plain reference's readings over the same first steps."""
    r = Reference(conf, mix, devs, quant=quant, faults=faults)
    params = weights.make(conf, seed, out_shardings=NamedSharding(r.mesh,
                                                                  P()))
    n = mix["check"]["steps"]
    batches = [gen.lm_batch(mix, conf["vocab_size"], seed, i)
               for i in range(n)]
    keys = [weights.key_for(seed, 1000 + i) for i in range(n)]
    losses, g_norms, change = r.run(params, batches, keys)
    return {"losses": losses, "grad_norms": g_norms, "change_norms": change}


def compare(prog, refr):
    """The three numbers compared: the widest loss gap over the steps,
    and by the worst leaf the gap of the first gradient's norm and of
    the change's norm, each over max(the leaf's, the median leaf's)
    reference norm. Leaves whose reference gradient is nought to
    rounding (under QUIET_LEAF of the median leaf's) are left out of
    the change."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                              refr["losses"]))
    pg = jax.tree.leaves(prog["grad_norms"])
    rg = jax.tree.leaves(refr["grad_norms"])
    pc = jax.tree.leaves(prog["change_norms"])
    rc = jax.tree.leaves(refr["change_norms"])
    g_med = statistics.median(rg)
    c_med = statistics.median(rc)
    grad_gap = max(abs(a - b) / max(b, g_med) for a, b in zip(pg, rg))
    upd_gap = max((abs(a - b) / max(b, c_med)
                   for a, b, g in zip(pc, rc, rg) if g >= QUIET_LEAF * g_med),
                  default=0.0)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": upd_gap}


def run(conf, mix, seed, seconds, trace, devs, t_proc, trace_dir):
    rec, cfg, prog = train_window(conf, mix, seed, seconds, trace, devs,
                                  t_proc, trace_dir)
    t_ref = time.perf_counter()
    common.log("bytes in use after the window: " + ", ".join(
        str((d.memory_stats() or {}).get("bytes_in_use")) for d in devs))
    refr = reference_readings(conf, mix, seed, devs)
    common.log(f"reference: {mix['check']['steps']} steps in "
               f"{time.perf_counter() - t_ref:.1f} s; program losses "
               f"{prog['losses']}, reference {refr['losses']}")
    gaps = compare(prog, refr)
    lim = mix["check"]
    checks = [common.check(k, v, lim[k]) for k, v in gaps.items()]
    return rec, cfg, checks
