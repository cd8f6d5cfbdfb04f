"""Profiler spans and scopes (``repro.obs.trace``): the scheduler's and
engine's host spans in a CPU profile, and the device scopes in the
compiled train step and decode block."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get as get_arch
from repro.models import model as Mo
from repro.obs.trace import named_span, trace_span
from repro.serve import Request, RobustDecodeConfig, Scheduler, ServeEngine
from repro.serve.engine import GREEDY


@pytest.fixture(scope="module")
def dense():
    cfg = get_arch("qwen3-1.7b").reduced()
    params = Mo.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _host_spans(trace_dir):
    """-> [(name, start_ns, end_ns, line, stats)] of the ``serve.*`` host
    spans in the profile under ``trace_dir``."""
    f, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(f).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                (plane.name, i), dict(e.stats)))
    return out


def _inside(a, b):
    return a[3] == b[3] and b[1] <= a[1] and a[2] <= b[2]


def test_trace_span_args_become_event_stats(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with trace_span("serve.step", active=3, queued=1):
        with trace_span("serve.wait", what="x"):
            jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    spans = {s[0]: s for s in _host_spans(str(tmp_path))}
    assert spans["serve.step"][4] == {"active": 3, "queued": 1}
    assert spans["serve.wait"][4] == {"what": "x"}
    assert _inside(spans["serve.wait"], spans["serve.step"])


def test_scheduler_spans_nest_and_carry_the_request(dense, tmp_path):
    cfg, params = dense
    rcfg = RobustDecodeConfig(m=4, estimator="vrmom", attack="signflip",
                              alpha=0.25)
    eng = ServeEngine(cfg, params, max_len=48, n_slots=2, robust=rcfg)
    sched = Scheduler(eng, decode_block=3)
    rs = np.random.RandomState(0)
    uids = [sched.submit(Request(tokens=rs.randint(0, cfg.vocab, size=(6,)),
                                 max_new_tokens=4)) for _ in range(3)]
    jax.profiler.start_trace(str(tmp_path))
    sched.run()
    jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.write_slot",
            "serve.first_token", "serve.decode_block", "serve.wait",
            "serve.evict"} <= set(by)
    admits = by["serve.admit"]
    assert sorted(a[4]["uid"] for a in admits) == sorted(uids)
    for a in admits:
        assert any(_inside(a, st) for st in by["serve.step"])
        assert a[4]["prompt_len"] == 6 and a[4]["queue_wait_us"] >= 0
        for kid in ("serve.prefill", "serve.write_slot", "serve.first_token"):
            k, = [k for k in by[kid] if _inside(k, a)]
            if kid == "serve.write_slot":
                assert k[4]["slot"] == a[4]["slot"]
    # the first token and each block's tokens are waited for
    whats = {w[4]["what"] for w in by["serve.wait"]}
    assert whats == {"first_token", "decode_block"}
    assert all(d[4]["n_steps"] == 3 for d in by["serve.decode_block"])
    assert sorted(e[4]["uid"] for e in by["serve.evict"]) == sorted(uids)
    # each request's spans share its uid
    for u in uids:
        a, = [a for a in admits if a[4]["uid"] == u]
        e, = [e for e in by["serve.evict"] if e[4]["uid"] == u]
        assert a[4]["slot"] == e[4]["slot"] and a[1] < e[1]


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def test_decode_block_holds_the_kv_cache_scope(dense):
    cfg, params = dense
    eng = ServeEngine(cfg, params, max_len=48, n_slots=2,
                      robust=RobustDecodeConfig(m=4, estimator="vrmom"))
    pool = eng.make_pool()
    fn = eng._decode_loop_fn(3, GREEDY, pool=True)
    lowered = fn.lower(eng.params, pool.caches, jnp.zeros((2,), jnp.int32),
                       jax.random.PRNGKey(0))
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit_serve_decode_block")
    kv = [n for n in _op_names(text) if "/decode.kv_cache/" in n]
    assert kv and all("serve.decode_scan/" in n for n in kv)
    # the relayout ahead of the kernel is a bitcast on the CPU; it keeps
    # the scope in the lowered program
    assert "decode.kv_cache/reshape" in lowered.as_text(debug_info=True)


def test_train_step_holds_grad_aggregate_and_optimizer_scopes(dense):
    from repro import optim as O
    from repro.launch.mesh import make_mesh
    from repro.train.step import make_train_step

    cfg, params = dense
    mesh = make_mesh((1, 1), ("data", "model"))
    setup = make_train_step(cfg, mesh, estimator="vrmom", mode="stacked-rrs")
    opt = jax.eval_shape(O.get(cfg.optimizer, lr=1e-3).init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    text = jax.jit(setup.step_fn).lower(params, opt, batch,
                                        jax.random.PRNGKey(0)).compile(
                                        ).as_text()
    assert text.startswith("HloModule jit_train_step")
    names = _op_names(text)
    for scope in ("train.grad", "rrs.aggregate", "train.optimizer"):
        assert any(f"jit(train_step)/{scope}/" in n for n in names), scope
    # backward ops keep the forward's path inside transpose(...)
    grad = [n for n in names if "/train.grad/" in n]
    assert any("transpose(" in n for n in grad)
    assert any("transpose(" not in n for n in grad)


def test_named_span_is_the_named_scope():
    def f(x):
        with named_span("decode.kv_cache"):
            return x * 2

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "decode.kv_cache/mul" in text
