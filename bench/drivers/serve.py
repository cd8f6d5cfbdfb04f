"""Serve driver: a closed loop of clients over ``Scheduler.step``.

Set-up makes the weights, builds ``ServeEngine`` and ``Scheduler``,
warms every prompt bucket's programs and the decode block, then runs
``ramp_blocks`` steps of the real clients so that the window opens on a
loaded, desynchronised pool. The window runs the loop for the given
seconds; each client submits its next request the moment its last one
completes. Times come from the host clock: a request's first token is
on the host when ``ServeEngine.admit`` returns, and a block's tokens
when ``Scheduler.step`` returns.

After the window the program's state is freed and the plain reference
(``bench/reference``) recomputes a sample of the finished requests.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import common, families, gen, weights
from ..reference import robust as rref

TRACE_SECONDS = 6.0   # a traced run records this much of its window
REF_CHUNK = 256       # positions per reference block


@dataclasses.dataclass
class Req:
    prompt: np.ndarray
    max_new: int
    client: int
    uid: int = -1
    t_submit: float = 0.0
    t_first: float = float("nan")
    t_done: float = float("nan")
    delivered: int = 0


def _shape(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _block_peak(engine, tracker, devs) -> int:
    """The decode block's own peak by the compiler's count
    (``common.program_peak``), for the call that ``decode_pool`` makes."""
    from repro.serve.engine import GREEDY

    caches, tok, n_steps, kw = tracker.block_args
    key = kw.get("key")
    key = jax.random.PRNGKey(0) if key is None else key
    fn = engine._decode_loop_fn(n_steps, kw.get("sampling", GREEDY),
                                pool=True)
    return common.program_peak(devs, fn, engine.params, caches, tok,
                               _shape(key))


class Tracker:
    """Wraps the engine's admit / decode_pool / evict on this instance to
    see which request sits in which slot, with no extra device sync."""

    def __init__(self, engine):
        self.pending = collections.deque()
        self.slot = {}
        self.spans = False
        self.work = None          # per-block counts while tracing
        self.block_args = None    # shapes of a decode block's call
        self._admit, self._decode = engine.admit, engine.decode_pool
        self._evict = engine.evict
        engine.admit, engine.decode_pool = self.admit, self.decode_pool
        engine.evict = self.evict

    def _span(self, name):
        if self.spans:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def admit(self, pool, slot, batch, **kw):
        with self._span("host.admit"):
            out = self._admit(pool, slot, batch, **kw)
        r = self.pending.popleft()
        if r.prompt.shape[0] != batch["tokens"].shape[1]:
            raise RuntimeError("bench: admission order is not FIFO")
        r.t_first, r.delivered = time.perf_counter(), 1
        self.slot[slot] = r
        if self.work is not None:
            self.work["prefill_lens"].append(int(r.prompt.shape[0]))
        return out

    def decode_pool(self, pool, cur_tok, n_steps, **kw):
        if self.block_args is None:
            self.block_args = (jax.tree.map(_shape, pool.caches),
                               _shape(jnp.asarray(cur_tok, jnp.int32)),
                               n_steps, kw)
        with self._span("host.decode_block"):
            out = self._decode(pool, cur_tok, n_steps, **kw)
        rows = []
        for r in self.slot.values():
            new = min(r.max_new, r.delivered + n_steps) - r.delivered
            # keys already written: the prompt and the tokens fed back
            rows.append((r.prompt.shape[0] + r.delivered - 1, new))
            r.delivered += new
        if self.work is not None:
            self.work["blocks"].append(rows)
        return out

    def evict(self, pool, slot):
        with self._span("host.evict"):
            out = self._evict(pool, slot)
        self.slot.pop(slot)
        return out


class Loop:
    """The clients, the scheduler and the tracker."""

    def __init__(self, sched, tracker, stream, clients: int):
        self.sched, self.tr = sched, tracker
        self.stream = stream
        self.next = 0
        self.live = {}
        self.done = []
        self.failed = 0
        for c in range(clients):
            self.submit(c)

    def submit(self, client: int):
        from repro.serve import Request

        prompt, max_new = self.stream[self.next % len(self.stream)]
        self.next += 1
        r = Req(prompt=prompt, max_new=max_new, client=client)
        r.t_submit = time.perf_counter()
        r.uid = self.sched.submit(Request(tokens=prompt,
                                          max_new_tokens=max_new))
        self.tr.pending.append(r)
        self.live[r.uid] = r

    def step(self) -> float:
        self.sched.step()
        t = time.perf_counter()
        for uid in [u for u in self.live if u in self.sched.completed]:
            r = self.live.pop(uid)
            c = self.sched.completed.pop(uid)
            r.t_done = t
            r.tokens = np.asarray(c.tokens, np.int32)
            if c.finished_by != "length" or len(c.tokens) != r.max_new \
                    or r.delivered != r.max_new:
                common.log(f"request {uid} ended {c.finished_by!r} with "
                           f"{len(c.tokens)} of {r.max_new} tokens")
                self.failed += 1
            else:
                self.done.append(r)
            self.submit(r.client)
        return t

    def delivered(self) -> int:
        return (sum(r.delivered for r in self.done)
                + sum(r.delivered for r in self.live.values()))


def _warm(engine, mix):
    """Compile every bucket's prefill and first-token programs, the slot
    write and evict, and the decode block, on a scheduler of its own."""
    from repro.serve import Request, Scheduler

    sched = Scheduler(engine, decode_block=mix["decode_block"])
    for L in mix["prompt"]["buckets"]:
        sched.submit(Request(tokens=np.zeros((L,), np.int32),
                             max_new_tokens=mix["decode_block"] + 1))
    sched.run()
    del sched
    gc.collect()


def _robust_config(mix):
    from repro.serve import RobustDecodeConfig

    r = mix["robust"]
    return RobustDecodeConfig(m=r["m"], estimator=r["estimator"], K=r["K"],
                              attack=r["attack"], alpha=r["alpha"])


def serve_window(conf, mix, seed, seconds, trace, devs, t_proc, trace_dir):
    """Set-up and window; -> (record, arch config, weights, requests
    finished in the window)."""
    from repro.models import model as M
    from repro.serve import ServeEngine, Scheduler

    t_start = time.perf_counter()
    cfg = common.arch_config(conf)
    params = weights.make(conf, seed)
    weights.check_layout(params, M.abstract_init(cfg))
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    engine = ServeEngine(cfg, params, max_len=mix["max_len"],
                         n_slots=mix["slots"], robust=_robust_config(mix))
    _warm(engine, mix)
    t_warm = time.perf_counter()
    tracker = Tracker(engine)
    # greedy sampling (the scheduler's default): the check compares
    # served tokens with the reference's best, which holds for greedy only
    sched = Scheduler(engine, decode_block=mix["decode_block"],
                      seed=seed % 2**31)
    stream = gen.serve_requests(mix, cfg.vocab, seed)
    loop = Loop(sched, tracker, stream, mix["clients"])
    for _ in range(mix["ramp_blocks"]):
        loop.step()
    jax.block_until_ready(sched.pool.caches)
    t_ramp = time.perf_counter()
    common.log(f"set-up phases: process start-up {t_start - t_proc:.3f} s, "
               f"weights {t_weights - t_start:.3f} s, engine and warm-up "
               f"{t_warm - t_weights:.3f} s, ramp {t_ramp - t_warm:.3f} s")

    counter = common.CompileCounter().install()
    tr_end = None

    def span(name):
        if trace and tr_end is None:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    d0, f0 = loop.delivered(), loop.failed
    t0 = time.perf_counter()
    setup_s = t0 - t_proc
    if trace:
        tracker.spans = True
        tracker.work = {"blocks": [], "prefill_lens": []}
        jax.profiler.start_trace(str(trace_dir))
        tr_start = time.perf_counter()
    t_end = t0 + seconds
    with counter:
        t = t0
        while t < t_end:
            with span("host.step"):
                t = loop.step()
            if trace and tr_end is None and t - t0 >= min(TRACE_SECONDS,
                                                          seconds):
                jax.profiler.stop_trace()
                tr_end = t
                tracker.spans = False
                work, tracker.work = tracker.work, None
    if trace and tr_end is None:
        jax.profiler.stop_trace()
        tr_end = t
        work = tracker.work
    elapsed = t - t0
    delivered = loop.delivered() - d0
    done = [r for r in loop.done if r.t_done <= t]
    ttft = [r.t_first - r.t_submit for r in done + list(loop.live.values())
            if t0 <= r.t_first <= t]
    tpot = [(r.t_done - r.t_first) / (r.max_new - 1) for r in done
            if t0 <= r.t_done <= t]
    rec = {
        "setup_s": setup_s, "elapsed_s": elapsed, "tokens": delivered,
        "ttft_s": ttft, "tpot_s": tpot, "compiles_in_window": counter.n,
        "attempted": sum(1 for r in done if t0 <= r.t_done)
        + len(loop.live) + loop.failed - f0,
        "completed": sum(1 for r in done if t0 <= r.t_done),
        "failed": loop.failed - f0,
    }
    if trace:
        rec["trace_window_s"] = tr_end - tr_start
        rec["work"] = work
    # the buffers' peak, or the decode block's own where the compiler
    # counts more (its temporaries)
    rec["memory_peak_bytes"] = max(common.memory_peak(devs),
                                   _block_peak(engine, tracker, devs))
    finished = [r for r in done if t0 <= r.t_done]
    del loop, sched, tracker, engine
    gc.collect()
    return rec, cfg, params, finished


# ------------------------------------------------------------ the check

def sample(finished, n: int, seed: int):
    """The longest finished request and n-1 more drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r.max_new + r.prompt.shape[0],
                                           -r.uid))
    rest = [r for r in finished if r is not longest]
    rng = gen.rng_for(seed, 3)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _pad_len(mix) -> int:
    n = mix["prompt"]["max"] + mix["output"]["max"] - 1
    return -(-n // REF_CHUNK) * REF_CHUNK


def make_logits_fn(conf, quant=None):
    ref = families.reference(conf)
    return jax.jit(lambda p, t: ref.logits(p, conf, t, quant))


def make_gap_fn(mix):
    """Robust aggregate of the attacked replica stack at each position;
    -> (gap of the given token below the aggregate's best, argmax)."""
    r = mix["robust"]
    n_bad = int(r["alpha"] * r["m"])

    @jax.jit
    def gaps(lg, tok):
        stack = rref.signflip_stack(lg, r["m"], n_bad)
        agg = rref.vrmom(stack, r["K"])
        best = jnp.max(agg, -1)
        got = jnp.take_along_axis(agg, tok[:, None], -1)[:, 0]
        return best - got, jnp.argmax(agg, -1).astype(jnp.int32)

    return gaps


def served_positions(r):
    """Reference input (prompt + served tokens but the last) and, for
    each served token, the position whose logits chose it."""
    seq = np.concatenate([r.prompt, r.tokens[:-1]])
    first = r.prompt.shape[0] - 1
    return seq, np.arange(first, first + r.tokens.shape[0])


def reference_gaps(params, conf, mix, reqs, quant=None, logits_fn=None):
    """Widest gap of each request's served tokens below the reference's
    robust best; with ``quant`` the control's own first choices are
    judged instead of the served tokens. -> (widest gap, positions)."""
    logits_fn = logits_fn or make_logits_fn(conf, None)
    gap_fn = make_gap_fn(mix)
    ctl_fn = make_logits_fn(conf, quant) if quant else None
    pad = _pad_len(mix)
    widest, n = 0.0, 0
    for r in reqs:
        seq, pos = served_positions(r)
        toks = np.zeros((pad,), np.int32)
        toks[:seq.shape[0]] = seq
        lg = logits_fn(params, jnp.asarray(toks))
        if ctl_fn is not None:
            clg = ctl_fn(params, jnp.asarray(toks))
            # the control's token at each position is its own argmax
            # (the robust aggregate of its sign-flipped stack is itself)
            chosen = np.asarray(jnp.argmax(clg, -1)[pos])
            del clg
        else:
            chosen = r.tokens
        for i in range(0, pos.shape[0], REF_CHUNK):
            p = pos[i:i + REF_CHUNK]
            t = np.zeros((REF_CHUNK,), np.int32)
            t[:p.shape[0]] = chosen[i:i + REF_CHUNK]
            idx = np.zeros((REF_CHUNK,), np.int32)
            idx[:p.shape[0]] = p
            g, _ = gap_fn(lg[jnp.asarray(idx)], jnp.asarray(t))
            widest = max(widest, float(np.max(np.asarray(g)[:p.shape[0]])))
        n += pos.shape[0]
        del lg
    return widest, n


def run(conf, mix, seed, seconds, trace, devs, t_proc, trace_dir):
    rec, cfg, params, finished = serve_window(conf, mix, seed, seconds, trace,
                                              devs, t_proc, trace_dir)
    reqs = sample(finished, mix["check"]["requests"], seed)
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        gap, n_pos = reference_gaps(params, conf, mix, reqs)
    common.log(f"reference: {len(reqs)} requests, {n_pos} served tokens "
               f"compared in {time.perf_counter() - t_ref:.1f} s")
    rec["checked_tokens"] = n_pos
    checks = [common.check("max_logit_gap", gap,
                           mix["check"]["max_logit_gap"])]
    if n_pos == 0:
        checks.append(common.check("served_tokens_compared", 0, -1))
    return rec, cfg, checks
