"""Prefill-then-decode serving engine (DESIGN.md §6).

Two entry styles over the same jitted step functions:

* fixed-batch ``generate`` — prefill a [B, S] prompt batch, then decode
  N tokens in ONE ``lax.scan`` dispatch (the per-step Python loop of the
  old example dispatched the jitted step N times from the host; the scan
  removes that per-token host round-trip and lets XLA pipeline the
  steps).
* slot-pool ``admit`` / ``decode_pool`` — the continuous-batching path:
  variable-length prompts prefill one request at a time into a free slot
  of a ``cache.SlotPool`` while the other slots keep decoding; the
  scheduler drives the admit/decode/retire cycle.

Sampling (greedy, temperature, top-k) is folded into the scanned loop so
sampled decode is a single dispatch too. With a ``RobustDecodeConfig``
every decode step runs replicated over ``m`` replicas and serves the
robustly aggregated logits (``serve.robust``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..models import attn_backend as AB
from ..models import model as M
from ..models.attention import KVCache
from ..obs.trace import named_span, trace_span
from . import cache as C
from . import robust as R

__all__ = ["Sampling", "GREEDY", "sample_tokens", "ServeEngine"]


class Sampling(NamedTuple):
    """Static sampling config (hashable — part of the jit cache key).

    method: 'greedy' | 'temperature' | 'top_k'
    """

    method: str = "greedy"
    temperature: float = 1.0
    top_k: int = 0


GREEDY = Sampling()


def sample_tokens(logits, key, sc: Sampling):
    """logits [..., V] -> sampled token ids [...] int32."""
    if sc.method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = logits.astype(jnp.float32) / max(sc.temperature, 1e-6)
    if sc.method == "top_k":
        if sc.top_k <= 0:
            raise ValueError("top_k sampling needs top_k > 0")
        kth = jax.lax.top_k(l, sc.top_k)[0][..., -1:]
        l = jnp.where(l < kth, -jnp.inf, l)
    elif sc.method != "temperature":
        raise ValueError(sc.method)
    return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)


class ServeEngine:
    """Holds (cfg, params, pool geometry) and a cache of jitted steps.

    max_len:  KV capacity per slot (prompt + generated must fit).
    n_slots:  pool capacity — concurrent sequences, decoupled from the
              number of queued requests.
    robust:   optional ``RobustDecodeConfig`` — decode replicated over
              ``robust.m`` replicas with robust logit aggregation.
    attn_backend: optional override of ``cfg.attn_backend`` (DESIGN.md
              §8) — carried on the config, so every jitted step
              (prefill, scanned decode, the replica-flat robust loop)
              inherits it and the fused decode-attention kernel runs
              inside the same scan as the fused aggregation kernel.
    obs:      optional ``obs.MetricsRegistry``. With a robust config the
              scanned decode loop additionally collects the per-token
              replica-disagreement rate as a fixed-edge histogram-counts
              aux (``obs.diag.ServeDiag`` — static shape, no host
              callbacks in the scan) drained into the registry's
              ``serve.replica_disagreement`` histogram after each
              dispatch. The diag flag joins the jit cache key, so the
              telemetry-free loop is a distinct compiled program whose
              tokens stay bit-identical to ``obs=None``.
    """

    def __init__(self, cfg, params, *, max_len: int, n_slots: int = 4,
                 window="cfg", robust: Optional[R.RobustDecodeConfig] = None,
                 attn_backend: Optional[str] = None,
                 kv_dtype: Optional[str] = None, obs=None):
        if attn_backend is not None:
            import dataclasses

            from ..models.attn_backend import BACKENDS

            if attn_backend not in BACKENDS:
                raise ValueError(f"unknown attn backend {attn_backend!r}; "
                                 f"known: {BACKENDS}")
            cfg = dataclasses.replace(cfg, attn_backend=attn_backend)
        if kv_dtype is not None:
            import dataclasses

            from ..models.attention import KV_DTYPES

            if kv_dtype not in KV_DTYPES:
                raise ValueError(f"unknown kv dtype {kv_dtype!r}; "
                                 f"known: {KV_DTYPES}")
            cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
        self.cfg = cfg
        self.params = params
        self.max_len = int(max_len)
        self.n_slots = int(n_slots)
        self.window = window
        self.robust = robust
        self.obs = obs
        # replicated emulation: replica state actually materialized
        # [m, ...] and every replica's forward executed. The default
        # (share_replica_compute) keeps plain-shaped state — one forward
        # feeds the whole logit stack (see RobustDecodeConfig).
        self._replicated = (robust is not None
                            and not robust.share_replica_compute)
        self._fns = {}
        self._dims = C.slot_dims(self._pool_caches)
        if obs is not None:
            # capacity gauge: KV bytes one slot costs (scales included,
            # and the m-fold replica stacking when the emulation
            # replicates state), from the abstract pool spec — no
            # allocation. Quantized KV shows up here as the
            # halved/quartered per-slot footprint.
            obs.gauge("serve.kv_bytes_per_slot",
                      float(C.kv_bytes_per_slot(self._pool_caches,
                                                self.n_slots)))
            # the decode path is fixed at trace time: 1 when decode
            # attention reads every K/V cache of the pool in place
            # (AB.decode_reads_pool), 0 when it copies the layer out or
            # the pool holds no K/V
            kv = [c for c in jax.tree.leaves(
                jax.eval_shape(lambda: self._pool_caches(1)),
                is_leaf=lambda x: isinstance(x, KVCache))
                if isinstance(c, KVCache)]
            obs.gauge("serve.decode_kv_inplace", float(bool(kv) and all(
                AB.decode_reads_pool(cfg, c.k.shape[-2]) for c in kv)))
        if self._replicated:
            # batch-dim indices of the UNSTACKED pool tree: the replica
            # dim the probe saw at axis 0 shifts every slot dim by one.
            self._pool_flat_dims = jax.tree.map(
                lambda d: d - 1 if d >= 0 else d, self._dims)
        self._prefill_dims_cache = {}

    # -- pool construction --------------------------------------------------

    def _pool_caches(self, n_slots: int):
        caches = C._pool_caches(self.cfg, n_slots, self.max_len,
                                window=self.window)
        if self._replicated:
            caches = R.stack_replicas(caches, self.robust.m)
        return caches

    def make_pool(self) -> C.SlotPool:
        pool = C.init_pool(self.cfg, self.n_slots, self.max_len,
                           window=self.window)
        if self._replicated:
            pool = pool._replace(
                caches=R.stack_replicas(pool.caches, self.robust.m))
        return pool

    # -- jitted step functions (cached per static signature) ----------------

    def _fn(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    def _prefill_fn(self):
        def serve_prefill(params, batch):
            logits, caches = M.prefill(params, self.cfg, batch,
                                       window=self.window,
                                       cache_len=self.max_len, last_only=True)
            return logits[:, -1], caches

        return self._fn("prefill", lambda: jax.jit(serve_prefill))

    def _prefill_dims(self, batch):
        """Per-leaf batch-dim indices of the prefill cache tree.

        Structural, like ``cache.slot_dims``: the prefill constructor is
        probed under ``eval_shape`` at two batch sizes (abstract — no
        compute) and the dim that tracks the batch is the batch dim.
        Keyed by the batch's field set (encdec extras change the tree).
        """
        key = tuple(sorted(batch))
        dims = self._prefill_dims_cache.get(key)
        if dims is None:
            def make(n):
                b = {k: jnp.zeros((n,) + v.shape[1:], v.dtype)
                     for k, v in batch.items()}
                return M.prefill(self.params, self.cfg, b, window=self.window,
                                 cache_len=self.max_len, last_only=True)[1]

            dims = self._prefill_dims_cache[key] = C.slot_dims(make)
        return dims

    def _decode_loop_fn(self, n_steps: int, sc: Sampling, pool: bool,
                        donate: bool = False):
        """Fused decode: one dispatch for ``n_steps`` steps of
        decode -> (attack/aggregate) -> sample, caches carried in-scan.

        Robust decode with ``share_replica_compute`` (default) runs ONE
        ``decode_step`` per scan step and broadcasts its logits into the
        [m, B, V] wire stack (honest replicas are bit-identical — see
        RobustDecodeConfig); the replicated emulation instead runs
        replica-FLAT (``robust.flatten_replicas``): the m replicas ride
        the batch dim through one ``decode_step`` call at batch m*B, and
        the [m*B, V] logits reshape to the wire stack. Either way the
        fused Estimator kernel aggregates the stack in-scan.
        The pool path passes (and receives) the replica-STACKED layout —
        admit/evict write [m, ...] rows — and the layout round-trip
        happens inside the jitted program so XLA fuses it with the
        first/last cache accesses instead of materializing eager
        transpose copies of the whole pool per block. The generate path
        passes pre-flattened caches (its conversion is once per call).
        """
        rcfg = self.robust
        flat_dims = (self._pool_flat_dims
                     if pool and self._replicated else None)
        # Telemetry variant: a distinct compiled program (diag joins the
        # cache key) whose scan additionally emits the per-token replica-
        # disagreement rates, folded post-scan into a static-shape
        # fixed-edge counts vector (obs.diag.ServeDiag). Tokens are
        # computed identically — the diag aux reads the logit stack and
        # never feeds back.
        diag = self.obs is not None and rcfg is not None
        # Greedy sampling with no simulated attack consumes no
        # randomness — skip the per-step threefry split (a measurable
        # slice of the step on a host-bound box). Token-identical: the
        # skipped keys were never read.
        stochastic = sc.method != "greedy" or (
            rcfg is not None and rcfg.attack != "none")

        def serve_decode_block(params, caches, tok, key, active=None):
            # active: optional [B] bool — pool-path slot liveness. Only
            # the diag aux reads it (inactive slots decode stale caches;
            # their disagreement rates are masked out of the histogram);
            # tokens and caches are computed identically either way.
            if flat_dims is not None:
                caches = R.flatten_replicas(caches, flat_dims, rcfg.m)

            def body(carry, _):
                tok, caches, key = carry
                if stochastic:
                    key, akey, skey = jax.random.split(key, 3)
                else:
                    akey = skey = key
                dis = None
                if rcfg is not None:
                    if rcfg.share_replica_compute:
                        # one forward feeds the whole wire stack — the
                        # replicas are bit-identical deterministic
                        # functions of the same carry (config docstring)
                        logits, caches = M.decode_step(params, self.cfg,
                                                       caches, tok,
                                                       window=self.window)
                        logits_r = jnp.broadcast_to(
                            logits, (rcfg.m,) + logits.shape)
                    else:
                        flat_tok = jnp.tile(tok, rcfg.m)  # replica-major
                        logits_f, caches = M.decode_step(params, self.cfg,
                                                         caches, flat_tok,
                                                         window=self.window)
                        logits_r = logits_f.reshape((rcfg.m, tok.shape[0])
                                                    + logits_f.shape[1:])
                    # the whole tail — attack, aggregate, sample — is one
                    # fused dispatch when rcfg.fuse_tail (DESIGN.md §12)
                    if diag:
                        nxt, dis = R.robust_sample(logits_r, rcfg, akey,
                                                   skey, sc, with_diag=True)
                    else:
                        nxt = R.robust_sample(logits_r, rcfg, akey, skey, sc)
                else:
                    logits, caches = M.decode_step(params, self.cfg, caches,
                                                   tok, window=self.window)
                    nxt = sample_tokens(logits, skey, sc)
                return (nxt, caches, key), (nxt, dis) if diag else nxt

            with named_span("serve.decode_scan"):
                (tok, caches, _), ys = jax.lax.scan(
                    body, (tok, caches, key), None, length=n_steps)
            if flat_dims is not None:
                caches = R.unflatten_replicas(caches, flat_dims, rcfg.m)
            if diag:
                from ..obs.catalog import FRACTION_EDGES
                from ..obs.diag import serve_diag

                toks, dis = ys  # dis: [n_steps, B] disagreement rates
                mask = None if active is None else active[None, :]
                return toks, caches, serve_diag(dis, FRACTION_EDGES,
                                                mask=mask)
            return ys, caches  # ys: toks [n_steps, B]

        # donate=True hands the caches buffer to XLA so the scan carry
        # reuses it in place instead of copying ~MB of KV at entry.
        # Only the generate() path asks for it — its caches are freshly
        # built per call and never touched again; pool/benchmark callers
        # re-feed the same caches across calls, which donation forbids.
        return self._fn(("loop", n_steps, sc, pool, diag, donate),
                        lambda: jax.jit(
                            serve_decode_block,
                            donate_argnums=(1,) if donate else ()))

    def _decode_step_fn(self, sc: Sampling):
        """Single-step dispatch — the Python-loop baseline the scan
        replaces (kept for benchmarks and debugging)."""
        rcfg = self.robust

        def serve_decode_step(params, caches, tok, key):
            akey, skey = jax.random.split(key)
            if rcfg is not None:
                logits, caches = R.robust_decode_step(
                    params, self.cfg, caches, tok, rcfg, akey,
                    window=self.window)
            else:
                logits, caches = M.decode_step(params, self.cfg, caches, tok,
                                               window=self.window)
            return sample_tokens(logits, skey, sc), caches

        return self._fn(("step", sc), lambda: jax.jit(serve_decode_step))

    def _drain_serve_diag(self, sd, n: int) -> None:
        """Fold a jit-side ``ServeDiag`` aux into the host registry:
        one device->host transfer of a fixed-size counts vector per
        dispatch (never per token)."""
        h = self.obs.histogram("serve.replica_disagreement")
        h.merge_counts([int(c) for c in sd.counts], float(sd.total), n)

    # -- fixed-batch generation ---------------------------------------------

    def prefill(self, batch):
        """-> (last-position logits [B, V], caches)."""
        return self._prefill_fn()(self.params, batch)

    def _check_capacity(self, prompt_len: int, n_tokens: int) -> None:
        # cache writes: prompt + one K/V per decode step (n_tokens - 1;
        # the first token samples off the prefill logits). Beyond
        # max_len the linear cache would silently clamp to its last
        # slot and corrupt attention.
        need = prompt_len + n_tokens - 1
        if need > self.max_len:
            raise ValueError(
                f"prompt {prompt_len} + {n_tokens} tokens needs {need} "
                f"cache slots > max_len {self.max_len}")

    def _first_token(self, logits, key, sc):
        """Sample token 0 from the prefill logits (jitted, cached).

        With a robust config the logits route through the same attack +
        aggregation as decode, so token 0 carries the robustness
        guarantee too: the prefill forward is deterministic, so
        row-stacking its logits is equivalent to re-running it on every
        replica.
        """
        rcfg = self.robust

        def serve_first_token(logits, key):
            if rcfg is not None:
                rep = jnp.broadcast_to(logits[None],
                                       (rcfg.m,) + logits.shape)
                return R.robust_sample(rep, rcfg, jax.random.fold_in(key, 1),
                                       jax.random.fold_in(key, 0), sc)
            return sample_tokens(logits, jax.random.fold_in(key, 0), sc)

        return self._fn(("first", sc),
                        lambda: jax.jit(serve_first_token))(logits, key)

    def _stack_flatten_fn(self, batch):
        """Jitted prefill-cache -> replica-flat conversion (cached per
        batch structure: the dims tree keys the compiled program)."""
        dims = self._prefill_dims(batch)
        leaves, treedef = jax.tree.flatten(dims)
        m = self.robust.m

        def serve_stack_flatten(caches):
            return R.flatten_replicas(R.stack_replicas(caches, m), dims, m)

        return self._fn(("stack-flatten", tuple(leaves), treedef),
                        lambda: jax.jit(serve_stack_flatten))

    def generate(self, batch, n_tokens: int, sampling: Sampling = GREEDY,
                 key=None):
        """Prefill + scanned decode. -> tokens [B, n_tokens] int32."""
        self._check_capacity(batch["tokens"].shape[1], n_tokens)
        key = jax.random.PRNGKey(0) if key is None else key
        logits, caches = self.prefill(batch)
        tok = self._first_token(logits, key, sampling)
        if n_tokens == 1:
            return tok[:, None]
        if self._replicated:
            caches = self._stack_flatten_fn(batch)(caches)
        out = self._decode_loop_fn(n_tokens - 1, sampling, pool=False,
                                   donate=True)(
            self.params, caches, tok, key)
        toks = out[0]
        if len(out) == 3:
            self._drain_serve_diag(out[2], (n_tokens - 1) * tok.shape[0])
        return jnp.concatenate([tok[:, None], toks.T], axis=1)

    def generate_python_loop(self, batch, n_tokens: int,
                             sampling: Sampling = GREEDY, key=None):
        """Same semantics as ``generate`` but one host dispatch per token
        (the pre-engine decode loop) — the benchmark baseline."""
        self._check_capacity(batch["tokens"].shape[1], n_tokens)
        key = jax.random.PRNGKey(0) if key is None else key
        logits, caches = self.prefill(batch)
        if self._replicated:
            caches = R.stack_replicas(caches, self.robust.m)
        tok = self._first_token(logits, key, sampling)
        step = self._decode_step_fn(sampling)
        out = [tok]
        for i in range(n_tokens - 1):
            tok, caches = step(self.params, caches, tok,
                               jax.random.fold_in(key, i + 1))
            out.append(tok)
        return jnp.stack(out, axis=1)

    # -- slot-pool path (continuous batching) -------------------------------

    def admit(self, pool: C.SlotPool, slot: int, batch,
              sampling: Sampling = GREEDY, key=None):
        """Prefill one request (batch dim 1) into ``slot``.

        Runs while the other slots hold live, partially-decoded
        sequences — their caches are untouched. Returns
        (pool, first sampled token as a python int).
        """
        n = batch["tokens"].shape[0]
        if n != 1:
            raise ValueError(f"admit() takes one request, got batch {n}")
        prompt_len = int(batch["tokens"].shape[1])
        if prompt_len >= self.max_len:
            raise ValueError(f"prompt ({prompt_len}) must leave decode room "
                             f"in max_len ({self.max_len})")
        key = jax.random.PRNGKey(int(slot)) if key is None else key
        with trace_span("serve.prefill", prompt_len=prompt_len):
            logits, caches = self.prefill(batch)
        caches = C.vectorize_pos(caches, 1)
        if self._replicated:
            caches = R.stack_replicas(caches, self.robust.m)
        with trace_span("serve.write_slot", slot=slot):
            pool = C.write_slot(pool, self._dims, caches, slot, prompt_len)
        with trace_span("serve.first_token"):
            tok = self._first_token(logits, key, sampling)
        with trace_span("serve.wait", what="first_token"):
            return pool, int(tok[0])

    def decode_pool(self, pool: C.SlotPool, cur_tok, n_steps: int,
                    sampling: Sampling = GREEDY, key=None):
        """Advance every slot ``n_steps`` tokens in one dispatch.

        cur_tok: [n_slots] int32 — each slot's last token (free slots
        carry a dummy; their output is discarded by the scheduler).
        Returns (pool, toks [n_steps, n_slots]).
        """
        key = jax.random.PRNGKey(0) if key is None else key
        # the pool rests replica-stacked (admit/evict write [m, ...]
        # rows); the jitted loop runs the block replica-flat and
        # restores the layout before returning.
        fn = self._decode_loop_fn(n_steps, sampling, pool=True)
        # the diag aux masks inactive slots (stale caches decode garbage
        # — their disagreement rates would dilute the live Byzantine
        # signal), so drain with the live sample count.
        diag = self.obs is not None and self.robust is not None
        args = (self.params, pool.caches, jnp.asarray(cur_tok, jnp.int32),
                key) + ((pool.active,) if diag else ())
        with trace_span("serve.decode_block", n_steps=n_steps):
            out = fn(*args)
        toks, caches = out[0], out[1]
        if len(out) == 3:
            with trace_span("serve.wait", what="active"):
                n_active = int(jax.device_get(pool.active).sum())
            self._drain_serve_diag(out[2], n_steps * n_active)
        lengths = jnp.where(pool.active, pool.lengths + n_steps, pool.lengths)
        return C.SlotPool(caches, lengths, pool.active), toks

    def evict(self, pool: C.SlotPool, slot: int) -> C.SlotPool:
        return C.evict_slot(pool, slot)
