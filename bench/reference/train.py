"""Plain float32 reference of the Byzantine-robust train step.

Each of W workers takes its rows of the global batch and computes its
mean next-token loss and its gradient in float32 (the family's reference,
``bench/reference/<model_type>.py``).
The last ``n_bad`` workers' gradients are replaced by the attack's
draw; the coordinate-wise VRMOM of the W rows is the update direction,
and AdamW moves the weights, which stay in the configuration's dtype.

The W workers run one to a chip (``shard_map`` over a mesh axis named
``w``); the aggregate is taken leaf by leaf with the coordinates spread
over the chips, so no chip holds more than its share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import families

from . import robust as rref

F32 = jnp.float32


def _coord_spec(shape, n):
    """Spread a [W, ...] leaf's coordinates over n chips: the first
    trailing dim that n divides."""
    for i, d in enumerate(shape[1:]):
        if d % n == 0:
            return P(None, *([None] * i), "w")
    return P()


def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))), tree)


class Reference:
    """Steps of the plain reference on a mesh of W chips."""

    def __init__(self, conf, mix, devs, quant=None, faults=()):
        import numpy as np

        self.conf, self.mix, self.quant = conf, mix, quant
        self.ref = families.reference(conf)
        self.faults = set(faults)
        self.W = mix["data"]
        self.mesh = jax.sharding.Mesh(np.asarray(devs[:self.W]), ("w",))
        self.n_bad = mix["byzantine_workers"]
        self.opt = mix["optimizer"]

    def _worker_grads_fn(self, S):
        conf, quant, W = self.conf, self.quant, self.W
        ref = self.ref
        half = "half_batch" in self.faults

        def one(params, toks):
            p32 = jax.tree.map(lambda x: x.astype(F32), params)
            rows = toks[: max(1, toks.shape[0] // 2)] if half else toks

            def loss(p):
                tot = sum(ref.seq_loss_sum(p, conf, rows[i], quant)
                          for i in range(rows.shape[0]))
                return tot / (rows.shape[0] * (S - 1))

            l, g = jax.value_and_grad(loss)(p32)
            return l[None], jax.tree.map(lambda x: x[None], g)

        fn = jax.shard_map(one, mesh=self.mesh, in_specs=(P(), P("w")),
                           out_specs=(P("w"), P("w")), check_vma=False)
        return jax.jit(fn)

    def _aggregate_fn(self):
        W, n_bad, K = self.W, self.n_bad, self.mix["K"]
        std = self.mix["attack_std"]
        local = "no_exchange" in self.faults
        mesh = self.mesh

        def agg(g, key):
            spec = _coord_spec(g.shape, W)
            if n_bad:
                noise = std * jax.random.normal(key, g.shape, jnp.bfloat16)
                bad = (jnp.arange(W) >= W - n_bad).reshape(
                    (W,) + (1,) * (g.ndim - 1))
                g = jnp.where(bad, noise.astype(F32), g)
            if local:
                # worker 0 keeps its own row: nothing crosses chips
                return jax.lax.with_sharding_constraint(
                    g[0], NamedSharding(mesh, P(*spec[1:])))
            g = jax.lax.with_sharding_constraint(g, NamedSharding(mesh, spec))
            return rref.vrmom(g, K)

        return jax.jit(agg)

    def run(self, params, batches, keys):
        """-> (losses, first aggregate's leaf norms, change leaf norms)."""
        o = self.opt
        S = batches[0].shape[1]
        rep = NamedSharding(self.mesh, P())
        wsh = NamedSharding(self.mesh, P("w"))
        grads_fn = self._worker_grads_fn(S)
        agg_fn = self._aggregate_fn()
        # the moments are made at the first step, laid out like the
        # aggregate: coordinates spread over the chips
        m = v = None
        zeros = jax.jit(jnp.zeros_like)
        p0 = params
        losses, g_norms = [], None
        adam = jax.jit(functools.partial(rref.adam, lr=o["lr"], b1=o["b1"],
                                         b2=o["b2"], eps=o["eps"]),
                       static_argnames=("t",), out_shardings=(rep, None,
                                                              None))
        for i, (b, k) in enumerate(zip(batches, keys)):
            toks = jax.device_put(b, wsh)
            l, g = grads_fn(params, toks)
            losses.append(float(jnp.mean(l)))
            flat, tdef = jax.tree.flatten(g)
            agg = jax.tree.unflatten(tdef, [agg_fn(x, k) for x in flat])
            del g, flat
            if i == 0:
                g_norms = jax.tree.map(float, _leaf_norms(agg))
            if "frozen" in self.faults:
                del agg
                continue
            if m is None:
                m = jax.tree.map(zeros, agg)
                v = jax.tree.map(zeros, agg)
            out = jax.tree.map(lambda p, gg, mm, vv: adam(p, gg, mm, vv,
                                                          t=i + 1),
                               params, agg, m, v)
            params = jax.tree.map(lambda x: x[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
            m = jax.tree.map(lambda x: x[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
            v = jax.tree.map(lambda x: x[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
            del agg, out
        change = jax.tree.map(
            lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(
                a.astype(F32) - b.astype(F32))))), params, p0)
        return losses, g_norms, change
