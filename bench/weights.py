"""Weights from the seed, made on the device in one jitted call.

The tree has the program's parameter layout (layers stacked on a
leading axis), built here from the configuration's widths alone, so
that the reference reads weights that the program did not make.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import families


def key_for(seed: int, stream: int = 0):
    """A PRNG key for any seed >= 0 (more than 32 bits included)."""
    k = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, int(seed) >> 31), stream)


def _fan_in(path: str, shape) -> int:
    if path.endswith("wo"):
        return shape[1] * shape[2]
    return shape[1]


def make(conf: dict, seed: int, out_shardings=None):
    """All weights in the served dtype: matrices N(0, 1/fan_in), the
    embedding N(0, 0.02^2), norm scales 1."""
    dt = jnp.dtype(conf["torch_dtype"])
    tree = families.get(conf).shapes(conf)
    flat, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]

    def build(key):
        leaves = []
        for i, (name, (_, shape)) in enumerate(zip(names, flat)):
            if "norm" in name.rsplit("/", 1)[-1]:
                leaves.append(jnp.ones(shape, dt))
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            scale = 0.02 if name == "embed" else _fan_in(name, shape) ** -0.5
            leaves.append((x * scale).astype(dt))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=out_shardings)(key_for(seed))


def check_layout(params, program_shapes) -> None:
    """Fail where the program's parameter tree differs from this one."""
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                        program_shapes)
    if got != want:
        raise SystemExit(f"bench: the program's parameter layout changed:\n"
                         f"bench {got}\nprogram {want}")
