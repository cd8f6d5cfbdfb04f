"""Slot-based KV cache pool for continuous batching (DESIGN.md §6).

The pool decouples cache capacity from the request batch: it holds
``n_slots`` cache rows (one per concurrently-decoding sequence), each
with its own fill level. Requests are admitted into free slots
mid-decode and retired slots are reused without touching the others.

Per-slot positions ride inside the model cache tree itself: every
``attention.KVCache.pos`` leaf is *vectorized* from a per-layer scalar
to a per-layer ``[n_slots]`` vector (``vectorize_pos``), which the
generalized ``attn_decode`` consumes row-wise. SSM caches are
positionless state and need no conversion.

Batch-dim discovery is structural, not name-based: the pool constructor
is probed with ``eval_shape`` at two slot counts and the dim that
changes is the slot dim (``slot_dims``). This keeps the pool agnostic to
cache layouts — the transformer K/V pool ``[L, B, T, Hkv*dh]`` (int8
scales ``[L, B, T]``), hybrid grouped ``[G, every, B, ...]``, whisper
cross ``[L, B, F, Hkv*dh]``, and the
replica-stacked trees of the robust path ``[m, L, B, ...]`` all work
through the same code.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..models import attention as A
from ..models import model as M

__all__ = [
    "SlotPool",
    "vectorize_pos",
    "slot_dims",
    "kv_bytes_per_slot",
    "init_pool",
    "write_slot",
    "evict_slot",
    "pool_specs",
]

_NO_SLOT_DIM = -1  # sentinel: leaf has no slot dim (replicated metadata)


class SlotPool(NamedTuple):
    """Cache pool: model caches + per-slot bookkeeping.

    caches:  model cache pytree with a slot dim per leaf (possibly
             replica-stacked by the robust path).
    lengths: [n_slots] int32 — tokens resident per slot (prompt + generated).
    active:  [n_slots] bool — slot currently owned by a live request.
    """

    caches: Any
    lengths: jnp.ndarray
    active: jnp.ndarray

    @property
    def n_slots(self) -> int:
        return self.lengths.shape[0]


def vectorize_pos(caches, n_slots: int):
    """Broadcast every KVCache.pos leaf to a trailing per-slot dim.

    [L]-shaped per-layer scalars become [L, n_slots]; the generalized
    ``attn_decode`` then advances each row independently.
    """
    def conv(c):
        if isinstance(c, A.KVCache):
            pos = jnp.broadcast_to(
                c.pos[..., None], c.pos.shape + (n_slots,)).astype(jnp.int32)
            return c._replace(pos=pos)
        return c

    return jax.tree.map(conv, caches,
                        is_leaf=lambda x: isinstance(x, A.KVCache))


def _pool_caches(cfg, n_slots: int, max_len: int, window="cfg"):
    return vectorize_pos(M.init_cache(cfg, n_slots, max_len, window=window),
                         n_slots)


def slot_dims(make, n_a: int = 2, n_b: int = 3):
    """Per-leaf slot-dim index for the cache tree built by ``make(n_slots)``.

    Probes ``make`` at two slot counts under ``eval_shape`` (no
    allocation) and returns, per leaf, the index of the dim whose size
    tracked the slot count, or ``_NO_SLOT_DIM`` for slot-free leaves
    (e.g. SSM layer-position metadata).
    """
    sa = jax.eval_shape(lambda: make(n_a))
    sb = jax.eval_shape(lambda: make(n_b))

    def one(x, y):
        diffs = [i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q]
        return diffs[0] if diffs else _NO_SLOT_DIM

    return jax.tree.map(one, sa, sb)


def kv_bytes_per_slot(make, n_slots: int) -> int:
    """HBM bytes one slot costs in the cache tree built by ``make``.

    Probed under ``eval_shape`` (no allocation): sum of leaf byte sizes
    — int8 quantization scales included, which is the point: the gauge
    reports the *stored* footprint, so ``kv_dtype`` shrinking the cache
    shows up directly. Replica-stacked robust trees count every
    replica's bytes (they all occupy HBM per slot).
    """
    tree = jax.eval_shape(lambda: make(n_slots))
    total = sum(int(x.size) * x.dtype.itemsize
                for x in jax.tree.leaves(tree))
    return total // n_slots


def init_pool(cfg, n_slots: int, max_len: int, window="cfg") -> SlotPool:
    """Empty pool: zeroed caches, zero lengths, all slots free."""
    return SlotPool(
        caches=_pool_caches(cfg, n_slots, max_len, window=window),
        lengths=jnp.zeros((n_slots,), jnp.int32),
        active=jnp.zeros((n_slots,), bool),
    )


def write_slot(pool: SlotPool, dims, req_caches, slot, length) -> SlotPool:
    """Admit one request: insert its (batch-1) cache row at ``slot``.

    ``dims`` is the ``slot_dims`` tree for ``pool.caches``;
    ``req_caches`` must match ``pool.caches`` structurally with slot-dim
    size 1 (vectorize + replica-stack first — the engine does this).
    """
    slot = jnp.asarray(slot, jnp.int32)

    def one(dst, d, src):
        if d == _NO_SLOT_DIM:
            return dst
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=d)

    caches = jax.tree.map(one, pool.caches, dims, req_caches)
    return SlotPool(
        caches=caches,
        lengths=pool.lengths.at[slot].set(jnp.asarray(length, jnp.int32)),
        active=pool.active.at[slot].set(True),
    )


def evict_slot(pool: SlotPool, slot) -> SlotPool:
    """Retire a slot. Cache contents stay (masked by per-slot lengths and
    overwritten on the next admit); only the bookkeeping is cleared."""
    slot = jnp.asarray(slot, jnp.int32)
    return SlotPool(
        caches=pool.caches,
        lengths=pool.lengths.at[slot].set(0),
        active=pool.active.at[slot].set(False),
    )


def pool_specs(cfg, pool: SlotPool, mesh, batch_axes):
    """PartitionSpec tree for a pool: caches via ``sharding.cache_specs``
    (slot dim plays the batch role), bookkeeping replicated."""
    from jax.sharding import PartitionSpec as P

    from ..dist import sharding as S

    cache_shapes = jax.eval_shape(lambda: pool.caches)
    cspecs = S.cache_specs(cfg, cache_shapes, mesh, batch_axes,
                           global_batch=pool.n_slots)
    return SlotPool(caches=cspecs, lengths=P(None), active=P(None))
