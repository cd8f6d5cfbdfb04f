"""PartitionSpec rules for params, batches, caches and optimizer state.

All rules are *divisibility-aware*: an axis is only placed on a dim when
the axis size divides it and the dim is at least twice the axis size
(so degenerate placements like sharding a 4-wide conv-tap dim across 4
FSDP shards are skipped). A rule that does not fit degrades to
replication, never to an error — the same config must lower on the
2x16x16 production mesh and a 4x2 host test mesh.

Naming conventions (DESIGN.md §3): ``model`` is the tensor-parallel
axis, ``data`` the FSDP/batch axis, ``pod`` an optional outer batch
axis; (``pod``, ``data``) together form the *worker axes* of the robust
aggregation.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "param_specs",
    "batch_axes_for",
    "batch_specs",
    "cache_specs",
    "stacked_grad_specs",
    "opt_state_specs",
    "to_named",
]

_WORKER_AXIS_ORDER = ("pod", "data")


def _axis(mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.axis_names else 1


def _fits(dim: int, ax: int) -> bool:
    """Is placing an axis of size ``ax`` on a dim of size ``dim`` sane?"""
    return ax > 1 and dim % ax == 0 and dim >= 2 * ax


def _key_str(k) -> str:
    return str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", ""))))


def param_specs(shapes, mesh):
    """Tree of PartitionSpecs for a params tree of ShapeDtypeStructs.

    Placement rules (model = TP axis, data = FSDP axis):

    * embed ``[V, D]`` — model on the vocab dim when divisible, else
      moved to ``D``, else dropped (whisper's 51865 vocab does not
      divide a 16-way model axis); data on whichever of the two dims
      remains divisible.
    * attention ``wq/wk/wv [L, D, H, dh]`` — model on the *head* dim
      only when the head count divides it; odd head counts (36, 24) are
      REPLICATED, never moved to head_dim — sharding ``dh`` splits every
      score contraction and forces a per-layer all-reduce of the
      attention scores. data on ``D``.
    * ``wo [L, H, dh, D]`` — model on heads, data on ``D``.
    * MLP / MoE ``w_gate/w_up`` — model on the ``d_ff`` (last) dim,
      ``w_down`` — model on ``d_ff`` (second-to-last); data on the
      ``d_model`` dim. Expert and layer-stack dims stay replicated
      ("tensor-parallel experts", DESIGN.md §4).
    * generic 2D+ fallback — model on the last dim, data on the
      second-to-last, each only when it fits.
    """
    tp = _axis(mesh, "model")
    dp = _axis(mesh, "data")

    def spec_for(path, leaf):
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd <= 1:
            return P(*([None] * nd))
        name = _key_str(path[-1]) if path else ""

        if name in ("embed", "lm_head"):
            # [V, D] or [D, V]; prefer model on the vocab dim.
            vdim = 0 if name == "embed" else 1
            entries = [None, None]
            if _fits(shape[vdim], tp):
                entries[vdim] = "model"
            elif _fits(shape[1 - vdim], tp):
                entries[1 - vdim] = "model"
            other = entries.index(None) if None in entries else None
            if other is not None and _fits(shape[other], dp):
                entries[other] = "data"
            return P(*entries)

        if name in ("wq", "wk", "wv", "wo") and nd in (3, 4):
            # stacked [L, D, H, dh] / [L, H, dh, D]; unstacked drops L.
            off = nd - 3
            h_dim = off + (0 if name == "wo" else 1)
            d_dim = off + (2 if name == "wo" else 0)
            entries = [None] * nd
            if _fits(shape[h_dim], tp):
                entries[h_dim] = "model"
            if _fits(shape[d_dim], dp):
                entries[d_dim] = "data"
            return P(*entries)

        if name in ("w_gate", "w_up", "w_down"):
            # [..., D, F] (gate/up) or [..., F, D] (down): model on F.
            f_dim = nd - 1 if name != "w_down" else nd - 2
            d_dim = nd - 2 if name != "w_down" else nd - 1
            entries = [None] * nd
            if _fits(shape[f_dim], tp):
                entries[f_dim] = "model"
            if _fits(shape[d_dim], dp):
                entries[d_dim] = "data"
            return P(*entries)

        if name == "router":
            # [..., D, E]: experts rarely divide the model axis; FSDP on D.
            entries = [None] * nd
            if _fits(shape[-1], tp):
                entries[-1] = "model"
            if _fits(shape[-2], dp):
                entries[-2] = "data"
            return P(*entries)

        # generic: model on last dim, data on second-to-last.
        entries = [None] * nd
        if _fits(shape[-1], tp):
            entries[-1] = "model"
        if _fits(shape[-2], dp):
            entries[-2] = "data"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec_for, shapes)


def batch_axes_for(mesh, global_batch: int):
    """Mesh axes to shard the batch dim over, or None when nothing fits.

    Tries the full worker-axis tuple first, then progressively drops
    outer axes: (pod, data) -> (data,) -> None.
    """
    names = [a for a in _WORKER_AXIS_ORDER if a in mesh.axis_names]
    for i in range(len(names)):
        axes = tuple(names[i:])
        total = 1
        for a in axes:
            total *= int(mesh.shape[a])
        if total > 0 and global_batch % total == 0:
            return axes
    return None


def batch_specs(specs, batch_axes):
    """P-tree for a batch tree: dim 0 on ``batch_axes``, rest replicated."""
    def one(leaf):
        nd = len(leaf.shape)
        if batch_axes is None or nd == 0:
            return P(*([None] * nd))
        return P(batch_axes, *([None] * (nd - 1)))

    return jax.tree.map(one, specs)


def cache_specs(cfg, cache_shapes, mesh, batch_axes, global_batch=None):
    """P-tree for decode caches: batch dim on ``batch_axes``, the widest
    post-batch dim on ``model`` when it fits, layer-stack dims replicated.

    The batch dim is located by size (``global_batch``); without it the
    cache is conservatively left batch-replicated.
    """
    tp = _axis(mesh, "model")

    def one(leaf):
        shape = tuple(leaf.shape)
        nd = len(shape)
        entries = [None] * nd
        b_dim = None
        if global_batch is not None and batch_axes is not None and nd >= 2:
            # Batch sits after the layer-stack dims: dim 1 for plain
            # stacked caches [L, B, ...] (the K/V pool [L, B, T,
            # Hkv*dh], whose widest tail dim is T or the folded heads),
            # dim 2 for hybrid group stacks [G, every, B, ...].
            # Size-matching cannot fully
            # disambiguate (a stack dim may equal the batch size);
            # preference order 1 > 2 > 0 resolves the common layouts,
            # and a wrong pick still yields a valid (divisible) if
            # suboptimal layout.
            cands = [i for i, d in enumerate(shape) if d == global_batch]
            for pref in (1, 2, 0):
                if pref in cands:
                    b_dim = pref
                    break
            if b_dim is None and cands:
                b_dim = cands[0]
            if b_dim is not None:
                entries[b_dim] = batch_axes
        if b_dim is not None and nd > b_dim + 1:
            tail = range(b_dim + 1, nd)
            cand = max(tail, key=lambda i: shape[i])
            if _fits(shape[cand], tp):
                entries[cand] = "model"
        return P(*entries)

    return jax.tree.map(one, cache_shapes)


def stacked_grad_specs(params_specs, worker_axes, mesh, shapes=None):
    """Specs for per-worker stacked grads ``[n_workers, *param_shape]``.

    Dim 0 goes on the worker axes; the param spec shifts right by one
    with any mention of a worker axis removed (a mesh axis cannot
    appear twice in one spec — FSDP placement on ``data`` is subsumed
    by the worker-stacking dim). ``shapes`` is accepted so callers can
    pass the matching param shapes for future divisibility re-checks.
    """
    wa = tuple(worker_axes)

    def one(spec):
        cleaned = []
        for e in spec:
            if e is None:
                cleaned.append(None)
            elif isinstance(e, tuple):
                kept = tuple(a for a in e if a not in wa)
                cleaned.append(kept if kept else None)
            else:
                cleaned.append(None if e in wa else e)
        return P(wa if wa else None, *cleaned)

    return jax.tree.map(one, params_specs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(opt_state_shapes, params, params_specs):
    """Specs for optimizer state mirroring the params tree.

    Handles: 'm'/'v' trees shaped like params; adafactor's nested
    {'vr','vc'} / {'v'} dicts (vr = spec[:-1], vc = spec minus dim -2).
    """
    flat_params, ptree = jax.tree.flatten(params)
    flat_specs = ptree.flatten_up_to(params_specs)
    shape2spec = {}
    for p, s in zip(flat_params, flat_specs):
        shape2spec.setdefault(tuple(p.shape), s)

    def leaf_spec(path, leaf):
        names = [_key_str(k) for k in path]
        shp = tuple(leaf.shape)
        if shp in shape2spec:
            return shape2spec[shp]
        name = names[-1] if names else ""
        # factored adafactor leaves: find the parent param by prefix match
        if name in ("vr", "vc"):
            for pshape, s in shape2spec.items():
                entries = list(s) + [None] * (len(pshape) - len(s))
                if name == "vr" and pshape[:-1] == shp:
                    return P(*entries[:-1])
                if name == "vc" and pshape[:-2] + pshape[-1:] == shp:
                    return P(*entries[:-2], entries[-1])
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(leaf_spec, opt_state_shapes)


def to_named(mesh, specs):
    """P-tree -> NamedSharding-tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
