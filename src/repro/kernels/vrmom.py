"""Fused robust-aggregation kernel family as Pallas TPU kernels.

The paper's only compute hot-spot is the aggregation itself (Remark 1:
O(m+n) vs O(m log m)); on TPU the aggregation of an m-way stack of
gradient chunks or replica logits is purely memory-bound, so the
kernel's job is to do the whole estimate in ONE pass over the [m, C]
stack held in VMEM — a single HBM read of the stack and a single [C]
write, instead of the >= 4 passes (median, abs-dev, median, correction)
a composition of jnp ops would take.

One kernel, four methods (DESIGN.md §7): ``median``/``mom``, ``vrmom``,
``trimmed_mean`` and ``mean`` all share the entry point. The sorted rows
are already resident in VMEM for the median, so the trimmed mean (a
static slice-and-average of the same sorted block) is essentially free,
and the mean skips the network entirely but reuses the tiling.

TPU adaptation choices (DESIGN.md §6/§7):

* The worker axis m is small and static (replica count or the data/pod
  mesh axes), so order statistics are computed with an **odd-even
  transposition sorting network** over the sublane axis: each phase
  compare-exchanges all row pairs at once against sublane rotations of
  the block — no gathers (Pallas TPU has no general gather), no strided
  slices, no data-dependent control flow, VPU-friendly.
* The order statistics then sit at *static* row indices of the sorted
  block; sums over rows fold in a fixed row order.
* Quantile counts use Sum_k 1(z <= Delta_k) with Delta_k baked in as
  compile-time constants (K static), accumulated k-at-a-time to keep the
  VMEM footprint at one [m, C_tile] block.

Grid: 1-D over coordinate tiles; block [m, C_TILE] in VMEM, output a
lane-dense [1, C_TILE] row. Batched inputs ([m, B, V] logit stacks from
the replicated decode path) are handled by the entry-point reshape:
every estimator is coordinate-wise, so trailing dims flatten into the
coordinate axis — the serve decode ``lax.scan`` calls the same kernel
the gradient path uses. The fused sampling tail reads the stack as
[B, m, V] so each batch row's worker slab is one [m, C_TILE] block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vrmom import _MAD_CONST, _deltas_cached, psi_sum

DEFAULT_TILE = 512        # compiled TPU path: [m, 512] block in VMEM
INTERPRET_TILE = 65536    # interpret mode: amortize per-grid-step
                          # interpreter overhead (host memory, no VMEM cap)

_NEG_INF = -1e30      # sampling mask for padded vocab columns
_BIG_IDX = 2 ** 30    # index sentinel for argmax/top-k tie-break

__all__ = [
    "aggregate_pallas",
    "aggregate_sample_pallas",
    "vrmom_pallas",
    "mom_pallas",
    "trimmed_mean_pallas",
    "mean_pallas",
]


def _sort_rows(x):
    """Odd-even transposition sort of ``x [m, C]`` along axis 0 (ascending).

    Each phase compare-exchanges every (even, odd) or (odd, even) row
    pair at once: the partner rows are sublane rotations of the whole
    block (``pltpu.roll``), and iota masks pick, per row, whether it
    keeps the min or the max of its pair. No strided slices and no
    interleave, so Mosaic lowers every op as a dense vector op. The
    rotation direction is read off a rotated row iota instead of being
    assumed. The phases run as a loop of (even, odd) pairs, so the
    program stays O(1) in m; m or m+1 phases sort m rows exactly.
    """
    m = x.shape[0]
    if m == 1:
        return x
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    src = pltpu.roll(row, 1, 0)        # source row of roll(x, 1)[i]
    a_is_next = src == row + 1
    a_is_prev = src == row - 1
    has_next, has_prev = row + 1 < m, row >= 1
    even = row % 2 == 0

    def phase(x, lo, hi):
        # lo rows pair with row i+1 and keep the min; hi rows pair with
        # row i-1 and keep the max; the rest pass through
        a, b = pltpu.roll(x, 1, 0), pltpu.roll(x, m - 1, 0)
        nxt = jnp.where(a_is_next, a, b)
        prv = jnp.where(a_is_prev, a, b)
        return jnp.where(lo, jnp.minimum(x, nxt),
                         jnp.where(hi, jnp.maximum(x, prv), x))

    def two_phases(_, x):
        x = phase(x, even & has_next, ~even & has_prev)
        return phase(x, ~even & has_next, even & has_prev)

    return jax.lax.fori_loop(0, (m + 1) // 2, two_phases, x)


def _sum_rows(x, lo, hi):
    """Sum of rows lo..hi-1 of ``x [m, C]`` -> [1, C], folded in row order
    (a fixed order, so both kernels produce the same bits)."""
    acc = x[lo : lo + 1]
    for i in range(lo + 1, hi):
        acc = acc + x[i : i + 1]
    return acc


def _agg_block(x, *, method, K, k_trim, eps):
    """Aggregate one VMEM-resident block over axis 0: [m, C] -> [1, C].

    Shared by the plain aggregation kernel and the fused sampling-tail
    kernel — both run the exact same op sequence, so fused greedy tokens
    are bit-identical to argmax over the unfused aggregate.
    """
    m = x.shape[0]
    if method == "mean":
        return _sum_rows(x, 0, m) / m
    xs = _sort_rows(x)
    if method == "trimmed_mean":
        # rows k_trim..m-k_trim-1 of the already-sorted block: the trim
        # is a static slice, so the trimmed mean costs one extra sum.
        return _sum_rows(xs, k_trim, m - k_trim) / (m - 2 * k_trim)
    med = 0.5 * (xs[(m - 1) // 2 : (m - 1) // 2 + 1] + xs[m // 2 : m // 2 + 1])
    if method == "median":
        return med
    # vrmom: MAD scale + quantile-count correction, same VMEM block
    devs = _sort_rows(jnp.abs(x - med))
    mad = 0.5 * (devs[(m - 1) // 2 : (m - 1) // 2 + 1]
                 + devs[m // 2 : m // 2 + 1])
    s = mad / _MAD_CONST
    z = (x - med) / jnp.maximum(s, eps)
    deltas = _deltas_cached(K)
    counts = jnp.zeros_like(z)
    for k in range(K):
        counts = counts + (z <= jnp.float32(deltas[k])).astype(jnp.float32)
    # integer-valued summands: the sum is exact in any order
    total = jnp.sum(counts - K / 2.0, axis=0, keepdims=True)
    out = med - s * total / (m * psi_sum(K))
    return jnp.where(s <= eps, med, out)


def _kernel(x_ref, o_ref, *, method, K, k_trim, eps):
    x = x_ref[...].astype(jnp.float32)  # [m, tile]
    out = _agg_block(x, method=method, K=K, k_trim=k_trim, eps=eps)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("method", "K", "k_trim", "tile", "interpret", "eps"),
)
def _agg_2d(x, method: str, K: int, k_trim: int, tile: int, interpret: bool,
            eps: float):
    m, c = x.shape
    tile = min(tile, -(-max(c, 1) // 128) * 128)  # lane-aligned, <= C
    c_pad = -(-c // tile) * tile
    if c_pad != c:
        x = jnp.pad(x, ((0, 0), (0, c_pad - c)), constant_values=1.0)
    # lane-dense [1, C] output: a 2-D block meets the (8, 128) tiling rule
    # (leading dim equal to the array's) where a 1-D block would have to
    # match XLA's 1024-element tiling
    out = pl.pallas_call(
        functools.partial(_kernel, method=method, K=K, k_trim=k_trim,
                          eps=eps),
        grid=(c_pad // tile,),
        in_specs=[pl.BlockSpec((m, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, c_pad), x.dtype),
        interpret=interpret,
    )(x)
    return out[0, :c]


def _topk_rows(cands, k):
    """Row-wise top-k over the union of ``(values, indices)`` candidate
    lists, each pair ``[B, n_j]``.

    Descending by value, ties broken toward the smaller index — the same
    order ``jax.lax.top_k`` produces — via k static max-extraction
    passes (no sort, no gather). Extracted entries are placed into their
    output lane with a select, so nothing is concatenated along lanes.
    Returns ([B, k] f32, [B, k] int32)."""
    vals0 = cands[0][0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (vals0.shape[0], k), 1)
    tv = jnp.full(lane.shape, _NEG_INF, jnp.float32)
    ti = jnp.zeros(lane.shape, jnp.int32)
    for j in range(k):
        mx = functools.reduce(jnp.maximum, [
            jnp.max(v, axis=1, keepdims=True) for v, _ in cands])
        sel = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(v == mx, i, _BIG_IDX), axis=1, keepdims=True)
            for v, i in cands])
        tv = jnp.where(lane == j, mx, tv)
        ti = jnp.where(lane == j, sel, ti)
        cands = [(jnp.where(i == sel, _NEG_INF, v), i) for v, i in cands]
    return tv, ti


def _tail_kernel(x_ref, *refs, method, K, k_trim, eps, tile, v_total, n_vt,
                 top_k, with_agg):
    """Aggregation + sampling epilogue on one [B, m, tile] block.

    The aggregate is computed once per vocab tile; the sampling tail
    (running argmax for greedy, running top-k otherwise) reuses the same
    VMEM-resident result, carrying its state across vocab tiles in
    scratch and writing token ids on the last tile."""
    refs = list(refs)
    agg_ref = refs.pop(0) if with_agg else None
    if top_k == 0:
        tok_ref, agg_scr, bv_scr, bi_scr = refs
    else:
        topv_ref, topi_ref, agg_scr, bv_scr, bi_scr = refs
    vi = pl.program_id(0)
    # each batch row's [m, tile] worker slab is a leading-dim slice
    for b in range(x_ref.shape[0]):
        agg_scr[b : b + 1] = _agg_block(x_ref[b].astype(jnp.float32),
                                        method=method, K=K, k_trim=k_trim,
                                        eps=eps)
    agg = agg_scr[...]  # [B, tile]
    if with_agg:
        agg_ref[...] = agg.astype(agg_ref.dtype)
    # mask the padded tail of the vocab axis so it can never win the
    # argmax/top-k (the pad value is a live logit magnitude, not -inf)
    pos = vi * tile + jax.lax.broadcasted_iota(jnp.int32, agg.shape, 1)
    a = jnp.where(pos < v_total, agg, _NEG_INF)

    @pl.when(vi == 0)
    def _init():
        bv_scr[...] = jnp.full(bv_scr.shape, _NEG_INF, jnp.float32)
        bi_scr[...] = jnp.zeros(bi_scr.shape, jnp.int32)

    if top_k == 0:
        tile_max = jnp.max(a, axis=1, keepdims=True)  # [B, 1]
        tile_idx = jnp.min(jnp.where(a == tile_max, pos, _BIG_IDX),
                           axis=1, keepdims=True)
        # strict >: an equal max in a later tile never displaces the
        # earlier index, matching jnp.argmax first-occurrence ties
        better = tile_max > bv_scr[...]
        bi_scr[...] = jnp.where(better, tile_idx, bi_scr[...])
        bv_scr[...] = jnp.where(better, tile_max, bv_scr[...])

        @pl.when(vi == n_vt - 1)
        def _write_tok():
            tok_ref[...] = bi_scr[...]
    else:
        mv, mi = _topk_rows([(bv_scr[...], bi_scr[...]), (a, pos)], top_k)
        bv_scr[...] = mv
        bi_scr[...] = mi

        @pl.when(vi == n_vt - 1)
        def _write_topk():
            topv_ref[...] = bv_scr[...]
            topi_ref[...] = bi_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("method", "K", "k_trim", "tile", "interpret", "eps",
                     "top_k", "with_agg"),
)
def _tail_3d(x, method: str, K: int, k_trim: int, tile: int, interpret: bool,
             eps: float, top_k: int, with_agg: bool):
    m, b, v = x.shape
    tile = max(min(tile, max(v, 1)), max(top_k, 1))
    v_pad = -(-v // tile) * tile
    n_vt = v_pad // tile
    # [B, m, V]: each batch row's worker stack is one [m, tile] block slab
    # (the transpose fuses into whatever produced the stack)
    x = jnp.swapaxes(x, 0, 1)
    if v_pad != v:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, v_pad - v)), constant_values=1.0)
    out_shape, out_specs = [], []
    if with_agg:
        out_shape.append(jax.ShapeDtypeStruct((b, v_pad), x.dtype))
        out_specs.append(pl.BlockSpec((b, tile), lambda i: (0, i)))
    scratch = [pltpu.VMEM((b, tile), jnp.float32)]
    if top_k == 0:
        # [B, 1] token column: a whole-array 2-D block (a 1-D [B] block
        # would have to match XLA's 1-D tiling)
        out_shape.append(jax.ShapeDtypeStruct((b, 1), jnp.int32))
        out_specs.append(pl.BlockSpec((b, 1), lambda i: (0, 0)))
        scratch += [pltpu.VMEM((b, 1), jnp.float32),
                    pltpu.VMEM((b, 1), jnp.int32)]
    else:
        out_shape.append(jax.ShapeDtypeStruct((b, top_k), jnp.float32))
        out_specs.append(pl.BlockSpec((b, top_k), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, top_k), jnp.int32))
        out_specs.append(pl.BlockSpec((b, top_k), lambda i: (0, 0)))
        scratch += [pltpu.VMEM((b, top_k), jnp.float32),
                    pltpu.VMEM((b, top_k), jnp.int32)]
    outs = pl.pallas_call(
        functools.partial(_tail_kernel, method=method, K=K, k_trim=k_trim,
                          eps=eps, tile=tile, v_total=v, n_vt=n_vt,
                          top_k=top_k, with_agg=with_agg),
        grid=(n_vt,),
        in_specs=[pl.BlockSpec((b, m, tile), lambda i: (0, 0, i))],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x)
    outs = list(outs)
    agg = outs.pop(0)[:, :v] if with_agg else None
    if top_k == 0:
        return agg, outs[0][:, 0]
    return agg, outs[0], outs[1]


def _default_interpret():
    return jax.default_backend() != "tpu"


def aggregate_pallas(x, method: str = "vrmom", K: int = 10, beta: float = 0.1,
                     tile=None, interpret=None, eps: float = 1e-12):
    """Fused aggregation over axis 0: ``[m, ...] -> [...]``.

    ``method``: median/mom | vrmom | trimmed_mean | mean. Trailing dims
    are coordinates — ``[m, B, V]`` logit stacks and ``[m, C]`` gradient
    chunks take the same path. ``tile=None`` picks per mode: a
    VMEM-sized block when compiled, a wide block when interpreted (the
    per-grid-step interpreter overhead dominates otherwise —
    ``BENCH_agg.json``). Dispatch policy lives in
    ``core.estimator.Estimator``; this is the execution entry point.
    """
    method, k_trim, tile, interpret = _resolve_call(
        method, beta, x.shape[0], tile, interpret)
    shape = x.shape[1:]
    x2 = x.reshape(x.shape[0], -1)
    from ..obs.trace import named_span

    with named_span("kernels.aggregate"):
        out = _agg_2d(x2, method=method, K=K, k_trim=k_trim, tile=tile,
                      interpret=interpret, eps=eps)
    return out.reshape(shape)


def _resolve_call(method, beta, m, tile, interpret):
    if interpret is None:
        interpret = _default_interpret()
    if tile is None:
        tile = INTERPRET_TILE if interpret else DEFAULT_TILE
    method = "median" if method == "mom" else method
    if method not in ("median", "vrmom", "trimmed_mean", "mean"):
        raise ValueError(f"no fused kernel for method {method!r}")
    k_trim = 0
    if method == "trimmed_mean":
        k_trim = int(beta * m)
        if k_trim == 0 or m - 2 * k_trim < 1:
            raise ValueError(
                f"trimmed_mean kernel: beta={beta} at m={m} trims "
                f"{k_trim} rows per end — spec must be validated "
                f"(Estimator.validate) before dispatch")
    return method, k_trim, tile, bool(interpret)


def aggregate_sample_pallas(x, method: str = "vrmom", K: int = 10,
                            beta: float = 0.1, top_k: int = 0, tile=None,
                            interpret=None, eps: float = 1e-12,
                            with_agg: bool = True):
    """Fused aggregation + sampling tail over a ``[m, B, V]`` logit stack.

    One Pallas dispatch does what the unfused robust-decode tail did in
    two (aggregate kernel, then a jnp argmax/top-k pass over the [B, V]
    aggregate written back to HBM): the sampling epilogue runs on the
    aggregate while it is still VMEM-resident.

    Returns ``(agg, tok)`` for ``top_k == 0`` — greedy, ``tok[b]``
    bit-identical to ``jnp.argmax(agg[b])`` — or ``(agg, topv, topi)``
    for ``top_k > 0`` with the ``jax.lax.top_k`` value/index order, so a
    categorical draw over ``topv`` reproduces the masked-vocab top-k
    sampling distribution. ``with_agg=False`` skips the [B, V] aggregate
    write entirely (greedy serve steps with diagnostics off) and returns
    ``agg=None``.
    """
    if x.ndim != 3:
        raise ValueError(f"fused tail wants [m, B, V] stacks, got {x.shape}")
    if not 0 <= top_k <= x.shape[-1]:
        raise ValueError(f"top_k={top_k} out of range for V={x.shape[-1]}")
    method, k_trim, tile, interpret = _resolve_call(
        method, beta, x.shape[0], tile, interpret)
    from ..obs.trace import named_span

    with named_span("kernels.aggregate_sample"):
        return _tail_3d(x, method=method, K=K, k_trim=k_trim, tile=tile,
                        interpret=interpret, eps=eps, top_k=int(top_k),
                        with_agg=bool(with_agg))


def vrmom_pallas(x, K: int = 10, tile=None, interpret=None,
                 eps: float = 1e-12):
    """Fused VRMOM over axis 0. x: [m, ...] -> [...]. MAD scale."""
    return aggregate_pallas(x, "vrmom", K=K, tile=tile, interpret=interpret,
                            eps=eps)


def mom_pallas(x, tile=None, interpret=None):
    """Fused coordinate-wise median over axis 0."""
    return aggregate_pallas(x, "median", tile=tile, interpret=interpret)


def trimmed_mean_pallas(x, beta: float = 0.1, tile=None, interpret=None):
    """Fused coordinate-wise beta-trimmed mean over axis 0."""
    return aggregate_pallas(x, "trimmed_mean", beta=beta, tile=tile,
                            interpret=interpret)


def mean_pallas(x, tile=None, interpret=None):
    """Coordinate-wise mean over axis 0 (shares the kernel tiling)."""
    return aggregate_pallas(x, "mean", tile=tile, interpret=interpret)
