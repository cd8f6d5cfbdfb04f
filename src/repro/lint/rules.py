"""Layer-1 AST rules (RL001–RL006, DESIGN.md §10).

Each rule is a small class with an ``applies(relpath)`` path filter and
a ``check(tree, src, relpath)`` generator of :class:`Finding`s. Rules
are conservative by construction: they flag only patterns that are
unambiguous in the AST (a direct ``jnp.median`` call, a ``jnp.repeat``
of a K/V-named tensor, a bare traced parameter in an ``if`` test) and
leave the gray zone to the layer-2 trace auditor. The price is missed
transitive cases; the payoff is a tree that can be lint-clean with zero
unexplained suppressions.

Everything here is stdlib-only — the AST layer must run in an
environment without jax (pre-commit, docs CI).
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .catalog import info
from .findings import Finding

__all__ = ["Rule", "RULES", "rule_ids"]


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def _build_parents(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ('jax.jit', 'pl.BlockSpec')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def _is_jit(node: ast.AST) -> bool:
    """Does this expression denote jax.jit (or a partial application)?"""
    d = _dotted(node)
    if d in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call) and _dotted(node.func) in (
            "functools.partial", "partial"):
        return bool(node.args) and _is_jit(node.args[0])
    return False


def _static_names(call: Optional[ast.Call]) -> Tuple[Set[str], Set[int]]:
    """static_argnames / static_argnums constants of a jit(...) call."""
    names: Set[str] = set()
    nums: Set[int] = set()
    if call is None:
        return names, nums
    for kw in call.keywords:
        vals: List[ast.expr]
        if isinstance(kw.value, (ast.Tuple, ast.List)):
            vals = list(kw.value.elts)
        else:
            vals = [kw.value]
        if kw.arg == "static_argnames":
            names |= {v.value for v in vals
                      if isinstance(v, ast.Constant) and isinstance(v.value, str)}
        elif kw.arg == "static_argnums":
            nums |= {v.value for v in vals
                     if isinstance(v, ast.Constant) and isinstance(v.value, int)}
    return names, nums


class Rule:
    """Base: subclasses set ``id`` and implement ``check``."""

    id: str = ""

    @property
    def name(self) -> str:
        return info(self.id).name

    def applies(self, relpath: str) -> bool:
        return True

    def check(self, tree: ast.AST, src: str,
              relpath: str) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, relpath: str, line: int, message: str) -> Finding:
        return Finding(rule_id=self.id, path=relpath, line=line,
                       message=message)


# ---------------------------------------------------------------------------
# RL001 — robust aggregation must route through core/estimator
# ---------------------------------------------------------------------------

class DirectAggregationRule(Rule):
    """DESIGN §7: the Estimator layer is the single dispatch site. A
    call site computing ``jnp.median`` over a worker/replica stack, or
    reaching into ``core.aggregators`` directly, silently bypasses
    backend dispatch, trace-time validation (trimmed_mean beta, the
    coordinatewise gate) and the fused kernel."""

    id = "RL001"

    # The estimator layer itself plus its numerical oracles.
    ALLOW = (
        "core/estimator.py",
        "core/aggregators.py",
        "core/adaptive.py",
        "core/vrmom.py",
        "core/__init__.py",
        "kernels/ref.py",
        "kernels/vrmom.py",
    )
    _AGG_FNS = ("median", "nanmedian", "quantile", "nanquantile",
                "percentile", "nanpercentile")
    _AGG_MODULE_ALIASES = ("aggregators", "_A", "_agg", "AGG")

    def applies(self, relpath: str) -> bool:
        return not relpath.endswith(self.ALLOW)

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                mod, _, attr = d.rpartition(".")
                if attr in self._AGG_FNS and mod in ("jnp", "jax.numpy"):
                    yield self.finding(
                        relpath, node.lineno,
                        f"direct `{d}` call bypasses the Estimator "
                        f"dispatch layer (core/estimator, DESIGN §7); "
                        f"use Estimator(method=...).apply(x, axis)")
                elif mod in self._AGG_MODULE_ALIASES:
                    yield self.finding(
                        relpath, node.lineno,
                        f"direct `{d}` call bypasses the Estimator "
                        f"dispatch layer; aggregator functions must "
                        f"not be called outside core/estimator")
            elif isinstance(node, ast.ImportFrom):
                module = node.module or ""
                if module.endswith("aggregators"):
                    yield self.finding(
                        relpath, node.lineno,
                        "importing from core.aggregators outside the "
                        "estimator layer — route through "
                        "core.estimator.Estimator instead")
                elif any(a.name == "aggregators" for a in node.names):
                    yield self.finding(
                        relpath, node.lineno,
                        "importing core.aggregators outside the "
                        "estimator layer — route through "
                        "core.estimator.Estimator instead")


# ---------------------------------------------------------------------------
# RL002 — no jnp.repeat of K/V head dims in models/ and kernels/
# ---------------------------------------------------------------------------

class KVRepeatRule(Rule):
    """DESIGN §8: GQA is computed grouped; repeating K/V to the query
    head count multiplies cache read traffic by H/Hkv. Name-based on the
    repeated tensor (k/v/cache.k/...) so SSM state-group expansion in
    mamba2 (different invariant, no KV cache) is not dragged in."""

    id = "RL002"

    _KV_NAMES = frozenset((
        "k", "v", "ck", "cv", "kf", "vf", "kk", "vv", "k2", "v2",
        "key", "value", "keys", "values", "k_cache", "v_cache",
    ))

    def applies(self, relpath: str) -> bool:
        return "models/" in relpath or "kernels/" in relpath

    def _kv_name(self, node: ast.expr) -> Optional[str]:
        if isinstance(node, ast.Name) and node.id.lower() in self._KV_NAMES:
            return node.id
        if isinstance(node, ast.Attribute) and \
                node.attr.lower() in self._KV_NAMES:
            return _dotted(node)
        return None

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if d not in ("jnp.repeat", "jax.numpy.repeat"):
                continue
            if not node.args:
                continue
            name = self._kv_name(node.args[0])
            if name is not None:
                yield self.finding(
                    relpath, node.lineno,
                    f"`jnp.repeat({name}, ...)` materializes K/V at the "
                    f"query-head count — GQA must stay grouped "
                    f"(kernels/decode_attention discipline, DESIGN §8)")


# ---------------------------------------------------------------------------
# RL003 — no Python branching / casts on traced jit parameters
# ---------------------------------------------------------------------------

class TraceUnsafePythonRule(Rule):
    """A Python ``if``/``while`` on a traced value raises
    TracerBoolConversionError at best and bakes a stale branch into the
    jaxpr at worst; ``int()``/``float()`` force a device sync or fail.
    Conservative scope: only functions that are *directly* jitted
    (decorated with jax.jit / functools.partial(jax.jit, ...) or passed
    by name to a jax.jit(...) call in the same file), only bare uses of
    their non-static parameters. ``.shape``/``.ndim``/``.dtype``/
    ``.size`` reads and ``is None`` tests are static and exempt."""

    id = "RL003"

    _STATIC_ATTRS = frozenset(("shape", "ndim", "dtype", "size", "aval",
                               "sharding"))
    _CASTS = frozenset(("int", "float", "bool"))

    # -- collect jitted functions ------------------------------------------

    def _jitted_functions(self, tree) -> List[Tuple[ast.FunctionDef,
                                                    Set[str]]]:
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        out: List[Tuple[ast.FunctionDef, Set[str]]] = []

        def traced_params(fn, static_names, static_nums):
            params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
            traced = set()
            for i, p in enumerate(params):
                if p in static_names or i in static_nums or p == "self":
                    continue
                traced.add(p)
            return traced

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jit(dec):
                        call = dec if isinstance(dec, ast.Call) else None
                        names, nums = _static_names(call)
                        out.append((node, traced_params(node, names, nums)))
            elif isinstance(node, ast.Call) and _is_jit(node.func) \
                    and node.args and isinstance(node.args[0], ast.Name):
                names, nums = _static_names(node)
                for fn in defs.get(node.args[0].id, ()):
                    out.append((fn, traced_params(fn, names, nums)))
        return out

    # -- offending-name detection ------------------------------------------

    def _offending(self, expr: ast.expr, traced: Set[str]) -> Optional[str]:
        """First traced parameter referenced outside a static-attr read."""

        def walk(node) -> Optional[str]:
            if isinstance(node, ast.Attribute):
                if node.attr in self._STATIC_ATTRS:
                    return None  # x.shape[...] etc. — static under jit
                return walk(node.value)
            if isinstance(node, ast.Name):
                return node.id if node.id in traced else None
            if isinstance(node, ast.Call):
                # len(x.shape) fine; isinstance(x, T) fine
                if _dotted(node.func) in ("len", "isinstance", "getattr",
                                          "hasattr", "type"):
                    return None
                hit = walk(node.func)
                if hit:
                    return hit
                for a in node.args:
                    hit = walk(a)
                    if hit:
                        return hit
                for kw in node.keywords:
                    hit = walk(kw.value)
                    if hit:
                        return hit
                return None
            if isinstance(node, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                    return None  # `x is None` — identity, not value
            for child in ast.iter_child_nodes(node):
                hit = walk(child)
                if hit:
                    return hit
            return None

        return walk(expr)

    def check(self, tree, src, relpath):
        seen: Set[Tuple[int, str]] = set()
        for fn, traced in self._jitted_functions(tree):
            if not traced:
                continue
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)):
                    hit = self._offending(node.test, traced)
                    if hit and (node.lineno, hit) not in seen:
                        seen.add((node.lineno, hit))
                        kind = ("while" if isinstance(node, ast.While)
                                else "if")
                        yield self.finding(
                            relpath, node.lineno,
                            f"Python `{kind}` on `{hit}`, a traced "
                            f"parameter of jitted `{fn.name}` — use "
                            f"lax.cond/jnp.where or make it static")
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id in self._CASTS:
                    for a in node.args:
                        hit = self._offending(a, traced)
                        if hit and (node.lineno, hit) not in seen:
                            seen.add((node.lineno, hit))
                            yield self.finding(
                                relpath, node.lineno,
                                f"`{node.func.id}()` cast of `{hit}`, a "
                                f"traced parameter of jitted "
                                f"`{fn.name}` — forces a host sync / "
                                f"fails under jit")


# ---------------------------------------------------------------------------
# RL004 — config-like statics must be hashable
# ---------------------------------------------------------------------------

class UnhashableStaticRule(Rule):
    """Specs used as jit static arguments key the trace cache by
    hash/eq. An unfrozen dataclass is unhashable (TypeError at the jit
    boundary); a hashable spec with a list/dict field hashes by content
    that can mutate — both are retrace hazards. Name-scoped to
    config-like classes so host-side mutable records (scheduler
    bookkeeping, cost tables) stay legal."""

    id = "RL004"

    _CONFIG_NAME = re.compile(r"(Config|Spec|Specs|Estimator|Sampling|Setup)$")
    _MUTABLE_TYPES = frozenset((
        "list", "dict", "set", "List", "Dict", "Set", "MutableMapping",
        "bytearray", "ndarray", "Array",
    ))

    def _dataclass_dec(self, cls: ast.ClassDef) -> Optional[ast.expr]:
        for dec in cls.decorator_list:
            d = _dotted(dec.func if isinstance(dec, ast.Call) else dec)
            if d in ("dataclass", "dataclasses.dataclass"):
                return dec
        return None

    def _is_frozen(self, dec: ast.expr) -> bool:
        if not isinstance(dec, ast.Call):
            return False
        return any(kw.arg == "frozen" and
                   isinstance(kw.value, ast.Constant) and kw.value.value is True
                   for kw in dec.keywords)

    def _is_namedtuple(self, cls: ast.ClassDef) -> bool:
        return any(_dotted(b) in ("NamedTuple", "typing.NamedTuple")
                   for b in cls.bases)

    def _mutable_ann(self, ann: ast.expr) -> Optional[str]:
        for node in ast.walk(ann):
            if isinstance(node, ast.Name) and node.id in self._MUTABLE_TYPES:
                return node.id
            if isinstance(node, ast.Attribute) and \
                    node.attr in self._MUTABLE_TYPES:
                return node.attr
        return None

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not self._CONFIG_NAME.search(node.name):
                continue
            dec = self._dataclass_dec(node)
            hashable_spec = self._is_namedtuple(node) or (
                dec is not None and self._is_frozen(dec))
            if dec is not None and not self._is_frozen(dec):
                yield self.finding(
                    relpath, node.lineno,
                    f"config-like dataclass `{node.name}` is not "
                    f"frozen=True: unhashable, so it cannot key a jit "
                    f"trace cache (retrace hazard, DESIGN §7)")
            if hashable_spec or dec is not None:
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign):
                        bad = self._mutable_ann(stmt.annotation)
                        if bad:
                            field = (stmt.target.id
                                     if isinstance(stmt.target, ast.Name)
                                     else "<field>")
                            yield self.finding(
                                relpath, stmt.lineno,
                                f"`{node.name}.{field}` is typed "
                                f"`{bad}` — unhashable field in a "
                                f"static spec (retrace hazard); use a "
                                f"tuple / frozen type")


# ---------------------------------------------------------------------------
# RL005 — Pallas BlockSpec index maps must be pure
# ---------------------------------------------------------------------------

class IndexMapPurityRule(Rule):
    """An index map runs at grid-scheduling time: anything beyond
    arithmetic on the grid indices (calls, attribute reads, subscripts
    into captured state) is either miscompiled or a hidden host
    dependency. Pure = names, constants, arithmetic, tuples, and reads
    of the map's own scalar-prefetch arguments (``layer[0]`` where
    ``layer`` is a parameter: the SMEM operand Pallas hands the map)."""

    id = "RL005"

    _IMPURE = (ast.Call, ast.Attribute, ast.Subscript, ast.Await,
               ast.NamedExpr, ast.ListComp, ast.SetComp, ast.DictComp,
               ast.GeneratorExp)

    def applies(self, relpath: str) -> bool:
        return True  # cheap: only fires on files that call BlockSpec

    def _index_map(self, call: ast.Call) -> Optional[ast.expr]:
        for kw in call.keywords:
            if kw.arg == "index_map":
                return kw.value
        if len(call.args) >= 2:
            return call.args[1]
        return None

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d.endswith("BlockSpec"):
                continue
            imap = self._index_map(node)
            if not isinstance(imap, ast.Lambda):
                continue
            params = {a.arg for a in imap.args.args}
            for sub in ast.walk(imap.body):
                if (isinstance(sub, ast.Subscript)
                        and isinstance(sub.value, ast.Name)
                        and sub.value.id in params):
                    continue  # scalar-prefetch read; its index is walked
                if isinstance(sub, self._IMPURE):
                    yield self.finding(
                        relpath, imap.lineno,
                        f"BlockSpec index map contains "
                        f"{type(sub).__name__} — index maps must be "
                        f"pure arithmetic over the grid indices")
                    break


# ---------------------------------------------------------------------------
# RL006 — padded tile loads need an in-kernel validity mask
# ---------------------------------------------------------------------------

class UnmaskedPaddedLoadRule(Rule):
    """If the wrapper pads operands to tile boundaries (jnp.pad before
    pl.pallas_call), the kernel sees fabricated rows/keys; the flash /
    decode-attention discipline (DESIGN §8) is that validity is masked
    *in-kernel* (jnp.where over a broadcasted_iota position, or an
    explicitly inert pad value). A kernel with padded inputs and no
    masking construct is flagged. The mask may live in a same-module
    helper the kernel calls (kernel families sharing an epilogue, e.g.
    ``vrmom._agg_block``) — the scan follows direct calls to
    module-level functions."""

    id = "RL006"

    def _kernel_name(self, arg: ast.expr) -> Optional[str]:
        if isinstance(arg, ast.Name):
            return arg.id
        if isinstance(arg, ast.Call) and _dotted(arg.func) in (
                "functools.partial", "partial") and arg.args and \
                isinstance(arg.args[0], ast.Name):
            return arg.args[0].id
        return None

    def _has_mask(self, fn: ast.AST, defs=None, seen=None) -> bool:
        seen = set() if seen is None else seen
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d.endswith(".where") or d.endswith("broadcasted_iota") \
                        or d == "where":
                    return True
                # masking via a shared same-module helper counts: follow
                # plain-name calls to module-level defs (one pass each)
                if defs and d in defs and d not in seen:
                    seen.add(d)
                    if self._has_mask(defs[d], defs, seen):
                        return True
        return False

    def check(self, tree, src, relpath):
        defs: Dict[str, ast.FunctionDef] = {}
        parents = None
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                defs[node.name] = node
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and
                    _dotted(node.func).endswith("pallas_call")):
                continue
            # kernel fn: first arg of pallas_call (maybe partial-wrapped),
            # or a local name bound to such a partial just above.
            kname = self._kernel_name(node.args[0]) if node.args else None
            if parents is None:
                parents = _build_parents(tree)
            enclosing = node
            while enclosing in parents and not isinstance(
                    enclosing, (ast.FunctionDef, ast.AsyncFunctionDef)):
                enclosing = parents[enclosing]
            if not isinstance(enclosing, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                continue
            if kname is not None and kname not in defs:
                # kernel may be a local alias: kernel = partial(_k, ...)
                for stmt in ast.walk(enclosing):
                    if isinstance(stmt, ast.Assign) and \
                            len(stmt.targets) == 1 and \
                            isinstance(stmt.targets[0], ast.Name) and \
                            stmt.targets[0].id == kname:
                        inner = self._kernel_name(stmt.value)
                        if inner:
                            kname = inner
                        break
            kernel = defs.get(kname) if kname else None
            pads = any(isinstance(n, ast.Call) and
                       _dotted(n.func).endswith(".pad")
                       for n in ast.walk(enclosing))
            if not pads or kernel is None:
                continue
            if not self._has_mask(kernel, defs):
                yield self.finding(
                    relpath, node.lineno,
                    f"pallas_call kernel `{kernel.name}` receives "
                    f"padded operands (jnp.pad in `{enclosing.name}`) "
                    f"but contains no validity mask "
                    f"(jnp.where/broadcasted_iota) — padded lanes leak "
                    f"into the result (DESIGN §8 mask discipline)")


# ---------------------------------------------------------------------------
# RL007 — wall-clock reads route through the obs layer
# ---------------------------------------------------------------------------

class WallClockOutsideObsRule(Rule):
    """DESIGN §11: ``obs.metrics.now()`` is the library's single
    wall-clock site. A stray ``time.time()``/``perf_counter()`` in
    library code is either dead telemetry (not drained into any
    registry/sink) or — worse — a host sync hiding inside a jit-adjacent
    path that no profiler span will attribute. Scoped to ``src/repro/``
    (scripts, benchmarks and tests time things however they like);
    the obs layer itself is the one allowed caller."""

    id = "RL007"

    _CLOCK_FNS = frozenset(("time", "perf_counter", "monotonic",
                            "process_time", "perf_counter_ns",
                            "monotonic_ns", "time_ns"))

    def applies(self, relpath: str) -> bool:
        return ("src/repro/" in relpath or relpath.startswith("repro/")) \
            and "/obs/" not in relpath

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                mod, _, attr = d.rpartition(".")
                if mod == "time" and attr in self._CLOCK_FNS:
                    yield self.finding(
                        relpath, node.lineno,
                        f"direct `{d}()` call outside the obs layer — "
                        f"library code reads the wall clock through "
                        f"repro.obs.metrics.now() so every timing "
                        f"lands in the metrics registry (DESIGN §11)")
            elif isinstance(node, ast.ImportFrom):
                if (node.module or "") == "time":
                    bad = [a.name for a in node.names
                           if a.name in self._CLOCK_FNS]
                    if bad:
                        yield self.finding(
                            relpath, node.lineno,
                            f"importing {', '.join(bad)} from time "
                            f"outside the obs layer — use "
                            f"repro.obs.metrics.now() (DESIGN §11)")


RULES: Sequence[Rule] = (
    DirectAggregationRule(),
    KVRepeatRule(),
    TraceUnsafePythonRule(),
    UnhashableStaticRule(),
    IndexMapPurityRule(),
    UnmaskedPaddedLoadRule(),
    WallClockOutsideObsRule(),
)


def rule_ids() -> Tuple[str, ...]:
    return tuple(r.id for r in RULES)
