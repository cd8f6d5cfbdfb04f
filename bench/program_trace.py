"""From a profiler trace to what the program itself names: each device
op's scope path and the program's host spans with their arguments.

``trace_reduce`` finds kernels by their instruction's name and programs
by the kernels they hold. This module reads what the program wrote
into the trace as well. On a TPU, the name stack an op was staged under
(``jax.named_scope``, via ``repro.obs.trace.named_span``) is the stat
``SCOPE_STAT`` of the op's event metadata, beside its ``program_id``.
``jax.profiler.ProfileData`` gives event stats but not metadata stats,
so ``op_scopes`` reads those from the ``.xplane.pb`` (an ``XSpace``
protobuf) itself, and ``load`` finds each op's program by the module
run on the ``XLA Modules`` line that holds it. Each host span that the
program opens with ``trace_span`` carries its keyword arguments as
event stats. Metric readers take device time per scope (``scope_ns``)
and host time per span (``spans``, ``exclusive_ns``) from here.

``load`` reads the ``.xplane.pb`` into plain tuples; everything else
works on those alone, so a test can hand it a small synthetic trace.

``snapshot`` reads the newest profile under the benchmark's trace
directory once per file and logs, for the first chip, on standard
error: the device's idle time split by the innermost host span covering
it, the share of busy time under each scope, and the leaf ops that take
most time with the scope of each (for the train step, also those under
none of its three scopes). ``bench/run.py`` loads the metric readers
after the traced run and before it removes the profile, so a reader
calls ``snapshot`` as it is loaded.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import Counter
from typing import NamedTuple

from bench import common
from bench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                _HOLDERS, base_name, union)

# the metadata stat that holds a TPU op's name stack
SCOPE_STAT = "tf_op"
PROGRAM_STAT = "program_id"
# host spans: the program's own, then the benchmark driver's
PROGRAM_PREFIX = "serve."
BENCH_PREFIX = "host."
# the scopes whose share of busy time ``snapshot`` logs
SCOPES = ("train.grad", "rrs.aggregate", "rrs.all_to_all", "train.optimizer",
          "serve.decode_scan", "decode.kv_cache", "kernels.decode_attention",
          "kernels.aggregate", "kernels.aggregate_sample")
TRAIN_SCOPES = ("train.grad", "rrs.aggregate", "train.optimizer")


class ScopedOp(NamedTuple):
    name: str        # the HLO instruction's text
    start_ns: float
    dur_ns: float
    scope: str       # its name stack, "" where the trace gives none


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    line: int        # the host thread's line: spans nest on one line
    args: dict


class ProgramTrace(NamedTuple):
    chips: list      # list of ScopedOp per chip
    spans: list      # Span of the program and of the benchmark driver


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _message(buf) -> dict:
    """One protobuf message's fields: {number: [values]}, a varint as an
    int, any other field as the memoryview of its bytes."""
    out, i = {}, 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        else:
            if wire == 2:
                n, i = _varint(buf, i)
            elif wire in (1, 5):
                n = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            v, i = buf[i:i + n], i + n
        out.setdefault(key >> 3, []).append(v)
    return out


def _text(msg: dict, field: int) -> str:
    return bytes(msg.get(field, [b""])[0]).decode()


def _map_values(msg: dict, field: int) -> list:
    """The value messages of a map field (entries: key 1, value 2)."""
    return [_message(_message(e).get(2, [b""])[0]) for e in msg.get(field, [])]


def op_scopes(raw: bytes) -> dict:
    """{TPU plane name: {(program id, op name): name stack}} from the
    event metadata of each TPU plane of a serialized ``XSpace`` (XSpace
    1 planes; XPlane 2 name, 4 event_metadata, 5 stat_metadata;
    XEventMetadata 2 name, 5 stats; XStat 1 metadata_id, 3 uint64, 4
    int64, 5 str, 7 ref to a stat metadata's name)."""
    out = {}
    for plane in _message(memoryview(raw)).get(1, []):
        p = _message(plane)
        name = _text(p, 2)
        if not (name.startswith(DEVICE_PREFIX) and "TPU" in name):
            continue
        stat = {m.get(1, [0])[0]: _text(m, 2) for m in _map_values(p, 5)}
        ops = {}
        for em in _map_values(p, 4):
            scope = program = None
            for st in em.get(5, []):
                x = _message(st)
                key = stat.get(x.get(1, [0])[0])
                if key == SCOPE_STAT:
                    scope = (_text(x, 5) if 5 in x
                             else stat.get(x.get(7, [0])[0], ""))
                elif key == PROGRAM_STAT:
                    program = x.get(3, x.get(4, [None]))[0]
            if scope is not None:
                ops[(program, _text(em, 2))] = scope
        out[name] = ops
    return out


def _program_id(module_name: str):
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


def _chip_ops(ops_line, modules_line, scopes: dict) -> list:
    """ScopedOp per op event, its scope found by the program run that
    holds it; by name alone where no run holds it."""
    runs = sorted((float(m.start_ns), float(m.start_ns + m.duration_ns),
                   _program_id(m.name)) for m in modules_line.events)
    starts = [r[0] for r in runs]
    by_name = {}
    for (_, name), scope in scopes.items():
        by_name[name] = scope if by_name.get(name, scope) == scope else ""
    out = []
    for e in ops_line.events:
        t = float(e.start_ns)
        i = bisect.bisect_right(starts, t) - 1
        scope = None
        if i >= 0 and t <= runs[i][1]:
            scope = scopes.get((runs[i][2], e.name))
        if scope is None:
            scope = by_name.get(e.name, "")
        out.append(ScopedOp(e.name, t, float(e.duration_ns), scope))
    return out


def load(trace_dir: str) -> ProgramTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profile under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            ops = []
            if OPS_LINE in lines and MODULES_LINE in lines:
                ops = _chip_ops(lines[OPS_LINE], lines[MODULES_LINE],
                                scopes.get(plane.name, {}))
            chips.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith((PROGRAM_PREFIX, BENCH_PREFIX)):
                        spans.append(Span(e.name, float(e.start_ns),
                                          float(e.duration_ns), i,
                                          dict(e.stats)))
    chips.sort(key=lambda c: c[0])
    return ProgramTrace([ops for _, ops in chips], spans)


def in_scope(path: str, scope: str) -> bool:
    """Whether a name stack holds ``scope`` as one of its components
    (also inside a transform's brackets, ``transpose(jvp(<scope>))``)."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/):]|$)",
                     path) is not None


def _leaves(ops):
    return [o for o in ops if not _HOLDERS.search(o.name)]


def scope_ns(ops, scope: str) -> float:
    """Device time under ``scope``: the union of its leaf ops' intervals
    (ops that only hold other ops are left out)."""
    hit = {}
    for o in ops:
        if o.scope not in hit:
            hit[o.scope] = in_scope(o.scope, scope)
    return sum(e - s for s, e in union(
        (o.start_ns, o.start_ns + o.dur_ns) for o in _leaves(ops)
        if hit[o.scope]))


def scope_ms_per_step(trace, scope: str, steps) -> float | None:
    """Device time under ``scope`` per step, averaged over the chips; None
    where nothing ran under it."""
    chips = [ops for ops in trace.chips if ops] if trace else []
    if not chips or not steps:
        return None
    ns = sum(scope_ns(ops, scope) for ops in chips) / len(chips)
    return 1e-6 * ns / steps if ns > 0 else None


def spans(trace, name: str) -> list:
    return [s for s in trace.spans if s.name == name] if trace else []


def _inside(inner: Span, outer: Span) -> bool:
    return (inner.line == outer.line and inner.start_ns >= outer.start_ns
            and inner.start_ns + inner.dur_ns <= outer.start_ns
            + outer.dur_ns)


def exclusive_ns(trace, outer: str, inner: str) -> float:
    """Summed length of the ``outer`` spans less the part that ``inner``
    spans inside them cover."""
    tot = 0.0
    kids = spans(trace, inner)
    for o in spans(trace, outer):
        covered = union((k.start_ns, k.start_ns + k.dur_ns) for k in kids
                        if _inside(k, o))
        tot += o.dur_ns - sum(e - s for s, e in covered)
    return tot


def _innermost(host, s: float, e: float) -> str:
    """The shortest span that covers all of [s, e), or ``none``."""
    best = None
    for h in host:
        if h.start_ns <= s and h.start_ns + h.dur_ns >= e and (
                best is None or h.dur_ns < best.dur_ns):
            best = h
    return best.name if best else "none"


def _pieces(host) -> list:
    """The host timeline cut at every span's ends; -> sorted [start, end,
    name] pieces, each named by the innermost program span covering it,
    else by the benchmark's."""
    prog = [h for h in host if h.name.startswith(PROGRAM_PREFIX)]
    bench = [h for h in host if not h.name.startswith(PROGRAM_PREFIX)]
    cuts = sorted({t for h in host for t in (h.start_ns,
                                             h.start_ns + h.dur_ns)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        name = _innermost(prog, a, b)
        out.append([a, b, name if name != "none"
                    else _innermost(bench, a, b)])
    return out


def idle_by_span(ops, host) -> dict:
    """Idle time between the first and last device op, in ns, split by
    the innermost host span covering it (``_pieces``); ``none`` where no
    span does."""
    busy = union((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    pieces = _pieces(host)
    starts = [p[0] for p in pieces]
    out = Counter()
    for s, e in gaps:
        left = e - s
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(pieces) and pieces[i][0] < e:
            a, b, name = pieces[i]
            over = min(b, e) - max(a, s)
            if over > 0:
                out[name] += over
                left -= over
            i += 1
        if left > 0:
            out["none"] += left
    return dict(out)


def scope_table(ops) -> dict:
    """Share of busy time (%) under each of ``SCOPES`` found."""
    busy = sum(e - s for s, e in union((o.start_ns, o.start_ns + o.dur_ns)
                                       for o in ops))
    if busy <= 0:
        return {}
    return {sc: 100.0 * ns / busy for sc in SCOPES
            if (ns := scope_ns(ops, sc)) > 0}


def top_leaf_scopes(ops, top: int = 12, outside=()) -> list:
    """The leaf ops that took most time, by base name, each with the
    scope that holds most of its time; ops under ``outside`` skipped.
    -> [[base name, seconds, scope], ...]."""
    tot, where = Counter(), {}
    for o in _leaves(ops):
        if any(in_scope(o.scope, sc) for sc in outside):
            continue
        n = base_name(o.name)
        tot[n] += o.dur_ns
        where.setdefault(n, Counter())[o.scope] += o.dur_ns
    return [[n, t / 1e9, where[n].most_common(1)[0][0]]
            for n, t in tot.most_common(top)]


def _log_tables(trace: ProgramTrace) -> None:
    ops = next((c for c in trace.chips if c), [])
    if not ops:
        return
    idle = idle_by_span(ops, trace.spans)
    common.log("device idle by innermost host span, first chip (ms): "
               + ", ".join(f"{k} {v / 1e6:.3f}" for k, v in sorted(
                   idle.items(), key=lambda kv: -kv[1])))
    common.log("busy time under each scope, first chip (%): "
               + ", ".join(f"{k} {v:.2f}" for k, v in
                           scope_table(ops).items()))
    common.log("top leaf ops with their scope, first chip (s): "
               + "; ".join(f"{n} {t:.6f} [{sc[-160:]}]"
                           for n, t, sc in top_leaf_scopes(ops)))
    if any(scope_ns(ops, sc) > 0 for sc in TRAIN_SCOPES):
        common.log("outside " + ", ".join(TRAIN_SCOPES) + ", first chip "
                   "(s): " + "; ".join(
                       f"{n} {t:.6f}" for n, t, _ in top_leaf_scopes(
                           ops, outside=TRAIN_SCOPES)))


_SNAPSHOTS = {}


def snapshot(trace_dir=None):
    """The newest profile under ``trace_dir`` (the benchmark's trace
    directory by default), read once per file and its tables logged;
    None where there is none or it cannot be read."""
    trace_dir = str(trace_dir or common.TRACE_DIR)
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    if files[-1] not in _SNAPSHOTS:
        try:
            trace = load(trace_dir)
            _log_tables(trace)
        except Exception as e:  # noqa: BLE001 - the readings are optional
            common.log(f"program trace: not read ({type(e).__name__}: {e})")
            trace = None
        _SNAPSHOTS[files[-1]] = trace
    return _SNAPSHOTS[files[-1]]
