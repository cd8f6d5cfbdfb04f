"""From a profiler trace to device busy time, kernel and program device
time, and the longest idle gaps, each gap named by what the host was
doing.

On a TPU the profiler names each device op by its HLO instruction
(``%_decode_grouped.4 = bf16[...] custom-call(...)``) and each program
run by its module (``jit_run(<fingerprint>)``); the ops carry no named
scope. So a kernel is found by its instruction's name, which Pallas
takes from the kernel's function, and a program by the kernels that run
inside it.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain tuples; ``reduce`` works on those alone, so a test can hand it a
small synthetic trace.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple


class Op(NamedTuple):
    name: str        # the HLO instruction's text, or a module's name
    start_ns: float
    dur_ns: float


class Chip(NamedTuple):
    ops: list        # Op on the chip's op line
    modules: list    # Op on its module line: one per program run


class Trace(NamedTuple):
    chips: list      # Chip per chip
    host: list       # (name, start_ns, dur_ns) host spans of the bench


DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops that only hold other ops (their time is their body's)
_HOLDERS = re.compile(r"\s(while|conditional|call)\(")


def base_name(op_name: str) -> str:
    """``%_decode_grouped.4 = ...`` -> ``_decode_grouped``."""
    head = op_name.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profile under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and "TPU" in plane.name:
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        Op(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            chips.append((plane.name, Chip(lines[OPS_LINE],
                                           lines[MODULES_LINE])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("host."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    chips.sort()
    return Trace([ops for _, ops in chips], host)


def union(intervals):
    """Merge [start, end) intervals; -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops) -> float:
    return sum(e - s for s, e in union((o.start_ns, o.start_ns + o.dur_ns)
                                       for o in ops))


def kernel_ns(ops, kernel: str) -> float:
    """Device time of the ops of one kernel (by instruction base name)."""
    return sum(o.dur_ns for o in ops if base_name(o.name) == kernel)


def program_ns(chip: Chip, kernel: str) -> float:
    """Device time of the program runs inside which ``kernel`` ran."""
    starts = sorted(o.start_ns for o in chip.ops
                    if base_name(o.name) == kernel)
    tot = 0.0
    for m in chip.modules:
        i = bisect.bisect_left(starts, m.start_ns)
        if i < len(starts) and starts[i] < m.start_ns + m.dur_ns:
            tot += m.dur_ns
    return tot


def idle_gaps(ops, host, top: int = 10):
    """The ``top`` longest gaps between device ops, each named by the
    shortest bench host span that covers most of it."""
    merged = union((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    gaps = [(s1[1], s2[0]) for s1, s2 in zip(merged, merged[1:])
            if s2[0] > s1[1]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for s, e in gaps[:top]:
        best, best_cover = "other", 0.0
        for name, hs, hd in host:
            cover = min(e, hs + hd) - max(s, hs)
            if cover > 0.5 * (e - s) and (best == "other" or hd < best_cover):
                best, best_cover = name, hd
        out.append([best, (e - s) / 1e9])
    return out


def top_ops(ops, top: int = 10):
    """The leaf ops that took most time, summed by instruction base name."""
    tot = {}
    for o in ops:
        if _HOLDERS.search(o.name):
            continue
        n = base_name(o.name)
        tot[n] = tot.get(n, 0.0) + o.dur_ns
    return [[n, t / 1e9] for n, t in sorted(tot.items(),
                                            key=lambda kv: -kv[1])[:top]]


def reduce(trace: Trace, kernels=(), programs=()) -> dict:
    """Seconds averaged over the chips: busy, per kernel, and per program
    (the runs that hold a given kernel); and the first chip's breakdown."""
    chips = [c for c in trace.chips if c.ops]
    if not chips:
        return {"busy_s": 0.0, "kernels": dict.fromkeys(kernels, 0.0),
                "programs": dict.fromkeys(programs, 0.0), "chips": 0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    n = len(chips)
    return {
        "busy_s": sum(busy_ns(c.ops) for c in chips) / n / 1e9,
        "kernels": {k: sum(kernel_ns(c.ops, k) for c in chips) / n / 1e9
                    for k in kernels},
        "programs": {k: sum(program_ns(c, k) for c in chips) / n / 1e9
                     for k in programs},
        "chips": n,
        "breakdown": {"device_ops": top_ops(chips[0].ops),
                      "idle_gaps": idle_gaps(chips[0].ops, trace.host)},
    }
