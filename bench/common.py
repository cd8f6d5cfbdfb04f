"""Pieces every driver shares: devices, the compile cache, the model
configuration, percentiles, the result line."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
# fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".bench_trace"


class NoDevice(SystemExit):
    """The run found no accelerator, or fewer chips than the cell asks."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration, by its family."""
    from bench import families

    return families.get(conf).arch_config(conf)


def require_devices(chips: int, allow_cpu: bool = False):
    """The devices of the run; exits non-zero with no result when JAX
    finds no accelerator or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        raise NoDevice(f"bench: needs an accelerator; JAX found "
                       f"{devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says); every program is cached."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    active: in a warm window there are none."""

    def __init__(self):
        self.n = 0
        self._on = False

    def install(self):
        import jax

        def listener(event, duration, **kwargs):
            if self._on and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
        return self

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over all values: the smallest value with
    at least q% of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def device_info(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported); the
    fullest chip's whole memory statistics go to standard error."""
    stats = [d.memory_stats() or {} for d in devs]
    full = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    log(f"memory statistics of the fullest chip: {json.dumps(full)}")
    return int(full.get("peak_bytes_in_use", 0))


def program_peak(devs, fn, *args) -> int:
    """What one call of the jitted ``fn`` on ``args`` holds at most on
    the fullest chip, by the compiler's count: the bytes in use when it
    starts, plus its outputs and temporaries, less what it aliases. The
    chip's ``peak_bytes_in_use`` counts buffers and leaves a program's
    temporaries out. 0 where the compiler reports nothing."""
    try:
        ma = fn.lower(*args).compile().memory_analysis()
        extra = (ma.output_size_in_bytes + ma.temp_size_in_bytes
                 - ma.alias_size_in_bytes)
    except Exception as e:  # noqa: BLE001 - the reading is optional
        log(f"program memory: not reported ({type(e).__name__}: {e})")
        return 0
    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                 for d in devs)
    log(f"program memory, per chip: arguments "
        f"{ma.argument_size_in_bytes}, outputs {ma.output_size_in_bytes}, "
        f"temporaries {ma.temp_size_in_bytes}, aliased "
        f"{ma.alias_size_in_bytes}; in use at its start {in_use}")
    return int(in_use + extra)


def emit(result: dict, checks: list) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result line as the last line of standard
    output (with the checks last in it)."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"({'ok' if c['ok'] else 'FAILED'})")
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), flush=True)


def check(name: str, value: float, limit: float) -> dict:
    """A number compared with its limit: correct while value <= limit."""
    ok = math.isfinite(value) and value <= limit
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(ok)}
