#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration (``bench/configs``), its traffic mix
(``bench/traffic``) and the driver that the mix names
(``bench/drivers``), sets up, measures for ``--seconds``, checks what
the window produced against the plain reference, and prints one JSON
line last on standard output. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``. Without an accelerator, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, gen  # noqa: E402

MANIFEST = CHECKOUT / "BENCHMARK.json"
METRICS_DIR = CHECKOUT / "bench" / "metrics"
PEAKS = CHECKOUT / "bench" / "peaks.json"


def load_manifest(path=MANIFEST) -> dict:
    if not Path(path).is_file():
        raise SystemExit(f"bench: no manifest at {path}")
    return json.loads(Path(path).read_text())


def cell_of(manifest: dict, name: str):
    """-> (workload entry, its config entry, end-to-end and per-layer
    metric entries that the cell reports)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]

    def mine(m):
        return name in m.get("workloads", [name])

    return (w, conf, [m for m in manifest["end_to_end"] if mine(m)],
            [m for m in manifest["per_layer"] if mine(m)])


def reader(name: str):
    """The metric's reader module, ``bench/metrics/<name>.py``."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise SystemExit(f"bench: no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"{PEAKS}")
    return table[kind]


def measure(conf_file: dict, mix: dict, *, seed: int, seconds: float,
            trace: bool, devs, metrics: list, t_proc: float = T_PROC):
    """Run the mix's driver once; -> (result dict, checks)."""
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    trace_dir = common.TRACE_DIR
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec, cfg, checks = driver.run(conf_file, mix, seed, seconds, trace, devs,
                                  t_proc, trace_dir)
    common.log(f"window {rec['elapsed_s']:.3f} s, set-up "
               f"{rec['setup_s']:.3f} s, programs compiled or loaded in "
               f"the window: {rec['compiles_in_window']}")
    for k in ("ttft_s", "tpot_s"):
        if rec.get(k):
            common.log(f"{k}: p90 {common.percentile(rec[k], 90):.6f} over "
                       f"{len(rec[k])} requests")
    device = common.device_info(devs, rec["memory_peak_bytes"])
    ctx = {"rec": rec, "conf": conf_file, "mix": mix, "cfg": cfg,
           "trace": None}
    if trace:
        from bench import trace_reduce

        ctx["peaks"] = peaks_for(devs[0].device_kind)
        mods = {m["name"]: reader(m["name"]) for m in metrics}
        kernels = sorted({k for mod in mods.values()
                          for k in getattr(mod, "KERNELS", ())})
        programs = sorted({k for mod in mods.values()
                           for k in getattr(mod, "PROGRAMS", ())})
        t = trace_reduce.reduce(trace_reduce.load(str(trace_dir)), kernels,
                                programs)
        shutil.rmtree(trace_dir, ignore_errors=True)
        t["window_s"] = rec["trace_window_s"]
        ctx["trace"] = t
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
    else:
        mods = {m["name"]: reader(m["name"]) for m in metrics}
    out = {}
    for m in metrics:
        v = mods[m["name"]].read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": out, "device": device}
    if trace:
        result["breakdown"] = ctx["trace"]["breakdown"]
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load_manifest()
    w, conf, e2e, per_layer = cell_of(manifest, args.workload)
    conf_file = json.loads((CHECKOUT / conf["file"]).read_text())
    mix = gen.load_mix(w["traffic"])
    try:
        devs = common.require_devices(w["chips"])
    except common.NoDevice as e:
        print(e, file=sys.stderr)
        return 3
    common.log(f"compile cache: {common.enable_compile_cache()}")
    result, checks = measure(conf_file, mix, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             devs=devs, metrics=per_layer if args.trace
                             else e2e)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
