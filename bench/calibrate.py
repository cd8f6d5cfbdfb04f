#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
      --control-seeds 1,2,3 [--seconds 10]

In one process, for each seed: the program through the cell's own
set-up and a short window at the cell's load, then the plain reference,
and the numbers the check compares. For the control seeds, the control
(the reference at fp8, put in the program's place) and the faults a
cell can have, read against the same reference. Prints one JSON line per
reading and a summary last. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench import common, gen  # noqa: E402
from bench import run as R  # noqa: E402


def say(**kw):
    print(json.dumps(kw), flush=True)


def serve(conf, mix, seeds, control_seeds, seconds, devs):
    from bench.drivers import serve as D

    out = {"program": [], "control": [], "altered_token": []}
    logits_fn = D.make_logits_fn(conf)
    for seed in seeds:
        t0 = time.perf_counter()
        rec, cfg, params, finished = D.serve_window(
            conf, mix, seed, seconds, False, devs, t0, common.TRACE_DIR)
        reqs = D.sample(finished, mix["check"]["requests"], seed)
        with jax.default_matmul_precision("highest"):
            t1 = time.perf_counter()
            gap, n = D.reference_gaps(params, conf, mix, reqs,
                                      logits_fn=logits_fn)
            say(seed=seed, kind="program", max_logit_gap=gap, tokens=n,
                setup_s=rec["setup_s"], ref_s=time.perf_counter() - t1,
                completed=rec["completed"], tok_s=rec["tokens"]
                / rec["elapsed_s"])
            out["program"].append(gap)
            if seed in control_seeds:
                cgap, _ = D.reference_gaps(params, conf, mix, reqs,
                                           quant="fp8", logits_fn=logits_fn)
                say(seed=seed, kind="control_fp8", max_logit_gap=cgap)
                out["control"].append(cgap)
                for r in reqs:   # one token altered where it is produced
                    r.tokens = r.tokens.copy()
                    r.tokens[len(r.tokens) // 2] = (
                        r.tokens[len(r.tokens) // 2] + 1) % conf[
                            "vocab_size"]
                fgap, _ = D.reference_gaps(params, conf, mix, reqs,
                                           logits_fn=logits_fn)
                say(seed=seed, kind="fault_altered_token",
                    max_logit_gap=fgap)
                out["altered_token"].append(fgap)
        del params, finished, reqs
        gc.collect()
    return out


def train(conf, mix, seeds, control_seeds, seconds, devs):
    from bench.drivers import train as D

    out = {}
    built = D.build(conf, mix, devs)
    for seed in seeds:
        t0 = time.perf_counter()
        rec, cfg, prog = D.train_window(conf, mix, seed, seconds, False,
                                        devs, t0, common.TRACE_DIR,
                                        built=built)
        t1 = time.perf_counter()
        refr = D.reference_readings(conf, mix, seed, devs)
        ref_s = time.perf_counter() - t1
        gaps = D.compare(prog, refr)
        say(seed=seed, kind="program", losses=prog["losses"],
            ref_losses=refr["losses"], set_up_s=rec["setup_s"],
            ref_s=ref_s, **gaps)
        out.setdefault("program", []).append(gaps)
        if seed in control_seeds:
            kinds = [("control_fp8", "fp8", ()),
                     ("fault_half_batch", None, ("half_batch",)),
                     ("fault_no_exchange", None, ("no_exchange",))]
            for kind, quant, faults in kinds:
                other = D.reference_readings(conf, mix, seed, devs,
                                             quant=quant, faults=faults)
                g = D.compare(other, refr)
                say(seed=seed, kind=kind, losses=other["losses"], **g)
                out.setdefault(kind, []).append(g)
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    w, conf, _, _ = R.cell_of(R.load_manifest(), args.workload)
    conf_file = json.loads((CHECKOUT / conf["file"]).read_text())
    mix = gen.load_mix(w["traffic"])
    devs = common.require_devices(w["chips"])
    common.enable_compile_cache()
    fn = {"serve": serve, "train": train}[mix["driver"]]
    out = fn(conf_file, mix, seeds, control, args.seconds, devs)

    def summary(v):
        if v and isinstance(v[0], dict):
            return {k: [min(x[k] for x in v), max(x[k] for x in v)]
                    for k in v[0]}
        return [min(v), max(v)] if v else None

    say(kind="summary", **{k: summary(v) for k, v in out.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
