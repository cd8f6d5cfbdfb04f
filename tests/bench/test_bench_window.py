"""The end-to-end readers on hand-built timelines: a rate is all the
work over all the window, a tail is over all requests."""
import importlib.util
from pathlib import Path

import pytest

import tiny
from bench import common

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_rates_are_all_work_over_all_the_window():
    rec = {"tokens": 900, "elapsed_s": 30.0, "setup_s": 12.5}
    assert reader("tok_s")({"rec": rec}) == pytest.approx(30.0)
    assert reader("train_tok_s")({"rec": rec}) == pytest.approx(30.0)
    assert reader("setup_s")({"rec": rec}) == 12.5


def test_tails_are_over_all_requests():
    # 20 requests: 17 fast, 3 slow; p90 (nearest rank: 18th of 20) is
    # the first slow one
    ttft = [0.05] * 17 + [0.5, 0.6, 0.7]
    tpot = [0.02] * 18 + [0.04, 0.05]
    ctx = {"rec": {"ttft_s": ttft, "tpot_s": tpot}}
    assert reader("tpot_p90_ms")(ctx) == pytest.approx(20.0)
    assert reader("tpot_p90_ms")({"rec": {"tpot_s": []}}) is None
    assert common.percentile(ttft, 90) == pytest.approx(0.5)


def test_per_layer_readers_on_a_hand_built_record():
    conf = tiny.conf()
    mix = tiny.serve_mix()
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    blocks = [[(10, 4), (20, 2)], [(14, 4)]]  # 8 decode steps of 4
    trace = {"busy_s": 0.75, "window_s": 1.0, "chips": 1,
             "programs": {"_decode_grouped": 0.4, "_flash_bh": 0.05},
             "kernels": {"_decode_grouped": 0.1, "_tail_3d": 0.02}}
    ctx = {"rec": {"work": {"blocks": blocks, "prefill_lens": [8]}},
           "conf": conf, "mix": mix, "peaks": peaks, "trace": trace}
    assert reader("serve.idle_share")(ctx) == pytest.approx(25.0)
    assert reader("decode.step_ms")(ctx) == pytest.approx(400 / 8)
    assert reader("robust_tail.ms_per_step")(ctx) == pytest.approx(20 / 8)
    from bench import work

    steps = work.decode_steps(blocks)
    nbytes = sum(work.decode_attention_bytes(conf, s) for s in steps)
    assert reader("decode_attention_roofline")(ctx) == pytest.approx(
        100 * nbytes / 1e9 / 0.1)
    flops = sum(work.decode_flops(conf, s) for s in steps)
    assert reader("decode.mfu")(ctx) == pytest.approx(100 * flops / 0.4
                                                      / 1e12)
    # nothing under a scope: nothing to read, never 0
    ctx["trace"] = dict(trace, kernels={k: 0.0 for k in trace["kernels"]},
                        programs={k: 0.0 for k in trace["programs"]})
    assert reader("decode_attention_roofline")(ctx) is None
    assert reader("decode.mfu")(ctx) is None
    assert reader("prefill.mfu")(ctx) is None


def test_prefill_readers():
    conf = tiny.conf()
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"rec": {"work": {"blocks": [], "prefill_lens": [8, 16]}},
           "conf": conf, "peaks": peaks,
           "trace": {"programs": {"_flash_bh": 0.012}, "kernels": {}}}
    from bench import work

    assert reader("prefill.ms_per_ktok")(ctx) == pytest.approx(
        12.0 / 0.024)
    assert reader("prefill.mfu")(ctx) == pytest.approx(
        100 * work.prefill_flops(conf, [8, 16]) / 0.012 / 1e12)
