"""trace_reduce on a small synthetic trace."""
import pytest

from bench import trace_reduce as TR


def op(name, start, dur):
    return TR.Op(name, float(start), float(dur))


def test_busy_kernels_programs_and_gaps():
    ops0 = [op("%while.3 = (s32[]) while(s32[] %t), body=%b", 0, 150),
            op("%fusion.1 = bf16[2] fusion(bf16[2] %a)", 0, 100),
            op("%_decode_grouped.4 = bf16[2] custom-call(%x)", 100, 50),
            op("%_flash_bh.7 = bf16[2] custom-call(%x)", 400, 100),
            op("%copy.2 = bf16[2] copy(%y)", 900, 50)]
    mods0 = [op("jit_run(111)", 0, 150), op("jit_run(222)", 400, 100),
             op("jit_dynamic_update_slice(3)", 900, 50)]
    ops1 = [op("%_decode_grouped.1 = bf16[2] custom-call(%x)", 0, 300)]
    mods1 = [op("jit_run(111)", 0, 300)]
    host = [("host.step", 0, 1000), ("host.admit", 140, 270),
            ("host.evict", 500, 300)]
    t = TR.reduce(TR.Trace([TR.Chip(ops0, mods0), TR.Chip(ops1, mods1)],
                           host),
                  kernels=["_decode_grouped", "_flash_bh"],
                  programs=["_decode_grouped", "_flash_bh"])
    # chip 0: [0,150) + [400,500) + [900,950) = 300 ns; chip 1: 300 ns
    assert t["busy_s"] == pytest.approx(300e-9)
    assert t["chips"] == 2
    assert t["kernels"]["_decode_grouped"] == pytest.approx(175e-9)
    assert t["kernels"]["_flash_bh"] == pytest.approx(50e-9)
    assert t["programs"]["_decode_grouped"] == pytest.approx(225e-9)
    assert t["programs"]["_flash_bh"] == pytest.approx(50e-9)
    gaps = t["breakdown"]["idle_gaps"]
    # gaps of chip 0, longest first: [500,900) under host.evict,
    # [150,400) under host.admit
    assert gaps[0][0] == "host.evict" and gaps[0][1] == pytest.approx(4e-7)
    assert gaps[1][0] == "host.admit" and gaps[1][1] == pytest.approx(
        2.5e-7)
    ops = dict(t["breakdown"]["device_ops"])
    assert "while" not in ops     # holders are not counted beside bodies
    assert ops["fusion"] == pytest.approx(1e-7)
    assert ops["_decode_grouped"] == pytest.approx(5e-8)


def test_empty_trace_reads_nothing():
    t = TR.reduce(TR.Trace([], []), ["x"], ["y"])
    assert t["busy_s"] == 0.0 and t["chips"] == 0
    assert t["kernels"] == {"x": 0.0} and t["programs"] == {"y": 0.0}


def test_union_and_base_name():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert TR.base_name("%_tail_3d.3 = s32[24,1] custom-call(") == "_tail_3d"
    assert TR.base_name("%all-to-all.12 = f32[4]") == "all-to-all"
