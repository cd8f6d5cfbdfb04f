"""program_trace and the readers of the program's spans and scopes, on
small synthetic traces and on a traced CPU run of the serve cell."""
import json

import pytest

from bench import common
from bench import program_trace as PT
from bench import run as R

SERVE = "serve.decode-heavy.robust-m8"
TRAIN = "train.rrs-vrmom.byz1"
READERS = {SERVE: ("sched.host_ms_per_block", "admit.ms",
                   "decode.kv_cache_ms_per_step"),
           TRAIN: ("rrs.aggregate_ms_per_step", "train.grad_ms_per_step",
                   "train.optimizer_ms_per_step")}


def op(name, start, dur, scope=""):
    return PT.ScopedOp(name, float(start), float(dur), scope)


def span(name, start, dur, line=0, **args):
    return PT.Span(name, float(start), float(dur), line, args)


def serve_trace():
    """Two scheduler steps, one admission, two decode blocks of 8 steps."""
    ops = [op("%while.1 = (s32[]) while(s32[] %t), body=%b", 0, 400,
              "jit(serve_decode_block)/serve.decode_scan/while"),
           op("%scatter.2 = bf16[2] scatter(%c)", 0, 30,
              "jit(serve_decode_block)/serve.decode_scan/while/body/"
              "decode.kv_cache/vmap(vmap())/scatter"),
           op("%copy.3 = bf16[2] copy(%c)", 30, 20,
              "jit(serve_decode_block)/serve.decode_scan/while/body/"
              "kernels.decode_attention/jit(_decode_grouped)/"
              "decode.kv_cache/reshape"),
           op("%_decode_grouped.4 = bf16[2] custom-call(%x)", 50, 350,
              "jit(serve_decode_block)/serve.decode_scan/while/body/"
              "kernels.decode_attention/jit(_decode_grouped)/pallas_call"),
           op("%_flash_bh.5 = bf16[2] custom-call(%x)", 600, 100,
              "jit(serve_prefill)/pallas_call"),
           op("%scatter.2 = bf16[2] scatter(%c)", 800, 50,
              "jit(serve_decode_block)/serve.decode_scan/while/body/"
              "decode.kv_cache/vmap(vmap())/scatter")]
    host = [span("host.step", 0, 2000),
            span("serve.step", 10, 500, active=2, queued=0),
            span("serve.decode_block", 20, 30, n_steps=8),
            span("serve.wait", 60, 380, what="decode_block"),
            span("serve.evict", 450, 40, uid=7, slot=1),
            span("serve.step", 510, 1400, active=1, queued=1),
            span("serve.admit", 520, 300, uid=8, slot=1, prompt_len=128,
                 queue_wait_us=12.0),
            span("serve.wait", 600, 150, what="first_token"),
            span("serve.decode_block", 830, 20, n_steps=8),
            span("serve.wait", 860, 1000, what="decode_block"),
            # another thread's span is not the scheduler's child
            span("serve.wait", 520, 100, line=1, what="elsewhere")]
    return PT.ProgramTrace([ops], host)


def train_trace():
    g = "jit(train_step)/train.grad/vmap(transpose(jvp()))/dot_general"
    a = "jit(train_step)/rrs.aggregate/shard_map/rrs.all_to_all/all_to_all"
    o = "jit(train_step)/train.optimizer/mul"
    chip = [op("%fusion.1 = f32[2] fusion(%a)", 0, 300, g),
            op("%all-to-all.2 = f32[2] all-to-all(%a)", 300, 60, a),
            op("%_agg_2d.3 = f32[2] custom-call(%a)", 360, 140,
               "jit(train_step)/rrs.aggregate/kernels.aggregate/pallas"),
            op("%fusion.4 = f32[2] fusion(%a)", 500, 40, o),
            op("%all-reduce.5 = f32[2] all-reduce(%a)", 540, 10, ""),
            op("%fusion.6 = f32[2] fusion(%a)", 1000, 300, g),
            op("%fusion.7 = f32[2] fusion(%a)", 1300, 100, a)]
    return PT.ProgramTrace([chip, [o2._replace(start_ns=o2.start_ns + 5)
                                   for o2 in chip]], [])


def test_in_scope_matches_whole_components():
    assert PT.in_scope("jit(f)/train.grad/vmap(jvp())/dot", "train.grad")
    assert PT.in_scope("jit(f)/transpose(jvp(decode.kv_cache))/mul",
                       "decode.kv_cache")
    assert not PT.in_scope("jit(f)/train.gradient/dot", "train.grad")
    assert not PT.in_scope("", "train.grad")


def test_scope_time_leaves_holders_out_and_unions_overlaps():
    ops = serve_trace().chips[0]
    # the while holds everything; only leaves under the scope count
    assert PT.scope_ns(ops, "decode.kv_cache") == 100.0
    assert PT.scope_ns(ops, "serve.decode_scan") == 450.0
    more = [op("%x.8 = f32[] add()", 10, 30, "a/decode.kv_cache/add"),
            op("%x.9 = f32[] add()", 900, 10, "a/decode.kv_cache/add")]
    assert PT.scope_ns(ops + more, "decode.kv_cache") == 110.0
    assert PT.scope_ms_per_step(serve_trace(), "nope", 4) is None
    assert PT.scope_ms_per_step(None, "decode.kv_cache", 4) is None


def test_spans_and_exclusive_host_time():
    t = serve_trace()
    assert len(PT.spans(t, "serve.step")) == 2
    assert PT.spans(t, "serve.admit")[0].args["uid"] == 8
    # 500 - 380 and 1400 - (150 + 1000); the other thread's wait is not
    # inside the step
    assert PT.exclusive_ns(t, "serve.step", "serve.wait") == 120 + 250
    assert PT.exclusive_ns(None, "serve.step", "serve.wait") == 0.0


def test_idle_split_by_innermost_span():
    t = serve_trace()
    main = [s for s in t.spans if s.line == 0]
    idle = PT.idle_by_span(t.chips[0], main)
    # gaps [400, 600) and [700, 800); the first under serve.wait (to
    # 440), serve.step (to 450), serve.evict (to 490), serve.step (to
    # 510), serve.step (the second, to 520), serve.admit (to 600); the
    # second under serve.wait (to 750), serve.admit (to 800)
    assert idle == {"serve.wait": 40.0 + 50.0, "serve.step": 10.0 + 20.0
                    + 10.0, "serve.evict": 40.0, "serve.admit": 80.0 + 50.0}
    # no program span: the benchmark's, else none
    assert PT.idle_by_span(t.chips[0], [span("host.step", 0, 500)]) == {
        "host.step": 100.0, "none": 200.0}


def test_scope_table_and_remainder():
    chip = train_trace().chips[0]
    table = PT.scope_table(chip)
    busy = 550.0 + 400.0
    assert table["train.grad"] == pytest.approx(100 * 600 / busy)
    assert table["rrs.aggregate"] == pytest.approx(100 * 300 / busy)
    assert table["rrs.all_to_all"] == pytest.approx(100 * 160 / busy)
    assert table["train.optimizer"] == pytest.approx(100 * 40 / busy)
    rest = PT.top_leaf_scopes(chip, outside=PT.TRAIN_SCOPES)
    assert rest == [["all-reduce", 1e-8, ""]]
    top = PT.top_leaf_scopes(chip, top=1)
    assert top[0][0] == "fusion" and top[0][2].startswith(
        "jit(train_step)/train.grad/")


def _load(name, monkeypatch, trace):
    monkeypatch.setattr(PT, "snapshot", lambda *a, **k: trace)
    return R.reader(name)


def _ctx(steps=2):
    return {"rec": {"work": {"steps": steps, "blocks": []}},
            "mix": {}, "conf": {}, "trace": None}


def test_serve_readers(monkeypatch):
    t = serve_trace()
    read = {n: _load(n, monkeypatch, t).read(_ctx())
            for n in READERS[SERVE]}
    assert read["sched.host_ms_per_block"] == pytest.approx(370 / 2 * 1e-6)
    assert read["admit.ms"] == pytest.approx(300e-6)
    # 100 ns under the scope over 16 scan steps
    assert read["decode.kv_cache_ms_per_step"] == pytest.approx(
        100 / 16 * 1e-6)


def test_train_readers(monkeypatch):
    t = train_trace()
    read = {n: _load(n, monkeypatch, t).read(_ctx(steps=2))
            for n in READERS[TRAIN]}
    assert read["train.grad_ms_per_step"] == pytest.approx(300e-6)
    assert read["rrs.aggregate_ms_per_step"] == pytest.approx(150e-6)
    assert read["train.optimizer_ms_per_step"] == pytest.approx(20e-6)


@pytest.mark.parametrize("cell", [SERVE, TRAIN])
@pytest.mark.parametrize("trace", ["none", "empty", "unscoped"])
def test_readers_read_nothing_without_spans_or_scopes(monkeypatch, cell,
                                                      trace):
    """A profile of a program without the spans and scopes (or none at
    all) gives no reading, and no reader raises."""
    t = {"none": None, "empty": PT.ProgramTrace([], []),
         "unscoped": PT.ProgramTrace(
             [[o._replace(scope="") for o in train_trace().chips[0]]],
             [span("host.step", 0, 100)])}[trace]
    for name in READERS[cell]:
        assert _load(name, monkeypatch, t).read(_ctx()) is None


def test_snapshot_without_a_profile(tmp_path):
    assert PT.snapshot(tmp_path) is None


def test_traced_serve_run_reads_the_program_spans(monkeypatch, tmp_path):
    """The harness loads the readers while the profile is on disk: on the
    CPU the host spans give their metrics, and the device scopes none.
    The profile goes to a directory of its own: other tests' runs clear
    the benchmark's."""
    from test_bench_serve_cpu import run_cell

    peaks = json.loads(R.PEAKS.read_text())["TPU v5 lite"]
    monkeypatch.setattr(R, "peaks_for", lambda kind: peaks)
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path / "trace")
    result, checks = run_cell(trace=True)
    assert result["correct"], checks
    m = result["metrics"]
    assert m["sched.host_ms_per_block"]["value"] > 0
    assert m["admit.ms"]["value"] > 0
    assert "decode.kv_cache_ms_per_step" not in m
    assert not (tmp_path / "trace").exists()


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*fields):
    """A protobuf message from (number, int | str | bytes) fields."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def plane(name, metas, stats, lines):
    """An XPlane: metas {id: (name, [(stat id, field, value)])}, stats
    {id: name}, lines [(name, timestamp_ns, [(meta id, offset_ps,
    duration_ps, [(stat id, field, value)])])]."""
    def stat(sid, field, v):
        return msg((1, sid), (field, v))

    return msg((2, name), *[
        (3, msg((2, ln), (3, ts), *[
            (4, msg((1, m), (2, off), (3, dur),
                    *[(4, stat(*x)) for x in xs]))
            for m, off, dur, xs in evs]))
        for ln, ts, evs in lines], *[
        (4, msg((1, i), (2, msg((1, i), (2, n),
                                *[(5, stat(*x)) for x in xs]))))
        for i, (n, xs) in metas.items()], *[
        (5, msg((1, i), (2, msg((1, i), (2, n))))) for i, n in stats.items()])


def test_load_reads_scopes_from_event_metadata(tmp_path):
    """Each op's name stack comes from its event metadata, found by the
    program run that holds it (one op name in two programs); a scope may
    be a reference to a stat name. Host spans keep their stats."""
    fused = "%fusion.1 = f32[2] fusion(f32[2] %a)"
    stats = {1: "tf_op", 2: "program_id", 3: "uid",
             4: "jit(f)/train.optimizer/mul:"}
    tpu = plane("/device:TPU:0", {
        1: (fused, [(1, 5, "jit(f)/train.grad/dot:"), (2, 3, 7)]),
        2: (fused, [(1, 5, "jit(g)/serve.decode_scan/add:"), (2, 3, 8)]),
        3: ("%fusion.2 = f32[2] fusion(f32[2] %b)", [(1, 7, 4), (2, 3, 7)]),
        4: ("jit_f(7)", []), 5: ("jit_g(8)", [])}, stats, [
        ("XLA Modules", 1000, [(4, 0, 500_000, []),
                               (5, 1_000_000, 500_000, [])]),
        ("XLA Ops", 1000, [(1, 0, 100_000, []), (3, 200_000, 100_000, []),
                           (2, 1_000_000, 100_000, [])])])
    host = plane("/host:CPU", {1: ("serve.admit", []), 2: ("other", [])},
                 stats, [("python", 1000, [(1, 0, 2_000_000, [(3, 4, 9)]),
                                           (2, 0, 10, [])])])
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(msg((1, tpu), (1, host)))
    t = PT.load(str(tmp_path))
    ops, = t.chips
    assert [(o.start_ns, o.dur_ns, o.scope) for o in ops] == [
        (1000.0, 100.0, "jit(f)/train.grad/dot:"),
        (1200.0, 100.0, "jit(f)/train.optimizer/mul:"),
        (2000.0, 100.0, "jit(g)/serve.decode_scan/add:")]
    assert PT.scope_ns(ops, "train.optimizer") == 100.0
    assert t.spans == [PT.Span("serve.admit", 1000.0, 2000.0, 0, {"uid": 9})]
