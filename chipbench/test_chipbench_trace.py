"""The reduction from a trace to numbers, against counts made by hand: on a
small hand-written trace whose intervals can be added up on paper, and on a
slice of a trace recorded on a TPU v5e (``testdata/``), whose counts were
made once when it was cut and are checked here against a brute-force
timeline."""

import os

import numpy as np
import pytest

from chipbench import harness, xtrace

OLMO = harness.load_json(harness.HERE, "configs", "olmo-1b.json")
WHILE = "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %a), condition=%c"
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)"
GATHER = "%closed_call.2 = f32[4,16,2048]{2,1,0} custom-call(s32[4] %t)"
REDUCE = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %y)"
COPY = "%copy.4 = f32[8]{0} copy(f32[8]{0} %z)"


def serve_trace():
    """One decode step [100, 900] ns holding a loop, a gather and a
    collective; one prefill [1200, 1300]; the window [0, 2000]."""
    ops = {"TPU:0": [[WHILE, 100, 900, ""], [FUSION, 100, 300, ""],
                     [GATHER, 300, 500, ""], [REDUCE, 450, 700, ""],
                     [COPY, 1200, 1300, ""]]}
    modules = {"TPU:0": [["jit_serve_step", 100, 900],
                         ["jit_prefill", 1200, 1300]]}
    host = [[xtrace.WINDOW_SPAN, 0, 2000], ["generate call 0", 0, 2000],
            ["np.asarray(jax.Array)", 900, 1150],
            ["PjitFunction(prefill)", 1150, 1200]]
    t = xtrace.Trace(ops, modules, host)
    xtrace.attach_programs(t)
    return t


def ctx_of(trace, **inputs):
    lo, hi = trace.window()
    return {"trace": trace, "window": (lo, hi), "window_s": (hi - lo) / 1e9,
            "config": OLMO, "chips": len(trace.devices),
            "peaks": {"hbm_bytes_per_s": 1e15, "bf16_flops_per_s": 1e15},
            "inputs": inputs}


def test_parse_op():
    assert xtrace.parse_op(WHILE)[:2] == ("%while.1", "while")
    assert xtrace.parse_op(GATHER) == ("%closed_call.2", "custom-call",
                                       "f32[4,16,2048]")
    assert xtrace.parse_op("PjitFunction(f)") == ("PjitFunction(f)", "", "")


def test_hand_counted_serve_trace():
    t = serve_trace()
    lo, hi = t.window()
    assert (lo, hi) == (0, 2000)
    # busy: [100, 900] and [1200, 1300]
    assert xtrace.busy_s(t, lo, hi) == pytest.approx(900e-9)
    assert xtrace.program_runs(t, "TPU:0", lo, hi) == {
        "jit_serve_step": [800e-9], "jit_prefill": [100e-9]}
    # the all-reduce [450, 700] overlaps compute until 500; the loop that
    # holds it is not compute
    assert xtrace.collective_s(t, "TPU:0", lo, hi) == pytest.approx(
        (250e-9, 200e-9))
    b = xtrace.breakdown(t, lo, hi)
    assert [round(v * 1e9) for _, v in b["device_ops"]] == [250, 200, 200,
                                                            100]
    assert b["device_ops"][0][0] == \
        "jit_serve_step/%all-reduce.3 all-reduce f32[8]"
    # gaps [1300, 2000], [900, 1200], [0, 100]
    assert b["idle_gaps"] == [
        ["host, after PjitFunction(prefill)", pytest.approx(700e-9)],
        ["np.asarray(jax.Array)", pytest.approx(300e-9)],
        ["host", pytest.approx(100e-9)]]


def test_hand_counted_serve_metrics():
    t = serve_trace()
    ctx = ctx_of(t, requests=[(20, 2)], cache_dtype="float32",
                 engine={"batch_size": 1, "max_len": 64, "page_size": 16})
    read = lambda name: harness.load_reader(name)(ctx)
    assert read("device_idle.serve") == pytest.approx(55.0)
    assert read("prefill_share.serve") == pytest.approx(100 / 9)
    assert read("decode_step_ms.serve") == pytest.approx(800e-9 * 1e3)
    # one gather of a 4-page view, whose one live input (21 tokens) sits on
    # 2 pages of 16 x 2048 f32: 6 pages at 1e15 B/s over 200 ns
    assert read("paged_gather_roofline.serve") == pytest.approx(
        100 * 6 * 131072 / 1e15 / 200e-9)
    # one step: the bf16 weights once and 21 tokens of bf16 K and V
    least = 2 * 1_176_764_416 + 21 * 131_072
    assert read("decode_hbm_share.serve") == pytest.approx(
        100 * least / 800e-9 / 1e15)
    # 20 prompt tokens and 1 served fed back: 21 x 2N and contexts 1..21
    flops = 21 * 2 * 1_176_764_416 + 4 * 16 * 16 * 128 * 231
    assert read("mfu.serve") == pytest.approx(100 * flops / 2000e-9 / 1e15)


def train_trace():
    """One step on each of two chips; chip 0 reduce-scatters [500, 800]
    under compute until 600, chip 1 all-gathers [300, 700] under compute
    until 400."""
    scatter = "%reduce-scatter.1 = f32[8]{0} reduce-scatter(f32[32] %g)"
    gather = "%all-gather.2 = f32[32]{0} all-gather(f32[8]{0} %p)"
    ops = {"TPU:0": [[FUSION, 0, 600, ""], [scatter, 500, 800, ""],
                     [COPY, 800, 900, ""]],
           "TPU:1": [[FUSION, 0, 400, ""], [gather, 300, 700, ""]]}
    modules = {d: [["jit_train_step", 0, 1000]] for d in ops}
    t = xtrace.Trace(ops, modules, [[xtrace.WINDOW_SPAN, 0, 1000]])
    xtrace.attach_programs(t)
    return t


def test_hand_counted_train_metrics():
    t = train_trace()
    ctx = ctx_of(t, tokens_per_step=16 * 2048, seq_len=2048)
    read = lambda name: harness.load_reader(name)(ctx)
    assert xtrace.collective_s(t, "TPU:0", 0, 1000) == pytest.approx(
        (300e-9, 200e-9))
    assert xtrace.collective_s(t, "TPU:1", 0, 1000) == pytest.approx(
        (400e-9, 300e-9))
    # busy 900 and 700 of 1000 ns
    assert read("device_idle.train") == pytest.approx(20.0)
    # per chip per step: (300 + 400) / 2 ns and (200 + 300) / 2 ns
    assert read("collective_ms.train") == pytest.approx(350e-9 * 1e3)
    assert read("exposed_collective_ms.train") == pytest.approx(
        250e-9 * 1e3)
    # one step of 32,768 tokens at 7,865,892,864 operations each, over
    # 1000 ns on 2 chips of 1e15
    assert read("mfu.train") == pytest.approx(
        100 * 32768 * 7_865_892_864 / 1000e-9 / 2e15)


def test_empty_window_reads_nothing():
    t = serve_trace()
    ctx = ctx_of(t, requests=[], cache_dtype="float32",
                 engine={"batch_size": 1, "max_len": 64, "page_size": 16})
    ctx["window"] = (1400, 2000)
    assert harness.load_reader("decode_step_ms.serve")(ctx) is None
    assert harness.load_reader("mfu.serve")(ctx) is None
    assert harness.load_reader("collective_ms.train")(ctx) is None


def test_json_round_trip(tmp_path):
    t = serve_trace()
    t.save(str(tmp_path / "t.json.gz"))
    u = xtrace.Trace.load(str(tmp_path / "t.json.gz"))
    assert u.to_json() == t.to_json()


def _brute_busy(trace, dev, lo, hi, step=1000):
    """Busy time by marking a timeline in ``step``-ns cells."""
    n = int((hi - lo) // step) + 1
    mark = np.zeros(n, bool)
    for _, s, e, _ in trace.ops[dev]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            mark[int((a - lo) // step): int(np.ceil((b - lo) / step))] = True
    return mark.sum() * step


RECORDED = os.path.join(harness.HERE, "testdata",
                        "serve_decode_slice.json.gz")


def test_recorded_slice():
    """Three decode steps of olmo1b-serve-decode on a TPU v5e and the gaps
    between them (4,531 operations), counted when the slice was cut."""
    t = xtrace.Trace.load(RECORDED)
    lo, hi = t.window()
    dev = t.devices[0]
    assert (hi - lo) / 1e9 == pytest.approx(0.25912105)
    busy = xtrace.busy_s(t, lo, hi)
    assert busy == pytest.approx(0.246964976)
    assert busy * 1e9 == pytest.approx(_brute_busy(t, dev, lo, hi),
                                       rel=2e-3)
    runs = xtrace.program_runs(t, dev, lo, hi)
    assert runs["jit_serve_step"] == pytest.approx(
        [0.082323276, 0.082330558, 0.082316181])
    assert len(runs["jit__threefry_fold_in"]) == 4
    b = xtrace.breakdown(t, lo, hi)
    # the loop over layers holds every costly operation and is not listed
    assert not any(" while " in name for name, _ in b["device_ops"])
    assert b["device_ops"][0][0] == \
        "jit_serve_step/%copy.164 copy f32[16,1025,16,16,128]"
    assert b["idle_gaps"][0] == ["np.asarray(jax.Array)",
                                 pytest.approx(0.002905129)]
    gaps = xtrace.gaps(xtrace.busy_intervals(t, dev, lo, hi), lo, hi)
    assert xtrace.length(gaps) / 1e9 == pytest.approx(
        0.25912105 - 0.246964976)


def test_recorded_four_chip_slice():
    """The end of one olmo1b-train-dp4 step on four TPU v5e chips: the last
    three gradient all-reduces and the eight parameter all-gathers run with
    nothing beside them, so all of their time is exposed; counted when the
    slice was cut."""
    t = xtrace.Trace.load(os.path.join(harness.HERE, "testdata",
                                       "train_dp4_slice.json.gz"))
    lo, hi = t.window()
    assert t.devices == ["TPU:0", "TPU:1", "TPU:2", "TPU:3"]
    assert (hi - lo) / 1e9 == pytest.approx(0.046197824)
    assert xtrace.busy_s(t, lo, hi) == pytest.approx(0.04582742575)
    want = {"TPU:0": 0.023652216, "TPU:1": 0.023653314,
            "TPU:2": 0.023652817, "TPU:3": 0.023655661}
    for dev, sec in want.items():
        total, exposed = xtrace.collective_s(t, dev, lo, hi)
        assert total == pytest.approx(sec)
        assert exposed == pytest.approx(sec)
        coll = xtrace.Trace({dev: [o for o in t.ops[dev] if
                                   xtrace.is_collective(xtrace.opcode(o[0]))]},
                            {}, [])
        assert total * 1e9 == pytest.approx(
            _brute_busy(coll, dev, lo, hi, step=100), rel=1e-3)
