"""The serve-scheduler readers of the engine's host spans (``serve.*``),
against counts made by hand: on a small hand-written trace whose idle
overlaps, scheduler times and counts can be worked out on paper, and on
recorded slices of TPU v5e traces (``testdata/``): the one recorded before
the engine had spans reads as it did, and nothing for the new readers; the
one recorded with them reads the counts made when it was cut."""

import os

import pytest

from chipbench import harness, spans, xtrace

NEW = ("sync_idle.serve", "sched_idle.serve", "sched_ms_per_step.serve",
       "host_syncs_per_token.serve")
ENGINE = {"batch_size": 2, "max_len": 64, "page_size": 16}


def span_trace():
    """A batch of two slots on one chip, window [0, 2000] ns: the prefill
    runs on the device [100, 300] and two decode steps [500, 900] and
    [1200, 1600]. The host builds the pool (mapping pages, one read), puts
    the prefill on the device, reads it back, records, admits a request
    (one read), puts the first step on the device and reads it, records,
    maps the pages of a page boundary (one read), puts the second step on
    the device and reads it, records, and leaves [1700, 2000] to no span."""
    ops = {"TPU:0": [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)",
                      100, 300, ""],
                     ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x)",
                      500, 900, ""],
                     ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x)",
                      1200, 1600, ""]]}
    modules = {"TPU:0": [["jit_prefill", 100, 300],
                         ["jit_serve_step", 500, 900],
                         ["jit_serve_step", 1200, 1600]]}
    host = [[xtrace.WINDOW_SPAN, 0, 2000], ["generate call 0", 0, 2000],
            ["serve.batch", 0, 1700],
            ["serve.pool_init", 0, 100], ["serve.page_alloc", 20, 80],
            ["serve.sync", 50, 70],
            ["serve.prefill", 100, 120], ["serve.sync", 120, 320],
            ["serve.record", 320, 360],
            ["serve.admit", 360, 480], ["serve.sync", 440, 470],
            ["serve.dispatch", 480, 500], ["serve.sync", 500, 950],
            ["serve.record", 950, 1000],
            ["serve.page_alloc", 1000, 1100], ["serve.sync", 1050, 1060],
            ["serve.dispatch", 1100, 1200], ["serve.sync", 1200, 1650],
            ["serve.record", 1650, 1700]]
    t = xtrace.Trace(ops, modules, host)
    xtrace.attach_programs(t)
    return t


def ctx_of(trace, requests):
    lo, hi = trace.window()
    return {"trace": trace, "window": (lo, hi), "window_s": (hi - lo) / 1e9,
            "config": harness.load_json(harness.HERE, "configs",
                                        "olmo-1b.json"),
            "chips": len(trace.devices), "peaks": None,
            "inputs": {"requests": requests, "engine": ENGINE,
                       "cache_dtype": "float32"}}


def test_hand_counted_intervals():
    t = span_trace()
    # reads: [50, 70], [120, 320], [440, 470], [500, 950], [1050, 1060],
    # [1200, 1650]
    assert spans.sync_intervals(t, 0, 2000) == [
        (50, 70), (120, 320), (440, 470), (500, 950), (1050, 1060),
        (1200, 1650)]
    # scheduler spans [0, 120], [320, 500], [950, 1200], [1650, 1700] less
    # the reads inside them
    assert spans.scheduler_intervals(t, 0, 2000) == [
        (0, 50), (70, 120), (320, 440), (470, 500), (950, 1050),
        (1060, 1200), (1650, 1700)]
    assert spans.count(t, spans.SYNC, 0, 2000) == 6
    assert spans.count(t, spans.DISPATCH, 0, 2000) == 2
    # a span that starts before the window is clipped, not counted
    assert spans.count(t, spans.SYNC, 100, 2000) == 5
    assert spans.sync_intervals(t, 300, 600) == [(300, 320), (440, 470),
                                                 (500, 600)]


def test_hand_counted_readers():
    t = span_trace()
    # request 0 (prompt 20) took 3 tokens, request 1 (prompt 8) 2
    ctx = ctx_of(t, [(20, 3), (8, 2)])
    read = lambda name: harness.load_reader(name)(ctx)
    # idle [0, 100], [300, 500], [900, 1200], [1600, 2000]: 1000 of 2000
    assert read("device_idle.serve") == pytest.approx(50.0)
    # idle in the reads: 20 + 20 + 30 + 50 + 10 + 50 ns
    assert read("sync_idle.serve") == pytest.approx(100 * 180 / 2000)
    # idle in the scheduler: 50 + 30 + 120 + 30 + 100 + 140 + 50 ns
    assert read("sched_idle.serve") == pytest.approx(100 * 520 / 2000)
    # the rest of the idle time, [1700, 2000], lies in no engine span
    # scheduler time 50 + 50 + 120 + 30 + 100 + 140 + 50 ns over 2 steps
    assert read("sched_ms_per_step.serve") == pytest.approx(270e-6)
    assert read("host_syncs_per_token.serve") == pytest.approx(6 / 5)
    # the spans now name the host's idle gaps
    gaps = xtrace.breakdown(t, 0, 2000)["idle_gaps"]
    assert gaps[0] == ["host, after serve.dispatch", pytest.approx(400e-9)]
    assert [name for name, _ in gaps[1:]] == [
        "serve.batch", "serve.admit", "serve.page_alloc"]


def test_readers_read_nothing_without_spans():
    t = span_trace()
    t.host = [h for h in t.host if not h[0].startswith(spans.PREFIX)]
    ctx = ctx_of(t, [(20, 3), (8, 2)])
    for name in NEW:
        assert harness.load_reader(name)(ctx) is None


def test_recorded_slice_without_spans_reads_as_before():
    """The slice of olmo1b-serve-decode recorded before the engine had
    spans: the readers of the device alone read what they read then."""
    t = xtrace.Trace.load(os.path.join(harness.HERE, "testdata",
                                       "serve_decode_slice.json.gz"))
    ctx = ctx_of(t, [(512, 300)] * 16)
    read = lambda name: harness.load_reader(name)(ctx)
    assert read("device_idle.serve") == pytest.approx(4.691272283745384)
    assert read("prefill_share.serve") == 0.0
    assert read("decode_step_ms.serve") == pytest.approx(82.323276)
    for name in NEW:
        assert read(name) is None


CHAT_SLICE = os.path.join(harness.HERE, "testdata",
                          "serve_chat_spans_slice.json.gz")


def test_recorded_slice_with_spans():
    """Two decode steps of olmo1b-serve-chat on a TPU v5e with one
    admission and one page-boundary allocation between them (3,011
    operations; 5 reads, 2 dispatches, 2 page allocations, one inside the
    admission), counted when the slice was cut."""
    t = xtrace.Trace.load(CHAT_SLICE)
    lo, hi = t.window()
    assert (hi - lo) / 1e9 == pytest.approx(0.047869968)
    counts = {n: spans.count(t, n, lo, hi) for n in (
        "serve.sync", "serve.dispatch", "serve.admit", "serve.page_alloc",
        "serve.record", "serve.prefill", "serve.pool_init")}
    assert counts == {"serve.sync": 5, "serve.dispatch": 2,
                      "serve.admit": 1, "serve.page_alloc": 2,
                      "serve.record": 1, "serve.prefill": 0,
                      "serve.pool_init": 0}
    runs = xtrace.program_runs(t, t.devices[0], lo, hi)
    assert len(runs["jit_serve_step"]) == 2 and len(runs["jit_admit"]) == 1
    # each request of the slice took 5 / 10 = 0.5 reads a token
    ctx = ctx_of(t, [(64, 10)])
    read = lambda name: harness.load_reader(name)(ctx)
    assert read("device_idle.serve") == pytest.approx(35.1017823951752)
    assert read("sync_idle.serve") == pytest.approx(17.636554509499568)
    assert read("sched_idle.serve") == pytest.approx(17.402996801669055)
    assert read("sched_ms_per_step.serve") == pytest.approx(6.011775)
    assert read("host_syncs_per_token.serve") == pytest.approx(0.5)
    # the engine's spans name the longest idle gaps
    gaps = xtrace.breakdown(t, lo, hi)["idle_gaps"]
    assert gaps[0] == ["serve.page_alloc", pytest.approx(0.003581804)]


def test_reads_and_scheduler_cover_the_engine_spans():
    """The reads and the scheduler's time are disjoint and together cover
    every engine span but the batch: the idle time they leave is the idle
    time outside the engine's spans (0.06% of the recorded slice)."""
    t = xtrace.Trace.load(CHAT_SLICE)
    lo, hi = t.window()
    sync = spans.sync_intervals(t, lo, hi)
    sched = spans.scheduler_intervals(t, lo, hi)
    assert xtrace.minus(sync, sched) == sync
    engine = xtrace.union(xtrace.clip(
        ((s, e) for n, s, e in t.host
         if n.startswith(spans.PREFIX) and n != "serve.batch"), lo, hi))
    assert xtrace.union(sync + sched) == engine
    idle = xtrace.gaps(xtrace.busy_intervals(t, t.devices[0], lo, hi),
                       lo, hi)
    outside = xtrace.length(xtrace.minus(idle, engine)) / 1e9
    covered = spans.idle_s(t, sync, lo, hi) + spans.idle_s(t, sched, lo, hi)
    assert covered + outside == pytest.approx(xtrace.length(idle) / 1e9)
    assert outside / ((hi - lo) / 1e9) == pytest.approx(0.0006, abs=1e-4)
