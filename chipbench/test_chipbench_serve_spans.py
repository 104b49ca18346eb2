"""The serve engine's host spans (``serve.*``) under the profiler on the CPU,
at the ``olmo1b-serve-chat`` cell's engine settings with call 0 of its
``chat`` traffic: the spans that ``xtrace.load_xplane`` keeps count the
decode steps and the host's reads, as counted apart by wrapping the
engine's jitted callables; the counts are those of the call's request
sizes, which are the same on any chip and for any seed; and the tokens are
the same with the profiler off."""

import glob
import os

import jax
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from chipbench import harness, spans, traffic, xtrace
from repro.configs import get_config
from repro.models.transformer import init_params
from repro.serve.engine import Request, ServeEngine

CELL = harness.load_json(harness.HERE, "cells", "olmo1b-serve-chat.json")
CHAT = harness.load_json(harness.HERE, "traffic", "chat.json")
SEED = 2 ** 33 + 17
# call 0 of the chat mix: 64 requests served in 3 batches and 31
# admissions over 354 decode steps, 3,447 tokens, 475 reads of the device
CALL0 = {"serve.batch": 3, "serve.prefill": 3, "serve.admit": 31,
         "serve.dispatch": 354, "serve.sync": 475}
CALL0_TOKENS = 3447


class Counted:
    """A callable that counts its calls into ``counts[name]``."""

    def __init__(self, fn, counts, name):
        self.fn, self.counts, self.name = fn, counts, name

    def __call__(self, *a, **k):
        self.counts[self.name] = self.counts.get(self.name, 0) + 1
        return self.fn(*a, **k)


def count_calls(eng, mp, counts):
    """Count the engine's calls whose results the host reads: the batch
    prefill, the decode step, the admission prefill and both page
    allocators."""
    for name in ("prefill", "step"):
        mp.setattr(eng, "_" + name,
                   Counted(getattr(eng, "_" + name), counts, name))
    admit_fn = eng._admit_fn
    mp.setattr(eng, "_admit_fn",
               lambda p: Counted(admit_fn(p), counts, "admit"))
    for name in ("alloc_slot_pages_jit", "alloc_step_pages_jit"):
        mp.setattr(engine_mod, name,
                   Counted(getattr(engine_mod, name), counts, name))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("olmo-1b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, **CELL["engine"])

    def requests():
        return [Request(prompt=p, max_new_tokens=o) for p, o in
                traffic.call_requests(CHAT, SEED, 0, cfg.vocab_size)]

    traced, plain = requests(), requests()
    counts = {}
    d = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with pytest.MonkeyPatch.context() as mp:
        count_calls(eng, mp, counts)
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
                eng.generate(traced)
        finally:
            jax.profiler.stop_trace()
    eng.generate(plain)
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    return {"traced": traced, "plain": plain, "counts": counts,
            "trace": xtrace.load_xplane(path)}


def test_spans_count_steps_and_reads(served):
    t, c = served["trace"], served["counts"]
    lo, hi = t.window()
    reads = (c["prefill"] + c["step"] + c.get("admit", 0)
             + c["alloc_slot_pages_jit"] + c.get("alloc_step_pages_jit", 0))
    assert c["step"] > 0 and c["admit"] > 0
    assert spans.count(t, spans.DISPATCH, lo, hi) == c["step"]
    assert spans.count(t, spans.SYNC, lo, hi) == reads
    assert spans.count(t, "serve.admit", lo, hi) == c["admit"]
    assert spans.count(t, "serve.prefill", lo, hi) == c["prefill"]
    assert spans.count(t, "serve.page_alloc", lo, hi) == (
        c["alloc_slot_pages_jit"] + c.get("alloc_step_pages_jit", 0))
    assert {h[0] for h in t.host if h[0].startswith(spans.PREFIX)} == {
        "serve.batch", "serve.pool_init", "serve.prefill", "serve.admit",
        "serve.page_alloc", "serve.record", "serve.dispatch", "serve.sync"}


def test_call_zero_counts(served):
    """The counts depend only on the call's request sizes and the engine's
    settings, since no request of the mix has a stop token: a traced chip
    window whose first call is call 0 reads the same."""
    t = served["trace"]
    lo, hi = t.window()
    assert traffic.call_sizes(CHAT, 0) == [
        (len(r.prompt), r.max_new_tokens) for r in served["traced"]]
    assert {name: spans.count(t, name, lo, hi) for name in CALL0} == CALL0
    assert sum(len(r.generated) for r in served["traced"]) == CALL0_TOKENS


def test_count_readers_on_the_cpu_trace(served):
    t, c = served["trace"], served["counts"]
    lo, hi = t.window()
    reqs = [(len(r.prompt), len(r.generated)) for r in served["traced"]]
    ctx = {"trace": t, "window": (lo, hi), "window_s": (hi - lo) / 1e9,
           "inputs": {"requests": reqs, "engine": CELL["engine"]}}
    read = lambda name: harness.load_reader(name)(ctx)
    assert read("host_syncs_per_token.serve") == \
        CALL0["serve.sync"] / CALL0_TOKENS
    sched = spans.scheduler_intervals(t, lo, hi)
    assert read("sched_ms_per_step.serve") == pytest.approx(
        1e3 * xtrace.length(sched) / 1e9 / c["step"])


def test_tokens_same_with_the_profiler_off(served):
    for a, b in zip(served["traced"], served["plain"]):
        np.testing.assert_array_equal(a.generated, b.generated)
        assert len(a.generated) == a.max_new_tokens
