#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the TPU this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (from process start: weights made on the device from the seed, the
program built, every shape the cell's traffic uses compiled or loaded from
the persistent compilation cache, and warmed) is ``setup_s``. The window
then runs for ``--seconds``; with ``--trace 0`` it reports the cell's
end-to-end metrics, with ``--trace 1`` it runs under the profiler and
reports the per-layer metrics that ``metrics/<name>.py`` read from the
trace. After the window the device's peak memory is read, the program's
state is freed, and what the window produced is compared with the float32
reference: each number compared is printed beside its limit as the last
lines of standard error and under ``checks`` in the result line, the last
line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, xtrace  # noqa: E402
from chipbench.entries import entry  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def layer_metrics(cell, run, trace, peaks, config):
    """(per-layer metrics, busy_s, window_s, breakdown) from the trace."""
    lo, hi = trace.window()
    ctx = {"trace": trace, "window": (lo, hi), "window_s": (hi - lo) / 1e9,
           "config": config, "peaks": peaks, "chips": cell.chips,
           "inputs": run.layer_inputs()}
    out = {}
    if not trace.devices:
        harness.say("[run] the trace holds no device operations")
        return out, 0.0, (hi - lo) / 1e9, xtrace.breakdown(trace, lo, hi)
    for m in cell.per_layer:
        value = harness.load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return (out, xtrace.busy_s(trace, lo, hi), (hi - lo) / 1e9,
            xtrace.breakdown(trace, lo, hi))


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        cell = harness.load_cell(args.workload, root)
        if require_tpu:
            devs = harness.require_chips(cell.chips)
        else:
            import jax
            devs = jax.devices()[: cell.chips]
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # small programs (the page allocator's) are cached too, so that every
    # run after the first loads them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.say(f"[run] {cell.name}: {len(devs)} x {devs[0].device_kind} "
                f"({devs[0].platform}), jax {jax.__version__}, compile "
                f"cache {cache}, seed {args.seed}")
    peaks = harness.peaks(devs[0].device_kind) if require_tpu else None
    clock = harness.CompileClock()
    run = entry(cell.entry).Run(cell, args.seed, devs)
    run.setup()
    setup_s = time.perf_counter() - T0

    annotate = jax.profiler.TraceAnnotation
    lowered = clock.lowered
    clock.recording = True
    if args.trace:
        with xtrace.capture() as cap:
            with annotate(xtrace.WINDOW_SPAN):
                res = run.window(args.seconds, annotate)
    else:
        res = run.window(args.seconds, annotate)
    clock.recording = False
    in_window = clock.lowered - lowered
    harness.say(f"[run] setup_s {setup_s:.3f} (backend compile "
                f"{clock.compile_s:.3f} s); programs compiled inside the "
                f"window: {in_window}")
    for name in clock.names:
        harness.say(f"[run] compiled inside the window: {name}")
    device = harness.device_record(devs)

    breakdown = None
    if args.trace:
        metrics, busy, window_s, breakdown = layer_metrics(
            cell, run, cap["trace"], peaks, cell.config)
        device["busy_s"] = busy
        device["window_s"] = window_s
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    run.free()
    compared = run.check()
    correct = all(c.ok for c in compared)
    for c in compared:
        harness.say(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
                    f"{'ok' if c.ok else 'FAILED'}")
    print(harness.result_line(correct=correct, attempted=res["attempted"],
                              failed=res["failed"], metrics=metrics,
                              device=device, compared=compared,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
