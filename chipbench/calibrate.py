#!/usr/bin/env python3
"""Readings that set a cell's correctness limits; not part of a benchmark
run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 20] [--faults half,local]

In one process, for each seed, the cell is set up as a run sets it up and
the numbers the check compares are read:

* serving: a short window at the cell's own load, then the widest gap of
  the served tokens (the program's reading) and, on the control seeds, the
  widest gap of the tokens that the float8 reference ranks first at each
  position of the same sequences (the control's reading);
* training: the set-up steps, then the check's three numbers against the
  float32 reference (the program's reading), against the float8 reference
  put in the program's place (the control's), and against the reference
  with each fault of ``--faults`` planted in it.

A cell need not be listed in BENCHMARK.json to be read here.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402
from chipbench.entries import entry  # noqa: E402


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    import contextlib
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, listed=False)
    devs = harness.require_chips(cell.chips)
    faults = [f for f in args.faults.split(",") if f]
    for seed in args.seeds:
        run = entry(cell.entry).Run(cell, seed, devs)
        run.setup()
        row = {"seed": seed}
        if cell.entry == "serve":
            run.window(args.seconds, lambda _: contextlib.nullcontext())
            run.free()
            g, n = run.gaps()
            row |= {"program": float(g.max()), "tokens": n}
            if seed in args.control_seeds:
                row["control"] = float(run.gaps(control=True)[0].max())
        else:
            run.free()
            row["program"] = run.compare(*run.reference())
            if seed in args.control_seeds:
                row["control"] = run.compare(*run.reference(control=True))
                for f in faults:
                    row[f] = run.compare(*run.reference(fault=f))
        print(json.dumps(row), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
