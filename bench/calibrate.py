#!/usr/bin/env python3
"""Readings that set a training cell's limits, at the cell's own size, on
the chip, in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--variants control,half_batch]

For every seed: set-up of the cell as a run makes it (the program's first
three steps through the window's own call), the program's state freed,
then the plain reference and each variant of it put in the program's
place. Prints one JSON line per seed: the numbers of ``compare.py`` for
the program (the lower readings) and for each variant (the control, in
the nearest precision below the configuration's, and the planted faults;
the upper readings). No window is measured.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import run as harness
    import compare
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    path = harness.load_module("paths", traffic["path"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log("calibrate: no TPU; nothing was run")
        return 2
    harness.log(f"[calibrate] compile cache {harness.enable_compile_cache()}")
    variants = [v for v in args.variants.split(",") if v]
    for seed in (int(s) for s in args.seeds.split(",")):
        runner = path.Runner(config, traffic, cell=cell["name"], seed=seed,
                             devices=devices[:cell["chips"]],
                             log=harness.log)
        runner.setup()
        runner.free()
        ref = runner.reference()
        line = {"seed": seed,
                "program": compare.training_numbers(runner.first, ref)}
        for v in variants:
            line[v] = compare.training_numbers(
                runner.reference(**runner.VARIANTS[v]), ref)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
