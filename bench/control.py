#!/usr/bin/env python3
"""The precision control: the plain reference put in the program's place,
computed in bfloat16, the step below the float32 the configurations
state, and run through the harness as a run of the cell runs the program.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 0.1]

For each seed it drives the cell's loop at the cell's own size with the
program's model replaced by the reference in bfloat16, and prints the
result's ``correct`` and each number compared beside its limit.  A limit
only holds where the control reads ``correct`` false.  It needs no chip,
and the benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ReferenceModel:
    """Stands where ``DDC`` stands in a batch-fit run: ``fit`` labels the
    same block partition as the program, by ``ddc_ref`` in ``dtype``."""

    def __init__(self, config: dict, dtype):
        self.config, self.dtype = config, dtype
        self.labels_ = None

    def fit(self, points):
        import numpy as np

        from bench.reference import ddc_ref

        pts = np.asarray(points, np.float32)
        parts = np.array_split(np.arange(len(pts)), int(self.config["shards"]))
        ref = ddc_ref.ddc([pts[p] for p in parts], self.config["ddc"], dtype=self.dtype)
        self.labels_ = np.concatenate(ref["labels"])
        return self


def control(parts: tuple, seed: int, seconds: float = 0.1) -> dict:
    """The result line of one run of a cell, given as ``harness.cell_parts``
    gives it, with the bfloat16 reference in the program's place."""
    import ml_dtypes

    from bench import harness, loops

    cell, config, mix, e2e, layer = parts
    build = loops.build_model
    loops.build_model = lambda cfg: ReferenceModel(cfg, ml_dtypes.bfloat16)
    try:
        run = loops.LOOPS[mix["loop"]](config, mix, seed, seconds, False,
                                       time.perf_counter())
    finally:
        loops.build_model = build
    return harness.result(run, cell, e2e, layer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    parts = harness.cell_parts(harness.load_doc(), args.workload)
    for seed in args.seeds:
        out = control(parts, seed, args.seconds)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
