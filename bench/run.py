#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number checked against the
plain reference beside its limit (also the last lines of standard error).

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.  The persistent compilation cache is placed
by ``repro.launch.compile_cache`` (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``).  Run it from the root of a checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    doc = harness.load_doc()
    cell = harness.cell_parts(doc, args.workload)[0]
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    # Every program, however quick to compile, goes to the cache, so only
    # a cell's first run in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    need = int(cell["chips"])
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"{args.workload} needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    out, lines = harness.run_cell(doc, args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    for line in lines:
        print(line, flush=True)
    for line in harness.compared_lines(out):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
