"""Batch-fit runs of ``spreader.fit`` with one fault planted in the
stream engine, in a process of its own.

    python3 bench/tests/_bench_fit_faults.py <fault> [--full] [--seeds N ...]

By default one run at toy size (``test_bench_faults.py`` runs it on the
CPU); with ``--full``, runs of the cell as committed, at its own size,
one per seed.  Prints each run's numbers compared and
``<fault> correct=<bool>``.

Faults, each planted before the program is built: ``state_unchanged``,
a refresh that leaves the service's state as it was; ``half_points``,
half of each ingest batch left out; ``exchange_left_out``, the merge of
local clusters over the shards left out, so each local cluster keeps an
id of its own; ``answer_altered``, global label 1 given out as 0 where
it is made.  ``sound`` plants nothing."""
import argparse
import sys
import time

import jax.numpy as jnp
import numpy as np
from _bench_toy import spreader

from bench import harness, loops

FAULTS = ("sound", "state_unchanged", "half_points", "exchange_left_out",
          "answer_altered")


def plant(fault: str) -> None:
    from repro.serve import cluster_service as cs

    if fault == "state_unchanged":
        cs.ClusterService.refresh = lambda self, *a, **k: self._global
    elif fault == "half_points":
        ingest = cs.ShardControlPlane.ingest

        def half(self, shard, points, t=None):
            keep = len(points) // 2
            t = t if t is None or np.ndim(t) == 0 else np.asarray(t)[:keep]
            return ingest(self, shard, np.asarray(points)[:keep], t)
        cs.ShardControlPlane.ingest = half
    elif fault == "exchange_left_out":
        merge = cs.ddc.merge_delta

        def unmerged(batch, *a, **k):
            glob, maps, d2 = merge(batch, *a, **k)
            own = jnp.arange(maps.size, dtype=maps.dtype).reshape(maps.shape)
            return glob, jnp.where(maps >= 0, own, maps), d2
        cs.ddc.merge_delta = unmerged
    elif fault == "answer_altered":
        labels = cs._global_labels
        cs._global_labels = lambda *a: (lambda g: jnp.where(g == 1, 0, g))(labels(*a))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("fault", choices=FAULTS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2**31 + 99])
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)
    if args.full:
        import jax
        from repro.launch import compile_cache

        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    parts = harness.cell_parts(harness.load_doc(), "spreader.fit") if args.full \
        else spreader()
    cell, config, mix, e2e, layer = parts
    plant(args.fault)
    for seed in args.seeds:
        run = loops.run_batch_fit(config, mix, seed, args.seconds, False,
                                  time.perf_counter())
        out = harness.result(run, cell, e2e, layer)
        print(seed, run.notes, out["compared"], flush=True)
        print(f"{args.fault} correct={out['correct']}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
