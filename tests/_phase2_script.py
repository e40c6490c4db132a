"""Merge-schedule equivalence property check, run under a 16-device CPU
override by tests/test_phase2_schedules.py.

For one layout (argv[1]) and every shard count in {2, 4, 8, 16}:
``merge_sync``, ``merge_async``, and ``merge_tree`` must produce the
IDENTICAL global clustering (same noise set, label bijection) as each
other and as the host oracle ``ddc_host`` on the same block partition.
Prints PASS lines; any exception fails.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import numpy as np
import jax.numpy as jnp

from repro.core import ddc
from repro.data import spatial
from repro.launch import mesh as mesh_mod

SHARD_COUNTS = (2, 4, 8, 16)

# Per-layout DDC parameters (eps, min_pts, grid, max_verts, max_clusters):
# tuned so no local OR merged contour overflows its vertex budget at any
# shard count and inter-cluster gaps clear both merge predicates with
# margin — see DESIGN.md §7.  The phase-2 layouts come from the shared
# spatial.PHASE2_LAYOUTS table; the remaining data/spatial.py generators
# get their own tuples here.
CASES = {
    "blobs": (lambda: spatial.make_blobs(1024, 5, seed=0, spread=0.015)[0],
              0.05, 5, 96, 48, 12),
    "clustered": (lambda: spatial.make_clustered(1024, 8, seed=0),
                  0.02, 5, 96, 64, 12),
    "d1": (lambda: spatial.make_d1(2048, seed=0), 0.02, 4, 64, 144, 16),
    "d2": (lambda: spatial.make_d2(2048, seed=1), 0.03, 4, 36, 104, 12),
    "worm_default": (lambda: spatial.make_worm(1024), 0.015, 5, 16, 96, 12),
}
CASES |= {
    name: (lambda spec=spec: spec["make"](2048), spec["eps"], spec["min_pts"],
           spec["grid"], spec["max_verts"], spec["max_clusters"])
    for name, spec in spatial.PHASE2_LAYOUTS.items()
}

same_partition = ddc.same_clustering


def check_layout(name: str):
    make, eps, min_pts, grid, max_verts, max_clusters = CASES[name]
    pts = make()
    x = jnp.asarray(pts)
    msk = jnp.ones(len(pts), bool)
    for k in SHARD_COUNTS:
        host_labels, _, _ = ddc.ddc_host(pts, k, eps, min_pts, contour="grid")
        mesh = mesh_mod.make_host_mesh(k)
        labels = {}
        for schedule in ("sync", "async", "tree"):
            cfg = ddc.DDCConfig(
                eps=eps, min_pts=min_pts, grid=grid, max_verts=max_verts,
                max_clusters=max_clusters, schedule=schedule,
            )
            run = ddc.make_ddc_fn(mesh, "data", cfg)
            glabels, gcs, _, _ = run(x, msk)
            assert not bool(np.asarray(gcs.overflow)), (
                f"{name} k={k} {schedule}: cluster budget overflow")
            labels[schedule] = np.asarray(glabels)
            assert same_partition(labels[schedule], host_labels), (
                f"{name} k={k}: {schedule} diverged from ddc_host")
        assert same_partition(labels["sync"], labels["async"])
        assert same_partition(labels["sync"], labels["tree"])
        print(f"PASS {name} k={k} "
              f"clusters={len(set(host_labels[host_labels >= 0]))}")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    names = list(CASES) if which == "all" else [which]
    for n in names:
        check_layout(n)
    print("ALL_OK")
