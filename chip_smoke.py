#!/usr/bin/env python3
"""Chip smoke check: the DDC clustering service's main path on a TPU.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the cross-chip path only

One process, no child processes; JAX is touched only after the arguments
are parsed.  The compile cache is placed by ``repro.launch.compile_cache``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).
Findings go to stdout line by line; the LAST line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only when every phase ran and passed.  Without a TPU, or
run from a directory that lacks the repo's ``src/``, it exits non-zero
and prints no such line.

One chip (all through the ``repro.ddc`` facade and the ``kernels.ops``
dispatch a user's call reaches; ``ops.FORCE`` is never set):

1. kernels — at one 8,192-point shard, ``neighbor_count`` and
   ``min_label_sweep``, dense and block-sparse, equal ``kernels/ref.py``
   on the chip exactly; ``contour_min_d2`` at m = v = 128 matches the
   reference within rtol 1e-6 (DESIGN.md §7); the lowered
   ``core.ddc.local_phase`` holds ``tpu_custom_call``.
2. host — ``stream`` at 4 × 8,192 points labels like the ``host``
   backend (numpy ``dbscan_ref`` + exact polygon merge) under
   ``same_clustering``.
3. real size — ``stream`` at 16 shards × 65,536-slot rings (1,048,576
   live points), ingested in 8,192-point batches with a refresh after
   each round over the shards; 256-point queries of stored points get
   those points' global labels.  Then ``jit`` (sync schedule, so the
   contour kernel runs) at shards=1 on one 65,536-point shard labels like
   ``stream`` at shards=1 on the same points.

Four chips (``--four-chips``): ``dist`` at shards=4, one shard per chip,
4 × 65,536 points, against ``stream`` on the same points and call
sequence in this process — labels, the cached pair-d2 matrix and query
answers bit-identical; then ``jit`` at shards=4 (butterfly schedule,
ppermutes across chips) against ``stream`` under ``same_clustering``.

Timings printed here are smoke timings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SHARD = 8_192            # kernel-phase shard and ingest batch
HOST_SHARDS = 4          # host-reference phase: 4 x 8,192 points
REAL_SHARDS = 16         # real size: 16 x 65,536 = 1,048,576 live points
REAL_CAP = 65_536
FOUR_CHIP_SHARDS = 4
QUERIES = 256
NEIGHBOURS = 30          # mean eps-neighbourhood size the eps is chosen for


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def layout(n: int, seed: int):
    """The ``linked_ovals`` deployment layout at ``n`` points (three uniform
    ellipses, two overlapping across any partition cut; Morton-ordered so a
    block partition hands each shard a compact region) with the eps that
    gives about ``NEIGHBOURS`` neighbours at that density, and the layout's
    own contour budgets from ``PHASE2_LAYOUTS``."""
    import numpy as np
    from repro.data import spatial

    spec = spatial.PHASE2_LAYOUTS["linked_ovals"]
    pts = spec["make"](n, seed=seed)
    area = np.pi * (2 * 0.14 * 0.05 + 0.07 * 0.03)     # the three ellipses
    eps = float(np.sqrt(NEIGHBOURS / (np.pi * n / area)))
    params = dict(eps=eps, min_pts=spec["min_pts"], grid=spec["grid"],
                  max_verts=spec["max_verts"],
                  max_clusters=spec["max_clusters"])
    return pts, params


def stream_in(model, pts, shards: int, batch: int) -> dict:
    """Ingest ``pts`` block-partitioned over ``shards`` in ``batch``-point
    chunks, one chunk per shard per round, refreshing after each round.
    Returns host-clock seconds (each phase ends in block_until_ready)."""
    import jax
    from repro.data import spatial

    chunks = spatial.stream_batches(pts, shards, batch)
    t = {"ingest_s": 0.0, "refresh_s": 0.0, "rounds": 0}
    for r in range(0, len(chunks), shards):
        t0 = time.perf_counter()
        for shard, chunk in chunks[r:r + shards]:
            model.partial_fit(shard, chunk)
        svc = model.service
        jax.block_until_ready((svc._pts, svc._mask))
        t1 = time.perf_counter()
        jax.block_until_ready(svc.refresh())
        t2 = time.perf_counter()
        t["ingest_s"] += t1 - t0
        t["refresh_s"] += t2 - t1
        t["rounds"] += 1
    return t


def stream_model(params: dict, shards: int, cap: int, backend: str = "stream"):
    from repro.ddc import DDC, DDCConfig

    return DDC(DDCConfig(**params, backend=backend, shards=shards,
                         capacity=cap, max_batch=min(SHARD, cap),
                         max_queries=QUERIES))


def fit_labels(params: dict, backend: str, shards: int, pts, **kw):
    from repro.ddc import DDC, DDCConfig

    cfg = DDCConfig(**params, backend=backend, shards=shards,
                    max_batch=SHARD, **kw)
    return DDC(cfg).fit(pts).labels_


def n_clusters(labels) -> int:
    return len(set(labels[labels >= 0].tolist()))


# -- phase 1: kernels against their references, on the chip ------------------


def phase_kernels(n: int, m: int, v: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ddc
    from repro.core import dbscan as db
    from repro.kernels import ops, ref

    print(f"[kernels] one shard of {n} points; contour m=v={m}", flush=True)
    pts, params = layout(n, seed)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(pts)
    mask = jnp.asarray(rng.random(n) > 0.05)
    eps = params["eps"]
    ref_count = jax.jit(ref.neighbor_count)
    ref_sweep = jax.jit(ref.min_label_sweep)

    counts = ops.neighbor_count(x, mask, eps)
    want = ref_count(x, mask, eps)
    check(np.array_equal(counts, want),
          f"dense neighbor_count == ref (mean count "
          f"{float(np.mean(np.asarray(want)[np.asarray(mask)])):.1f})")
    core = (counts >= params["min_pts"]) & mask
    labels = jnp.asarray(rng.permutation(n), jnp.int32)
    check(np.array_equal(ops.min_label_sweep(x, mask, labels, core, eps),
                         ref_sweep(x, mask, labels, core, eps)),
          "dense min_label_sweep == ref")

    bt = ddc.DDCConfig().block_tile
    sp, sm, _ = db.spatial_sort(x, mask, bt)
    pairs = ops.build_tile_pairs(sp, sm, eps, bt=bt)
    print(f"  active tile pairs {int(pairs.n_active)} of "
          f"{(sp.shape[0] // bt) ** 2} (fraction {float(pairs.frac):.4f})",
          flush=True)
    scounts = ops.neighbor_count_sparse(sp, sm, eps, pairs, bt=bt)
    check(np.array_equal(scounts, ref_count(sp, sm, eps))
          and np.array_equal(scounts, jax.jit(
              ref.neighbor_count_sparse, static_argnums=6)(
              sp, sm, eps, pairs.rows, pairs.cols, pairs.flags, bt)),
          "sparse neighbor_count == ref (dense and pair-list oracles)")
    score = (scounts >= params["min_pts"]) & sm
    slabels = jnp.asarray(rng.permutation(sp.shape[0]), jnp.int32)
    check(np.array_equal(
        ops.min_label_sweep_sparse(sp, sm, slabels, score, eps, pairs, bt=bt),
        ref_sweep(sp, sm, slabels, score, eps)),
        "sparse min_label_sweep == ref")

    contours = jnp.asarray(rng.uniform(0, 1, (m, v, 2)), jnp.float32)
    ccounts = jnp.asarray(rng.integers(0, v + 1, m), jnp.int32)
    cvalid = jnp.asarray(rng.random(m) > 0.1)
    got = np.asarray(ops.contour_min_d2(contours, ccounts, cvalid))
    want = np.asarray(jax.jit(ref.contour_min_d2)(contours, ccounts, cvalid))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    print(f"  ok: contour_min_d2 == ref within rtol 1e-6 "
          f"(bit-equal: {np.array_equal(got, want)}, max |diff| "
          f"{float(np.max(np.abs(got - want)))})", flush=True)

    cfg = ddc.DDCConfig(eps=eps, min_pts=params["min_pts"])
    text = ddc.local_phase.lower(x, mask, cfg).as_text()
    check("tpu_custom_call" in text,
          f"lowered local_phase holds tpu_custom_call "
          f"({text.count('tpu_custom_call')} sites)")


# -- phase 2: the stream engine against the host reference -------------------


def phase_host(shards: int, per: int, seed: int) -> None:
    from repro.core.ddc import same_clustering
    from repro.ddc import DDCConfig

    n = shards * per
    print(f"[host] stream vs host at {shards} x {per} points", flush=True)
    pts, params = layout(n, seed)
    DDCConfig(**params, backend="host", shards=shards).validate(
        sample=pts[::shards])
    print(f"  sizing probe passed on a {len(pts[::shards])}-point sample; "
          f"eps={params['eps']:.6g} min_pts={params['min_pts']}", flush=True)
    t0 = time.perf_counter()
    stream = fit_labels(params, "stream", shards, pts)
    t1 = time.perf_counter()
    host = fit_labels(params, "host", shards, pts)
    t2 = time.perf_counter()
    print(f"  stream fit {t1 - t0:.3f} s, host fit {t2 - t1:.3f} s", flush=True)
    check(same_clustering(stream, host),
          f"stream == host under same_clustering ({n_clusters(host)} "
          f"clusters, {int((host < 0).sum())} noise points)")


# -- phase 3: real size -------------------------------------------------------


def check_queries(model, seed: int, rounds: int = 2) -> list:
    """Query ``QUERIES`` stored points ``rounds`` times (the first round
    compiles); every stored clustered point must get its own global label.
    Returns the per-round host-clock seconds."""
    import numpy as np

    pts, labels = model.points_, model.labels_
    rng = np.random.default_rng(seed)
    secs = []
    for _ in range(rounds):
        idx = rng.choice(len(pts), QUERIES, replace=False)
        t0 = time.perf_counter()
        got = np.asarray(model.query(pts[idx]))
        secs.append(time.perf_counter() - t0)
        want = labels[idx]
        hit = want >= 0
        check(np.array_equal(got[hit], want[hit]),
              f"{int(hit.sum())}/{QUERIES} queried stored clustered points "
              f"got their global labels")
    return secs


def phase_real(shards: int, cap: int, batch: int, seed: int) -> None:
    import jax
    import numpy as np
    from repro.core.ddc import same_clustering

    n = shards * cap
    print(f"[real] stream at {shards} shards x {cap} slots = {n} live "
          f"points, batches of {batch}", flush=True)
    pts, params = layout(n, seed)
    print(f"  eps={params['eps']:.6g} min_pts={params['min_pts']} "
          f"grid={params['grid']} max_verts={params['max_verts']} "
          f"max_clusters={params['max_clusters']}", flush=True)
    model = stream_model(params, shards, cap)
    t = stream_in(model, pts, shards, batch)
    check(model.service.n_live() == n, f"{n} live points after "
          f"{t['rounds']} rounds")
    labels = model.labels_
    check(n_clusters(labels) > 0 and not bool(model.service.global_set
                                              .overflow),
          f"{n_clusters(labels)} global clusters, no slot overflow")
    q = check_queries(model, seed)
    print(f"  smoke timings (one run, not benchmark numbers): ingest "
          f"{t['ingest_s']:.3f} s, refresh {t['refresh_s']:.3f} s over "
          f"{t['rounds']} rounds (first round compiles); query of "
          f"{QUERIES} points {q[-1]:.4f} s warm, {q[0]:.4f} s first",
          flush=True)
    del model

    one = pts[:cap]
    t0 = time.perf_counter()
    jit_labels = fit_labels(params, "jit", 1, one, schedule="sync")
    t1 = time.perf_counter()
    stream_labels = fit_labels(params, "stream", 1, one)
    t2 = time.perf_counter()
    print(f"  jit fit {t1 - t0:.3f} s, stream fit {t2 - t1:.3f} s at shards=1 "
          f"x {cap} points (each includes its compile)", flush=True)
    check(same_clustering(jit_labels, stream_labels),
          f"jit == stream at shards=1 ({n_clusters(stream_labels)} clusters)")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)


# -- --four-chips: the path that exists only across chips --------------------


def phase_four_chips(shards: int, cap: int, batch: int, seed: int) -> None:
    import numpy as np
    from repro.core.ddc import same_clustering

    n = shards * cap
    print(f"[four-chips] dist vs stream at {shards} shards x {cap} points",
          flush=True)
    pts, params = layout(n, seed)
    runs = {}
    for backend in ("dist", "stream"):
        model = stream_model(params, shards, cap, backend)
        t = stream_in(model, pts, shards, batch)
        runs[backend] = (model, t)
        print(f"  {backend}: smoke timings ingest {t['ingest_s']:.3f} s, "
              f"refresh {t['refresh_s']:.3f} s over {t['rounds']} rounds",
              flush=True)
    dist, stream = runs["dist"][0], runs["stream"][0]
    svc = dist.service
    for name in ("_pts", "_mask", "_dense", "_glabels"):
        devs = sorted(d.id for d in getattr(svc, name).sharding.device_set)
        print(f"  dist lane array {name}: devices {devs}", flush=True)
        check(len(devs) == shards, f"{name} spread over {shards} chips")
    check(np.array_equal(dist.labels_, stream.labels_),
          "dist labels bit-identical to stream")
    check(np.array_equal(np.asarray(svc.pair_d2),
                         np.asarray(stream.service.pair_d2)),
          "dist cached pair-d2 bit-identical to stream")
    rng = np.random.default_rng(seed)
    live = dist.points_
    q = live[rng.choice(len(live), QUERIES, replace=False)]
    check(np.array_equal(np.asarray(dist.query(q)),
                         np.asarray(stream.query(q))),
          "dist query answers bit-identical to stream")
    stream_labels = stream.labels_
    t0 = time.perf_counter()
    jit_labels = fit_labels(params, "jit", shards, pts)
    print(f"  jit fit at shards={shards}: {time.perf_counter() - t0:.3f} s "
          f"(includes compile)", flush=True)
    check(same_clustering(jit_labels, stream_labels),
          f"jit at shards={shards} == stream "
          f"({n_clusters(stream_labels)} clusters)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip dist/jit path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated layout and query")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    import jax
    from jax import monitoring

    compile_s, hits, misses = [0.0], [0], [0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            misses[0] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    need = FOUR_CHIP_SHARDS if args.four_chips else 1
    if device["platform"] != "tpu" or len(devices) < need:
        print(f"needs {need} TPU chip(s), found {device}; this check runs "
              f"only on the chip", file=sys.stderr)
        return 2
    print(f"device {device}; compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(FOUR_CHIP_SHARDS, REAL_CAP, SHARD, args.seed)
    else:
        phase_kernels(SHARD, 128, 128, args.seed)
        phase_host(HOST_SHARDS, SHARD, args.seed)
        phase_real(REAL_SHARDS, REAL_CAP, SHARD, args.seed)
    print(f"wall {time.perf_counter() - t0:.1f} s; backend compile "
          f"{compile_s[0]:.1f} s; persistent cache hits {hits[0]}, misses "
          f"{misses[0]}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
