"""Plain reference of the paper's two phases, in NumPy and SciPy only.

Phase 1 clusters each shard on its own with DBSCAN: a point is core when
its eps-neighbourhood, itself included, holds at least ``min_pts``
points; core points within eps of each other share a cluster; a border
point joins the neighbouring core cluster whose smallest member index is
least; the rest is noise.  Each local cluster is reduced to its contour:
the occupied cells of a ``grid`` x ``grid`` raster over ``bounds`` that
have an empty (or off-grid) 4-neighbour.

Phase 2 merges local clusters, from any shards, whose contours come
within the merge radius ``base + 1.5 * cell`` (``base`` is ``merge_eps``
or eps, ``cell`` the larger side of a raster cell), and closes the merge
transitively.  Contours are compared in whole cells, so the radius test
is an exact integer test (``merge_threshold`` refuses a radius that lies
too near a lattice distance for floating point to decide it).

The distance test of phase 1 is evaluated in ``dtype`` exactly as the
configuration states it, ``(xi - xj)**2 + (yi - yj)**2 <= eps**2`` with
every operation rounded to ``dtype``: float32 for the configurations
here, bfloat16 for the precision control.  Nothing here imports the
system under test.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy import sparse
from scipy.sparse import csgraph
from scipy.spatial import cKDTree

NOISE = -1


def _as(dtype, x):
    return np.asarray(x, np.float64).astype(dtype)


def neighbour_pairs(pts: np.ndarray, eps: float, dtype=np.float32):
    """(i, j) index arrays, i < j, of the pairs within eps by the
    configured test in ``dtype``.  A k-d tree proposes candidates with a
    radius a little wider than eps; the test itself decides."""
    p = _as(dtype, pts)
    slack = 1.05 if np.dtype(dtype).itemsize < 4 else 1.0 + 1e-4
    tree = cKDTree(p.astype(np.float64))
    cand = tree.query_pairs(float(eps) * slack, output_type="ndarray")
    if len(cand) == 0:
        return np.zeros((0,), np.int64), np.zeros((0,), np.int64)
    i, j = cand[:, 0], cand[:, 1]
    px, py = p[:, 0].copy(), p[:, 1].copy()
    dx = px[i] - px[j]
    dy = py[i] - py[j]
    d2 = dx * dx + dy * dy
    e = _as(dtype, eps)
    keep = d2 <= e * e
    return i[keep].astype(np.int64), j[keep].astype(np.int64)


def dbscan(pts: np.ndarray, eps: float, min_pts: int, dtype=np.float32):
    """Labels (n,) int64: the smallest index of each cluster's core
    points, -1 for noise; and the core flags (n,).  Points that coincide
    once rounded to ``dtype`` are searched as one site with a
    multiplicity, so a coarse ``dtype`` that stacks thousands of points
    on one site does not list every pair among them."""
    n = len(pts)
    if n == 0:
        return np.zeros((0,), np.int64), np.zeros((0,), bool)
    p = _as(dtype, pts)
    bits = np.ascontiguousarray(p).view(f"u{p.dtype.itemsize}")
    _, first, of, mult = np.unique(bits, axis=0, return_index=True,
                                   return_inverse=True, return_counts=True)
    site, of = p[first], of.reshape(-1)
    i, j = neighbour_pairs(site, eps, dtype)
    counts = mult + np.bincount(i, mult[j], len(site)) + np.bincount(j, mult[i], len(site))
    core_site = counts >= min_pts
    cc = core_site[i] & core_site[j]
    graph = sparse.coo_matrix(
        (np.ones(int(cc.sum()), np.int8), (i[cc], j[cc])), shape=(len(site),) * 2)
    _, comp = csgraph.connected_components(graph, directed=False)
    core = core_site[of]
    big = np.iinfo(np.int64).max
    root = np.full(comp.max() + 1, big, np.int64)
    np.minimum.at(root, comp[of[core]], np.nonzero(core)[0])
    labels = np.full(len(site), big, np.int64)
    labels[core_site] = root[comp[core_site]]
    # Border sites: the least label among their core neighbours.
    for a, b in ((i, j), (j, i)):
        sel = core_site[b] & ~core_site[a]
        np.minimum.at(labels, a[sel], labels[b[sel]])
    labels[labels == big] = NOISE
    return labels[of], core


def cells(pts: np.ndarray, bounds, grid: int, dtype=np.float32):
    """(n, 2) int raster cell of every point, rounded in ``dtype`` as
    the configuration's arithmetic rounds it."""
    x0, y0, x1, y1 = (float(b) for b in bounds)
    p = _as(dtype, pts)
    sx = _as(dtype, (grid - 1) / max(x1 - x0, 1e-12))
    sy = _as(dtype, (grid - 1) / max(y1 - y0, 1e-12))
    fx = (p[:, 0] - _as(dtype, x0)) * sx
    fy = (p[:, 1] - _as(dtype, y0)) * sy
    ix = np.clip(fx.astype(np.float64), 0, grid - 1).astype(np.int64)
    iy = np.clip(fy.astype(np.float64), 0, grid - 1).astype(np.int64)
    return np.stack([ix, iy], axis=-1)


def contour(cell_xy: np.ndarray, grid: int) -> np.ndarray:
    """Boundary cells (m, 2) of the occupied set, in row-major order."""
    occ = np.zeros((grid + 2, grid + 2), bool)
    occ[cell_xy[:, 0] + 1, cell_xy[:, 1] + 1] = True
    inner = (occ[2:, 1:-1] & occ[:-2, 1:-1] & occ[1:-1, 2:] & occ[1:-1, :-2])
    bx, by = np.nonzero(occ[1:-1, 1:-1] & ~inner)
    return np.stack([bx, by], axis=-1)


def merge_threshold(params: dict) -> float:
    """The merge radius squared, in squared raster-cell steps.  Contour
    vertices are cell centres, ``(x1 - x0) / (grid - 1)`` apart."""
    x0, y0, x1, y1 = (float(b) for b in params["bounds"])
    if not math.isclose(x1 - x0, y1 - y0, rel_tol=1e-9):
        raise ValueError(f"the reference needs square bounds, got {params['bounds']}")
    grid = int(params["grid"])
    base = params.get("merge_eps") or params["eps"]
    radius = float(base) + 1.5 * (x1 - x0) / grid
    step = (x1 - x0) / (grid - 1)
    t = (radius / step) ** 2
    if abs(t - round(t)) < 1e-3 * max(t, 1.0):
        raise ValueError(
            f"merge radius {radius} lies within rounding of a lattice "
            f"distance ({t} squared steps); float32 cannot decide it")
    return t


def ddc(shards: list, params: dict, dtype=np.float32, threads: int | None = None) -> dict:
    """Both phases over ``shards``, a list of (n_s, 2) point arrays, each
    in the shard's own slot order.  Returns a dict: ``labels``, one
    array of global component ids per shard (-1 noise); ``local``, the
    local cluster count per shard; ``contour_max``, the largest local
    contour in cells; ``n_global``, the number of global clusters."""
    eps, min_pts = float(params["eps"]), int(params["min_pts"])
    grid, bounds = int(params["grid"]), params["bounds"]
    t = merge_threshold(params)
    local_labels, owners, contours, n_local = [], [], [], []
    # SciPy's k-d tree and NumPy release the interpreter lock, so the
    # shards' phase 1 runs on threads; the result does not depend on it.
    with ThreadPoolExecutor(max_workers=min(len(shards), threads or os.cpu_count() or 1)) as pool:
        phase1 = list(pool.map(lambda p: dbscan(p, eps, min_pts, dtype)[0], shards))
    for s, (pts, lab) in enumerate(zip(shards, phase1)):
        ids = np.unique(lab[lab >= 0])
        dense = np.full(len(lab), -1, np.int64)
        if len(ids):
            dense[lab >= 0] = np.searchsorted(ids, lab[lab >= 0])
        local_labels.append(dense)
        n_local.append(len(ids))
        cxy = cells(pts, bounds, grid, dtype)
        for c in range(len(ids)):
            contours.append(contour(cxy[dense == c], grid))
            owners.append((s, c))
    m = len(contours)
    sizes = [len(c) for c in contours]
    if m:
        flat = np.concatenate(contours).astype(np.float64)
        who = np.repeat(np.arange(m), sizes)
        pairs = cKDTree(flat).query_pairs(math.sqrt(t), output_type="ndarray")
        a, b = who[pairs[:, 0]], who[pairs[:, 1]]
        graph = sparse.coo_matrix((np.ones(len(a), np.int8), (a, b)), shape=(m, m))
        _, comp = csgraph.connected_components(graph, directed=False)
    else:
        comp = np.zeros((0,), np.int64)
    base = np.cumsum([0] + n_local)
    out = []
    for s, dense in enumerate(local_labels):
        g = np.full(len(dense), -1, np.int64)
        hit = dense >= 0
        g[hit] = comp[base[s] + dense[hit]]
        out.append(g)
    return {"labels": out, "local": n_local,
            "contour_max": max(sizes, default=0),
            "n_global": int(len(np.unique(comp)))}


def partition_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Points on which two labelings differ as partitions: noise against
    cluster, plus the clustered points left over by the best one-to-one
    pairing of their cluster ids (greedy on the largest overlaps).  0
    exactly when the two describe the same clustering."""
    got = np.asarray(got, np.int64)
    want = np.asarray(want, np.int64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    both = (got >= 0) & (want >= 0)
    agree = int(((got < 0) & (want < 0)).sum())
    if both.any():
        pair = np.stack([got[both], want[both]], axis=1)
        uniq, counts = np.unique(pair, axis=0, return_counts=True)
        used_g, used_w = set(), set()
        for k in np.argsort(-counts, kind="stable"):
            g, w = int(uniq[k, 0]), int(uniq[k, 1])
            if g in used_g or w in used_w:
                continue
            used_g.add(g)
            used_w.add(w)
            agree += int(counts[k])
    return int(len(got) - agree)
