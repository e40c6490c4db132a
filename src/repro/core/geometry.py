"""Computational-geometry primitives for DDC.

Two families live here:

* ``*_np`` — host-side NumPy reference implementations (exact, dynamic
  shapes).  These are the oracles used by tests and by the host
  (paper-faithful) DDC path.
* JAX functions — static-shape, mask-aware, TPU-friendly versions used by
  the distributed ``shard_map`` DDC path.  Contours are fixed-size padded
  buffers so they can cross TPU collectives.

The paper extracts non-convex cluster boundaries with a triangulation
algorithm (O(n log n)).  On TPU we replace triangulation with an
occupancy-grid boundary (rasterise + morphological erosion, conv-style),
which vectorises; the exact convex hull (monotone chain / Jarvis march)
is kept both as a compact fallback and as the test oracle.  See
DESIGN.md §3.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# ---------------------------------------------------------------------------
# NumPy reference geometry (host path + oracles)
# ---------------------------------------------------------------------------


def convex_hull_np(points: np.ndarray) -> np.ndarray:
    """Andrew's monotone chain.  Returns hull vertices in CCW order.

    ``points``: (n, 2).  Handles degenerate inputs (n <= 2, collinear).
    """
    pts = np.unique(np.asarray(points, dtype=np.float64), axis=0)
    n = len(pts)
    if n <= 2:
        return pts
    order = np.lexsort((pts[:, 1], pts[:, 0]))
    pts = pts[order]

    def cross(o, a, b):
        return (a[0] - o[0]) * (b[1] - o[1]) - (a[1] - o[1]) * (b[0] - o[0])

    lower: list = []
    for p in pts:
        while len(lower) >= 2 and cross(lower[-2], lower[-1], p) <= 0:
            lower.pop()
        lower.append(p)
    upper: list = []
    for p in pts[::-1]:
        while len(upper) >= 2 and cross(upper[-2], upper[-1], p) <= 0:
            upper.pop()
        upper.append(p)
    return np.array(lower[:-1] + upper[:-1])


def point_in_polygon_np(query: np.ndarray, poly: np.ndarray) -> np.ndarray:
    """Crossing-number point-in-polygon test.

    ``query``: (m, 2); ``poly``: (v, 2) ordered vertices.  Returns (m,) bool.
    """
    query = np.atleast_2d(query)
    x, y = query[:, 0], query[:, 1]
    v = len(poly)
    inside = np.zeros(len(query), dtype=bool)
    j = v - 1
    for i in range(v):
        xi, yi = poly[i]
        xj, yj = poly[j]
        crosses = ((yi > y) != (yj > y)) & (
            x < (xj - xi) * (y - yi) / (yj - yi + 1e-30) + xi
        )
        inside ^= crosses
        j = i
    return inside


def _segments_intersect_np(p1, p2, q1, q2) -> bool:
    def orient(a, b, c):
        val = (b[0] - a[0]) * (c[1] - a[1]) - (b[1] - a[1]) * (c[0] - a[0])
        return 0 if abs(val) < 1e-12 else (1 if val > 0 else -1)

    o1, o2 = orient(p1, p2, q1), orient(p1, p2, q2)
    o3, o4 = orient(q1, q2, p1), orient(q1, q2, p2)
    if o1 != o2 and o3 != o4:
        return True

    def on_seg(a, b, c):
        return (
            min(a[0], b[0]) - 1e-12 <= c[0] <= max(a[0], b[0]) + 1e-12
            and min(a[1], b[1]) - 1e-12 <= c[1] <= max(a[1], b[1]) + 1e-12
        )

    if o1 == 0 and on_seg(p1, p2, q1):
        return True
    if o2 == 0 and on_seg(p1, p2, q2):
        return True
    if o3 == 0 and on_seg(q1, q2, p1):
        return True
    if o4 == 0 and on_seg(q1, q2, p2):
        return True
    return False


def polygons_overlap_np(a: np.ndarray, b: np.ndarray) -> bool:
    """Exact polygon-overlap test: bbox prefilter, then containment /
    edge-intersection.  This is the paper's phase-2 merge predicate."""
    if len(a) == 0 or len(b) == 0:
        return False
    if len(a) < 3 or len(b) < 3:
        # Degenerate: fall back to proximity of point sets.
        d = np.linalg.norm(a[:, None, :] - b[None, :, :], axis=-1)
        return bool(d.min() < 1e-9)
    if (a[:, 0].max() < b[:, 0].min() or b[:, 0].max() < a[:, 0].min()
            or a[:, 1].max() < b[:, 1].min() or b[:, 1].max() < a[:, 1].min()):
        return False
    if point_in_polygon_np(a[:1], b)[0] or point_in_polygon_np(b[:1], a)[0]:
        return True
    na, nb = len(a), len(b)
    for i in range(na):
        p1, p2 = a[i], a[(i + 1) % na]
        for j in range(nb):
            q1, q2 = b[j], b[(j + 1) % nb]
            if _segments_intersect_np(p1, p2, q1, q2):
                return True
    return False


def grid_contour_np(
    points: np.ndarray, bounds: Tuple[float, float, float, float], grid: int
) -> np.ndarray:
    """Occupancy-grid boundary of a point set (NumPy oracle for the JAX
    version).  Returns boundary-cell centres, unordered."""
    x0, y0, x1, y1 = bounds
    sx = (grid - 1) / max(x1 - x0, 1e-12)
    sy = (grid - 1) / max(y1 - y0, 1e-12)
    ix = np.clip(((points[:, 0] - x0) * sx).astype(int), 0, grid - 1)
    iy = np.clip(((points[:, 1] - y0) * sy).astype(int), 0, grid - 1)
    occ = np.zeros((grid, grid), dtype=bool)
    occ[ix, iy] = True
    padded = np.pad(occ, 1)
    interior = np.ones_like(occ)
    for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
        interior &= padded[1 + dx : 1 + dx + grid, 1 + dy : 1 + dy + grid]
    boundary = occ & ~interior
    bx, by = np.nonzero(boundary)
    cx = x0 + (bx + 0.5) / sx
    cy = y0 + (by + 0.5) / sy
    return np.stack([cx, cy], axis=-1)


# ---------------------------------------------------------------------------
# JAX geometry — static shapes, mask-aware
# ---------------------------------------------------------------------------

BIG = 1e30


def grid_occupancy(
    points: Array,
    mask: Array,
    bounds: Tuple[float, float, float, float],
    grid: int,
) -> Array:
    """Rasterise masked points onto a (grid, grid) bool occupancy map.

    Bounds are *global* (config-static) so cells align across shards.
    """
    x0, y0, x1, y1 = bounds
    sx = (grid - 1) / max(x1 - x0, 1e-12)
    sy = (grid - 1) / max(y1 - y0, 1e-12)
    ix = jnp.clip(((points[:, 0] - x0) * sx), 0, grid - 1).astype(jnp.int32)
    iy = jnp.clip(((points[:, 1] - y0) * sy), 0, grid - 1).astype(jnp.int32)
    flat = ix * grid + iy
    occ = jnp.zeros((grid * grid,), jnp.int32)
    occ = occ.at[flat].add(mask.astype(jnp.int32), mode="drop")
    return (occ > 0).reshape(grid, grid)


def grid_boundary(occ: Array) -> Array:
    """Boundary cells: occupied with at least one unoccupied 4-neighbour
    (morphological erosion by a plus-shaped structuring element)."""
    occ_i = occ.astype(jnp.int32)
    padded = jnp.pad(occ_i, 1)
    g = occ.shape[0]
    interior = (
        padded[2:, 1:-1] * padded[:-2, 1:-1] * padded[1:-1, 2:] * padded[1:-1, :-2]
    )
    return occ & (interior == 0)


def cells_to_points(
    cells: Array, bounds: Tuple[float, float, float, float], max_verts: int
) -> Tuple[Array, Array]:
    """Select up to ``max_verts`` active cells and return their centres.

    Returns (points (max_verts, 2), count (), active cells ()); the
    contour was cut when the active cells outnumber ``max_verts``.
    Deterministic: row-major top-k on the active flag.
    """
    grid = cells.shape[0]
    x0, y0, x1, y1 = bounds
    sx = (grid - 1) / max(x1 - x0, 1e-12)
    sy = (grid - 1) / max(y1 - y0, 1e-12)
    flat = cells.reshape(-1)
    n_active = jnp.sum(flat.astype(jnp.int32))
    # Rank active cells first while preserving row-major order.
    keys = jnp.where(flat, jnp.arange(flat.shape[0]), flat.shape[0] + jnp.arange(flat.shape[0]))
    chosen_flat = -jax.lax.top_k(-keys, max_verts)[0]
    valid = chosen_flat < flat.shape[0]
    chosen = jnp.where(valid, chosen_flat, 0)
    bx = chosen // grid
    by = chosen % grid
    cx = x0 + (bx.astype(jnp.float32) + 0.5) / sx
    cy = y0 + (by.astype(jnp.float32) + 0.5) / sy
    pts = jnp.stack([cx, cy], axis=-1)
    pts = jnp.where(valid[:, None], pts, 0.0)
    return pts, jnp.minimum(n_active, max_verts), n_active


def contour_cells(
    points: Array,
    mask: Array,
    bounds: Tuple[float, float, float, float],
    grid: int,
    max_verts: int,
) -> Tuple[Array, Array, Array]:
    """``extract_contour`` plus the number of boundary cells it chose
    from: (contour (max_verts, 2), n_verts (), boundary cells ())."""
    occ = grid_occupancy(points, mask, bounds, grid)
    return cells_to_points(grid_boundary(occ), bounds, max_verts)


def extract_contour(
    points: Array,
    mask: Array,
    bounds: Tuple[float, float, float, float],
    grid: int,
    max_verts: int,
) -> Tuple[Array, Array]:
    """Grid-based contour of a masked point set.

    Returns (contour (max_verts, 2), n_verts ()).  This is DDC's data
    reduction: the contour is the cluster's network representation.
    """
    pts, cnt, _ = contour_cells(points, mask, bounds, grid, max_verts)
    return pts, cnt


def convex_hull_jax(points: Array, mask: Array, max_verts: int) -> Tuple[Array, Array]:
    """Jarvis-march (gift wrapping) convex hull with static shapes.

    O(max_verts * n) — fine for the contour budgets DDC uses.  Returns
    (hull (max_verts, 2) CCW from the lowest point, count ()).  Masked-out
    points are ignored.
    """
    n = points.shape[0]
    inf_pt = jnp.array([BIG, BIG], points.dtype)
    pts = jnp.where(mask[:, None], points, inf_pt)

    # Start: lexicographically smallest (y, then x).
    key = pts[:, 1] * (2 * BIG) + pts[:, 0]
    start = jnp.argmin(key)

    def cross(o, a, b):
        return (a[0] - o[0]) * (b[1] - o[1]) - (a[1] - o[1]) * (b[0] - o[0])

    def step(carry, _):
        cur, done, count = carry
        o = pts[cur]

        def better(cand, i):
            # candidate i beats current candidate `cand` if it is more
            # clockwise (cross < 0), or collinear and farther.
            c = cross(o, pts[cand], pts[i])
            d_cand = jnp.sum((pts[cand] - o) ** 2)
            d_i = jnp.sum((pts[i] - o) ** 2)
            valid = mask[i] & (i != cur)
            take = valid & ((c < 0) | ((jnp.abs(c) < 1e-12) & (d_i > d_cand)))
            invalid_cand = ~mask[cand] | (cand == cur)
            return jnp.where(take | (invalid_cand & valid), i, cand)

        nxt = jax.lax.fori_loop(0, n, lambda i, cand: better(cand, i), cur)
        emit = jnp.where(done, inf_pt, o)
        new_done = done | (nxt == start)
        return (nxt, new_done, count + (~done).astype(jnp.int32)), emit

    (_, _, count), hull = jax.lax.scan(
        step, (start, jnp.array(False), jnp.array(0, jnp.int32)), None, length=max_verts
    )
    hull = jnp.where(hull >= BIG, 0.0, hull)
    return hull, count


def vert_validity(counts: Array, valid: Array, max_verts: int) -> Array:
    """(m, max_verts) per-vertex validity of padded contour buffers: the
    first ``counts[i]`` vertices of each valid slot are real, the rest are
    padding.  Shared by the phase-2 merge matrix and slot matching."""
    return (jnp.arange(max_verts)[None, :] < counts[:, None]) & valid[:, None]


def min_cross_distance_sq(
    a: Array, a_count: Array, b: Array, b_count: Array
) -> Array:
    """Minimum squared distance between two padded point buffers."""
    va = jnp.arange(a.shape[0]) < a_count
    vb = jnp.arange(b.shape[0]) < b_count
    d2 = jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    d2 = jnp.where(va[:, None] & vb[None, :], d2, BIG)
    return jnp.min(d2)


def farthest_point_subsample(
    points: Array, mask: Array, k: int
) -> Tuple[Array, Array]:
    """Greedy k-centre subsampling of a masked point buffer.

    Used when a merged cluster's contour union exceeds the vertex budget:
    keeps the outline's extremes first.  Returns (subset (k, 2), count ()).
    """
    n = points.shape[0]
    inf_pt = jnp.array([BIG, BIG], points.dtype)
    pts = jnp.where(mask[:, None], points, inf_pt)
    n_valid = jnp.sum(mask.astype(jnp.int32))

    start = jnp.argmax(mask)  # first valid point
    d2 = jnp.where(mask, jnp.sum((pts - pts[start]) ** 2, axis=-1), -1.0)

    def step(carry, _):
        d2, last = carry
        nxt = jnp.argmax(d2)
        emit = pts[nxt]
        nd = jnp.sum((pts - pts[nxt]) ** 2, axis=-1)
        d2 = jnp.minimum(d2, jnp.where(mask, nd, -1.0))
        return (d2, nxt), emit

    (_, _), subset = jax.lax.scan(step, (d2, start), None, length=k - 1)
    subset = jnp.concatenate([pts[start][None], subset], axis=0)
    count = jnp.minimum(n_valid, k)
    valid = jnp.arange(k) < count
    subset = jnp.where(valid[:, None], subset, 0.0)
    return subset, count
