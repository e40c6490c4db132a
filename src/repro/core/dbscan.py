"""DBSCAN — the paper's local clustering algorithm, in two forms.

* ``dbscan_ref`` — classic BFS DBSCAN in NumPy (the oracle; O(n^2) with
  blockwise distance computation, matching the paper's complexity model).
* ``dbscan`` — TPU-native JAX version: ε-neighbour counts and min-label
  propagation are fused distance tiles (kernels/pairwise_dist.py), cluster
  labels converge by fixed-point iteration under ``lax.while_loop``.

Two composed optimisations make the JAX version near-linear on clustered
spatial data (DESIGN.md §4–§5):

* **Block-sparse spatial pruning** (``block_sparse``): points are sorted
  by Morton code so ε-neighbours land in nearby tiles, per-tile bounding
  boxes prune provably-far tile pairs, and the sweeps run gathered-grid
  kernels over the active-pair list only (dense-kernel fallback when the
  active fraction is high).  Labels come back in caller order, bit-exact
  with the dense path.
* **Pointer doubling** (``pointer_doubling``): each sweep is followed by
  ``labels <- min(labels, labels[labels])`` shortcut steps, collapsing
  label-chase chains so convergence needs O(log n) sweeps instead of
  O(core-graph diameter) — a worm-shaped cluster needs tens, not
  hundreds, of O(n²)-cost sweeps.

Semantics (both): a point is *core* iff its ε-neighbourhood (self
included) has >= min_pts points.  Core points within ε of each other share
a cluster; border points adopt the smallest neighbouring core label;
everything else is noise (-1).  Labels are canonicalised to the smallest
point index in the cluster, so the two implementations agree exactly up
to the tie-break rule for border points shared by several clusters —
both use min-label, making outputs identical.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partitioner
from repro.kernels import ops

NOISE = -1
SENTINEL = 2**30

# Runtime dense fallback: when more than this fraction of tile pairs is
# active, bounding-box pruning cannot pay for its gather overhead and the
# sweeps use the dense kernels instead (same math, same results).
DENSE_FALLBACK_FRAC = 0.5


def dbscan_ref(points: np.ndarray, eps: float, min_pts: int) -> np.ndarray:
    """NumPy oracle.  Returns labels (n,) int32, noise = -1, labels are
    the minimum point index of each cluster's core set."""
    pts = np.asarray(points, dtype=np.float64)
    n = len(pts)
    if n == 0:
        return np.zeros((0,), np.int32)
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    adj = d2 <= eps * eps
    counts = adj.sum(1)
    core = counts >= min_pts

    labels = np.full(n, SENTINEL, np.int64)
    # Connected components over core points (edges between core pairs).
    for i in range(n):
        if not core[i] or labels[i] != SENTINEL:
            continue
        stack = [i]
        labels[i] = i
        while stack:
            u = stack.pop()
            for v in np.nonzero(adj[u] & core)[0]:
                if labels[v] == SENTINEL:
                    labels[v] = i
                    stack.append(v)
    # Canonicalise: min core index per component.
    for comp in set(labels[core]):
        members = np.nonzero(core & (labels == comp))[0]
        labels[members] = members.min()
    # Border points: min label among core neighbours.
    for i in range(n):
        if core[i]:
            continue
        neigh = np.nonzero(adj[i] & core)[0]
        labels[i] = labels[neigh].min() if len(neigh) else SENTINEL
    labels[labels == SENTINEL] = NOISE
    return labels.astype(np.int32)


class DBSCANResult(NamedTuple):
    labels: jax.Array   # (n,) int32; -1 noise, else min core index
    core: jax.Array     # (n,) bool
    n_clusters: jax.Array  # () int32
    n_sweeps: jax.Array  # () int32 — propagation sweeps to convergence


def _shortcut(labels: jax.Array, steps: int) -> jax.Array:
    """Pointer-doubling: ``labels <- min(labels, labels[labels])``, ``steps``
    times.  Valid because for core i, labels[i] is always the index of a
    core point in the same cluster (so the jump stays in-cluster and is
    monotone non-increasing); SENTINEL entries (non-core / padding, all
    >= n) never jump.  ``steps`` = ceil(log2 n) fully compresses any
    label chain a sweep can produce."""
    n = labels.shape[0]

    def body(_, l):
        jumped = jnp.take(l, jnp.where(l < n, l, 0))
        return jnp.minimum(l, jnp.where(l < n, jumped, l))

    return jax.lax.fori_loop(0, steps, body, labels)


def spatial_sort(points: jax.Array, mask: jax.Array, bt: int):
    """Block-sparse preamble: pad to a ``bt`` multiple and Morton-sort.

    Bounds for the Morton grid come from *masked* points only — padding
    zeros or masked garbage must not stretch the grid (offset data would
    otherwise collapse into one cell and defeat the pruning entirely).
    Masked/padding points sort to the tail tiles.  Returns
    (sorted_points, sorted_mask, order); shared by the benchmark so the
    measured sort is the shipped sort."""
    n = points.shape[0]
    pad = (-n) % bt
    pp = jnp.pad(points, ((0, pad), (0, 0)))
    mm = jnp.pad(mask, (0, pad))
    big = jnp.float32(3.4e38)
    lo = jnp.min(jnp.where(mm[:, None], pp, big), axis=0)
    hi = jnp.max(jnp.where(mm[:, None], pp, -big), axis=0)
    code = partitioner.morton_code(pp, bounds=(lo[0], lo[1], hi[0], hi[1]))
    code = jnp.where(mm, code, jnp.int32(2**30))
    order = jnp.argsort(code)
    return jnp.take(pp, order, axis=0), jnp.take(mm, order), order


def _propagate(sweep_fn, init: jax.Array, core: jax.Array, max_iters: int,
               doubling_steps: int):
    """Iterate min-label sweeps (+ optional pointer doubling) to fixed
    point.  Returns (labels, n_sweeps)."""

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    def body(state):
        labels, _, it = state
        swept = sweep_fn(labels)
        new = jnp.where(core, jnp.minimum(labels, swept), labels)
        if doubling_steps:
            new = _shortcut(new, doubling_steps)
        return new, jnp.any(new != labels), it + 1

    labels, _, n_sweeps = jax.lax.while_loop(
        cond, body, (init, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    )
    return labels, n_sweeps


@functools.partial(
    jax.jit,
    static_argnames=("min_pts", "max_iters", "block_sparse", "bt",
                     "pointer_doubling", "dense_fallback_frac"),
)
def dbscan(
    points: jax.Array,
    mask: jax.Array,
    eps: float | jax.Array,
    min_pts: int,
    max_iters: int = 512,
    *,
    block_sparse: str = "auto",
    bt: int = 512,
    pointer_doubling: bool = True,
    dense_fallback_frac: float = DENSE_FALLBACK_FRAC,
) -> DBSCANResult:
    """TPU-native DBSCAN on a padded point buffer.

    points: (n, d); mask: (n,) bool (padding excluded everywhere).
    Label propagation: L_i <- min(L_i, min_{j in N(i) ∩ core} L_j) for core
    i, iterated to fixed point; pointer-doubling shortcut steps after each
    sweep bound the sweep count by O(log n) instead of the core-graph
    diameter.

    ``block_sparse``: "never" | "auto" | "always".  "auto" engages the
    Morton-sorted block-sparse path once there are enough points for more
    than one tile pair to exist; within that path, sweeps fall back to
    the dense kernels at runtime when the active-tile fraction exceeds
    ``dense_fallback_frac`` (the sparse and dense paths are bit-identical
    either way).
    """
    assert block_sparse in ("never", "auto", "always"), block_sparse
    n = points.shape[0]
    # Masked rows are zeroed so padding never carries stale values into
    # the tiles.  The kernels' difference-form d2 is exact at any
    # coordinate offset (DESIGN.md §4 item 6).
    points = jnp.where(mask[:, None], points, 0.0)
    doubling_steps = max(1, math.ceil(math.log2(max(n, 2)))) if pointer_doubling else 0
    # "auto" engages the sparse path only with enough points for several
    # tiles AND a Pallas backend — on pure-jnp reference backends the
    # sparse fold is sequential, so dense tiles are the faster CPU path.
    use_sparse_path = block_sparse == "always" or (
        block_sparse == "auto" and n >= 2 * bt and ops.use_pallas_backend()
    )
    if use_sparse_path:
        return _dbscan_block_sparse(
            points, mask, eps, min_pts, max_iters, bt=bt,
            doubling_steps=doubling_steps,
            dense_fallback_frac=dense_fallback_frac,
        )

    counts = ops.neighbor_count(points, mask, eps)
    core = (counts >= min_pts) & mask
    init = jnp.where(core, jnp.arange(n, dtype=jnp.int32), SENTINEL)
    labels, n_sweeps = _propagate(
        lambda l: ops.min_label_sweep(points, mask, l, core, eps),
        init, core, max_iters, doubling_steps,
    )

    # Border points: min core-neighbour label (non-core, in-mask).
    swept = ops.min_label_sweep(points, mask, labels, core, eps)
    labels = jnp.where(core, labels, swept)
    labels = jnp.where(mask & (labels < SENTINEL), labels, SENTINEL)

    # Count clusters: labels that are their own index and core.
    is_root = core & (labels == jnp.arange(n, dtype=jnp.int32))
    n_clusters = jnp.sum(is_root.astype(jnp.int32))
    labels = jnp.where(labels == SENTINEL, NOISE, labels)
    return DBSCANResult(labels, core, n_clusters, n_sweeps)


def _dbscan_block_sparse(
    points: jax.Array,
    mask: jax.Array,
    eps: float | jax.Array,
    min_pts: int,
    max_iters: int,
    *,
    bt: int,
    doubling_steps: int,
    dense_fallback_frac: float,
) -> DBSCANResult:
    """Block-sparse DBSCAN: Morton sort -> bbox tile pruning -> gathered
    sweeps -> canonicalise -> inverse permutation.  Bit-identical to the
    dense path (see DESIGN.md §4 for the argument)."""
    n = points.shape[0]
    sp, sm, order = spatial_sort(points, mask, bt)
    npad = sp.shape[0]

    pairs = ops.build_tile_pairs(sp, sm, eps, bt=bt)
    use_sparse = pairs.frac <= dense_fallback_frac

    def sweep(labels, core):
        return jax.lax.cond(
            use_sparse,
            lambda l, c: ops.min_label_sweep_sparse(sp, sm, l, c, eps, pairs, bt=bt),
            lambda l, c: ops.min_label_sweep(sp, sm, l, c, eps),
            labels, core,
        )

    counts = jax.lax.cond(
        use_sparse,
        lambda: ops.neighbor_count_sparse(sp, sm, eps, pairs, bt=bt),
        lambda: ops.neighbor_count(sp, sm, eps),
    )
    core = (counts >= min_pts) & sm
    init = jnp.where(core, jnp.arange(npad, dtype=jnp.int32), SENTINEL)
    labels, n_sweeps = _propagate(
        lambda l: sweep(l, core), init, core, max_iters, doubling_steps
    )

    # Canonicalise: converged labels hold min *sorted* index per cluster;
    # remap every cluster to its min ORIGINAL index so output labels (and
    # the border-point tie-break below) match the dense path bit-exactly.
    orig = order.astype(jnp.int32)              # sorted slot -> original idx
    root = jnp.where(core, labels, 0)
    min_orig = jnp.full((npad,), SENTINEL, jnp.int32).at[root].min(
        jnp.where(core, orig, SENTINEL)
    )
    canon = jnp.where(core, jnp.take(min_orig, root), SENTINEL)

    # Border points: min canonical core-neighbour label.
    swept = sweep(canon, core)
    labels_s = jnp.where(core, canon, swept)
    labels_s = jnp.where(sm & (labels_s < SENTINEL), labels_s, SENTINEL)

    # Inverse permutation: results back in caller order.
    labels = jnp.zeros((npad,), jnp.int32).at[order].set(labels_s)[:n]
    core_o = jnp.zeros((npad,), bool).at[order].set(core)[:n]

    is_root = core_o & (labels == jnp.arange(n, dtype=jnp.int32))
    n_clusters = jnp.sum(is_root.astype(jnp.int32))
    labels = jnp.where(labels == SENTINEL, NOISE, labels)
    return DBSCANResult(labels, core_o, n_clusters, n_sweeps)


def relabel_dense(labels: jax.Array, max_clusters: int) -> jax.Array:
    """Map arbitrary min-index labels to dense ids [0, max_clusters) by
    cluster-root order; -1 stays -1.  Clusters beyond the budget map to -1
    (callers size ``max_clusters`` generously; overflow is reported by
    ddc.py)."""
    n = labels.shape[0]
    is_root = labels == jnp.arange(n)
    # Rank roots by index.
    root_rank = jnp.cumsum(is_root.astype(jnp.int32)) - 1  # rank at root pos
    dense_at_root = jnp.where(is_root, root_rank, 0)
    safe = jnp.clip(labels, 0, n - 1)
    dense = jnp.take(dense_at_root, safe)
    dense = jnp.where(labels == NOISE, NOISE, dense)
    dense = jnp.where(dense >= max_clusters, NOISE, dense)
    return dense.astype(jnp.int32)
