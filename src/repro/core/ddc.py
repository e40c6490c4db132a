"""Dynamic Distributed Clustering (DDC) — the paper's contribution.

Phase 1 (SPMD, zero communication): every shard clusters its local points
(DBSCAN or K-Means) and reduces each cluster to a fixed-size *contour*
buffer — the paper's 1–2 % data-reduction step.

Phase 2 (hierarchical aggregation): contour buffers are merged across
shards.  Two schedules:

* ``sync``  — barrier all-gather of every shard's contours, then one fold
  (the paper's synchronous model: everyone waits for the slowest, then
  merges).  Collective bytes per lane: (K-1)·B.
* ``async`` — butterfly / recursive-doubling: log2(K) rounds of pairwise
  ``ppermute`` exchange + merge; merge compute of round ℓ overlaps the
  round ℓ+1 permute in XLA's schedule (the paper's asynchronous model:
  neighbours merge as soon as both are ready).  Collective bytes per
  lane: log2(K)·B.

Both schedules produce identical global clusters (a paper claim we test).

Static shapes throughout: a shard's clusters live in a ``ClusterSet``
(C clusters × V contour vertices, padded + masked) so buffers can cross
TPU collectives.  ``merge_pair`` returns slot-mappings so each shard can
relabel its local points to global cluster ids without any extra
communication.

Host path: ``ddc_host`` (NumPy, exact polygon-overlap merge) is the
paper-faithful oracle; ``dbscan_ref`` on the unpartitioned data is the
sequential baseline T1 used for the speedup experiments.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dbscan as dbscan_mod
from repro.core import geometry, kmeans
from repro.kernels import ops, ref

SENTINEL = 2**30


@dataclasses.dataclass(frozen=True)
class DDCConfig:
    """Static configuration of the DDC pipeline (hashable, jit-static)."""

    eps: float = 0.05                  # DBSCAN radius (data units)
    min_pts: int = 5
    bounds: Tuple[float, float, float, float] = (0.0, 0.0, 1.0, 1.0)
    grid: int = 128                    # contour raster resolution
    max_clusters: int = 32             # C: per-shard cluster budget
    max_verts: int = 128               # V: per-cluster contour budget
    merge_eps: float | None = None     # contour-overlap distance; default eps
    local_algo: str = "dbscan"         # "dbscan" | "kmeans"
    kmeans_k: int = 8
    schedule: str = "async"            # "sync" | "async" | "tree"
    tree_degree: int = 2               # D for the paper's Algorithm-2 tree
    merge_refine: str = "grid"         # "grid" | "fps"
    block_sparse: str = "auto"         # phase-1 spatial pruning (dbscan.py)
    block_tile: int = 512              # tile size for the block-sparse path

    @property
    def merge_radius(self) -> float:
        # Contours are grid-cell centres; two touching clusters' boundary
        # cells are within one cell diagonal + eps of each other.
        cell = max(
            (self.bounds[2] - self.bounds[0]) / self.grid,
            (self.bounds[3] - self.bounds[1]) / self.grid,
        )
        base = self.merge_eps if self.merge_eps is not None else self.eps
        return base + 1.5 * cell

    def buffer_bytes(self) -> int:
        """Bytes a ClusterSet occupies on the wire (the 1–2 % claim)."""
        c, v = self.max_clusters, self.max_verts
        return c * v * 2 * 4 + c * 4 + c * 4 + c * 1 + 1


class ClusterSet(NamedTuple):
    """Fixed-size representation of a shard's clusters (network format)."""

    contours: jax.Array  # (C, V, 2) f32 — padded contour vertices
    counts: jax.Array    # (C,)     i32 — valid vertices per cluster
    sizes: jax.Array     # (C,)     i32 — member-point counts
    valid: jax.Array     # (C,)     bool
    overflow: jax.Array  # ()       bool — cluster budget exceeded somewhere


@functools.lru_cache(maxsize=None)
def _empty_clusterset(c: int, v: int) -> ClusterSet:
    return ClusterSet(
        contours=jnp.zeros((c, v, 2), jnp.float32),
        counts=jnp.zeros((c,), jnp.int32),
        sizes=jnp.zeros((c,), jnp.int32),
        valid=jnp.zeros((c,), bool),
        overflow=jnp.asarray(False),
    )


def empty_clusterset(cfg: DDCConfig) -> ClusterSet:
    """The all-invalid ClusterSet for ``cfg``'s budgets.  Cached per
    (C, V): callers hit this on every empty-shard code path, so repeated
    calls must not rebuild (or retrace over) fresh device buffers."""
    return _empty_clusterset(cfg.max_clusters, cfg.max_verts)


# ---------------------------------------------------------------------------
# Phase 1 — local clustering + contour reduction
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def local_phase(
    points: jax.Array, mask: jax.Array, cfg: DDCConfig, key: jax.Array | None = None
) -> Tuple[jax.Array, ClusterSet]:
    """Cluster a shard's points and reduce to contours.

    Returns (dense local labels (n,), ClusterSet).  Zero communication.
    """
    n = points.shape[0]
    c_budget = cfg.max_clusters
    if cfg.local_algo == "dbscan":
        res = dbscan_mod.dbscan(
            points, mask, cfg.eps, cfg.min_pts,
            block_sparse=cfg.block_sparse, bt=cfg.block_tile,
        )
        dense = dbscan_mod.relabel_dense(res.labels, c_budget)
        n_clusters = res.n_clusters
    elif cfg.local_algo == "kmeans":
        if key is None:
            key = jax.random.PRNGKey(0)
        km = kmeans.kmeans(key, points, mask, min(cfg.kmeans_k, c_budget))
        dense = km.labels
        n_clusters = jnp.asarray(min(cfg.kmeans_k, c_budget), jnp.int32)
    else:  # pragma: no cover
        raise ValueError(cfg.local_algo)

    sizes = jnp.zeros((c_budget,), jnp.int32).at[jnp.clip(dense, 0)].add(
        (dense >= 0).astype(jnp.int32), mode="drop"
    )
    valid = sizes > 0

    def one_contour(cid):
        m = mask & (dense == cid)
        pts, cnt = geometry.extract_contour(
            points, m, cfg.bounds, cfg.grid, cfg.max_verts
        )
        return pts, cnt

    contours, counts = jax.vmap(one_contour)(jnp.arange(c_budget))
    cs = ClusterSet(
        contours=contours,
        counts=jnp.where(valid, counts, 0),
        sizes=sizes,
        valid=valid,
        overflow=n_clusters > c_budget,
    )
    return dense, cs


# ---------------------------------------------------------------------------
# Phase 2 — batched ClusterSet merge engine (the aggregation kernel)
# ---------------------------------------------------------------------------


def _components(overlap: jax.Array, valid: jax.Array) -> jax.Array:
    """Min-label connected components over an (M, M) overlap graph.

    Each iteration does one neighbour-min sweep followed by
    ``ceil(log2 M)`` pointer-doubling shortcut steps
    (``labels ← min(labels, labels[labels])`` — the same hook-and-compress
    trick as phase 1, DESIGN.md §5), so convergence takes O(log M)
    sweeps instead of O(component diameter).  For a valid node i,
    ``labels[i]`` is always the index of a valid node in the same
    component with label ≤ i, so jumping through the representative stays
    in-component and the fixed point (sweep-stability) still forces every
    member to the component minimum.
    """
    m = overlap.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    labels = jnp.where(valid, idx, SENTINEL).astype(jnp.int32)
    n_shortcut = max(1, (m - 1).bit_length())

    def cond(state):
        labels, changed = state
        return changed

    def body(state):
        labels, _ = state
        neigh = jnp.where(overlap, labels[None, :], SENTINEL)
        new = jnp.minimum(labels, jnp.min(neigh, axis=1))
        new = jnp.where(valid, new, SENTINEL)

        def shortcut(_, lab):
            jump = lab[jnp.clip(lab, 0, m - 1)]
            return jnp.where(valid, jnp.minimum(lab, jump), lab)

        new = jax.lax.fori_loop(0, n_shortcut, shortcut, new)
        return new, jnp.any(new != labels)

    labels, _ = jax.lax.while_loop(cond, body, (labels, jnp.asarray(True)))
    return labels


def contour_pair_d2(batch: ClusterSet, cfg: DDCConfig) -> jax.Array:
    """The (K·C, K·C) slot×slot min-contour-distance matrix of a stacked
    ClusterSet batch — one kernel call (``ops.contour_min_d2``), no
    per-pair row scans.  Factored out of ``merge_many`` so the streaming
    delta path (serve/cluster_service.py) can cache it and refresh only
    dirty rows/columns (``update_pair_d2``)."""
    c, v = cfg.max_clusters, cfg.max_verts
    m = batch.valid.shape[0] * c
    return ops.contour_min_d2(
        batch.contours.reshape(m, v, 2),
        batch.counts.reshape(m),
        batch.valid.reshape(m),
    )


def cross_min_d2(ca: jax.Array, cnta: jax.Array, va: jax.Array,
                 cb: jax.Array, cntb: jax.Array, vb: jax.Array) -> jax.Array:
    """Rectangular min squared distance between two padded contour
    buffers: (A, V, 2) × (B, V, 2) → (A, B), 1e30 where either slot is
    empty.  Memory-bounded (one A-row at a time) and written in the same
    difference form as ``kernels/ref.py::contour_min_d2``, so a row
    computed here is bit-identical to the corresponding row of the full
    matrix on the reference backend — the invariant the delta-merge
    exactness argument rests on (DESIGN.md §8)."""
    a, v, _ = ca.shape
    b = cb.shape[0]
    pa = geometry.vert_validity(cnta, va, v)                    # (A, V)
    pb = geometry.vert_validity(cntb, vb, v).reshape(b * v)     # (B·V,)
    flat = cb.astype(jnp.float32).reshape(b * v, 2)
    pts = ca.astype(jnp.float32)

    def row(i):
        d2 = ref.diff_d2(pts[i], flat.T)
        d2 = jnp.where(pa[i][:, None] & pb[None, :], d2, geometry.BIG)
        return jnp.min(d2.reshape(v, b, v), axis=(0, 2))        # (B,)

    return jax.lax.map(row, jnp.arange(a))


@functools.partial(jax.jit, static_argnames=("cfg",))
def contour_pair_d2_exact(batch: ClusterSet, cfg: DDCConfig) -> jax.Array:
    """``contour_pair_d2`` in the difference form on every backend.

    The streaming engine's cached matrix is patched row by row through
    ``cross_min_d2`` (``update_pair_d2``), so it builds the full matrix
    through the same function: every entry, full or patched, then comes
    from one expression on every backend, and bit-identity near the merge
    threshold does not rest on the kernel and ``cross_min_d2`` rounding
    alike."""
    c, v = cfg.max_clusters, cfg.max_verts
    m = batch.valid.shape[0] * c
    contours = batch.contours.reshape(m, v, 2)
    counts = batch.counts.reshape(m)
    valid = batch.valid.reshape(m)
    return cross_min_d2(contours, counts, valid, contours, counts, valid)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def update_pair_d2(pair_d2: jax.Array, batch: ClusterSet, shard,
                   cfg: DDCConfig) -> jax.Array:
    """Refresh one shard's rows + columns of a cached slot×slot distance
    matrix after that shard's ClusterSet changed (the streaming
    delta-merge path: O(C·M·V²) work instead of the full O(M²·V²)
    rebuild).  ``shard`` may be a traced index, so one compilation serves
    every dirty shard.  d2 is symmetric under IEEE ((a−b)² == (b−a)²), so
    mirroring the freshly computed rows into the columns keeps the matrix
    bit-identical to ``contour_pair_d2`` recomputed from scratch."""
    c, v = cfg.max_clusters, cfg.max_verts
    m = batch.valid.shape[0] * c
    contours = batch.contours.reshape(m, v, 2)
    counts = batch.counts.reshape(m)
    valid = batch.valid.reshape(m)
    row0 = shard * c
    bc = jax.lax.dynamic_slice(contours, (row0, 0, 0), (c, v, 2))
    bcnt = jax.lax.dynamic_slice(counts, (row0,), (c,))
    bval = jax.lax.dynamic_slice(valid, (row0,), (c,))
    rows = cross_min_d2(bc, bcnt, bval, contours, counts, valid)   # (C, M)
    pair_d2 = jax.lax.dynamic_update_slice(pair_d2, rows, (row0, 0))
    return jax.lax.dynamic_update_slice(pair_d2, rows.T, (0, row0))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def update_pair_d2_many(pair_d2: jax.Array, batch: ClusterSet, shards,
                        cfg: DDCConfig) -> jax.Array:
    """Batched ``update_pair_d2``: refresh the rows + columns of EVERY
    shard in ``shards`` ((m,) i32, traced) with one rectangular
    ``cross_min_d2`` over the m·C dirty rows.  Replaces the sequential
    per-shard patch loop, which recomputed every dirty×dirty block once
    per dirty shard (m× redundant work) and paid m kernel dispatches.

    Bit-exact vs the loop: each dirty row is the identical per-row
    difference-form computation over the identical batch (the dirty rows
    were all replaced before any patch runs), and the column mirror is
    exact under IEEE symmetry — so scatter order cannot matter, even for
    duplicated indices (callers pad ``shards`` to a power of two by
    repeating an entry; the duplicate writes carry bit-identical values).
    """
    c, v = cfg.max_clusters, cfg.max_verts
    m = batch.valid.shape[0] * c
    contours = batch.contours.reshape(m, v, 2)
    counts = batch.counts.reshape(m)
    valid = batch.valid.reshape(m)
    rows_idx = (shards[:, None] * c
                + jnp.arange(c, dtype=jnp.int32)[None, :]).reshape(-1)
    rows = cross_min_d2(contours[rows_idx], counts[rows_idx],
                        valid[rows_idx], contours, counts, valid)  # (mC, M)
    pair_d2 = pair_d2.at[rows_idx].set(rows)
    return pair_d2.at[:, rows_idx].set(rows.T)


@functools.partial(jax.jit, static_argnames=("cfg",))
def merge_from_d2(batch: ClusterSet, pair_d2: jax.Array,
                  cfg: DDCConfig,
                  exclude: jax.Array | None = None
                  ) -> Tuple[ClusterSet, jax.Array]:
    """The merge fold given a precomputed slot×slot distance matrix:
    overlap predicate → transitive closure → ranked rebuild.  Everything
    downstream of the matrix is a pure function of (batch, pair_d2), so
    feeding a cached-and-patched matrix (streaming delta path) yields the
    exact same global clustering as a from-scratch ``merge_many``.

    ``exclude`` (optional, (K,) bool) masks whole shards out of the fold
    without touching the cached matrix — the degraded-merge path for
    quarantined shards: their slots are treated as invalid (maps row all
    -1, their sizes and overflow flags ignored), so healthy shards keep
    merging and the matrix stays pristine for a bit-exact rejoin.
    ``exclude=None`` traces separately and is the identical healthy
    path."""
    c, v = cfg.max_clusters, cfg.max_verts
    k = batch.valid.shape[0]
    m = k * c
    contours = batch.contours.reshape(m, v, 2)
    counts = batch.counts.reshape(m)
    sizes = batch.sizes.reshape(m)
    valid = batch.valid.reshape(m)
    if exclude is not None:
        valid = valid & ~jnp.repeat(exclude, c)
    r = cfg.merge_radius
    overlap = (pair_d2 <= r * r) & valid[:, None] & valid[None, :]
    overlap = overlap | (jnp.eye(m, dtype=bool) & valid[:, None])

    comp = _components(overlap, valid)                         # (M,)
    roots = valid & (comp == jnp.arange(m, dtype=jnp.int32))
    comp_safe = jnp.clip(comp, 0, m - 1)
    comp_size = jnp.zeros((m,), jnp.int32).at[comp_safe].add(
        jnp.where(valid, sizes, 0)
    )

    # Rank component roots by size (desc); keep top C.
    rank_key = jnp.where(roots, comp_size, -1)
    order = jnp.argsort(-rank_key)                             # (M,) root idx by size
    new_slot_of_root = jnp.full((m,), -1, jnp.int32)
    kept = jnp.arange(m) < c
    new_slot_of_root = new_slot_of_root.at[order].set(
        jnp.where(kept & (rank_key[order] > 0), jnp.arange(m, dtype=jnp.int32), -1)
    )
    slot_of_old = jnp.where(valid, new_slot_of_root[comp_safe], -1)  # (M,)

    n_components = jnp.sum(roots.astype(jnp.int32))
    shard_overflow = batch.overflow if exclude is None \
        else batch.overflow & ~exclude
    overflow = jnp.any(shard_overflow) | (n_components > c)

    # Build merged contours per new slot.
    flat_pts = contours.reshape(m * v, 2)
    vert_valid = geometry.vert_validity(counts, valid, v)       # (M, V)

    def build(slot):
        member = slot_of_old == slot                            # (M,)
        pmask = (vert_valid & member[:, None]).reshape(m * v)
        if cfg.merge_refine == "grid":
            pts, cnt = geometry.extract_contour(
                flat_pts, pmask, cfg.bounds, cfg.grid, v
            )
        else:
            pts, cnt = geometry.farthest_point_subsample(flat_pts, pmask, v)
        size = jnp.sum(jnp.where(member, sizes, 0))
        return pts, cnt, size, size > 0

    nc, ncnt, nsize, nvalid = jax.vmap(build)(jnp.arange(c))
    merged = ClusterSet(
        contours=nc,
        counts=jnp.where(nvalid, ncnt, 0),
        sizes=nsize,
        valid=nvalid,
        overflow=overflow,
    )
    return merged, slot_of_old.reshape(k, c)


def merge_delta(batch: ClusterSet, pair_d2: jax.Array | None,
                dirty, cfg: DDCConfig,
                exclude: jax.Array | None = None
                ) -> Tuple[ClusterSet, jax.Array, jax.Array]:
    """The aggregator side of a delta exchange: fold axis-gathered dirty
    ClusterSets into a cached slot-distance matrix and re-close the merge.

    ``batch`` is the aggregator's mirror of every shard's ClusterSet with
    the ``dirty`` rows already replaced by the freshly exchanged deltas
    (the only payload that crossed the axis).  With a cached ``pair_d2``
    the matrix is patched in one batched update over every dirty shard
    (``update_pair_d2_many``; a single dirty shard keeps the narrower
    ``update_pair_d2`` kernel, and the dirty list is padded to a power of
    two so compilations stay bounded at log2(K) per config);
    with ``pair_d2=None`` (or ``dirty=None``) it is rebuilt from scratch
    in the same difference form (``contour_pair_d2_exact``), so both
    paths produce the bit-identical matrix — the DESIGN.md §8 exactness
    argument.  Shared by the host-driven streaming engine
    (serve/cluster_service.py) and the device-resident ``dist`` data
    plane (serve/dist_service.py); returns (global, maps, pair_d2).

    ``exclude`` ((K,) bool or None) is the quarantine mask forwarded to
    ``merge_from_d2``: excluded shards never patch the matrix (they are
    not in ``dirty``) and are masked out of the fold, but their cached
    rows stay intact so recovery is one ordinary row patch.
    """
    if pair_d2 is None or dirty is None:
        pair_d2 = contour_pair_d2_exact(batch, cfg)
    else:
        dirty = [int(i) for i in dirty]
        if len(dirty) == 1:
            pair_d2 = update_pair_d2(pair_d2, batch, dirty[0], cfg)
        elif len(dirty) > 1:
            width = 1 << (len(dirty) - 1).bit_length()
            padded = dirty + [dirty[-1]] * (width - len(dirty))
            pair_d2 = update_pair_d2_many(
                pair_d2, batch, jnp.asarray(padded, jnp.int32), cfg)
    merged, maps = merge_from_d2(batch, pair_d2, cfg, exclude)
    return merged, maps, pair_d2


@functools.partial(jax.jit, static_argnames=("cfg",))
def merge_many(batch: ClusterSet, cfg: DDCConfig) -> Tuple[ClusterSet, jax.Array]:
    """Fold an arbitrary batch of ClusterSets into one (the paper's
    polygon-overlay step, batched).

    ``batch``: a ClusterSet whose leaves carry a leading stack axis —
    contours (K, C, V, 2), counts/sizes/valid (K, C), overflow (K,).  All
    K·C slots are merged in one shot: the slot×slot min-distance matrix
    comes from one kernel call (``contour_pair_d2``), components are
    the transitive closure of the overlap predicate (contours within
    ``merge_radius`` — the TPU-friendly stand-in for exact polygon
    intersection, DESIGN.md §3/§7; the host oracle uses the exact test),
    and merged contours are re-extracted once per output slot
    (``merge_from_d2``).

    Returns (merged, maps) where maps (K, C) sends every input slot to
    its output slot (or -1) so each contributor can relabel its points
    locally.  Deterministic and order-equivariant: permuting the batch
    permutes ``maps`` rows but yields the identical merged clustering
    (components are ranked by total member count, ties by slot index).
    """
    return merge_from_d2(batch, contour_pair_d2(batch, cfg), cfg)


def merge_pair(
    a: ClusterSet, b: ClusterSet, cfg: DDCConfig
) -> Tuple[ClusterSet, jax.Array, jax.Array]:
    """Merge two ClusterSets — a batch-2 ``merge_many``.

    Returns (merged, map_a, map_b): old-slot → new-slot (or -1) mappings
    so each side can relabel its points locally.  Deterministic and
    symmetric: merge_pair(a, b) and the (b, a) maps agree through
    composition.
    """
    batch = jax.tree.map(lambda x, y: jnp.stack([x, y]), a, b)
    merged, maps = merge_many(batch, cfg)
    return merged, maps[0], maps[1]


# ---------------------------------------------------------------------------
# Phase 2 schedules — thin collective schedules over merge_many
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CommMeter:
    """Trace-time comm-volume accounting for the phase-2 schedules.

    Schedules call the ``add_*`` hooks while they trace.  Every quantity
    is static (permutation lists, gather widths, and buffer shapes are
    all known at trace time), so the meter is exact without instrumenting
    the compiled program.  Fill it by tracing once (e.g.
    ``jit(fn).lower(...)``) and read ``snapshot()``; re-tracing the same
    function re-counts, so ``reset()`` between traces.

    ``bytes_total`` sums message bytes over every lane→lane link (an
    all-gather among K lanes of a B-byte buffer counts K·(K−1)·B, a
    ppermute counts B per (src, dst) pair).  ``merge_steps`` counts
    merge_many invocations on the critical path; ``merge_slots`` sums the
    K·C slot counts those merges closed over.
    """

    bytes_total: int = 0
    collectives: int = 0
    merge_steps: int = 0
    merge_slots: int = 0

    def add_collective(self, links: int, nbytes: int) -> None:
        self.bytes_total += links * nbytes
        self.collectives += 1

    def add_merge(self, batch: int, slots: int) -> None:
        self.merge_steps += 1
        self.merge_slots += batch * slots

    def reset(self) -> None:
        self.bytes_total = self.collectives = 0
        self.merge_steps = self.merge_slots = 0

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


def _wire_bytes(cs: ClusterSet) -> int:
    from repro.parallel import compress
    return compress.pytree_wire_bytes(cs)


def _permute(tree, axis: str, perm, meter: CommMeter | None):
    if meter is not None:
        meter.add_collective(len(perm), _wire_bytes(tree))
    return jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), tree)


def merge_sync(cs: ClusterSet, cfg: DDCConfig, axis: str,
               meter: CommMeter | None = None):
    """Barrier schedule: all-gather every shard's ClusterSet, then ONE
    batched merge_many over all K·C slots (the paper's synchronous model:
    everyone waits for the slowest, then merges).  Collective bytes per
    lane: (K−1)·B.  Returns (global ClusterSet, local-slot → global-slot
    map (C,)).
    """
    k = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    if meter is not None:
        meter.add_collective(k * (k - 1), _wire_bytes(cs))
        meter.add_merge(k, cfg.max_clusters)
    gathered = jax.lax.all_gather(cs, axis)   # pytree: leaves (K, ...)
    gcs, maps = merge_many(gathered, cfg)
    my_map = jnp.take(maps, me, axis=0)
    return gcs, jnp.where(cs.valid, my_map, -1)


def merge_async(cs: ClusterSet, cfg: DDCConfig, axis: str,
                meter: CommMeter | None = None):
    """Butterfly (recursive-doubling) schedule: log2(K) ppermute + batch-2
    merge rounds; merge compute of round ℓ overlaps the round ℓ+1 permute
    in XLA's schedule.  Matches the paper's asynchronous model (merge as
    soon as the partner is ready).  Collective bytes per lane: log2(K)·B.
    """
    k = jax.lax.axis_size(axis)
    assert k & (k - 1) == 0, f"async schedule needs power-of-two shards, got {k}"
    me = jax.lax.axis_index(axis)
    my_map = jnp.arange(cfg.max_clusters, dtype=jnp.int32)
    my_map = jnp.where(cs.valid, my_map, -1)

    acc = cs
    rounds = k.bit_length() - 1
    for level in range(rounds):
        stride = 1 << level
        perm = [(i, i ^ stride) for i in range(k)]
        partner_cs = _permute(acc, axis, perm, meter)
        low = (me & stride) == 0
        a = jax.tree.map(lambda s, p: jnp.where(low, s, p), acc, partner_cs)
        b = jax.tree.map(lambda s, p: jnp.where(low, p, s), acc, partner_cs)
        # `a`/`b` ordering is lane-consistent, so both sides compute the
        # identical merged buffer (deterministic merge).
        if meter is not None:
            meter.add_merge(2, cfg.max_clusters)
        acc, map_a, map_b = merge_pair(a, b, cfg)
        mine = jnp.where(low, map_a, map_b)
        my_map = jnp.where(my_map >= 0, mine[jnp.clip(my_map, 0)], -1)
    return acc, my_map


def merge_tree(cs: ClusterSet, cfg: DDCConfig, axis: str,
               meter: CommMeter | None = None):
    """The paper's Algorithm 2: nodes join groups of D, elect the
    lowest-index member as leader, members SEND their contours to the
    leader (ppermute); the leader folds its whole group in ONE batch-D
    merge_many; repeat up the tree until the root holds the global
    clusters, then broadcast down.

    Wire cost per level: each member sends one ClusterSet to its leader
    ((D-1)/D of lanes send), + one broadcast at the end — between sync's
    (K-1)·B all-gather and async's log2(K)·B butterfly.  Unlike the
    butterfly, non-leaders idle above their level (the paper's Fig. 1).
    """
    k = jax.lax.axis_size(axis)
    d = cfg.tree_degree
    me = jax.lax.axis_index(axis)
    my_map = jnp.where(cs.valid, jnp.arange(cfg.max_clusters, dtype=jnp.int32), -1)

    acc = cs
    stride = 1
    while stride < k:
        # Group = lanes {base, base+stride, ..., base+(D-1)*stride};
        # leader = base.  Members send to the leader (one ppermute per
        # member rank — ppermute sources must be unique); the leader
        # closes over the whole group in a single batched merge.
        batch = [acc]
        for j in range(1, d):
            src_off = j * stride
            if src_off >= k:
                break
            perm = [(i, i - src_off) for i in range(k) if i - src_off >= 0
                    and (i // stride) % d == j and (i - src_off) // (stride * d) == i // (stride * d)]
            batch.append(_permute(acc, axis, perm, meter))
        is_leader = (me // stride) % d == 0
        if meter is not None:
            meter.add_merge(len(batch), cfg.max_clusters)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batch)
        merged, maps = merge_many(stacked, cfg)
        # Leaders fold; everyone else keeps their acc (their map will be
        # resolved by the broadcast below).  Slot 0 of the batch is the
        # leader's own accumulator.
        acc = jax.tree.map(
            lambda m, a: jnp.where(is_leader, m, a), merged, acc)
        my_map = jnp.where(is_leader & (my_map >= 0),
                           maps[0][jnp.clip(my_map, 0)], my_map)
        stride *= d

    # Root (lane 0) broadcasts the global ClusterSet down the same tree
    # (one ppermute per (level, member) hop — ppermute sources must be
    # unique, so a flat one-to-all broadcast is not expressible).
    gcs = acc
    strides = []
    s = 1
    while s < k:
        strides.append(s)
        s *= d
    for stride in reversed(strides):      # top of the tree first
        for j in range(1, d):
            if j * stride >= k:
                continue
            perm = [(b, b + j * stride) for b in range(0, k, stride * d)
                    if b + j * stride < k]
            moved = _permute(gcs, axis, perm, meter)
            is_receiver = (me % (stride * d)) == j * stride
            gcs = jax.tree.map(
                lambda g, mv: jnp.where(is_receiver, mv, g), gcs, moved)
    # Non-root lanes resolve their local slots against the global set by
    # contour proximity (their intermediate maps stopped at their last
    # leader level).
    resolved = match_to_global(cs, gcs, cfg)
    my_map = jnp.where(me == 0, my_map, resolved)
    return gcs, my_map


def match_to_global(cs: ClusterSet, gcs: ClusterSet, cfg: DDCConfig) -> jax.Array:
    """Map each local cluster to the nearest global cluster (by min
    contour distance, within merge_radius).  Returns (C,) slot ids/-1.

    Short-circuits on empty inputs: when either side has no valid slots
    (an empty shard, or a shard whose points were all noise) the result
    is all -1 by definition, so the per-slot distance scans are skipped
    entirely at runtime (``lax.cond``) instead of being computed eagerly.
    """
    c, v = cfg.max_clusters, cfg.max_verts
    gvalid_pts = geometry.vert_validity(gcs.counts, gcs.valid, v).reshape(c * v)
    gflat = gcs.contours.reshape(c * v, 2)

    def one(i):
        d2 = jnp.sum((cs.contours[i][:, None, :] - gflat[None, :, :]) ** 2, -1)
        vi = (jnp.arange(v) < cs.counts[i]) & cs.valid[i]
        d2 = jnp.where(vi[:, None] & gvalid_pts[None, :], d2, geometry.BIG)
        per_g = jnp.min(d2.reshape(v, c, v), axis=(0, 2))        # (C,)
        best = jnp.argmin(per_g)
        r = cfg.merge_radius
        ok = cs.valid[i] & (per_g[best] <= r * r)
        return jnp.where(ok, best, -1).astype(jnp.int32)

    def compute(_):
        return jax.lax.map(one, jnp.arange(c))

    def empty(_):
        return jnp.full((c,), -1, jnp.int32)

    any_work = jnp.any(cs.valid) & jnp.any(gcs.valid)
    return jax.lax.cond(any_work, compute, empty, None)


def ddc_shard(
    points: jax.Array,
    mask: jax.Array,
    cfg: DDCConfig,
    axis: str,
    key: jax.Array | None = None,
    meter: CommMeter | None = None,
):
    """Full DDC inside ``shard_map``: phase 1 locally, phase 2 across
    ``axis``.  Returns (global labels for local points (n,),
    global ClusterSet, local→global slot map)."""
    dense, cs = local_phase(points, mask, cfg, key)
    if cfg.schedule == "sync":
        gcs, my_map = merge_sync(cs, cfg, axis, meter)
    elif cfg.schedule == "tree":
        gcs, my_map = merge_tree(cs, cfg, axis, meter)
    else:
        gcs, my_map = merge_async(cs, cfg, axis, meter)
    glabels = jnp.where(dense >= 0, my_map[jnp.clip(dense, 0)], -1)
    return glabels, gcs, my_map


def make_ddc_fn(mesh, axis: str, cfg: DDCConfig, meter: CommMeter | None = None):
    """Build the jit-able distributed DDC entry point over ``mesh``.

    points: (N, 2) sharded along ``axis``; mask: (N,).  An optional
    ``meter`` collects static comm-volume counters while the function
    traces (see CommMeter).
    """
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def run(points, mask):
        fn = jax.shard_map(
            lambda p, m: ddc_shard(p, m, cfg, axis, meter=meter),
            mesh=mesh,
            in_specs=(P(axis, None), P(axis)),
            out_specs=(P(axis), P(), P(axis)),
            check_vma=False,
        )
        return fn(points, mask)

    return run


def same_clustering(a: np.ndarray, b: np.ndarray) -> bool:
    """True iff two label arrays describe the IDENTICAL clustering: the
    same noise set (label < 0) and a bijection between cluster labels.
    This is the bit-exactness predicate the phase-2 benchmarks and the
    schedule-equivalence tests apply between the distributed path and
    ``ddc_host``."""
    a = np.asarray(a)
    b = np.asarray(b)
    if ((a < 0) != (b < 0)).any():
        return False
    m = a >= 0
    pairs = set(zip(a[m].tolist(), b[m].tolist()))
    return len(pairs) == len(set(a[m].tolist())) == len(set(b[m].tolist()))


# ---------------------------------------------------------------------------
# Host (paper-faithful) path — NumPy oracle + sequential baseline
# ---------------------------------------------------------------------------


def ddc_host(
    points: np.ndarray,
    n_partitions: int,
    eps: float,
    min_pts: int,
    partition: str = "block",
    contour: str = "hull",
):
    """Reference DDC on the host: dbscan_ref per partition, exact
    polygon-overlap merge (paper's phase-2 predicate).

    ``partition``: "block" (contiguous array_split), "strided", or an
    explicit list of index arrays (one per shard — the streaming serve
    tests hand over the engine's exact per-shard membership, including
    holes left by eviction; ``n_partitions`` is ignored then).

    Returns (global labels (n,), list of merged-cluster polygons,
    exchanged_points: how many contour vertices crossed the 'network' —
    drives the 1–2 % exchange claim).
    """
    n = len(points)
    if isinstance(partition, (list, tuple)):
        parts = [np.asarray(p, dtype=np.int64) for p in partition]
    elif partition == "block":
        parts = np.array_split(np.arange(n), n_partitions)
    else:
        parts = [np.arange(n)[i::n_partitions] for i in range(n_partitions)]
    labels = np.full(n, -1, np.int64)
    polys: list = []       # (part, local_cluster, polygon, member_idx)
    exchanged = 0
    for pi, idx in enumerate(parts):
        if len(idx) == 0:
            continue
        local = dbscan_mod.dbscan_ref(points[idx], eps, min_pts)
        for cid in sorted(set(local[local >= 0])):
            members = idx[local == cid]
            if contour == "hull":
                poly = geometry.convex_hull_np(points[members])
            else:
                x0, y0 = points[:, 0].min(), points[:, 1].min()
                x1, y1 = points[:, 0].max(), points[:, 1].max()
                poly = geometry.grid_contour_np(points[members], (x0, y0, x1, y1), 128)
            polys.append({"members": members, "poly": poly})
            exchanged += len(poly)

    # Union-find over polygons by exact overlap (dilated by eps: two
    # clusters merge when their polygons overlap or come within eps).
    m = len(polys)
    parent = list(range(m))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    def union(i, j):
        ri, rj = find(i), find(j)
        if ri != rj:
            parent[max(ri, rj)] = min(ri, rj)

    for i in range(m):
        for j in range(i + 1, m):
            a, b = polys[i]["poly"], polys[j]["poly"]
            # Hull contours are ordered polygons: exact overlap test.
            # Grid contours are unordered boundary samples: proximity only
            # (this is what preserves non-convexity — a convex hull would
            # wrongly merge a cluster with one that surrounds it, the
            # paper's motivating D1 case).
            if contour == "hull":
                hit = polygons_near(a, b, eps)
            else:
                d = np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)).min()
                hit = bool(d <= eps * 1.5)
            if hit:
                union(i, j)

    global_ids = {}
    for i in range(m):
        r = find(i)
        gid = global_ids.setdefault(r, len(global_ids))
        labels[polys[i]["members"]] = gid
    return labels, polys, exchanged


def polygons_near(a: np.ndarray, b: np.ndarray, eps: float) -> bool:
    """Exact overlap OR min vertex-to-vertex distance <= eps (clusters
    that touch across a partition boundary merge, matching DBSCAN)."""
    if len(a) == 0 or len(b) == 0:
        return False
    if geometry.polygons_overlap_np(a, b):
        return True
    d = np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)).min()
    return bool(d <= eps)
