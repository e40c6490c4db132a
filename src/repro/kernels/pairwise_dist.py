"""Pallas TPU kernels: tiled pairwise squared distances + fused ε-neighbour
counting and min-label sweeps — the DDC phase-1 hot-spot (DBSCAN region
queries).

The paper's DBSCAN does per-point region queries (pointer chasing).  The
TPU-native formulation is blocked: for a row tile X (bn, d) and a column
tile Y (bm, d) the kernels compute the (bn, bm) squared-distance tile in
VMEM and reduce it at once — a per-row neighbour count or a per-row min
label — so the (n, m) distance matrix never reaches HBM.

Layout (Mosaic accepts only 2-D vector shapes with a lane-dense last
dimension or a full-array one): row-side operands are columns ((bn, d)
points, (bn, 1) masks and outputs), column-side operands are rows (Y
passed transposed as (d, bm), (1, bm) masks, labels and core flags), so
every broadcast is (bn, 1) against (1, bm).  eps² is one f32 scalar in
SMEM.  The fused kernels evaluate d² in difference form
(``ref.diff_d2``): for d = 2 it is four VPU ops per pair, no MXU pass,
and it rounds identically on every backend, so the kernels are bit-equal
to their oracles (DESIGN.md §4).

Grid layout (dense): (n // bn, m // bm); the m axis is the innermost
(sequential) loop so per-row results accumulate in the output block
across j-steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

DEF_BN = 512
DEF_BM = 512
NO_LABEL = 2**30

# Active-pair flag bits (see ops.build_tile_pairs): bit0 = pair is real
# (not tail padding), bit1 = first pair of its row tile (output block must
# be initialised before accumulating).
PAIR_VALID = 1
PAIR_FIRST = 2

_SMEM_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def _dist_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)
    yy = jnp.sum(y * y, axis=-1)[None, :]
    d2 = xx + yy - 2.0 * jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    o_ref[...] = jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def pairwise_dist_sq(
    x: jax.Array, y: jax.Array, *, bn: int = DEF_BN, bm: int = DEF_BM,
    interpret: bool = False,
) -> jax.Array:
    """Tiled (n, m) squared-distance matrix (MXU expansion, HIGHEST
    precision).  n, m must be tile-multiples (ops.py pads)."""
    n, d = x.shape
    m = y.shape[0]
    bn = min(bn, n)
    bm = min(bm, m)
    assert n % bn == 0 and m % bm == 0, (n, m, bn, bm)
    return pl.pallas_call(
        _dist_kernel,
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(x, y)


def _within(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref):
    """(bn, bm) bool: masked pairs within eps (row masks (bn, 1), column
    masks (1, bm))."""
    d2 = ref.diff_d2(x_ref[...], yt_ref[...])
    return (d2 <= eps_sq_ref[0, 0]) & (xm_ref[...] > 0) & (ym_ref[...] > 0)


def _count_tile(o_ref, ok):
    o_ref[...] += jnp.sum(ok.astype(jnp.int32), axis=1, keepdims=True)


def _min_label_tile(o_ref, ok, lab_ref):
    labs = jnp.where(ok, lab_ref[...], jnp.int32(NO_LABEL))
    o_ref[...] = jnp.minimum(o_ref[...], jnp.min(labs, axis=1, keepdims=True))


def _count_kernel(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _count_tile(o_ref, _within(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref))


def _min_label_kernel(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref, lab_ref,
                      core_ref, o_ref):
    """One label-propagation sweep tile: o[i] = min(o[i], min_{j in N(i),
    core j} lab[j])."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, NO_LABEL, jnp.int32)

    ok = (_within(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref)
          & (core_ref[...] > 0))
    _min_label_tile(o_ref, ok, lab_ref)


def _eps_sq(eps) -> jax.Array:
    return (jnp.asarray(eps, jnp.float32) ** 2).reshape(1, 1)


def _col(v: jax.Array) -> jax.Array:
    return v.astype(jnp.int32).reshape(-1, 1)


def _row(v: jax.Array) -> jax.Array:
    return v.astype(jnp.int32).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def neighbor_count(
    x: jax.Array, mask: jax.Array, eps: float | jax.Array, *,
    bn: int = DEF_BN, bm: int = DEF_BM, interpret: bool = False,
) -> jax.Array:
    """Fused per-point ε-neighbour count (self included), masked.

    x: (n, d), mask: (n,) bool -> (n,) int32.  n must be a tile multiple.
    """
    n, d = x.shape
    bn = min(bn, n)
    bm = min(bm, n)
    assert n % bn == 0 and n % bm == 0, (n, bn, bm)
    out = pl.pallas_call(
        _count_kernel,
        grid=(n // bn, n // bm),
        in_specs=[
            _SMEM_SCALAR,
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bm), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )(_eps_sq(eps), x, x.T, _col(mask), _row(mask))
    return out.reshape(n)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def min_label_sweep(
    x: jax.Array, mask: jax.Array, labels: jax.Array, core: jax.Array,
    eps: float | jax.Array, *, bn: int = DEF_BN, bm: int = DEF_BM,
    interpret: bool = False,
) -> jax.Array:
    """One blocked sweep of DBSCAN min-label propagation (see dbscan.py).

    Returns new_labels[i] = min over ε-neighbours j (core only) of labels[j],
    (2**30 where none).  Fused distance+min so the adjacency matrix never
    hits HBM.
    """
    n, d = x.shape
    bn = min(bn, n)
    bm = min(bm, n)
    assert n % bn == 0 and n % bm == 0
    out = pl.pallas_call(
        _min_label_kernel,
        grid=(n // bn, n // bm),
        in_specs=[
            _SMEM_SCALAR,
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bm), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )(_eps_sq(eps), x, x.T, _col(mask), _row(mask), _row(labels), _row(core))
    return out.reshape(n)


# ---------------------------------------------------------------------------
# Block-sparse (gathered-grid) variants — DDC phase 1 on spatially sorted
# points.  The grid iterates an *active-pair list* (built by
# ops.build_tile_pairs from per-tile bounding boxes) instead of the full
# (T, T) tile product: tile pairs provably farther than eps apart are never
# fetched or computed.  Scalar-prefetched row/col indices drive the block
# gather; pairs arrive sorted by row tile so each output block is resident
# for exactly one contiguous run of grid steps (init on PAIR_FIRST,
# accumulate while PAIR_VALID, write-back when the row index advances).
# ---------------------------------------------------------------------------


def _count_sparse_kernel(rows_ref, cols_ref, flags_ref, eps_sq_ref,
                         x_ref, yt_ref, xm_ref, ym_ref, o_ref):
    flags = flags_ref[pl.program_id(0)]

    @pl.when((flags & PAIR_FIRST) != 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((flags & PAIR_VALID) != 0)
    def _acc():
        _count_tile(o_ref, _within(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref))


def _min_label_sparse_kernel(rows_ref, cols_ref, flags_ref, eps_sq_ref,
                             x_ref, yt_ref, xm_ref, ym_ref, lab_ref, core_ref,
                             o_ref):
    flags = flags_ref[pl.program_id(0)]

    @pl.when((flags & PAIR_FIRST) != 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, NO_LABEL, jnp.int32)

    @pl.when((flags & PAIR_VALID) != 0)
    def _acc():
        ok = (_within(eps_sq_ref, x_ref, yt_ref, xm_ref, ym_ref)
              & (core_ref[...] > 0))
        _min_label_tile(o_ref, ok, lab_ref)


def _sparse_call(kernel, n_pairs: int, n: int, d: int, bt: int,
                 n_col_rows: int, interpret: bool):
    """pallas_call over the active-pair grid: SMEM eps², the row tile
    (bt, d), the transposed column tile (d, bt), the row mask (bt, 1) and
    ``n_col_rows`` column-side (1, bt) operands; output (n, 1)."""
    col_row = pl.BlockSpec((1, bt), lambda p, r, c, f: (0, c[p]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_pairs,),
        in_specs=[
            _SMEM_SCALAR,
            pl.BlockSpec((bt, d), lambda p, r, c, f: (r[p], 0)),
            pl.BlockSpec((d, bt), lambda p, r, c, f: (0, c[p])),
            pl.BlockSpec((bt, 1), lambda p, r, c, f: (r[p], 0)),
        ] + [col_row] * n_col_rows,
        out_specs=pl.BlockSpec((bt, 1), lambda p, r, c, f: (r[p], 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def neighbor_count_sparse(
    x: jax.Array, mask: jax.Array, eps: float | jax.Array,
    rows: jax.Array, cols: jax.Array, flags: jax.Array, *,
    bt: int = DEF_BN, interpret: bool = False,
) -> jax.Array:
    """Masked ε-neighbour count over an active tile-pair list.

    x: (n, d) spatially sorted, n a multiple of ``bt``; rows/cols/flags:
    (P,) int32 pair list sorted by row (every row tile appears — the
    diagonal pair is always active).  Matches the dense ``neighbor_count``
    bit-exactly when the pair list covers every within-eps tile pair.
    """
    n, d = x.shape
    assert n % bt == 0, (n, bt)
    call = _sparse_call(_count_sparse_kernel, rows.shape[0], n, d, bt, 1,
                        interpret)
    out = call(rows, cols, flags, _eps_sq(eps), x, x.T, _col(mask),
               _row(mask))
    return out.reshape(n)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def min_label_sweep_sparse(
    x: jax.Array, mask: jax.Array, labels: jax.Array, core: jax.Array,
    eps: float | jax.Array, rows: jax.Array, cols: jax.Array,
    flags: jax.Array, *, bt: int = DEF_BN, interpret: bool = False,
) -> jax.Array:
    """One min-label propagation sweep over an active tile-pair list.

    Same semantics as the dense ``min_label_sweep`` (2**30 where a point
    has no in-range core neighbour) restricted to listed pairs — identical
    output when the list covers every within-eps tile pair.
    """
    n, d = x.shape
    assert n % bt == 0, (n, bt)
    call = _sparse_call(_min_label_sparse_kernel, rows.shape[0], n, d, bt, 3,
                        interpret)
    out = call(rows, cols, flags, _eps_sq(eps), x, x.T, _col(mask),
               _row(mask), _row(labels), _row(core))
    return out.reshape(n)
