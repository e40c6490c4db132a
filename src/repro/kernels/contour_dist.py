"""Pallas TPU kernel: batched contour-pair min-distance matrix — the DDC
phase-2 hot-spot (cluster-merge proximity tests).

Phase 2 decides which clusters merge by the minimum pairwise distance
between their contour vertex buffers.  The per-pair formulation (one row
of clusters at a time against all vertices, ``lax.map``) serialises M
small reductions; the batched formulation below computes the full
(M, M) slot×slot proximity matrix in one pallas_call.

Layout (Mosaic's tiling rule: a block's last dim is a multiple of 128 or
the whole array, its second-to-last a multiple of 8 or the whole array):

* row side — the (M·V, 2) cluster-major vertices and an (M·V, 1) validity
  column, one (bi·V, …) strip per grid row;
* column side — the same vertices transposed to (2, V, M) plus a (V, M)
  validity, so column *slots* sit on lanes: one (…, V, bj) strip per grid
  column;
* output — the (bi, bj) tile of the (M, M) matrix.

Each grid step loops over the V column vertices; for column vertex b it
computes, per row slot, the (V, bj) squared distances from that slot's V
vertices to vertex b of every column slot, in difference form (the same
IEEE ops as ``ref.contour_min_d2``, so results are bit-equal to it), and
keeps a running (V, bj) min per row slot in VMEM.  A final sublane min
gives each row slot's (1, bj) output row.

Invalid vertices contribute ``BIG``; a slot with no valid vertices gets a
BIG row/column, which callers treat as "never merges".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEF_BI = 8
DEF_BJ = 128
BIG = 1e30


def _contour_min_kernel(x_ref, xv_ref, c_ref, cv_ref, o_ref, acc_ref, *,
                        v: int):
    bi = o_ref.shape[0]
    acc_ref[...] = jnp.full(acc_ref.shape, BIG, jnp.float32)

    def body(b, carry):
        cx = c_ref[0, pl.ds(b, 1), :]                 # (1, bj)
        cy = c_ref[1, pl.ds(b, 1), :]
        cok = cv_ref[pl.ds(b, 1), :] > 0
        for r in range(bi):
            rows = slice(r * v, (r + 1) * v)
            dx = x_ref[rows, 0:1] - cx                # (v, bj)
            dy = x_ref[rows, 1:2] - cy
            d2 = dx * dx + dy * dy
            ok = (xv_ref[rows, :] > 0) & cok
            acc_ref[rows, :] = jnp.minimum(acc_ref[rows, :],
                                           jnp.where(ok, d2, BIG))
        return carry

    jax.lax.fori_loop(0, v, body, 0)
    for r in range(bi):
        o_ref[r:r + 1, :] = jnp.min(acc_ref[r * v:(r + 1) * v, :], axis=0,
                                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("v", "bi", "bj", "interpret"))
def contour_min_d2(
    x: jax.Array, xv: jax.Array, v: int, *, bi: int = DEF_BI, bj: int = DEF_BJ,
    interpret: bool = False,
) -> jax.Array:
    """Slot×slot min squared contour distance.

    x: (m·v, 2) flattened contour vertices (cluster-major); xv: (m·v,)
    int32 vertex validity.  m must be a multiple of both ``bi`` and
    ``bj`` (ops.py pads with invalid slots).  Returns (m, m) f32 with BIG
    where either slot has no valid vertices.
    """
    n, d = x.shape
    assert d == 2 and n % v == 0, (n, d, v)
    m = n // v
    bi = min(bi, m)
    bj = min(bj, m)
    assert m % bi == 0 and m % bj == 0, (m, bi, bj)
    x = x.astype(jnp.float32)
    xv = xv.astype(jnp.int32)
    cols = x.reshape(m, v, 2).transpose(2, 1, 0)      # (2, v, m)
    cols_valid = xv.reshape(m, v).T                   # (v, m)
    return pl.pallas_call(
        functools.partial(_contour_min_kernel, v=v),
        grid=(m // bi, m // bj),
        in_specs=[
            pl.BlockSpec((bi * v, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bi * v, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((2, v, bj), lambda i, j: (0, 0, j)),
            pl.BlockSpec((v, bj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi * v, bj), jnp.float32)],
        interpret=interpret,
    )(x, xv.reshape(n, 1), cols, cols_valid)
