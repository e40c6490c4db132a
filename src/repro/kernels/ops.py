"""Public kernel entry points.

Each op dispatches to the Pallas TPU kernel on TPU backends and to the
pure-jnp reference elsewhere.  Off the chip the kernels are checked in
interpret mode (tests/test_kernels.py) and compiled for a described v5e
(tests/test_tpu_compile.py); ``chip_smoke.py`` checks them against the
references on the chip.  Padding to tile multiples happens here so
kernels stay shape-strict.

Set ``repro.kernels.ops.FORCE`` to "pallas" / "ref" / "interpret" to
override dispatch (tests use "interpret").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import contour_dist as _cd
from . import flash_attention as _fa
from . import pairwise_dist as _pd
from . import ref
from . import ssd_scan as _ssd

FORCE: str | None = None


def _use_pallas() -> bool:
    if FORCE == "pallas":
        return True
    if FORCE in ("ref",):
        return False
    if FORCE == "interpret":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return FORCE == "interpret" or jax.default_backend() != "tpu"


def use_pallas_backend() -> bool:
    """Public probe: do ops dispatch to Pallas kernels right now?  Callers
    (dbscan's block-sparse "auto" mode) use this to skip gather-based
    layouts whose wins are kernel-side only."""
    return _use_pallas()


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# -- pairwise distance / neighbour counting --------------------------------

def pairwise_dist_sq(x: jax.Array, y: jax.Array, *, bn: int = 512, bm: int = 512) -> jax.Array:
    if not _use_pallas():
        return ref.pairwise_dist_sq(x, y)
    xp, n = _pad_to(x, 0, bn)
    yp, m = _pad_to(y, 0, bm)
    out = _pd.pairwise_dist_sq(xp, yp, bn=min(bn, xp.shape[0]), bm=min(bm, yp.shape[0]),
                               interpret=_interpret())
    return out[:n, :m]


def neighbor_count(x: jax.Array, mask: jax.Array, eps, *, bn: int = 512, bm: int = 512) -> jax.Array:
    if not _use_pallas():
        return ref.neighbor_count(x, mask, eps)
    xp, n = _pad_to(x, 0, bn)
    mp, _ = _pad_to(mask, 0, bn)
    out = _pd.neighbor_count(xp, mp, eps, bn=min(bn, xp.shape[0]), bm=min(bm, xp.shape[0]),
                             interpret=_interpret())
    return out[:n]


def min_label_sweep(x, mask, labels, core, eps, *, bn: int = 512, bm: int = 512) -> jax.Array:
    if not _use_pallas():
        return ref.min_label_sweep(x, mask, labels, core, eps)
    xp, n = _pad_to(x, 0, bn)
    mp, _ = _pad_to(mask, 0, bn)
    lp, _ = _pad_to(labels, 0, bn)
    cp, _ = _pad_to(core, 0, bn)
    out = _pd.min_label_sweep(xp, mp, lp, cp, eps, bn=min(bn, xp.shape[0]),
                              bm=min(bm, xp.shape[0]), interpret=_interpret())
    return out[:n]


def contour_min_d2(contours: jax.Array, counts: jax.Array, valid: jax.Array,
                   *, bi: int = _cd.DEF_BI, bj: int = _cd.DEF_BJ) -> jax.Array:
    """DDC phase-2 merge matrix: (m, m) min squared distance between every
    pair of padded contour buffers (1e30 where either side is empty).

    contours: (m, v, 2); counts: (m,); valid: (m,) bool.  The kernel and
    the jnp reference both use the difference form, so they agree to the
    bit on coordinates at any offset.  The slot axis is padded with
    invalid slots: to a multiple of ``bi`` while it fits one column strip
    (the strip is then the whole axis), else to a multiple of ``bj``.
    """
    if not _use_pallas():
        return ref.contour_min_d2(contours, counts, valid)
    m, v, d = contours.shape
    vert_valid = (jnp.arange(v)[None, :] < counts[:, None]) & valid[:, None]
    flat = contours.astype(jnp.float32).reshape(m * v, d)
    fv = vert_valid.reshape(m * v).astype(jnp.int32)
    mult = bi if m <= bj else bj
    pad = (-m) % mult
    if pad:
        flat = jnp.pad(flat, ((0, pad * v), (0, 0)))
        fv = jnp.pad(fv, (0, pad * v))
    out = _cd.contour_min_d2(flat, fv, v, bi=bi, bj=bj, interpret=_interpret())
    return out[:m, :m]


# -- block-sparse spatial pruning (DDC phase 1) ------------------------------


class TilePairs(NamedTuple):
    """Static-shape active tile-pair list for the block-sparse kernels.

    rows/cols/flags: (T*T,) int32 — active pairs first, in row-major
    order (so the kernels' output blocks see one contiguous run per row
    tile), tail-padded by repeating the last active pair with flags=0.
    flags bit0 = pair is real, bit1 = first pair of its row tile.
    n_active / frac are traced scalars (the pair *values* are data
    dependent; only shapes are static).  The kernels' grid is
    ``n_active`` steps long — it walks the active pairs only and never
    visits the tail, which the pair-list references skip by its flags.
    """

    rows: jax.Array     # (P,) int32 row-tile index
    cols: jax.Array     # (P,) int32 col-tile index
    flags: jax.Array    # (P,) int32 PAIR_VALID | PAIR_FIRST bits
    n_active: jax.Array  # () int32 — number of real pairs
    frac: jax.Array     # () f32 — n_active / T², the active-tile fraction


def build_tile_pairs(x: jax.Array, mask: jax.Array, eps, *, bt: int = 512) -> TilePairs:
    """Bounding-box pruning over ``bt``-point tiles of spatially sorted x.

    A tile pair (i, j) is *active* when the min distance between the two
    tiles' bounding boxes is <= eps — every within-eps point pair lives in
    an active tile pair, so skipping inactive pairs is exact, not an
    approximation.  Diagonal pairs are always active, which also
    guarantees every row tile appears in the list (the kernels rely on
    that to initialise all output blocks).  jit-traceable.
    """
    n, d = x.shape
    assert n % bt == 0, (n, bt)
    t = n // bt
    big = jnp.float32(3.4e38)
    xb = x.astype(jnp.float32).reshape(t, bt, d)
    mb = mask.reshape(t, bt)
    lo = jnp.min(jnp.where(mb[..., None], xb, big), axis=1)    # (T, d)
    hi = jnp.max(jnp.where(mb[..., None], xb, -big), axis=1)   # (T, d)
    has_pts = jnp.any(mb, axis=1)
    # Per-dim gap between boxes i and j (0 when they overlap on that dim).
    gap = jnp.maximum(lo[:, None, :] - hi[None, :, :],
                      lo[None, :, :] - hi[:, None, :])
    gap = jnp.maximum(gap, 0.0)
    gap_d2 = jnp.sum(gap * gap, axis=-1)                       # (T, T)
    eps_sq = jnp.asarray(eps, jnp.float32) ** 2
    active = (gap_d2 <= eps_sq) & has_pts[:, None] & has_pts[None, :]
    active = active | jnp.eye(t, dtype=bool)
    flat = active.reshape(t * t)
    n_active = jnp.sum(flat.astype(jnp.int32))
    # Active flat indices in row-major order; pad by repeating the last
    # active pair (same row tile -> no spurious output-block switch).
    (idx,) = jnp.nonzero(flat, size=t * t, fill_value=0)
    p = t * t
    is_real = jnp.arange(p, dtype=jnp.int32) < n_active
    last = idx[jnp.maximum(n_active - 1, 0)]
    idx = jnp.where(is_real, idx, last)
    rows = (idx // t).astype(jnp.int32)
    cols = (idx % t).astype(jnp.int32)
    first = is_real & jnp.concatenate(
        [jnp.asarray([True]), rows[1:] != rows[:-1]]
    )
    flags = (is_real.astype(jnp.int32) * _pd.PAIR_VALID
             | first.astype(jnp.int32) * _pd.PAIR_FIRST)
    frac = n_active.astype(jnp.float32) / float(t * t)
    return TilePairs(rows, cols, flags, n_active, frac)


def neighbor_count_sparse(x, mask, eps, pairs: TilePairs, *, bt: int = 512) -> jax.Array:
    """Block-sparse ``neighbor_count`` over spatially sorted points.

    x must already be padded to a multiple of ``bt`` (the block-sparse
    dbscan path owns the sort+pad so the pair list and data agree)."""
    if not _use_pallas():
        return ref.neighbor_count_sparse(x, mask, eps, pairs.rows,
                                         pairs.cols, pairs.flags, bt)
    return _pd.neighbor_count_sparse(x, mask, eps, pairs.rows, pairs.cols,
                                     pairs.flags, pairs.n_active, bt=bt,
                                     interpret=_interpret())


def min_label_sweep_sparse(x, mask, labels, core, eps, pairs: TilePairs, *,
                           bt: int = 512) -> jax.Array:
    """Block-sparse ``min_label_sweep`` over spatially sorted points."""
    if not _use_pallas():
        return ref.min_label_sweep_sparse(x, mask, labels, core, eps,
                                          pairs.rows, pairs.cols,
                                          pairs.flags, bt)
    return _pd.min_label_sweep_sparse(x, mask, labels, core, eps, pairs.rows,
                                      pairs.cols, pairs.flags, pairs.n_active,
                                      bt=bt, interpret=_interpret())


# -- attention ---------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, scale=None, window=None,
                    bq: int = 128, bk: int = 128) -> jax.Array:
    """q: (b, h, sq, d); k, v: (b, hkv, skv, d)."""
    if not _use_pallas() or v.shape[-1] != q.shape[-1]:
        # (MLA trains with d_v != d_qk; the pallas kernel assumes equal dims
        # — on TPU the MLA layer pads v, on CPU the ref handles it.)
        if q.shape[2] * k.shape[2] > 2**21 and v.shape[-1] == q.shape[-1]:
            # Large sequences: chunked online softmax — the CPU stand-in for
            # the Pallas kernel.  The named scope tells the roofline analyzer
            # (launch/hlo_cost.py) that these intermediates live in VMEM on
            # the TPU target and must not count as HBM traffic.
            with jax.named_scope("vmem_kernel_attn"):
                return ref.flash_attention_chunked(
                    q, k, v, causal=causal, scale=scale, window=window)
        return ref.flash_attention(q, k, v, causal=causal, scale=scale, window=window)
    qp, sq = _pad_to(q, 2, bq)
    kp, skv = _pad_to(k, 2, bk)
    vp, _ = _pad_to(v, 2, bk)
    # Padding keys get masked out by causality only when padding is at the
    # end and queries are right-aligned; pad K with +inf positions instead:
    # simplest correct route — require multiples for the pallas path.
    if qp.shape[2] != sq or kp.shape[2] != skv:
        return ref.flash_attention(q, k, v, causal=causal, scale=scale, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale, window=window,
                               bq=bq, bk=bk, interpret=_interpret())


# -- SSD scan ----------------------------------------------------------------

def ssd_scan(x, a, b, c, *, chunk: int = 128) -> jax.Array:
    if not _use_pallas():
        if x.shape[1] >= 2 * chunk:
            with jax.named_scope("vmem_kernel_ssd"):
                return ref.ssd_scan_chunked(x, a, b, c, chunk=chunk)
        return ref.ssd_scan(x, a, b, c)
    if x.shape[1] % min(chunk, x.shape[1]) != 0:
        return ref.ssd_scan(x, a, b, c)
    return _ssd.ssd_scan(x, a, b, c, chunk=chunk, interpret=_interpret())
