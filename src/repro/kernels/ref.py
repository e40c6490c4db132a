"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here defines the semantics; the Pallas kernels must match it
(tests sweep shapes/dtypes and assert_allclose against these).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def pairwise_dist_sq(x: Array, y: Array) -> Array:
    """Squared Euclidean distances.  x: (n, d), y: (m, d) -> (n, m).

    Uses the MXU-friendly expansion ||x||^2 + ||y||^2 - 2 x.y^T with the
    dot at ``Precision.HIGHEST`` (a TPU's default f32 matmul takes one
    bf16 pass).  For arbitrary d (k-means embeddings); the eps-threshold
    ops below use the exact difference form instead.
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    d2 = (
        jnp.sum(x * x, axis=-1)[:, None]
        + jnp.sum(y * y, axis=-1)[None, :]
        - 2.0 * jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    )
    return jnp.maximum(d2, 0.0)


def diff_d2(x: Array, yt: Array) -> Array:
    """Squared distances in difference form: x (n, d) rows against yt
    (d, m) columns -> (n, m), summed over d in index order.

    Only IEEE subtract, multiply and add, so every backend rounds it the
    same way: the phase-1 kernels evaluate this very expression on their
    tiles, which is what makes them bit-equal to the oracles below on any
    device (DESIGN.md §4)."""
    d2 = None
    for k in range(x.shape[1]):
        diff = x[:, k:k + 1].astype(jnp.float32) - yt[k:k + 1, :].astype(jnp.float32)
        d2 = diff * diff if d2 is None else d2 + diff * diff
    return d2


def neighbor_count(x: Array, mask: Array, eps: float) -> Array:
    """DDC/DBSCAN hot-spot: per-point count of masked points within eps
    (self included).  x: (n, d), mask: (n,) bool -> (n,) int32."""
    d2 = diff_d2(x, x.T)
    adj = (d2 <= jnp.asarray(eps, jnp.float32) ** 2) & mask[None, :] & mask[:, None]
    return jnp.sum(adj, axis=1).astype(jnp.int32)


def contour_min_d2(contours: Array, counts: Array, valid: Array) -> Array:
    """DDC phase-2 merge matrix: min squared distance between every pair
    of padded contour buffers.

    contours: (m, v, 2); counts: (m,) valid verts per slot; valid: (m,)
    slot validity.  Returns (m, m) f32 with 1e30 where either slot has no
    valid vertices.  Memory-bounded: one row of clusters at a time against
    all vertices, in the difference form the Pallas kernel also uses."""
    m, v, _ = contours.shape
    big = jnp.float32(1e30)
    pts = contours.astype(jnp.float32)
    vert_valid = (jnp.arange(v)[None, :] < counts[:, None]) & valid[:, None]
    flat = pts.reshape(m * v, 2)
    flat_valid = vert_valid.reshape(m * v)

    def row(i):
        d2 = diff_d2(pts[i], flat.T)
        d2 = jnp.where(vert_valid[i][:, None] & flat_valid[None, :], d2, big)
        return jnp.min(d2.reshape(v, m, v), axis=(0, 2))  # (m,)

    return jax.lax.map(row, jnp.arange(m))


def min_label_sweep(x: Array, mask: Array, labels: Array, core: Array,
                    eps) -> Array:
    """One DBSCAN min-label sweep: per point, the min label over masked
    core points within eps (2**30 where none)."""
    d2 = diff_d2(x, x.T)
    ok = (
        (d2 <= jnp.asarray(eps, jnp.float32) ** 2)
        & mask[:, None] & mask[None, :] & core[None, :]
    )
    labs = jnp.where(ok, labels[None, :].astype(jnp.int32), jnp.int32(2**30))
    return jnp.min(labs, axis=1)


# -- block-sparse variants (active tile-pair lists; see ops.build_tile_pairs)


def _pair_scan(x: Array, mask: Array, rows: Array, cols: Array,
               flags: Array, bt: int, init, contrib, combine):
    """Shared skeleton: sequentially fold listed (row, col) tile pairs into
    a per-row-tile accumulator — O(P · bt²) work and O(bt²) memory, the
    jnp mirror of the gathered-grid Pallas kernels."""
    n, d = x.shape
    t = n // bt
    xb = x.reshape(t, bt, d)
    mb = mask.reshape(t, bt)

    def step(acc, pair):
        r, c, f = pair
        valid = (f & 1) != 0
        out = contrib(jnp.take(xb, r, axis=0), jnp.take(xb, c, axis=0),
                      jnp.take(mb, r, axis=0), jnp.take(mb, c, axis=0),
                      r, c, valid)
        return combine(acc, r, out), None

    acc0 = jnp.full((t, bt), init, jnp.int32)
    acc, _ = jax.lax.scan(step, acc0, (rows, cols, flags))
    return acc.reshape(n)


def neighbor_count_sparse(x: Array, mask: Array, eps,
                          rows: Array, cols: Array, flags: Array,
                          bt: int) -> Array:
    """``neighbor_count`` restricted to listed tile pairs — bit-identical
    to the dense path when the list covers every within-eps tile pair."""
    eps_sq = jnp.asarray(eps, jnp.float32) ** 2

    def contrib(xt, yt, xm, ym, r, c, valid):
        d2 = diff_d2(xt, yt.T)
        within = (d2 <= eps_sq) & xm[:, None] & ym[None, :] & valid
        return jnp.sum(within.astype(jnp.int32), axis=1)

    return _pair_scan(x, mask, rows, cols, flags, bt, 0, contrib,
                      lambda acc, r, out: acc.at[r].add(out))


def min_label_sweep_sparse(x: Array, mask: Array, labels: Array, core: Array,
                           eps, rows: Array, cols: Array, flags: Array,
                           bt: int) -> Array:
    """``min_label_sweep`` restricted to listed tile pairs."""
    n = x.shape[0]
    t = n // bt
    eps_sq = jnp.asarray(eps, jnp.float32) ** 2
    lb = labels.astype(jnp.int32).reshape(t, bt)
    cb = core.reshape(t, bt)

    def contrib(xt, yt, xm, ym, r, c, valid):
        d2 = diff_d2(xt, yt.T)
        ok = ((d2 <= eps_sq) & xm[:, None] & ym[None, :]
              & jnp.take(cb, c, axis=0)[None, :] & valid)
        labs = jnp.where(ok, jnp.take(lb, c, axis=0)[None, :], jnp.int32(2**30))
        return jnp.min(labs, axis=1)

    return _pair_scan(x, mask, rows, cols, flags, bt, 2**30, contrib,
                      lambda acc, r, out: acc.at[r].min(out))


def flash_attention(
    q: Array, k: Array, v: Array, *, causal: bool = True, scale: float | None = None,
    window: int | None = None,
) -> Array:
    """Reference attention. q: (b, h, sq, d), k/v: (b, hkv, skv, d).

    GQA: h may be a multiple of hkv.  ``window``: optional local-attention
    width (attend to keys in (i - window, i]).
    """
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    skv = k.shape[2]
    qpos = jnp.arange(sq)[:, None] + (skv - sq)  # right-aligned (decode-friendly)
    kpos = jnp.arange(skv)[None, :]
    if causal:
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    if window is not None:
        logits = jnp.where(kpos > qpos - window, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_chunked(
    q: Array, k: Array, v: Array, *, causal: bool = True,
    scale: float | None = None, window: int | None = None,
    bq: int = 512, bk: int = 512,
) -> Array:
    """Pure-jnp online-softmax attention, chunked over Q and KV blocks.

    Numerically matches ``flash_attention`` but never materialises the
    (sq, skv) logits — O(bq*bk) temporaries, like the Pallas kernel's
    VMEM behaviour.  This is what the model stack runs on non-TPU
    backends (incl. the dry-run), so memory_analysis reflects the TPU
    kernel's footprint rather than a quadratic jnp fallback.  The inner
    step is checkpointed so the backward pass recomputes logits blocks
    instead of storing them.
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq = min(bq, sq)
    bk = min(bk, skv)
    # Pad to block multiples.
    pq = (-sq) % bq
    pk = (-skv) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    nq, nk = qp.shape[2] // bq, kp.shape[2] // bk
    qb = qp.reshape(b, hkv, rep, nq, bq, d).astype(jnp.float32) * scale
    kb = kp.reshape(b, hkv, nk, bk, d).astype(jnp.float32)
    vb = vp.reshape(b, hkv, nk, bk, d).astype(jnp.float32)
    q_off = skv - sq  # right-aligned positions

    def kv_step(carry, j):
        m_run, l_run, acc, qi = carry
        kj = jax.lax.dynamic_index_in_dim(kb, j, axis=2, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, axis=2, keepdims=False)
        qi_blk = jax.lax.dynamic_index_in_dim(qb, qi, axis=3, keepdims=False)
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qi_blk, kj)      # (b,hkv,rep,bq,bk)
        qpos = q_off + qi * bq + jnp.arange(bq)[:, None]
        kpos = j * bk + jnp.arange(bk)[None, :]
        mask = kpos < skv  # padding
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_run, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bgrqk,bgkd->bgrqd", p, vj)
        return (m_new, l_new, acc, qi), None

    kv_step = jax.checkpoint(kv_step)

    def q_step(_, qi):
        m0 = jnp.full((b, hkv, rep, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, rep, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, rep, bq, d), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            kv_step, (m0, l0, a0, qi), jnp.arange(nk)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))  # (nq,b,hkv,rep,bq,d)
    out = jnp.moveaxis(blocks, 0, 3).reshape(b, hkv, rep, nq * bq, d)
    out = out.reshape(b, h, nq * bq, d)[:, :, :sq]
    return out.astype(q.dtype)


def ssd_scan_chunked(x: Array, a: Array, b: Array, c: Array, *, chunk: int = 128) -> Array:
    """Chunked SSD in pure jnp — same math as the Pallas kernel
    (intra-chunk masked matmul + carried inter-chunk state).  Used as the
    CPU/dry-run stand-in for long sequences; see kernels/ssd_scan.py for
    the chunking algebra."""
    bsz, l, h, dh = x.shape
    ds = b.shape[-1]
    ch = min(chunk, l)
    pad = (-l) % ch
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = x.shape[1] // ch

    def to_chunks(t):
        return jnp.moveaxis(
            t.reshape((bsz, n, ch) + t.shape[2:]), 1, 0
        ).astype(jnp.float32)

    xs, as_, bs, cs = map(to_chunks, (x, a, b, c))   # (n, bsz, ch, ...)
    causal = jnp.tril(jnp.ones((ch, ch), jnp.float32))

    def step(state, inp):
        xc, ac, bc, cc = inp                          # (bsz, ch, h, ...)
        cum = jnp.cumsum(ac, axis=1)                  # (bsz, ch, h)
        decay = jnp.exp(cum[:, :, None] - cum[:, None, :])  # (bsz, ch, ch, h)
        decay = decay * causal[None, :, :, None]
        cb = jnp.einsum("bihs,bjhs->bijh", cc, bc)
        y = jnp.einsum("bijh,bjhd->bihd", cb * decay, xc)
        y += jnp.exp(cum)[..., None] * jnp.einsum("bihs,bhsd->bihd", cc, state)
        last = cum[:, -1]                             # (bsz, h)
        w = jnp.exp(last[:, None] - cum)              # (bsz, ch, h)
        state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bihs,bihd,bih->bhsd", bc, xc, w
        )
        return state, y

    s0 = jnp.zeros((bsz, h, ds, dh), jnp.float32)
    _, ys = jax.lax.scan(step, s0, (xs, as_, bs, cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, n * ch, h, dh)
    return y[:, :l].astype(x.dtype)


def ssd_scan(x: Array, a: Array, b: Array, c: Array) -> Array:
    """Mamba-2 SSD (state-space dual) reference, sequential scan.

    x: (b, l, h, dh)  input (already gated/projected)
    a: (b, l, h)      per-step log-decay (a = -softplus(...), i.e. <= 0)
    b: (b, l, h, ds)  input->state projection ("B" in SSD)
    c: (b, l, h, ds)  state->output projection ("C" in SSD)
    returns y: (b, l, h, dh) with state recurrence
        S_t = exp(a_t) * S_{t-1} + b_t^T x_t       (ds, dh)
        y_t = c_t @ S_t
    """
    bsz, l, h, dh = x.shape
    ds = b.shape[-1]

    def step(state, inp):
        xt, at, bt, ct = inp
        decay = jnp.exp(at)[..., None, None]  # (b, h, 1, 1)
        state = state * decay + bt[..., :, None] * xt[..., None, :]
        yt = jnp.einsum("bhs,bhsd->bhd", ct, state)
        return state, yt

    s0 = jnp.zeros((bsz, h, ds, dh), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(a, 1, 0).astype(jnp.float32),
        jnp.moveaxis(b, 1, 0).astype(jnp.float32),
        jnp.moveaxis(c, 1, 0).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
