"""Block-sparse phase-1 kernels + pointer-doubling DBSCAN.

Equivalence contract: bounding-box pruning is exact (every within-eps
point pair lives in an active tile pair), so the block-sparse kernels and
the block-sparse dbscan path must match the dense reference **bit-exactly**
— on random, clustered, and adversarial (all points in one cell) layouts.
Pallas kernels run in interpret mode (CPU container).
"""
import math

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import dbscan as db
from repro.data import spatial
from repro.kernels import ops, ref
from repro.kernels import pairwise_dist as pd

RNG = np.random.default_rng(7)
make_worm = spatial.make_worm


def make_layout(name: str, n: int) -> np.ndarray:
    if name == "random":
        return RNG.uniform(0, 1, (n, 2)).astype(np.float32)
    if name == "clustered":
        return spatial.make_clustered(n, seed=int(RNG.integers(1 << 20)))
    if name == "one_cell":  # adversarial: zero pruning possible
        return (0.5 + RNG.normal(0, 0.001, (n, 2))).astype(np.float32)
    raise ValueError(name)


def sorted_inputs(pts, mask, bt):
    """Morton-sort + pad exactly the way the block-sparse dbscan path does."""
    sp, sm, _ = db.spatial_sort(jnp.asarray(pts), jnp.asarray(mask), bt)
    return sp, sm


class TestTilePairs:
    @pytest.mark.parametrize("layout", ["random", "clustered", "one_cell"])
    def test_invariants(self, layout):
        x, m = sorted_inputs(make_layout(layout, 500), RNG.random(500) > 0.2, 64)
        pairs = ops.build_tile_pairs(x, m, 0.06, bt=64)
        t = x.shape[0] // 64
        rows, cols, flags = map(np.asarray, (pairs.rows, pairs.cols, pairs.flags))
        n_active = int(pairs.n_active)
        valid = (flags & pd.PAIR_VALID) != 0
        assert valid.sum() == n_active
        assert valid[:n_active].all() and not valid[n_active:].any()
        # rows sorted; every row tile appears (diagonal always active)
        assert (np.diff(rows[:n_active]) >= 0).all()
        assert set(rows[:n_active]) == set(range(t))
        # exactly one FIRST flag per row tile, on its first pair
        first = (flags & pd.PAIR_FIRST) != 0
        assert first.sum() == t
        # tail padding repeats the last active pair (no block switch)
        assert (rows[n_active:] == rows[n_active - 1]).all()
        assert (cols[n_active:] == cols[n_active - 1]).all()
        assert 0.0 < float(pairs.frac) <= 1.0

    def test_offset_data_still_prunes(self):
        """Morton-grid bounds must come from masked points only: data far
        from the origin (with zero padding rows in the buffer) previously
        collapsed the sort grid into one cell, silently degrading frac to
        ~1.0.  Translation must not change the active fraction at all."""
        base = spatial.make_clustered(500, seed=3)
        fracs = []
        for off in (0.0, 100.0):
            x, m = sorted_inputs(base + np.float32(off), np.ones(500, bool), 64)
            fracs.append(float(ops.build_tile_pairs(x, m, 0.02, bt=64).frac))
        assert fracs[0] == fracs[1], fracs
        # and clustering the offset data stays exact through the sparse path
        pts = base + np.float32(100.0)
        got = db.dbscan(jnp.asarray(pts), jnp.ones(500, bool), 0.05, 5,
                        block_sparse="always", bt=64)
        np.testing.assert_array_equal(np.asarray(got.labels),
                                      db.dbscan_ref(pts, 0.05, 5))

    def test_pruning_is_exact(self):
        """No within-eps point pair may fall in an inactive tile pair."""
        x, m = sorted_inputs(make_layout("clustered", 400), np.ones(400, bool), 64)
        eps = 0.05
        pairs = ops.build_tile_pairs(x, m, eps, bt=64)
        t = x.shape[0] // 64
        active = np.zeros((t, t), bool)
        rows, cols = np.asarray(pairs.rows), np.asarray(pairs.cols)
        active[rows[: int(pairs.n_active)], cols[: int(pairs.n_active)]] = True
        d2 = np.asarray(ref.pairwise_dist_sq(x, x))
        within = (d2 <= eps * eps) & np.asarray(m)[:, None] & np.asarray(m)[None, :]
        ti = np.arange(x.shape[0]) // 64
        for i, j in zip(*np.nonzero(within)):
            assert active[ti[i], ti[j]]


class TestKernelEquivalence:
    @pytest.mark.parametrize("layout", ["random", "clustered", "one_cell"])
    @pytest.mark.parametrize("eps", [0.03, 0.1])
    def test_neighbor_count(self, layout, eps):
        pts = make_layout(layout, 384)
        mask = RNG.random(384) > 0.15
        x, m = sorted_inputs(pts, mask, 64)
        pairs = ops.build_tile_pairs(x, m, eps, bt=64)
        want = np.asarray(ref.neighbor_count(x, m, eps))
        got = pd.neighbor_count_sparse(x, m, eps, pairs.rows, pairs.cols,
                                       pairs.flags, pairs.n_active, bt=64,
                                       interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        got_ref = ref.neighbor_count_sparse(x, m, eps, pairs.rows, pairs.cols,
                                            pairs.flags, 64)
        np.testing.assert_array_equal(np.asarray(got_ref), want)

    @pytest.mark.parametrize("layout", ["random", "clustered", "one_cell"])
    def test_min_label_sweep(self, layout):
        pts = make_layout(layout, 384)
        mask = RNG.random(384) > 0.15
        x, m = sorted_inputs(pts, mask, 64)
        eps = 0.06
        n = x.shape[0]
        labels = jnp.asarray(RNG.permutation(n), jnp.int32)
        core = jnp.asarray(RNG.random(n) > 0.4)
        pairs = ops.build_tile_pairs(x, m, eps, bt=64)
        want = np.asarray(ref.min_label_sweep(x, m, labels, core, eps))
        got = pd.min_label_sweep_sparse(x, m, labels, core, eps, pairs.rows,
                                        pairs.cols, pairs.flags,
                                        pairs.n_active, bt=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        got_ref = ref.min_label_sweep_sparse(x, m, labels, core, eps,
                                             pairs.rows, pairs.cols,
                                             pairs.flags, 64)
        np.testing.assert_array_equal(np.asarray(got_ref), want)

    @pytest.mark.parametrize("kernel", ["neighbor_count", "min_label_sweep"])
    def test_tail_never_visited(self, kernel):
        """The grid stops at ``n_active``: a tail overwritten with other
        tiles flagged as real first pairs must not change a single bit."""
        bt, eps = 512, 0.03
        x, m = sorted_inputs(RNG.uniform(0, 1, (4096, 2)).astype(np.float32),
                             np.ones(4096, bool), bt)
        n = x.shape[0]
        t = n // bt
        labels = jnp.asarray(RNG.permutation(n), jnp.int32)
        core = jnp.asarray(RNG.random(n) > 0.4)
        pairs = ops.build_tile_pairs(x, m, eps, bt=bt)
        k = int(pairs.n_active)
        assert t <= k < t * t
        tail = np.arange(t * t - k)
        poison = lambda a, v: jnp.asarray(a).at[k:].set(v)
        rows = poison(pairs.rows, (tail * 5) % t)
        cols = poison(pairs.cols, (tail * 3 + 1) % t)
        flags = poison(pairs.flags, pd.PAIR_VALID | pd.PAIR_FIRST)
        if kernel == "neighbor_count":
            run = lambda r, c, f: pd.neighbor_count_sparse(
                x, m, eps, r, c, f, pairs.n_active, bt=bt, interpret=True)
            want = ref.neighbor_count(x, m, eps)
        else:
            run = lambda r, c, f: pd.min_label_sweep_sparse(
                x, m, labels, core, eps, r, c, f, pairs.n_active, bt=bt,
                interpret=True)
            want = ref.min_label_sweep(x, m, labels, core, eps)
        clean = np.asarray(run(pairs.rows, pairs.cols, pairs.flags))
        np.testing.assert_array_equal(clean, np.asarray(want))
        np.testing.assert_array_equal(np.asarray(run(rows, cols, flags)),
                                      clean)


class TestDBSCANBlockSparse:
    @pytest.mark.parametrize("layout", ["random", "clustered", "one_cell"])
    def test_matches_oracle(self, layout):
        pts = make_layout(layout, 420)
        eps, min_pts = (0.05, 5) if layout != "one_cell" else (0.002, 5)
        want = db.dbscan_ref(pts, eps, min_pts)
        got = db.dbscan(jnp.asarray(pts), jnp.ones(len(pts), bool), eps,
                        min_pts, block_sparse="always", bt=64)
        np.testing.assert_array_equal(np.asarray(got.labels), want)

    def test_sparse_equals_dense_path(self):
        pts, _ = spatial.make_blobs(700, 6, seed=11)
        mask = jnp.asarray(RNG.random(700) > 0.1)
        dense = db.dbscan(jnp.asarray(pts), mask, 0.05, 5, block_sparse="never")
        sparse = db.dbscan(jnp.asarray(pts), mask, 0.05, 5,
                           block_sparse="always", bt=64)
        np.testing.assert_array_equal(np.asarray(dense.labels),
                                      np.asarray(sparse.labels))
        np.testing.assert_array_equal(np.asarray(dense.core),
                                      np.asarray(sparse.core))
        assert int(dense.n_clusters) == int(sparse.n_clusters)

    def test_dense_fallback_threshold(self):
        """frac > dense_fallback_frac must route to the dense kernels and
        still give identical results (one_cell forces frac = 1)."""
        pts = make_layout("one_cell", 300)
        want = db.dbscan_ref(pts, 0.002, 4)
        got = db.dbscan(jnp.asarray(pts), jnp.ones(300, bool), 0.002, 4,
                        block_sparse="always", bt=64, dense_fallback_frac=0.1)
        np.testing.assert_array_equal(np.asarray(got.labels), want)

    def test_padding_mask(self):
        pts, _ = spatial.make_blobs(220, 3, seed=4)
        padded = np.concatenate([pts, np.zeros((120, 2), np.float32)])
        mask = jnp.asarray([True] * 220 + [False] * 120)
        res = db.dbscan(jnp.asarray(padded), mask, 0.05, 5,
                        block_sparse="always", bt=64)
        np.testing.assert_array_equal(np.asarray(res.labels)[:220],
                                      db.dbscan_ref(pts, 0.05, 5))
        assert (np.asarray(res.labels)[220:] == db.NOISE).all()


def fixed_trip_propagate(sweep_fn, init, core, max_iters, doubling_steps):
    """The reference: sweeps each followed by all ``doubling_steps``
    shortcut steps, run whether or not they change a label (the loop the
    fixed-point stop replaced).  Same contract as ``db._propagate``."""
    n = init.shape[0]

    def shortcut(_, l):
        jumped = jnp.take(l, jnp.where(l < n, l, 0))
        return jnp.minimum(l, jnp.where(l < n, jumped, l))

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    def body(state):
        labels, _, it = state
        swept = sweep_fn(labels)
        new = jnp.where(core, jnp.minimum(labels, swept), labels)
        new = jax.lax.fori_loop(0, doubling_steps, shortcut, new)
        return new, jnp.any(new != labels), it + 1

    labels, _, n_sweeps = jax.lax.while_loop(
        cond, body, (init, jnp.asarray(True), jnp.asarray(0, jnp.int32)))
    return labels, n_sweeps, n_sweeps * doubling_steps


def _doubling_case(name):
    if name == "worm":
        return make_worm(800, seed=3), 0.02, 5
    if name == "blobs":
        return spatial.make_blobs(400, 5, seed=2)[0], 0.05, 5
    spec = spatial.PHASE2_LAYOUTS[name]
    return spec["make"](600), spec["eps"], spec["min_pts"]


class TestPointerDoubling:
    def test_labels_identical(self):
        pts, _ = spatial.make_blobs(400, 5, seed=2)
        a = db.dbscan(jnp.asarray(pts), jnp.ones(400, bool), 0.05, 5,
                      pointer_doubling=False, block_sparse="never")
        b = db.dbscan(jnp.asarray(pts), jnp.ones(400, bool), 0.05, 5,
                      pointer_doubling=True, block_sparse="never")
        np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))

    def test_worm_sweep_reduction(self):
        """The tentpole claim: ≥3× fewer sweeps on a diameter-bound layout."""
        worm = make_worm(1024)
        kw = dict(block_sparse="never")
        plain = db.dbscan(jnp.asarray(worm), jnp.ones(1024, bool), 0.02, 5,
                          pointer_doubling=False, **kw)
        doubled = db.dbscan(jnp.asarray(worm), jnp.ones(1024, bool), 0.02, 5,
                            pointer_doubling=True, **kw)
        np.testing.assert_array_equal(np.asarray(plain.labels),
                                      np.asarray(doubled.labels))
        assert int(plain.n_sweeps) >= 3 * int(doubled.n_sweeps), (
            int(plain.n_sweeps), int(doubled.n_sweeps))

    def test_worm_oracle(self):
        worm = make_worm(800, seed=3)
        want = db.dbscan_ref(worm, 0.02, 5)
        got = db.dbscan(jnp.asarray(worm), jnp.ones(800, bool), 0.02, 5,
                        block_sparse="always", bt=128)
        np.testing.assert_array_equal(np.asarray(got.labels), want)

    @pytest.mark.parametrize("block_sparse", ["never", "always"])
    @pytest.mark.parametrize("layout", ["worm", "blobs", "rings"])
    def test_bit_identical_to_fixed_trips(self, layout, block_sparse,
                                          monkeypatch):
        """Doubling that stops at its fixed point returns what the
        fixed-trip loop returns, in no more gathers."""
        pts, eps, min_pts = _doubling_case(layout)
        n = len(pts)
        args = (jnp.asarray(pts), jnp.ones(n, bool), eps, min_pts)
        kw = dict(block_sparse=block_sparse, bt=128)
        got = db.dbscan(*args, **kw)
        with monkeypatch.context() as m:
            m.setattr(db, "_propagate", fixed_trip_propagate)
            # A fresh function, so the trace cache cannot hand back the
            # program traced with the program's own loop.
            want = jax.jit(lambda: db.dbscan.__wrapped__(*args, **kw))()
        for field in ("labels", "core", "n_clusters", "n_sweeps"):
            np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                          np.asarray(getattr(want, field)),
                                          err_msg=field)
        cap = int(want.n_doubling_steps)
        assert cap == int(want.n_sweeps) * math.ceil(math.log2(n))
        assert 1 <= int(got.n_doubling_steps) <= cap
        if layout == "worm":
            assert int(got.n_doubling_steps) < cap

    def test_no_steps_without_doubling(self):
        pts, eps, min_pts = _doubling_case("worm")
        res = db.dbscan(jnp.asarray(pts), jnp.ones(len(pts), bool), eps,
                        min_pts, pointer_doubling=False, block_sparse="never")
        assert int(res.n_sweeps) > 1 and int(res.n_doubling_steps) == 0
