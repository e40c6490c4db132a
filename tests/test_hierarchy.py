"""Hierarchical≡flat equivalence suite for the aggregator tree
(DESIGN.md §13).

The contract under test: a ``ClusterService`` running the
tree-of-aggregators (``agg_degree`` set) produces BIT-IDENTICAL
per-shard global labels and slot maps to the flat aggregator on the
same ingest schedule — for every tuned layout, shard count, and tree
degree, through quarantine/recovery and snapshot restore.  The root's
canonical relabel (size desc, min composed flat slot asc) is what makes
the slot ids line up; the per-node pair-d2 caches must always equal a
from-scratch rebuild of the node batch (``cache_exact``).

Scope note (same envelope as the ``merge_tree ≡ merge_sync`` suite):
internal nodes re-extract merged contours before folding upward, so a
*pathological* partition could change overlap reachability mid-tree.
The equivalence promised — and swept here — is over the engines' real
partition orders (round-robin / contiguous streaming).

The dist-engine cells need 16 CPU devices, so they run in a subprocess
(tests/_hierarchy_script.py) mirroring the chaos-suite pattern.  Big
sweeps are marked ``slow``.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ddc
from repro.data import spatial
from repro.ddc import DDC, DDCConfig
from repro.ddc.config import ConfigError
from repro.serve import ClusterService, StreamConfig
from repro.serve.hierarchy import AggregatorTree

from test_serve_stream import N, build_service, layout_cfg, stream

SCRIPT = os.path.join(os.path.dirname(__file__), "_hierarchy_script.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def build_pair(layout: str, k: int, degree: int):
    """A flat service and a tree-of-aggregators twin on the same layout.
    ``max_batch`` is capped at the shard capacity: the ingest ring
    rejects a batch larger than itself (16 shards of N leave 128 slots)."""
    spec = spatial.PHASE2_LAYOUTS[layout]
    pts = spec["make"](N)
    cap = spatial.shard_capacity(N, k)
    flat = ClusterService(StreamConfig(
        shards=k, capacity=cap, max_batch=min(256, cap),
        ddc=layout_cfg(spec)))
    hier = ClusterService(StreamConfig(
        shards=k, capacity=cap, max_batch=min(256, cap), agg_degree=degree,
        ddc=layout_cfg(spec)))
    return flat, hier, pts, spec


def assert_equiv(flat, hier):
    """Bit-identical where the §13 contract promises it: per-shard global
    labels, slot maps, and the global set's occupancy (valid/sizes).
    Root contours are re-extracted per level, so their raw vertices may
    differ without changing any label — they are not compared."""
    _, _, lab_flat = flat.live()
    _, _, lab_hier = hier.live()
    np.testing.assert_array_equal(lab_hier, lab_flat)
    np.testing.assert_array_equal(
        np.asarray(hier._maps), np.asarray(flat._maps))
    np.testing.assert_array_equal(
        np.asarray(hier.global_set.valid), np.asarray(flat.global_set.valid))
    np.testing.assert_array_equal(
        np.asarray(hier.global_set.sizes), np.asarray(flat.global_set.sizes))
    tree = hier.hierarchy
    assert tree is not None and hier.pair_d2 is None
    assert tree.cache_exact(), "a node cache diverged from scratch rebuild"


class TestBatchedPairD2Patch:
    """The ``update_pair_d2_many`` rewrite of ``merge_delta``'s dirty
    loop must be bit-exact vs both the sequential per-shard patch and a
    from-scratch matrix (including the pow2 duplicate-index padding)."""

    def _batch_and_cfg(self):
        svc, pts, _ = build_service("rings", 8)
        stream(svc, pts, 8)
        return svc._batch, svc.cfg, np.asarray(svc.pair_d2)

    def test_many_equals_sequential_equals_scratch(self):
        batch, cfg, exact = self._batch_and_cfg()
        c = cfg.max_clusters
        dirty = [1, 3, 6]
        stale = exact.copy()
        for s in dirty:                      # poison the rows to be patched
            stale[s * c:(s + 1) * c, :] = 123.0
            stale[:, s * c:(s + 1) * c] = 123.0
        seq = jnp.asarray(stale)
        for s in dirty:
            seq = ddc.update_pair_d2(seq, batch, s, cfg)
        padded = dirty + [dirty[-1]]         # pow2 pad repeats the last shard
        many = ddc.update_pair_d2_many(
            jnp.asarray(stale), batch, jnp.asarray(padded, jnp.int32), cfg)
        np.testing.assert_array_equal(np.asarray(many), np.asarray(seq))
        np.testing.assert_array_equal(np.asarray(many), exact)

    def test_merge_delta_multi_dirty_equals_full(self):
        batch, cfg, exact = self._batch_and_cfg()
        c = cfg.max_clusters
        dirty = [0, 2, 5, 7]
        stale = exact.copy()
        for s in dirty:
            stale[s * c:(s + 1) * c, :] = -1.0
            stale[:, s * c:(s + 1) * c] = -1.0
        m_d, maps_d, d2_d = ddc.merge_delta(
            batch, jnp.asarray(stale), dirty, cfg, None)
        m_f, maps_f, d2_f = ddc.merge_delta(batch, None, None, cfg, None)
        np.testing.assert_array_equal(np.asarray(d2_d), np.asarray(d2_f))
        np.testing.assert_array_equal(np.asarray(maps_d), np.asarray(maps_f))
        for a, b in zip(jax.tree.leaves(m_d), jax.tree.leaves(m_f)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestTreeTopology:
    def test_shapes(self):
        cfg = layout_cfg(spatial.PHASE2_LAYOUTS["rings"])
        t = AggregatorTree(16, 2, cfg)
        assert (t.depth, t.n_nodes, t.internal_edges) == (4, 15, 14)
        t = AggregatorTree(16, 4, cfg)
        assert (t.depth, t.n_nodes) == (2, 5)
        t = AggregatorTree(5, 4, cfg)        # ragged last group
        assert [len(lvl) for lvl in t.levels] == [2, 1]
        t = AggregatorTree(1, 2, cfg)        # degenerate single shard
        assert (t.depth, t.n_nodes, t.internal_edges) == (1, 1, 0)
        assert not t.ready

    def test_rejects_bad_shapes(self):
        cfg = layout_cfg(spatial.PHASE2_LAYOUTS["rings"])
        with pytest.raises(ValueError):
            AggregatorTree(8, 1, cfg)
        with pytest.raises(ValueError):
            AggregatorTree(0, 2, cfg)


class TestHierEqualsFlatStream:
    @pytest.mark.parametrize("layout,k,degree", [
        ("rings", 4, 2), ("linked_ovals", 8, 4),
        ("worm", 4, 4), ("noise_heavy", 8, 2)])
    def test_stream_cells(self, layout, k, degree):
        flat, hier, pts, spec = build_pair(layout, k, degree)
        for svc in (flat, hier):
            stream(svc, pts, k)
        assert_equiv(flat, hier)
        assert hier.delta_refreshes > 0, "tree never took the delta path"

    def test_depth1_root_cache_is_the_flat_cache(self):
        """k == degree collapses the tree to one node whose batch IS the
        shard batch — its cache must literally equal flat ``pair_d2``."""
        flat, hier, pts, _ = build_pair("rings", 4, 4)
        for svc in (flat, hier):
            stream(svc, pts, 4)
        tree = hier.hierarchy
        assert (tree.depth, tree.n_nodes) == (1, 1)
        np.testing.assert_array_equal(
            tree.cache_arrays()[0], np.asarray(flat.pair_d2))
        assert_equiv(flat, hier)

    def test_quarantined_leaf_and_recovery(self):
        """Fencing a shard excludes it at its leaf node only; recovery
        is one ordinary delta patch — both states must match flat."""
        flat, hier, pts, _ = build_pair("linked_ovals", 8, 2)
        for svc in (flat, hier):
            stream(svc, pts, 8)
        for svc in (flat, hier):
            svc._quarantine(3, "test fence")
            svc.refresh(force=True)
        _, _, lab_flat = flat.live()
        _, _, lab_hier = hier.live()
        np.testing.assert_array_equal(lab_hier, lab_flat)
        for svc in (flat, hier):
            assert svc.recover(3)
            svc.refresh(force=True)
        assert_equiv(flat, hier)

    def test_state_roundtrip_keeps_tree_mode(self):
        _, hier, pts, _ = build_pair("rings", 4, 2)
        stream(hier, pts, 4)
        arrays, manifest = hier.state_dict()
        assert manifest["agg_degree"] == 2
        svc2 = ClusterService.from_state(hier.scfg, arrays, manifest)
        assert svc2.hierarchy is not None and svc2.pair_d2 is None
        _, _, lab = hier.live()
        _, _, lab2 = svc2.live()
        np.testing.assert_array_equal(lab2, lab)
        assert svc2.hierarchy.cache_exact()

    @pytest.mark.slow
    @pytest.mark.parametrize("layout", sorted(spatial.PHASE2_LAYOUTS))
    def test_hier_equals_flat_sweep(self, layout):
        """Every layout × {4, 8, 16} shards × degree {2, 4}."""
        for k in (4, 8, 16):
            for degree in (2, 4):
                flat, hier, pts, _ = build_pair(layout, k, degree)
                for svc in (flat, hier):
                    stream(svc, pts, k)
                assert_equiv(flat, hier)


class TestTreeRefreshBytes:
    """The reason DESIGN §13 gives for the tree: at K = 32 a steady-state
    single-dirty refresh (one shard re-ingested, global structure
    unchanged) moves fewer bytes through the tree than through the flat
    aggregator, whose down-leg broadcasts a (C,) map row to every shard.

    Workload: 8 well-separated blobs, contiguous partition (a subtree
    covers a contiguous point range), small slot budgets."""

    K = 32
    CFG = ddc.DDCConfig(eps=0.03, min_pts=3, grid=48,
                        max_clusters=8, max_verts=24)

    @staticmethod
    def blobs(n: int = 8192, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        centers = [(0.18 + 0.32 * (i % 3), 0.18 + 0.32 * (i // 3))
                   for i in range(8)]
        pts = np.concatenate([
            c + rng.normal(scale=0.018, size=(n // 8, 2)) for c in centers])
        return np.clip(pts, 0.01, 0.99).astype(np.float32)

    @pytest.fixture(scope="class")
    def flat(self):
        """The (K, C) shard batch and the flat arm after one steady
        single-dirty ``merge_delta`` refresh."""
        cfg, k = self.CFG, self.K
        slices = np.array_split(self.blobs(), k)
        cap = max(len(s) for s in slices)
        sets = []
        for sl in slices:
            buf = np.zeros((cap, 2), np.float32)
            buf[:len(sl)] = sl
            mask = np.arange(cap) < len(sl)
            _, cs = ddc.local_phase(jnp.asarray(buf), jnp.asarray(mask), cfg)
            sets.append(cs)
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *sets)
        _, _, d2 = ddc.merge_delta(batch, None, None, cfg, None)
        _, maps, _ = ddc.merge_delta(batch, d2, [0], cfg, None)
        return batch, np.asarray(maps)

    @pytest.mark.parametrize("degree", [2, 4])
    def test_tree_refresh_moves_fewer_bytes_than_flat(self, flat, degree):
        batch, flat_maps = flat
        cfg, k = self.CFG, self.K
        bbytes, row = cfg.buffer_bytes(), cfg.max_clusters * 4
        tree = AggregatorTree(k, degree, cfg)
        tree.refresh(batch, None, None)
        tree.refresh(batch, [0], None)
        _, maps = tree.refresh(batch, [0], None)
        s = tree.last_stats
        # Wire model: a ClusterSet per dirty shard payload and per internal
        # summary push; a (C,) i32 row per down map edge and changed shard.
        tree_bytes = ((s["up_shard_payloads"] + s["internal_up_edges"]) * bbytes
                      + (s["down_internal_edges"] + s["down_shard_rows"]) * row)
        # Flat: one shard payload up, the engine's K-row map broadcast down.
        flat_bytes = bbytes + k * row
        np.testing.assert_array_equal(np.asarray(maps), flat_maps)
        assert tree_bytes < flat_bytes
        assert (tree_bytes, flat_bytes) == (1609, 2633)


class TestFacade:
    def test_validation_rejects_bad_degrees(self):
        for bad in (1, 3, 6):
            with pytest.raises(ConfigError):
                DDCConfig(backend="stream", agg_degree=bad).validate()
        with pytest.raises(ConfigError):
            DDCConfig(backend="host", agg_degree=2).validate()
        DDCConfig(backend="stream", agg_degree=4).validate()

    def test_manifest_roundtrip(self):
        cfg = DDCConfig(backend="stream", agg_degree=4).validate()
        assert DDCConfig.from_manifest(cfg.to_manifest()) == cfg

    def test_facade_labels_match_flat(self):
        spec = spatial.PHASE2_LAYOUTS["rings"]
        pts = spec["make"](512)
        kw = dict(eps=spec["eps"], min_pts=spec["min_pts"],
                  grid=spec["grid"], max_clusters=spec["max_clusters"],
                  max_verts=spec["max_verts"], backend="stream", shards=4)
        flat = DDC(DDCConfig(**kw).validate()).fit(pts)
        hier = DDC(DDCConfig(agg_degree=2, **kw).validate()).fit(pts)
        np.testing.assert_array_equal(hier.labels_, flat.labels_)
        assert hier.backend.service.hierarchy is not None


# -- dist engine (16 CPU devices -> subprocess) -----------------------------

def run_script(arg: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, SCRIPT, arg],
        capture_output=True, text=True, timeout=1800, env=env)
    assert proc.returncode == 0, (
        f"{arg} failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout


def test_dist_hier_quick():
    """Two cells on the device-resident engine: labels/maps equal flat,
    node caches exact, delta path actually taken."""
    out = run_script("quick")
    assert "ALL_OK" in out and out.count("PASS") == 2


@pytest.mark.slow
def test_dist_hier_sweep():
    """Every layout × {4, 8, 16} shards × degree {2, 4} on dist."""
    out = run_script("all")
    assert "ALL_OK" in out
