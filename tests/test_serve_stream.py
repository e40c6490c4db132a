"""Streaming≡batch equivalence suite for the DDC serve engine.

The contract under test (DESIGN.md §8): any sequence of ingest batches,
refreshed incrementally (dirty-shard phase 1 + delta-merge), yields the
IDENTICAL global clustering as batch ``ddc_host`` on the union of live
points with the same per-shard membership — bit-exact in the
``same_clustering`` sense (same noise set, label bijection).  Plus the
delta-merge internals (cached matrix == from-scratch matrix), the comm
accounting of delta vs full re-merge, and the eviction regressions
(emptied shard -> cached ``empty_clusterset`` path; ring overwrite).

Big sweeps are marked ``slow`` (the separate tier1-slow CI job); the
unmarked subset keeps the tier-1 CI job light.
"""
import json

import numpy as np
import pytest

from repro.core import ddc
from repro.data import spatial
from repro.serve import ClusterService, StreamConfig

from _hyp import given, settings, st  # optional-hypothesis shim

N = 2048


def layout_cfg(spec) -> ddc.DDCConfig:
    return ddc.DDCConfig(
        eps=spec["eps"], min_pts=spec["min_pts"], grid=spec["grid"],
        max_clusters=spec["max_clusters"], max_verts=spec["max_verts"])


def build_service(layout: str, k: int, meter=None, capacity=None,
                  max_batch=256):
    spec = spatial.PHASE2_LAYOUTS[layout]
    pts = spec["make"](N)
    cap = capacity or spatial.shard_capacity(N, k)
    scfg = StreamConfig(shards=k, capacity=cap, max_batch=max_batch,
                        ddc=layout_cfg(spec))
    return ClusterService(scfg, meter=meter), pts, spec


def stream(svc, pts, k, order="round_robin", seed=None, batch=256,
           refresh_every=1):
    batches = spatial.stream_batches(pts, k, batch, order=order, seed=seed)
    for i, (shard, chunk) in enumerate(batches):
        svc.ingest(shard, chunk)
        if refresh_every and (i + 1) % refresh_every == 0:
            svc.refresh()
    svc.refresh()


def assert_matches_host(svc, spec):
    pts, parts, labels = svc.live()
    host, _, _ = ddc.ddc_host(pts, len(parts), spec["eps"], spec["min_pts"],
                              partition=parts, contour="grid")
    assert ddc.same_clustering(labels, host), (
        "streaming clustering diverged from batch ddc_host")
    return labels


class TestStreamEqualsBatch:
    @pytest.mark.parametrize("layout,k", [
        ("rings", 2), ("linked_ovals", 4), ("noise_heavy", 2)])
    def test_incremental_stream_matches_host(self, layout, k):
        svc, pts, spec = build_service(layout, k)
        stream(svc, pts, k)
        assert_matches_host(svc, spec)

    @pytest.mark.slow
    @pytest.mark.parametrize("layout", sorted(spatial.PHASE2_LAYOUTS))
    def test_stream_matches_host_sweep(self, layout):
        """Every layout × 2/4/8 shards, refresh after every batch."""
        for k in (2, 4, 8):
            svc, pts, spec = build_service(layout, k)
            stream(svc, pts, k)
            assert_matches_host(svc, spec)

    def test_refresh_cadence_invariant(self):
        """Refreshing after every batch vs once at the end is the same
        clustering (delta folds commute with batching)."""
        ref = None
        for every in (1, 3, 0):
            svc, pts, spec = build_service("rings", 4)
            stream(svc, pts, 4, refresh_every=every)
            labels = assert_matches_host(svc, spec)
            if ref is None:
                ref = labels
            else:
                assert ddc.same_clustering(labels, ref)

    def test_delta_state_equals_full_remerge(self):
        """The incrementally maintained distance matrix and global labels
        are bit-identical to a from-scratch re-merge."""
        svc, pts, spec = build_service("linked_ovals", 4)
        stream(svc, pts, 4)
        d2_delta = np.asarray(svc.pair_d2)
        _, _, labels_delta = svc.live()
        svc.remerge_full()
        np.testing.assert_array_equal(d2_delta, np.asarray(svc.pair_d2))
        _, _, labels_full = svc.live()
        np.testing.assert_array_equal(labels_delta, labels_full)


class TestIngestOrderings:
    """Hypothesis-driven ingest orderings: the final clustering must not
    depend on the order batches arrived or where refreshes landed."""

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           batch=st.sampled_from((128, 256)),
           refresh_every=st.integers(1, 4))
    def test_shuffled_order_matches_host(self, seed, batch, refresh_every):
        svc, pts, spec = build_service("linked_ovals", 2)
        stream(svc, pts, 2, order="shuffled", seed=seed, batch=batch,
               refresh_every=refresh_every)
        assert_matches_host(svc, spec)

    @pytest.mark.slow
    @pytest.mark.parametrize("layout", sorted(spatial.PHASE2_LAYOUTS))
    @settings(max_examples=2, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), k=st.sampled_from((2, 4, 8)))
    def test_shuffled_order_sweep(self, layout, seed, k):
        svc, pts, spec = build_service(layout, k)
        stream(svc, pts, k, order="shuffled", seed=seed)
        assert_matches_host(svc, spec)


class TestEviction:
    def test_cleared_shard_takes_cached_empty_path(self):
        """Evicting every point from a shard must reduce it to the cached
        empty_clusterset (the PR 2 empty-shard fix, streaming edition) and
        keep the global state equal to batch on the remaining points."""
        svc, pts, spec = build_service("noise_heavy", 4)
        stream(svc, pts, 4)
        assert svc.clear(1) > 0
        svc.refresh()
        empty = ddc.empty_clusterset(svc.cfg)
        assert svc.local_set(1).contours is empty.contours  # cached, not rebuilt
        _, parts, _ = svc.live()
        assert len(parts[1]) == 0
        assert_matches_host(svc, spec)

    def test_clear_all_shards_goes_global_empty(self):
        svc, pts, spec = build_service("rings", 2)
        stream(svc, pts, 2)
        for s in range(2):
            svc.clear(s)
        svc.refresh()
        assert svc.n_live() == 0
        assert int(np.asarray(svc.global_set.valid).sum()) == 0
        assert (svc.query(pts[:16]) == -1).all()

    def test_ring_overwrite_evicts_oldest(self):
        """Ingesting past capacity overwrites the oldest points in place;
        the result must equal batch on exactly the surviving window."""
        cfg = ddc.DDCConfig(eps=0.05, min_pts=5, max_clusters=16,
                            max_verts=64, grid=96)
        svc = ClusterService(StreamConfig(shards=2, capacity=512,
                                          max_batch=128, ddc=cfg))
        pts, _ = spatial.make_blobs(1400, 4, seed=3)
        for shard, chunk in spatial.stream_batches(pts, 2, 128):
            svc.ingest(shard, chunk)
        svc.refresh()
        live_pts, parts, labels = svc.live()
        assert len(live_pts) == 2 * 512
        host, _, _ = ddc.ddc_host(live_pts, 2, cfg.eps, cfg.min_pts,
                                  partition=parts, contour="grid")
        assert ddc.same_clustering(labels, host)

    def test_evict_then_reingest_is_idempotent(self):
        svc, pts, spec = build_service("rings", 2)
        stream(svc, pts, 2)
        ref = assert_matches_host(svc, spec)
        part0 = np.array_split(pts, 2)[0]
        svc.clear(0)
        svc.refresh()
        svc.ingest(0, part0)
        svc.refresh()
        labels = assert_matches_host(svc, spec)
        assert ddc.same_clustering(labels, ref)


class TestCommAccounting:
    def test_delta_moves_fewer_bytes_than_full(self):
        """Steady-state single-shard ingest: delta ships one ClusterSet
        up (+ map rows down); a full re-merge ships all K.  The exact
        counter values are static, so assert them, not just the order."""
        k = 8
        meter = ddc.CommMeter()
        svc, pts, spec = build_service("rings", k, meter=meter)
        stream(svc, pts, k)
        b = svc.cfg.buffer_bytes()
        c = svc.cfg.max_clusters

        meter.reset()
        svc.ingest(0, pts[:8])          # one dirty shard
        svc.refresh()
        delta_bytes = meter.snapshot()["bytes_total"]
        assert delta_bytes == 1 * b + k * c * 4

        meter.reset()
        svc.remerge_full()
        full_bytes = meter.snapshot()["bytes_total"]
        assert full_bytes == k * b + k * c * 4
        assert delta_bytes < full_bytes

    def test_noop_refresh_is_free(self):
        meter = ddc.CommMeter()
        svc, pts, _ = build_service("rings", 2, meter=meter)
        stream(svc, pts, 2)
        before = meter.snapshot()
        svc.refresh()                    # nothing dirty
        assert meter.snapshot() == before


class TestQuery:
    def test_query_live_points_and_noise(self):
        svc, pts, spec = build_service("rings", 4)
        stream(svc, pts, 4)
        live_pts, _, labels = svc.live()
        got = svc.query(live_pts[:400])
        clustered = labels[:400] >= 0
        np.testing.assert_array_equal(got[clustered], labels[:400][clustered])
        # A clustered point queries back to its own cluster; a far-away
        # probe is noise.
        assert (svc.query(np.array([[5.0, 5.0], [-3.0, 7.0]])) == -1).all()

    def test_query_autorefreshes_pending_writes(self):
        svc, pts, spec = build_service("rings", 2)
        stream(svc, pts, 2)
        svc.ingest(0, pts[:32])          # leave shard dirty
        before = svc.refreshes
        svc.query(pts[:8])
        assert svc.refreshes == before + 1


class TestFitSpansAndPhase1Counters:
    """A stream fit through the facade records its layer spans
    (``repro.obs``) and folds each shard's phase-1 stats into
    ``ServiceStats``; block-sparse is forced so the tile-pair counts are
    not 0."""

    K = 4

    def fit(self):
        from repro import obs
        from repro.ddc import DDC, DDCConfig

        spec = spatial.PHASE2_LAYOUTS["rings"]
        pts = spec["make"](N)
        cfg = DDCConfig(eps=spec["eps"], min_pts=spec["min_pts"],
                        grid=spec["grid"], max_clusters=spec["max_clusters"],
                        max_verts=spec["max_verts"], block_sparse="always",
                        block_tile=128, backend="stream", shards=self.K,
                        max_batch=256)
        obs.clear()
        model = DDC(cfg).fit(pts)
        labels = model.labels_
        return model, spec, labels, obs.spans()

    def test_one_phase1_span_per_dirty_shard(self):
        model, spec, labels, spans = self.fit()
        by = lambda name: [s for s in spans if s.name == name]  # noqa: E731
        (fit,) = by("ddc.fit")
        (refresh,) = by("ddc.refresh")
        (agg,) = by("ddc.aggregate")
        (live,) = by("ddc.live")
        p1 = by("ddc.phase1")
        assert sorted(s.attrs["shard"] for s in p1) == list(range(self.K))
        assert all(s.parent_id == refresh.span_id for s in p1)
        assert agg.parent_id == refresh.span_id
        assert refresh.parent_id == fit.span_id
        ingest = by("ddc.ingest")
        assert len(ingest) == N // 256
        assert sum(s.attrs["n"] for s in ingest) == N
        assert all(s.parent_id == fit.span_id for s in ingest)
        assert {s.trace_id for s in [refresh, agg, *p1, *ingest]} \
            == {fit.span_id}
        assert live.attrs["n_live"] == N and live.trace_id != fit.trace_id
        assert fit.start <= refresh.start and refresh.end <= fit.end
        assert refresh.attrs == {"dirty": self.K, "mode": "delta"}
        assert agg.attrs == {"mode": "delta", "staged": self.K}
        pts, parts, _ = model.service.live()
        host, _, _ = ddc.ddc_host(pts, len(parts), spec["eps"],
                                  spec["min_pts"], partition=parts,
                                  contour="grid")
        assert ddc.same_clustering(labels, host)

    def test_counters_equal_the_spans_and_survive_restore(self, tmp_path):
        from repro.ddc import DDC
        from repro.serve.cluster_service import PHASE1_COUNTERS

        model, _, labels, spans = self.fit()
        p1 = [s for s in spans if s.name == "ddc.phase1"]
        c = model.stats().counters
        assert c.phase1_runs == self.K
        assert c.phase1_sweeps == sum(s.attrs["sweeps"] for s in p1) > 0
        assert c.phase1_doubling_steps \
            == sum(s.attrs["doubling_steps"] for s in p1) > 0
        tiles = (N // self.K // 128) ** 2
        assert c.phase1_tile_pairs == self.K * tiles \
            == sum(s.attrs["tile_pairs"] for s in p1)
        assert 0 < c.phase1_tile_pairs_active <= c.phase1_tile_pairs
        assert c.phase1_tile_pairs_active \
            == sum(s.attrs["tile_pairs_active"] for s in p1)
        assert c.phase1_dense_fallbacks \
            == sum(int(s.attrs["dense_fallback"]) for s in p1)
        d = model.stats().as_dict()
        assert all(d[k] == getattr(c, k) for k in PHASE1_COUNTERS)
        path = model.save(str(tmp_path / "snap"))
        back = DDC.load(path)
        c2 = back.stats().counters
        assert all(getattr(c2, k) == getattr(c, k) for k in PHASE1_COUNTERS)
        assert c2.phase1_doubling_steps == c.phase1_doubling_steps
        np.testing.assert_array_equal(back.labels_, labels)
        # A snapshot written before the doubling counter loads it as 0.
        manifest = tmp_path / "snap" / "manifest.json"
        doc = json.loads(manifest.read_text())
        assert doc["state"].pop("phase1_doubling_steps") \
            == c.phase1_doubling_steps
        manifest.write_text(json.dumps(doc))
        old = DDC.load(path).stats().counters
        assert old.phase1_doubling_steps == 0
        assert old.phase1_sweeps == c.phase1_sweeps

    def test_stats_entry_matches_local_phase_bit_for_bit(self):
        """The stream engine's phase-1 entry gives the same labels and
        ClusterSet as ``local_phase`` for every shard."""
        model, _, _, _ = self.fit()
        svc = model.service
        cfg = svc.cfg
        for i in range(self.K):
            dense, cs = ddc.local_phase(svc._pts[i], svc._mask[i], cfg)
            dense2, cs2, st = ddc.local_phase_stats(
                svc._pts[i], svc._mask[i], cfg)
            np.testing.assert_array_equal(dense, dense2)
            for a, b in zip(cs, cs2):
                np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(np.asarray(svc._dense)[i], dense)
            assert int(st.sweeps) >= 1
