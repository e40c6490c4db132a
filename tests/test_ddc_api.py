"""Facade suite: `repro.ddc.DDC` over pluggable backends.

The API contract under test:

* ``DDCConfig.validate()`` rejects every backend/schedule mismatch and
  (with a sample) DESIGN §7 sizing violations at construction time;
* ``host`` / ``jit`` / ``stream`` produce the identical global
  clustering through the one ``fit``/``partial_fit`` surface (the jit
  backend needs a multi-device override, so that sweep runs in a
  subprocess — tests/_api_script.py);
* ``save`` → ``load`` → resume is bit-identical to an uninterrupted
  streaming run (labels AND the cached pair-d2 matrix);
* TTL eviction (``partial_fit(..., t=...)`` + ``expire``) drops exactly
  the stamped points and the survivors still match batch ``ddc_host``;
* a query against a fresh service returns all-noise without compiling
  or refreshing anything.

Big sweeps are marked ``slow`` (the separate tier1-slow CI job).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import ddc as core_ddc
from repro.data import spatial
from repro.ddc import (
    BACKENDS, DDC, ConfigError, DDCConfig, same_clustering,
)

N = 2048
SCRIPT = os.path.join(os.path.dirname(__file__), "_api_script.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def layout_config(layout: str, **kw) -> DDCConfig:
    spec = spatial.PHASE2_LAYOUTS[layout]
    return DDCConfig(
        eps=spec["eps"], min_pts=spec["min_pts"], grid=spec["grid"],
        max_clusters=spec["max_clusters"], max_verts=spec["max_verts"], **kw)


def layout_points(layout: str, n: int = N) -> np.ndarray:
    return spatial.PHASE2_LAYOUTS[layout]["make"](n)


class TestConfigValidate:
    def test_all_backends_registered(self):
        assert set(BACKENDS) == {"host", "jit", "stream", "dist"}

    @pytest.mark.parametrize("kw", [
        dict(eps=-1.0),
        dict(min_pts=0),
        dict(grid=1),
        dict(bounds=(0.0, 0.0, 0.0, 1.0)),
        dict(backend="no-such-backend"),
        dict(schedule="ring-allreduce"),
        dict(local_algo="optics"),
        dict(merge_refine="chaikin"),
        dict(merge_mode="approx"),
        dict(tree_degree=1),
        dict(shards=0),
        dict(backend="jit", schedule="async", shards=6),
        dict(backend="stream", capacity=8, max_batch=64),
    ])
    def test_rejects_broken_configs(self, kw):
        with pytest.raises(ConfigError):
            DDCConfig(**kw).validate()

    def test_async_non_pow2_is_fine_off_the_jit_backend(self):
        # The butterfly constraint is a jit-backend property: the host
        # oracle and the stream engine never run the schedule.
        DDCConfig(backend="host", schedule="async", shards=6).validate()
        DDCConfig(backend="stream", schedule="async", shards=6).validate()

    def test_validate_returns_self(self):
        cfg = layout_config("rings")
        assert cfg.validate() is cfg

    def test_sizing_probe_rejects_overflowing_merged_contour(self):
        # The §7 failure mode: the worm's *global* outline at a fine
        # raster exceeds a small vertex budget even though every
        # per-shard segment would fit.
        spec = spatial.PHASE2_LAYOUTS["worm"]
        pts = layout_points("worm")
        with pytest.raises(ConfigError, match="merged contour"):
            DDCConfig(eps=spec["eps"], min_pts=spec["min_pts"],
                      grid=128, max_verts=32, max_clusters=8,
                      ).validate(sample=pts)

    def test_sizing_probe_rejects_cluster_budget_overflow(self):
        pts = layout_points("noise_heavy")
        spec = spatial.PHASE2_LAYOUTS["noise_heavy"]
        with pytest.raises(ConfigError, match="max_clusters"):
            DDCConfig(eps=spec["eps"], min_pts=spec["min_pts"],
                      grid=spec["grid"], max_verts=spec["max_verts"],
                      max_clusters=2).validate(sample=pts)

    @pytest.mark.parametrize("layout", ("rings", "worm"))
    def test_sizing_probe_accepts_tuned_layouts(self, layout):
        layout_config(layout).validate(sample=layout_points(layout))


class TestFacade:
    def test_host_equals_stream_through_fit(self):
        pts = layout_points("rings")
        labels = {}
        for backend in ("host", "stream"):
            model = DDC(layout_config("rings", backend=backend, shards=2))
            labels[backend] = model.fit(pts).labels_
        assert same_clustering(labels["host"], labels["stream"])

    def test_partial_fit_equals_fit(self):
        pts = layout_points("linked_ovals")
        cfg = layout_config("linked_ovals", backend="host", shards=2)
        whole = DDC(cfg).fit(pts)
        piecewise = DDC(cfg)
        for shard, idx in enumerate(np.array_split(np.arange(len(pts)), 2)):
            for off in range(0, len(idx), 300):
                piecewise.partial_fit(shard, pts[idx[off:off + 300]])
        assert np.array_equal(whole.labels_, piecewise.labels_)
        assert np.array_equal(whole.points_, piecewise.points_)

    def test_query_returns_own_labels(self):
        pts = layout_points("rings")
        model = DDC(layout_config("rings", backend="host", shards=2)).fit(pts)
        labels = model.labels_
        got = model.query(pts[:256])
        clustered = labels[:256] >= 0
        np.testing.assert_array_equal(got[clustered], labels[:256][clustered])
        assert (model.query(np.array([[7.0, 7.0]])) == -1).all()

    def test_comm_stats_records_backend(self):
        pts = layout_points("rings", 512)
        model = DDC(layout_config("rings", backend="host", shards=2)).fit(pts)
        stats = model.comm_stats()
        assert stats["backend"] == "host"
        assert stats["bytes_total"] > 0

    def test_jit_stats_count_phase1_of_every_lane(self):
        """``DDC.stats()`` on ``jit`` sums each lane's ``Phase1Stats``,
        the numbers its ``ddc.run`` span lists; ``host`` keeps them 0."""
        from repro import obs

        pts = layout_points("rings", 512)
        obs.clear()
        model = DDC(layout_config("rings", backend="jit", shards=1))
        for _ in range(2):
            model.fit(pts).labels_
        c = model.stats().counters
        runs = [s for s in obs.spans() if s.name == "ddc.run"]
        assert len(runs) == 2 and c.refits == 2
        assert c.phase1_runs == 2
        assert c.phase1_sweeps == sum(sum(s.attrs["sweeps"]) for s in runs) > 0
        assert c.phase1_doubling_steps == sum(
            sum(s.attrs["doubling_steps"]) for s in runs)
        assert c.phase1_tile_pairs == sum(sum(s.attrs["tile_pairs"]) for s in runs)
        assert c.phase1_dense_fallbacks == sum(
            sum(s.attrs["dense_fallback"]) for s in runs)
        host = DDC(layout_config("rings", backend="host", shards=2)).fit(pts)
        hc = host.stats().counters
        assert hc.refits == 1
        assert (hc.phase1_runs, hc.phase1_sweeps, hc.phase1_doubling_steps,
                hc.phase1_tile_pairs) == (0, 0, 0, 0)
        obs.clear()

    def test_expire_requires_stream_backend(self):
        model = DDC(layout_config("rings", backend="host", shards=2))
        with pytest.raises(ConfigError, match="stream"):
            model.expire(0.0)

    def test_save_load_host_backend(self, tmp_path):
        pts = layout_points("rings")
        model = DDC(layout_config("rings", backend="host", shards=2)).fit(pts)
        model.save(str(tmp_path / "ckpt"))
        restored = DDC.load(str(tmp_path / "ckpt"))
        assert restored.config == model.config
        assert np.array_equal(restored.labels_, model.labels_)
        assert np.array_equal(restored.points_, model.points_)


class TestQueryBeforeRefresh:
    def test_fresh_service_queries_all_noise_without_refresh(self):
        """Regression: a query before any refresh (no global set yet)
        must return all-noise labels, not fail — and must not compile
        or run the merge pipeline for an empty service."""
        model = DDC(layout_config("rings", backend="stream", shards=2,
                                  capacity=64, max_batch=64))
        out = model.query(np.array([[0.5, 0.5], [0.1, 0.9]]))
        np.testing.assert_array_equal(out, [-1, -1])
        assert model.service.refreshes == 0

    def test_first_ingest_then_query_refreshes(self):
        pts = layout_points("rings", 512)
        model = DDC(layout_config("rings", backend="stream", shards=2,
                                  capacity=512))
        model.partial_fit(0, pts[:256])
        got = model.query(pts[:8])
        assert model.service.refreshes == 1
        assert got.shape == (8,)


class TestBackendEquivalence:
    """All three backends through one front door == one clustering."""

    def run_script(self, layout: str) -> str:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.abspath(SRC)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, SCRIPT, layout],
            capture_output=True, text=True, timeout=900, env=env)
        assert proc.returncode == 0, (
            f"{layout} failed:\n{proc.stdout}\n{proc.stderr}")
        return proc.stdout

    def test_backends_agree_quick(self):
        out = self.run_script("linked_ovals")
        assert "ALL_OK" in out and out.count("PASS") == 3

    @pytest.mark.slow
    @pytest.mark.parametrize("layout", sorted(spatial.PHASE2_LAYOUTS))
    def test_backends_agree_sweep(self, layout):
        out = self.run_script(layout)
        assert "ALL_OK" in out and out.count("PASS") == 3


def stream_halves(layout: str, k: int, capacity: int | None = None):
    pts = layout_points(layout)
    cfg = layout_config(
        layout, backend="stream", shards=k,
        capacity=capacity or spatial.shard_capacity(len(pts), k),
        max_batch=128)
    batches = spatial.stream_batches(pts, k, 128)
    return cfg, batches, len(batches) // 2


def assert_resume_bit_identical(layout: str, k: int, tmp_path):
    """Stream N batches, save, load, stream M more: labels and the
    cached pair-d2 matrix must equal an uninterrupted run bit-for-bit."""
    cfg, batches, half = stream_halves(layout, k)

    uninterrupted = DDC(cfg)
    for shard, chunk in batches:
        uninterrupted.partial_fit(shard, chunk)
    ref_labels = uninterrupted.labels_

    interrupted = DDC(cfg)
    for shard, chunk in batches[:half]:
        interrupted.partial_fit(shard, chunk)
    interrupted.labels_                      # refresh mid-stream
    path = str(tmp_path / f"ckpt-{layout}-{k}")
    interrupted.save(path)
    resumed = DDC.load(path)
    for shard, chunk in batches[half:]:
        resumed.partial_fit(shard, chunk)

    np.testing.assert_array_equal(ref_labels, resumed.labels_)
    np.testing.assert_array_equal(
        np.asarray(uninterrupted.service.pair_d2),
        np.asarray(resumed.service.pair_d2))


class TestSnapshotRestore:
    def test_resume_bit_identical_quick(self, tmp_path):
        assert_resume_bit_identical("rings", 2, tmp_path)

    def test_restore_preserves_engine_counters_and_state(self, tmp_path):
        cfg, batches, half = stream_halves("rings", 2)
        model = DDC(cfg)
        for shard, chunk in batches[:half]:
            model.partial_fit(shard, chunk)
        model.labels_
        path = str(tmp_path / "ckpt")
        model.save(path)
        restored = DDC.load(path)
        svc, rsvc = model.service, restored.service
        assert rsvc.refreshes == svc.refreshes
        assert rsvc.n_live() == svc.n_live()
        assert rsvc._head == svc._head and rsvc._count == svc._count
        np.testing.assert_array_equal(
            np.asarray(svc.pair_d2), np.asarray(rsvc.pair_d2))
        # No pending work: the restored service answers reads directly.
        before = rsvc.refreshes
        np.testing.assert_array_equal(restored.labels_, model.labels_)
        assert rsvc.refreshes == before

    @pytest.mark.slow
    @pytest.mark.parametrize("layout", sorted(spatial.PHASE2_LAYOUTS))
    def test_resume_bit_identical_sweep(self, layout, tmp_path):
        for k in (2, 4, 8):
            assert_resume_bit_identical(layout, k, tmp_path)


class TestTTLEviction:
    def assert_matches_host(self, model):
        pts, parts, labels = model.service.live()
        spec = spatial.PHASE2_LAYOUTS["rings"]
        host, _, _ = core_ddc.ddc_host(
            pts, len(parts), spec["eps"], spec["min_pts"],
            partition=parts, contour="grid")
        assert same_clustering(labels, host)

    def test_expire_drops_exactly_the_stamped_window(self):
        pts = layout_points("rings")
        model = DDC(layout_config("rings", backend="stream", shards=2,
                                  capacity=1024))
        for i, (shard, chunk) in enumerate(
                spatial.stream_batches(pts, 2, 256)):
            model.partial_fit(shard, chunk, t=float(i))
        assert len(model.labels_) == len(pts)
        evicted = model.expire(t=4.0)        # drop batches stamped 0..3
        assert evicted == 4 * 256
        assert len(model.labels_) == len(pts) - evicted
        self.assert_matches_host(model)

    def test_default_timestamps_are_ingest_sequence(self):
        pts = layout_points("rings", 512)
        model = DDC(layout_config("rings", backend="stream", shards=2,
                                  capacity=512))
        model.partial_fit(0, pts[:200])
        model.partial_fit(1, pts[200:400])
        assert model.expire(t=100.0) == 100   # first 100 ingested points
        assert len(model.labels_) == 300

    def test_ttl_holes_then_ring_overwrite_stays_consistent(self):
        """Punch TTL holes mid-ring, then ingest past capacity: the
        append wrap must keep the live set exact (holes are legal)."""
        pts = layout_points("rings", 1024)
        model = DDC(layout_config("rings", backend="stream", shards=2,
                                  capacity=256, max_batch=128))
        svc = model.service
        for i, (shard, chunk) in enumerate(
                spatial.stream_batches(pts[:512], 2, 128)):
            model.partial_fit(shard, chunk, t=float(i))
        assert svc.evict_older_than(0, 2.0) > 0
        # Overfill both rings: wraps over dead and oldest-live slots.
        for shard, chunk in spatial.stream_batches(pts[512:], 2, 128):
            model.partial_fit(shard, chunk, t=99.0)
        live_pts, parts, labels = svc.live()
        assert len(live_pts) == sum(len(p) for p in parts)
        assert svc.n_live() == len(live_pts)
        self.assert_matches_host(model)

    def test_fit_timestamp_joins_wall_clock_expiry(self):
        """Regression: the facade lifecycle fit(pts, t=t0) →
        partial_fit(..., t=now) → expire(cutoff) must age out only what
        the cutoff names — fit-ingested data must not be treated as
        infinitely old (the default sequence stamps would be)."""
        pts = layout_points("rings", 512)
        t0 = 1_700_000_000.0
        model = DDC(layout_config("rings", backend="stream", shards=2,
                                  capacity=512))
        model.fit(pts, t=t0)
        model.partial_fit(0, pts[:16], t=t0 + 60.0)
        assert model.expire(t0 - 3600.0) == 0     # nothing is older
        assert len(model.labels_) == 512 + 16
        assert model.expire(t0 + 30.0) == 512     # only the fitted batch
        assert len(model.labels_) == 16

    def test_append_refills_ttl_holes_before_touching_live(self):
        """Regression: TTL holes *behind* the ring head must be refilled
        by the next append — live (newer) points are only overwritten
        when the buffer is genuinely full."""
        model = DDC(layout_config("rings", backend="stream", shards=1,
                                  capacity=8, max_batch=8))
        svc = model.service
        rng = np.random.default_rng(1)
        a = rng.uniform(0, 1, (4, 2)).astype(np.float32)
        b = rng.uniform(0, 1, (4, 2)).astype(np.float32)
        c = rng.uniform(0, 1, (4, 2)).astype(np.float32)
        model.partial_fit(0, a, t=100.0)      # slots 0-3 (new data)
        model.partial_fit(0, b, t=1.0)        # slots 4-7 (old data)
        assert svc.evict_older_than(0, 50.0) == 4   # holes at 4-7
        model.partial_fit(0, c, t=200.0)      # must land in the holes
        assert svc.n_live() == 8
        live = np.asarray(svc._pts[0])[np.asarray(svc._mask[0])]
        survivors = {tuple(p) for p in live.tolist()}
        for p in np.concatenate([a, c]).tolist():
            assert tuple(p) in survivors      # nothing live was lost

    def test_evict_oldest_follows_sequence_across_holes(self):
        model = DDC(layout_config("rings", backend="stream", shards=1,
                                  capacity=64, max_batch=64))
        svc = model.service
        rng = np.random.default_rng(0)
        model.partial_fit(0, rng.uniform(0, 1, (30, 2)), t=0.0)
        model.partial_fit(0, rng.uniform(0, 1, (20, 2)), t=1.0)
        svc.evict_older_than(0, 0.5)          # kill the first 30 -> hole
        assert svc.n_live() == 20
        assert svc.evict_oldest(0, 5) == 5    # oldest survivors, by seq
        assert svc.n_live() == 15
        assert svc.evict_oldest(0, 99) == 15  # clamped to live count
        assert svc.n_live() == 0
