"""Device-resident ``dist`` backend suite + the satellites that ride on
the control-plane split.

* **dist≡host equivalence** — the multi-device sweeps (labels AND the
  cached pair-d2 matrix, exact axis-byte CommMeter asserts, hypothesis
  ingest/evict orderings) need ``len(jax.devices()) >= shards``, so they
  run in a subprocess with the 8-device CPU override
  (tests/_dist_backend_script.py), mirroring the facade suite's pattern.
* **Shard-range validation** — ``ingest``/``evict_*`` (and the facade's
  ``partial_fit``) with an out-of-range shard index must raise a clear
  ``ValueError`` up front, not a raw IndexError deep in the ring write
  path.
* **Bbox query routing** — the control plane's per-shard live-point bbox
  mirrors route query chunks to the shards that could hold an
  ε-neighbour; routing must be invisible in the answers (exactness) and
  visible in the scanned-shard counters.
* **Config rules** — ``backend='dist'`` validates the mesh-vs-shards
  rule at construction (this pytest process sees one CPU device, so any
  multi-shard dist config must be rejected loudly here).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data import spatial
from repro.ddc import BACKENDS, ConfigError, DDC, DDCConfig
from repro.serve import ClusterService, StreamConfig

from test_serve_stream import build_service, layout_cfg, stream  # noqa: F401

SCRIPT = os.path.join(os.path.dirname(__file__), "_dist_backend_script.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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


class TestDistEqualsHost:
    """dist == stream bit-for-bit (labels AND pair-d2) == host clustering,
    with exact axis-crossing byte asserts — in an 8-device subprocess."""

    def test_dist_registered(self):
        assert "dist" in BACKENDS

    def test_equivalence_quick(self):
        out = run_script("linked_ovals")
        assert "ALL_OK" in out and out.count("PASS") == 3

    @pytest.mark.slow
    @pytest.mark.parametrize("layout", sorted(spatial.PHASE2_LAYOUTS))
    def test_equivalence_sweep(self, layout):
        out = run_script(layout)
        assert "ALL_OK" in out and out.count("PASS") == 3

    @pytest.mark.slow
    def test_ingest_evict_orderings(self):
        out = run_script("orderings")
        assert "ALL_OK" in out


class TestDistConfigRules:
    def test_rejects_more_shards_than_devices(self):
        # This pytest process initialised jax with a single CPU device,
        # so any multi-shard dist deployment must fail validate() with
        # the XLA_FLAGS fix spelled out.
        with pytest.raises(ConfigError, match="xla_force_host_platform"):
            DDCConfig(backend="dist", shards=8).validate()

    def test_shortfall_on_a_chip_names_chips_not_the_cpu_flag(self, monkeypatch):
        # On an accelerator the CPU device-count flag does nothing: the
        # advice is the number of chips the shards need.
        import jax

        from repro.launch import mesh as mesh_mod

        class Chip:
            platform = "tpu"

        monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
        msg = mesh_mod.device_shortfall(4)
        assert "4 chips" in msg and "xla_force" not in msg
        assert mesh_mod.device_shortfall(1) is None

    def test_rejects_capacity_below_max_batch(self):
        with pytest.raises(ConfigError, match="max_batch"):
            DDCConfig(backend="dist", shards=1, capacity=8,
                      max_batch=64).validate()

    def test_single_shard_dist_runs_in_process(self):
        # One shard fits the one-device pytest process: the full dist
        # data plane (shard_map over a 1-lane mesh) must work end to end.
        pts = spatial.PHASE2_LAYOUTS["rings"]["make"](512)
        cfg = DDCConfig(
            **{k: spatial.PHASE2_LAYOUTS["rings"][k]
               for k in ("eps", "min_pts", "grid", "max_verts",
                         "max_clusters")},
            backend="dist", shards=1, capacity=512).validate()
        model = DDC(cfg).fit(pts)
        ref = DDC(DDCConfig(
            **{k: spatial.PHASE2_LAYOUTS["rings"][k]
               for k in ("eps", "min_pts", "grid", "max_verts",
                         "max_clusters")},
            backend="stream", shards=1, capacity=512)).fit(pts)
        np.testing.assert_array_equal(model.labels_, ref.labels_)


class TestShardRangeValidation:
    """Out-of-range shard indices fail loudly at the entry points, not
    as IndexErrors deep in the ring write path."""

    def make_service(self, shards=2) -> ClusterService:
        return ClusterService(StreamConfig(
            shards=shards, capacity=64, max_batch=64,
            ddc=layout_cfg(spatial.PHASE2_LAYOUTS["rings"])))

    @pytest.mark.parametrize("shard", (-1, 2, 99))
    def test_ingest_rejects_out_of_range(self, shard):
        svc = self.make_service()
        with pytest.raises(ValueError, match="out of range"):
            svc.ingest(shard, np.zeros((4, 2), np.float32))

    @pytest.mark.parametrize("method,args", [
        ("evict_oldest", (5,)),
        ("evict_older_than", (0.0,)),
        ("clear", ()),
        ("local_set", ()),
        ("shard_bbox", ()),
    ])
    @pytest.mark.parametrize("shard", (-1, 2))
    def test_evict_entry_points_reject_out_of_range(self, method, args, shard):
        svc = self.make_service()
        with pytest.raises(ValueError, match="out of range"):
            getattr(svc, method)(shard, *args)

    def test_out_of_range_leaves_state_untouched(self):
        svc = self.make_service()
        svc.ingest(0, np.full((4, 2), 0.5, np.float32))
        before = svc.n_live()
        for call in (lambda: svc.ingest(7, np.zeros((2, 2))),
                     lambda: svc.evict_oldest(-3, 1),
                     lambda: svc.clear(2)):
            with pytest.raises(ValueError):
                call()
        assert svc.n_live() == before

    def test_facade_partial_fit_rejects_out_of_range(self):
        model = DDC(DDCConfig(
            backend="stream", shards=2, capacity=64, max_batch=64))
        with pytest.raises(ValueError, match="out of range"):
            model.partial_fit(9, np.zeros((4, 2), np.float32))
        # batch backends keep their (ConfigError, a ValueError) contract
        host = DDC(DDCConfig(backend="host", shards=2))
        with pytest.raises(ValueError, match="out of range"):
            host.partial_fit(9, np.zeros((4, 2), np.float32))


class TestBboxRouting:
    """Routing must be exact (same labels as an all-shard scan would
    give) and actually skip shards whose bbox cannot hold a neighbour."""

    def build(self, k=4):
        svc, pts, spec = build_service("rings", k)
        stream(svc, pts, k)
        return svc, pts, spec

    def test_far_probe_scans_zero_shards(self):
        svc, pts, _ = self.build()
        got = svc.query(np.array([[7.0, 7.0], [-2.0, 3.0]], np.float32))
        np.testing.assert_array_equal(got, [-1, -1])
        assert svc.query_chunks == 1
        assert svc.query_shards_scanned == 0

    def test_local_probe_skips_distant_shards(self):
        svc, pts, _ = self.build(k=4)
        # One live point's own coordinates: at most the shards whose
        # dilated bbox reaches it are scanned — never all four (the
        # rings layout is Morton-partitioned into compact blocks).
        live, _, labels = svc.live()
        probe = live[:1]
        got = svc.query(probe)
        assert got[0] == labels[0]
        assert 1 <= svc.query_shards_scanned < 4 * svc.query_chunks

    def test_routing_is_invisible_in_answers(self):
        svc, pts, _ = self.build(k=4)
        live, _, labels = svc.live()
        rng = np.random.default_rng(0)
        q = np.concatenate([live[rng.integers(0, len(live), 300)],
                            rng.uniform(-0.2, 1.2, (100, 2))]).astype(
                                np.float32)
        got = svc.query(q)
        # reference: brute-force nearest clustered live point within eps
        eps = svc.cfg.eps
        ref = np.full(len(q), -1, np.int32)
        keep = labels >= 0
        d2 = ((q[:, None, :].astype(np.float32)
               - live[None, keep, :]) ** 2).sum(-1)
        j = np.argmin(d2, axis=1)
        hit = d2[np.arange(len(q)), j] <= np.float32(eps) * np.float32(eps)
        ref = np.where(hit, labels[keep][j], -1)
        np.testing.assert_array_equal(got, ref)

    def test_bbox_mirror_tracks_ingest_and_evict(self):
        svc = ClusterService(StreamConfig(
            shards=1, capacity=64, max_batch=64,
            ddc=layout_cfg(spatial.PHASE2_LAYOUTS["rings"])))
        assert svc.shard_bbox(0) is None
        svc.ingest(0, np.array([[0.1, 0.2], [0.4, 0.9]]), t=0.0)
        assert svc.shard_bbox(0) == pytest.approx((0.1, 0.2, 0.4, 0.9))
        svc.ingest(0, np.array([[0.8, 0.05]]), t=1.0)
        assert svc.shard_bbox(0) == pytest.approx((0.1, 0.05, 0.8, 0.9))
        svc.evict_older_than(0, 0.5)      # drop the first two points
        assert svc.shard_bbox(0) == pytest.approx((0.8, 0.05, 0.8, 0.05))
        svc.clear(0)
        assert svc.shard_bbox(0) is None

    def test_counters_surface_in_stats(self):
        svc, pts, _ = self.build(k=2)
        svc.query(pts[:16])
        stats = svc.stats()
        assert stats["query_chunks"] >= 1
        assert 0 <= stats["query_shards_scanned"] \
            <= stats["query_shards_possible"]
