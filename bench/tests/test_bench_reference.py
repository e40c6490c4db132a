"""The benchmark's plain reference equals the system's ``host`` backend
(``ddc_host``: NumPy DBSCAN per shard, exact contour merge) on the
phase-2 layouts, at a small size on the CPU."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.reference import ddc_ref  # noqa: E402


def _host_labels(pts, spec, shards):
    from repro.ddc import DDC, DDCConfig

    cfg = DDCConfig(eps=spec["eps"], min_pts=spec["min_pts"], grid=spec["grid"],
                    max_verts=spec["max_verts"], max_clusters=spec["max_clusters"],
                    backend="host", shards=shards)
    return np.asarray(DDC(cfg).fit(pts).labels_)


@pytest.mark.parametrize("layout", ["rings", "linked_ovals", "noise_heavy"])
@pytest.mark.parametrize("shards", [2, 4])
def test_reference_equals_host_backend(layout, shards):
    from repro.data import spatial

    spec = spatial.PHASE2_LAYOUTS[layout]
    pts = spec["make"](2048, seed=5)
    params = dict(eps=spec["eps"], min_pts=spec["min_pts"], grid=spec["grid"],
                  bounds=[0.0, 0.0, 1.0, 1.0])
    parts = np.array_split(np.arange(len(pts)), shards)
    ref = ddc_ref.ddc([pts[p] for p in parts], params)
    want = _host_labels(pts, spec, shards)
    got = np.concatenate(ref["labels"])
    assert ref["n_global"] >= 2
    assert ddc_ref.partition_mismatch(got, want) == 0


def test_dbscan_matches_brute_force():
    rng = np.random.default_rng(3)
    pts = np.concatenate([rng.normal(0.3, 0.02, (300, 2)),
                          rng.normal(0.7, 0.03, (300, 2)),
                          rng.uniform(0, 1, (100, 2))]).astype(np.float32)
    eps, min_pts = 0.02, 6
    lab, core = ddc_ref.dbscan(pts, eps, min_pts)
    d = pts[:, None, :] - pts[None, :, :]
    adj = (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) <= np.float32(eps) ** 2
    want_core = adj.sum(1) >= min_pts
    assert np.array_equal(core, want_core)
    for i in np.nonzero(~want_core)[0]:
        neigh = np.nonzero(adj[i] & want_core)[0]
        assert lab[i] == (lab[neigh].min() if len(neigh) else -1)


def test_partition_mismatch_counts_points():
    a = np.array([0, 0, 1, 1, -1, 2])
    assert ddc_ref.partition_mismatch(a, np.array([5, 5, 7, 7, -1, 9])) == 0
    assert ddc_ref.partition_mismatch(a, np.array([5, 5, 5, 5, -1, 9])) == 2
    assert ddc_ref.partition_mismatch(a, np.array([5, 5, 7, 7, 3, 9])) == 1


def test_merge_threshold_refuses_an_undecidable_radius():
    # radius 0.1 + 1.5 * 0.1 over a 10-cell raster of the unit square is
    # 0.25 = 2.25 steps of 1/9: squared, 5.0625; a radius of exactly two
    # steps is refused.
    assert ddc_ref.merge_threshold(dict(eps=0.1, grid=10, bounds=[0, 0, 1, 1])) \
        == pytest.approx((0.25 * 9) ** 2)
    with pytest.raises(ValueError):
        ddc_ref.merge_threshold(dict(eps=2 / 9 - 0.15, grid=10, bounds=[0, 0, 1, 1]))
