"""Each mix's loop at toy size on the CPU, through its Python function:
the same seed gives the same traffic, a sound run is correct, and the
result line carries exactly the keys the benchmark's contract names."""
import time

import numpy as np
from _bench_toy import spreader

from bench import datagen, harness, loops, traffic

KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
SEED = 2**31 + 12345          # seeds go past 32 signed bits


def test_fit_traffic_repeats_for_a_seed():
    _, config, mix, _, _ = spreader()
    a = traffic.batch_fit(config, mix, SEED)["points"]
    assert np.array_equal(a, traffic.batch_fit(config, mix, SEED)["points"])
    assert not np.array_equal(a, traffic.batch_fit(config, mix, SEED + 1)["points"])


def test_seed_spreader_shape():
    """Every seed gets the same sizes: the configured noise share, the
    spreader's points in discs round its path, all inside the domain,
    handed on in Morton order."""
    _, config, _, _, _ = spreader()
    data = config["data"]
    lo, hi = data["domain"]
    for seed in (SEED, 7):
        pts = datagen.draw(data, 20_000, np.random.default_rng(seed))
        assert pts.shape == (20_000, 2)
        assert pts.min() >= lo and pts.max() <= hi
        code = datagen.morton_code(pts, (lo, lo, hi, hi))
        assert np.all(np.diff(code) >= 0)
        # A spreader's disc is 100 wide; the uniform noise is not, so the
        # share of isolated points is about the noise share.
        from scipy.spatial import cKDTree

        d, _ = cKDTree(pts).query(pts, k=2)
        lonely = (d[:, 1] > 4 * data["r_vicinity"]).mean()
        assert lonely <= 2 * data["noise"] + 1e-3


def test_batch_fit_loop_line():
    cell, config, mix, e2e, layer = spreader(shards=2, n=8192)
    run = loops.run_batch_fit(config, mix, SEED, 0.5, False, time.perf_counter())
    out = harness.result(run, cell, e2e, layer)
    assert list(out) == KEYS
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"setup_s", "fit_s"}
    assert out["attempted"] == run.info["fits"] >= 1
    assert run.info["compiles"] == (0, 0)



def test_every_seed_fits_the_same_points():
    """Every seed hands each shard the same points, in its own order, so
    the reference finds the same clusters: the same work."""
    from bench.reference import ddc_ref

    _, config, mix, _, _ = spreader()
    blocks, shapes = [], []
    for seed in (SEED, 7):
        pts = traffic.batch_fit(config, mix, seed)["points"]
        parts = np.array_split(np.arange(len(pts)), int(config["shards"]))
        blocks.append([pts[p] for p in parts])
        ref = ddc_ref.ddc(blocks[-1], config["ddc"])
        shapes.append((ref["n_global"], ref["local"]))
    for a, b in zip(*blocks):
        assert not np.array_equal(a, b)
        assert np.array_equal(a[np.lexsort(a.T)], b[np.lexsort(b.T)])
    assert shapes[0] == shapes[1]
