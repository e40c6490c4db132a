"""Phase-2 batched merge engine unit tests: merge_many, comm meters,
and the empty-shard short-circuit regression.

The distributed shard_map schedules are covered by
tests/test_phase2_schedules.py (subprocess, 16 CPU devices).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ddc
from repro.data import spatial
from repro.parallel import compress

CFG = ddc.DDCConfig(eps=0.05, min_pts=5, max_clusters=16, max_verts=64, grid=96)


def local_sets(pts, n_shards, cfg=CFG):
    parts = np.array_split(np.arange(len(pts)), n_shards)
    out = []
    for idx in parts:
        dense, cs = ddc.local_phase(
            jnp.asarray(pts[idx]), jnp.ones(len(idx), bool), cfg)
        out.append((np.asarray(dense), cs))
    return parts, out


def stack_sets(sets):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[cs for _, cs in sets])


class TestMergeMany:
    def test_matches_pairwise_on_separated_blobs(self):
        """On well-separated clusters a batched K-way merge and a pairwise
        fold are the same clustering (components never interact)."""
        pts, _ = spatial.make_blobs(600, 5, seed=7, spread=0.012)
        parts, sets = local_sets(pts, 4)
        merged, maps = ddc.merge_many(stack_sets(sets), CFG)
        assert int(merged.valid.sum()) == 5
        acc = sets[0][1]
        for _, cs in sets[1:]:
            acc, _, _ = ddc.merge_pair(acc, cs, CFG)
        assert int(acc.valid.sum()) == 5
        np.testing.assert_allclose(
            np.sort(np.asarray(merged.sizes)), np.sort(np.asarray(acc.sizes)))

    def test_sizes_conserved(self):
        pts, _ = spatial.make_blobs(500, 4, seed=8)
        _, sets = local_sets(pts, 8)
        merged, _ = ddc.merge_many(stack_sets(sets), CFG)
        total = sum(int(np.asarray(cs.sizes).sum()) for _, cs in sets)
        assert int(np.asarray(merged.sizes).sum()) == total

    def test_maps_route_every_valid_slot(self):
        pts, _ = spatial.make_blobs(500, 4, seed=9)
        _, sets = local_sets(pts, 4)
        batch = stack_sets(sets)
        merged, maps = ddc.merge_many(batch, CFG)
        maps = np.asarray(maps)
        valid = np.asarray(batch.valid)
        assert (maps[valid] >= 0).all()
        assert (maps[~valid] == -1).all()
        # Routed sizes must land on the slot that accumulated them.
        msizes = np.zeros(CFG.max_clusters, np.int64)
        sizes = np.asarray(batch.sizes)
        for k in range(maps.shape[0]):
            for c in range(maps.shape[1]):
                if maps[k, c] >= 0:
                    msizes[maps[k, c]] += sizes[k, c]
        np.testing.assert_array_equal(msizes, np.asarray(merged.sizes))

    def test_order_equivariant(self):
        """Permuting the batch permutes maps rows, same clustering."""
        pts, _ = spatial.make_blobs(400, 3, seed=10)
        _, sets = local_sets(pts, 4)
        batch = stack_sets(sets)
        m1, maps1 = ddc.merge_many(batch, CFG)
        perm = [2, 0, 3, 1]
        batch2 = jax.tree.map(lambda x: x[jnp.asarray(perm)], batch)
        m2, maps2 = ddc.merge_many(batch2, CFG)
        np.testing.assert_array_equal(np.asarray(m1.valid), np.asarray(m2.valid))
        np.testing.assert_array_equal(np.asarray(m1.sizes), np.asarray(m2.sizes))
        np.testing.assert_array_equal(
            np.asarray(maps1)[perm], np.asarray(maps2))

    def test_transitive_chain_closes_in_one_shot(self):
        """A cluster chained across many shards closes transitively even
        when no two contour sets are mutually complete."""
        pts = spatial.make_worm(512, waves=1, amp=0.1)
        cfg = ddc.DDCConfig(eps=0.015, min_pts=5, max_clusters=8,
                            max_verts=96, grid=32)
        _, sets = local_sets(pts, 8, cfg)
        merged, maps = ddc.merge_many(stack_sets(sets), cfg)
        assert int(merged.valid.sum()) == 1
        maps = np.asarray(maps)
        assert set(maps[maps >= 0].tolist()) == {0}


class TestEmptyShardPath:
    def test_empty_clusterset_is_cached(self):
        a = ddc.empty_clusterset(CFG)
        b = ddc.empty_clusterset(CFG)
        assert a.contours is b.contours  # no per-call rebuild
        other = ddc.DDCConfig(max_clusters=8, max_verts=32)
        c = ddc.empty_clusterset(other)
        assert c.contours.shape == (8, 32, 2)

    def test_match_to_global_empty_short_circuits(self):
        empty = ddc.empty_clusterset(CFG)
        pts, _ = spatial.make_blobs(300, 3, seed=1)
        _, gcs = ddc.local_phase(jnp.asarray(pts), jnp.ones(len(pts), bool), CFG)
        out = np.asarray(ddc.match_to_global(empty, gcs, CFG))
        np.testing.assert_array_equal(out, -1)
        out = np.asarray(ddc.match_to_global(gcs, empty, CFG))
        np.testing.assert_array_equal(out, -1)
        # The expensive per-slot scan must sit behind a runtime branch.
        jaxpr = str(jax.make_jaxpr(
            lambda c, g: ddc.match_to_global(c, g, CFG))(empty, gcs))
        assert "cond" in jaxpr

    def test_merge_with_empty_preserves(self):
        pts, _ = spatial.make_blobs(200, 3, seed=2)
        _, cs = ddc.local_phase(jnp.asarray(pts), jnp.ones(len(pts), bool), CFG)
        empty = ddc.empty_clusterset(CFG)
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), empty, cs, empty)
        merged, maps = ddc.merge_many(batch, CFG)
        assert int(merged.valid.sum()) == int(cs.valid.sum())
        maps = np.asarray(maps)
        assert (maps[0] == -1).all() and (maps[2] == -1).all()

    def test_all_empty_batch(self):
        empty = ddc.empty_clusterset(CFG)
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), empty, empty)
        merged, maps = ddc.merge_many(batch, CFG)
        assert int(merged.valid.sum()) == 0
        assert (np.asarray(maps) == -1).all()


class TestCommMeter:
    def test_wire_bytes_matches_config_budget(self):
        cs = ddc.empty_clusterset(CFG)
        assert compress.pytree_wire_bytes(cs) == CFG.buffer_bytes()

    def test_counters(self):
        m = ddc.CommMeter()
        m.add_collective(links=6, nbytes=100)
        m.add_collective(links=2, nbytes=50)
        m.add_merge(batch=4, slots=16)
        snap = m.snapshot()
        assert snap == {"bytes_total": 700, "collectives": 2,
                        "merge_steps": 1, "merge_slots": 64}
        m.reset()
        assert m.snapshot()["bytes_total"] == 0

    def test_schedule_accounting(self):
        """Static comm counts for the three schedules at K=8 (filled at
        trace time — no devices needed beyond eval_shape's abstract run)."""
        cfg = ddc.DDCConfig(max_clusters=8, max_verts=32, schedule="sync")
        b = cfg.buffer_bytes()
        cs = ddc.empty_clusterset(cfg)

        meters = {}
        for sched in ("sync", "async", "tree"):
            meter = ddc.CommMeter()
            fn = {"sync": ddc.merge_sync, "async": ddc.merge_async,
                  "tree": ddc.merge_tree}[sched]
            # Trace over an abstract 8-lane axis without running.
            jax.eval_shape(
                lambda c: _with_axis(fn, c, cfg, meter), cs)
            meters[sched] = meter.snapshot()

        assert meters["sync"]["bytes_total"] == 8 * 7 * b
        assert meters["sync"]["merge_steps"] == 1
        assert meters["async"]["bytes_total"] == 3 * 8 * b   # log2(8) rounds
        assert meters["async"]["merge_steps"] == 3
        # Tree(d=2): 4+4+4 up-sends + 1+2+4 broadcast hops = 19 links.
        assert meters["tree"]["bytes_total"] == 19 * b
        assert meters["tree"]["merge_steps"] == 3
        assert meters["tree"]["bytes_total"] < meters["async"]["bytes_total"]
        assert meters["async"]["bytes_total"] < meters["sync"]["bytes_total"]


def _with_axis(fn, cs, cfg, meter):
    """Run a schedule under an abstract 8-lane mesh (shape-only trace)."""
    mesh = _abstract_mesh8()
    from jax.sharding import PartitionSpec as P

    wrapped = jax.shard_map(
        lambda c: fn(c, cfg, "data", meter),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), cs),),
        out_specs=(jax.tree.map(lambda _: P(), cs), P()),
        check_vma=False,
    )
    return wrapped(cs)


def _abstract_mesh8():
    from jax.sharding import AbstractMesh
    return AbstractMesh((8,), ("data",))
