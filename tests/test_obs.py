"""The span recorder (``repro.obs``): nesting, ids, threads, the ring."""
import threading
import time

import pytest

from repro import obs


def test_nesting_sets_parent_and_trace_ids():
    rec = obs.Recorder()
    with rec.span("root"):
        with rec.span("child"):
            with rec.span("grandchild"):
                pass
        with rec.span("sibling"):
            pass
    with rec.span("next root"):
        pass
    got = {s.name: s for s in rec.spans()}
    root = got["root"]
    assert root.parent_id is None and root.trace_id == root.span_id
    assert got["child"].parent_id == root.span_id
    assert got["grandchild"].parent_id == got["child"].span_id
    assert got["sibling"].parent_id == root.span_id
    for name in ("child", "grandchild", "sibling"):
        assert got[name].trace_id == root.span_id
    nxt = got["next root"]
    assert nxt.parent_id is None and nxt.trace_id == nxt.span_id != root.span_id
    assert len({s.span_id for s in got.values()}) == 5
    # Spans are pushed as they close: children before their parent.
    assert [s.name for s in rec.spans()] == [
        "grandchild", "child", "sibling", "root", "next root"]


def test_span_in_another_thread_starts_its_own_trace():
    rec = obs.Recorder()

    def work():
        with rec.span("worker"):
            pass

    with rec.span("fit"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    got = {s.name: s for s in rec.spans()}
    assert got["worker"].parent_id is None
    assert got["worker"].trace_id == got["worker"].span_id
    assert got["worker"].trace_id != got["fit"].trace_id


def test_times_are_on_the_perf_counter_clock():
    rec = obs.Recorder()
    t0 = time.perf_counter()
    with rec.span("sleep"):
        time.sleep(0.01)
    t1 = time.perf_counter()
    (s,) = rec.spans()
    assert t0 <= s.start < s.end <= t1
    assert s.seconds >= 0.01


def test_attrs_set_inside_the_span_are_kept():
    rec = obs.Recorder()
    with rec.span("phase", shard=3) as attrs:
        attrs["sweeps"] = 17
    (s,) = rec.spans()
    assert s.attrs == {"shard": 3, "sweeps": 17}


def test_span_is_recorded_when_its_body_raises():
    rec = obs.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("fails"):
                raise ValueError("boom")
    assert [s.name for s in rec.spans()] == ["fails", "outer"]
    with rec.span("after"):
        pass
    assert rec.spans()[-1].parent_id is None


def test_ring_is_bounded_and_counts_what_it_drops():
    rec = obs.Recorder(size=4)
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
    kept = rec.spans()
    assert [s.name for s in kept] == ["s2", "s3", "s4", "s5"]
    assert rec.dropped == 2
    assert rec.lost_since(kept[0].start - 1.0)   # s0, s1 ended in there
    assert not rec.lost_since(kept[0].end)
    copy = rec.spans()
    copy.clear()
    assert len(rec.spans()) == 4                 # spans() hands out a copy
    rec.clear()
    assert rec.spans() == [] and rec.dropped == 0
    assert not rec.lost_since(0.0)


def test_module_recorder_and_names():
    obs.clear()
    with obs.span("ddc.fit", n=1):
        pass
    (s,) = [s for s in obs.spans() if s.name == "ddc.fit"]
    assert s.attrs == {"n": 1}
    assert set(obs.SPAN_NAMES) == {"ddc.fit", "ddc.ingest", "ddc.refresh",
                                   "ddc.phase1", "ddc.aggregate", "ddc.live",
                                   "ddc.refit", "ddc.run"}
    obs.clear()


def test_jit_refit_nests_its_run_under_one_trace_per_fit():
    """The ``jit`` backend's pipeline runs at the first read after a
    write: one ``ddc.refit`` trace per fit, its ``ddc.run`` child inside
    it, neither under the ``ddc.fit`` that only split the points."""
    from repro.data import spatial
    from repro.ddc import DDC, DDCConfig

    pts, _ = spatial.make_blobs(1024, 5, seed=3)
    model = DDC(DDCConfig(eps=0.05, min_pts=5, grid=96, max_clusters=16,
                          max_verts=64, backend="jit", shards=1))
    obs.clear()
    for _ in range(2):
        model.fit(pts)
        model.labels_
    model.labels_                                # no write: no refit
    spans = obs.spans()
    fits = [s for s in spans if s.name == "ddc.fit"]
    refits = [s for s in spans if s.name == "ddc.refit"]
    runs = [s for s in spans if s.name == "ddc.run"]
    assert len(fits) == len(refits) == len(runs) == 2
    for fit, refit, run in zip(fits, refits, runs):
        assert refit.parent_id is None and refit.trace_id == refit.span_id
        assert refit.trace_id != fit.trace_id and fit.end <= refit.start
        assert run.parent_id == refit.span_id
        assert run.trace_id == refit.trace_id
        assert refit.start <= run.start <= run.end <= refit.end
        assert refit.attrs == {"backend": "jit", "shards": 1, "cap": 1024}
        for key in ("sweeps", "doubling_steps", "tile_pairs_active",
                    "tile_pairs", "dense_fallback"):
            assert len(run.attrs[key]) == 1, key
        assert run.attrs["sweeps"][0] >= 1
        assert run.attrs["overflow"] is False
        assert run.attrs["truncated"] == 0
    assert refits[0].trace_id != refits[1].trace_id
    obs.clear()
