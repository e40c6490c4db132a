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
                                   "ddc.phase1", "ddc.aggregate", "ddc.live"}
    obs.clear()
