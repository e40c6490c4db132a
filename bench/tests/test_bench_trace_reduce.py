"""The trace reduction on a small trace recorded on a TPU v5 lite
(``data/small.xplane.pb``, made by ``record_trace.py``), and on events
laid out by hand."""
import pathlib

import pytest
from _bench_toy import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import trace_reduce

SMALL = pathlib.Path(__file__).with_name("data") / "small.xplane.pb"


def test_reduce_by_hand():
    ms = 1_000_000
    events = {
        "devices": {
            "/device:TPU:0": [("neighbor_count_kernel", 10 * ms, 30 * ms),
                              ("fusion.1", 25 * ms, 40 * ms),
                              ("all-gather.2", 70 * ms, 80 * ms)],
            "/device:TPU:1": [("neighbor_count_kernel", 10 * ms, 20 * ms)],
        },
        "host": [("window", 0, 100 * ms), ("refresh", 5 * ms, 45 * ms),
                 ("query", 45 * ms, 85 * ms)],
    }
    out = trace_reduce.reduce(events, groups={"phase1": ["neighbor_count"],
                                              "collective": ["all-gather"]},
                              span_names=("refresh", "query"))
    assert out["window_s"] == pytest.approx(0.1)
    assert out["devices"] == 2
    # device 0 busy 10..40 and 70..80 = 40 ms; device 1 10 ms; mean 25 ms
    assert out["busy_s"] == pytest.approx(0.025)
    assert out["groups"]["phase1"] == pytest.approx(0.015)      # (20 + 10) / 2
    assert out["groups"]["collective"] == pytest.approx(0.005)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["query", pytest.approx(0.03)]           # 40..70, in query
    assert ["idle", pytest.approx(0.02)] in gaps                # 80..100
    assert out["breakdown"]["device_ops"][0][0] == "neighbor_count_kernel"


def test_reduce_recorded_trace():
    events = trace_reduce.load_events(str(SMALL))
    assert any(p.startswith("/device:TPU") for p in events["devices"])
    out = trace_reduce.reduce(events, span_names=("refresh", "query"))
    assert 0.05 < out["window_s"] < 5.0
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert out["groups"]["phase1"] > 0.0
    assert out["breakdown"]["idle_gaps"][0][0] == "query"
    assert out["breakdown"]["idle_gaps"][0][1] >= 0.045
