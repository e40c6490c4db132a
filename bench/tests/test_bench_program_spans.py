"""The per-layer readers of the program's own spans (``repro.obs``), on
a toy-size run of the seed-spreader cell on the CPU."""
import time

import pytest
from _bench_toy import spreader

from bench import harness, loops

SEED = 2**31 + 424242
SPAN_METRICS = ("ingest_ms.fit", "phase1_ms.fit", "phase2_ms.fit",
                "live_ms.fit")


@pytest.fixture(scope="module")
def run():
    _, config, mix, _, _ = spreader(shards=2, n=8192)
    return loops.run_batch_fit(config, mix, SEED, 0.5, False,
                               time.perf_counter())


def read(metric, run):
    return harness.reader(metric)(run)


def test_span_metrics_fit_inside_the_window(run):
    assert run.correct, run.compared
    vals = {m: read(m, run) for m in SPAN_METRICS}
    assert all(v is not None and v > 0 for v in vals.values()), vals
    fit_ms = run.info["window_s"] * 1e3 / run.info["fits"]
    assert sum(vals.values()) <= fit_ms, (vals, fit_ms)


def test_sweeps_and_ms_per_sweep(run):
    sweeps = read("sweeps.fit", run)
    assert sweeps > 0 and sweeps == int(sweeps)
    assert read("p1_ms_per_sweep.fit", run) is None      # untraced run
    run.trace = {"groups": {"phase1": 0.5}}
    try:
        per_sweep = read("p1_ms_per_sweep.fit", run)
    finally:
        run.trace = None
    # two shards a fit, each with one border sweep beyond its counted ones
    fits = run.info["fits"]
    assert per_sweep == pytest.approx(500.0 / (fits * (sweeps + 2)))


def test_readers_give_none_when_spans_are_missing(run, monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "lost_since", lambda t: True)
    for m in SPAN_METRICS + ("sweeps.fit",):
        assert read(m, run) is None, m
    monkeypatch.undo()
    monkeypatch.setattr(obs, "spans", lambda: [])
    for m in SPAN_METRICS + ("sweeps.fit",):
        assert read(m, run) is None, m
