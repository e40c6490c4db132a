"""The six per-layer readers of ``spreader.fit4`` on synthetic runs: the
program's ``ddc.fit`` / ``ddc.refit`` / ``ddc.run`` spans with per-lane
lists, and a trace of four devices whose operations include the
butterfly's collectives.  Each reader gives None where its spans or the
trace are missing, as on a program that records no ``ddc.run``."""
import pytest
from _bench_toy import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import harness, loops, trace_reduce
from bench.spans import Recorder
from bench.spans import Span as BenchSpan

MS = 1_000_000
SPAN_READERS = ("run_ms.fit4", "host_ms.fit4", "sweeps.fit4")
TRACE_READERS = ("p1_kernel_ms.fit4", "collective_ms.fit4", "idle_share.fit4")
LANE_SWEEPS = ([30, 41, 28, 35], [31, 40, 29, 36])


def program_spans():
    """Two fits, each a ``ddc.fit`` root and a ``ddc.refit`` root with
    its ``ddc.run`` child, all inside the benchmark's two ``fit`` spans
    (0-1 s and 1-2 s)."""
    from repro import obs

    out, ids = [], iter(range(1, 100))
    for i, sweeps in enumerate(LANE_SWEEPS):
        fit, refit, run = next(ids), next(ids), next(ids)
        out.append(obs.Span("ddc.fit", i + 0.00, i + 0.01, fit, None, fit,
                            {"backend": "jit", "n": 16}))
        out.append(obs.Span("ddc.run", i + 0.10, i + 0.80, run, refit, refit,
                            {"sweeps": sweeps, "doubling_steps": [9] * 4,
                             "overflow": False, "truncated": 0}))
        out.append(obs.Span("ddc.refit", i + 0.02, i + 0.90, refit, None,
                            refit, {"backend": "jit", "shards": 4, "cap": 4}))
    return out


def four_chip_trace():
    """1 s window; each chip runs a phase-1 kernel 100 ms and the
    butterfly's permutes 4 + 6 ms, plus a fusion of 50 ms."""
    devices = {}
    for d in range(4):
        devices[f"/device:TPU:{d}"] = [
            ("min_label_sweep_sparse.2", 100 * MS, 200 * MS),
            ("fusion.7", 200 * MS, 250 * MS),
            ("collective-permute-start.1", 250 * MS, 252 * MS),
            ("collective-permute-done.1", 252 * MS, 254 * MS),
            ("collective-permute-start.2", 300 * MS, 303 * MS),
            ("collective-permute-done.2", 303 * MS, 306 * MS),
        ]
    events = {"devices": devices, "host": [("window", 0, 1000 * MS)]}
    return trace_reduce.reduce(events)


@pytest.fixture
def run(monkeypatch):
    from repro import obs

    rec = Recorder()
    rec.spans = [BenchSpan("fit", 0.0, 1.0, {}), BenchSpan("fit", 1.0, 2.0, {})]
    spans = program_spans()
    monkeypatch.setattr(obs, "spans", lambda: list(spans))
    monkeypatch.setattr(obs, "lost_since", lambda t: False)
    return loops.Run(values={}, rec=rec, info={"fits": 2}, compared={},
                     attempted=2, failed=0, trace=four_chip_trace())


def read(metric, run):
    return harness.reader(metric)(run)


def test_span_readers(run):
    assert read("run_ms.fit4", run) == pytest.approx(700.0)
    # ddc.fit 10 ms + ddc.refit 880 ms less its ddc.run 700 ms, per fit
    assert read("host_ms.fit4", run) == pytest.approx(190.0)
    # the slowest lane of each fit: 41 and 40 sweeps
    assert read("sweeps.fit4", run) == pytest.approx(40.5)


def test_trace_readers_on_four_devices(run):
    assert run.trace["devices"] == 4
    assert read("p1_kernel_ms.fit4", run) == pytest.approx(50.0)   # 100 / 2 fits
    assert read("collective_ms.fit4", run) == pytest.approx(5.0)   # 10 / 2 fits
    assert read("idle_share.fit4", run) == pytest.approx(1 - 0.160)   # busy 100..254, 300..306


def test_trace_readers_give_none_without_a_trace(run):
    run.trace = None
    for m in TRACE_READERS:
        assert read(m, run) is None, m


def test_collective_ms_none_without_collectives(run):
    ops = {"/device:TPU:0": [("min_label_sweep_sparse.2", 0, 10 * MS)]}
    run.trace = trace_reduce.reduce({"devices": ops,
                                     "host": [("window", 0, 100 * MS)]})
    assert read("collective_ms.fit4", run) is None
    assert read("p1_kernel_ms.fit4", run) == pytest.approx(5.0)


@pytest.mark.parametrize("missing", ["lost", "empty", "no_refit"])
def test_span_readers_give_none_when_spans_are_missing(run, monkeypatch, missing):
    from repro import obs

    if missing == "lost":
        monkeypatch.setattr(obs, "lost_since", lambda t: True)
    elif missing == "empty":
        monkeypatch.setattr(obs, "spans", lambda: [])
    else:       # a program without the jit path's spans: ddc.fit alone
        fits = [s for s in program_spans() if s.name == "ddc.fit"]
        monkeypatch.setattr(obs, "spans", lambda: fits)
    for m in SPAN_READERS:
        assert read(m, run) is None, m
