"""The ``doubling_steps.fit`` reader of the program's ``ddc.phase1``
spans, on a toy-size run of the seed-spreader cell on the CPU."""
import time

import pytest
from _bench_toy import spreader

from bench import harness, loops

SEED = 2**31 + 434343


@pytest.fixture(scope="module")
def run():
    _, config, mix, _, _ = spreader(shards=2, n=8192)
    return loops.run_batch_fit(config, mix, SEED, 0.5, False,
                               time.perf_counter())


def read(metric, run):
    return harness.reader(metric)(run)


def window_phase1_spans(run):
    from repro import obs

    lo = run.rec.named("fit")[0].start
    hi = run.rec.named("fit")[-1].end
    return [s for s in obs.spans()
            if s.name == "ddc.phase1" and lo <= s.start and s.end <= hi]


def test_doubling_steps_per_fit(run):
    assert run.correct, run.compared
    steps = read("doubling_steps.fit", run)
    total = sum(s.attrs["doubling_steps"] for s in window_phase1_spans(run))
    assert steps == total / run.info["fits"] and steps > 0


def test_none_on_spans_without_the_attribute(run, monkeypatch):
    from repro import obs

    bare = [s._replace(attrs={k: v for k, v in s.attrs.items()
                              if k != "doubling_steps"}) for s in obs.spans()]
    monkeypatch.setattr(obs, "spans", lambda: bare)
    assert read("doubling_steps.fit", run) is None
    assert read("sweeps.fit", run) is not None


@pytest.mark.parametrize("missing", ["lost", "empty"])
def test_none_when_spans_are_missing(run, monkeypatch, missing):
    from repro import obs

    if missing == "lost":
        monkeypatch.setattr(obs, "lost_since", lambda t: True)
    else:
        monkeypatch.setattr(obs, "spans", lambda: [])
    assert read("doubling_steps.fit", run) is None
