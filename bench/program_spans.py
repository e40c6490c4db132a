"""The program's own spans (``repro.obs``) inside a run's window, for
the per-layer readers.

The window runs from the start of the run's first ``fit`` span to the
end of its last; a program span counts when it lies wholly inside it.
Each reader returns None where there is nothing to read: a program
without ``repro.obs``, no span of the name in the window, or a ring that
dropped spans the window held.
"""
from __future__ import annotations


def window(run):
    """(program spans inside the window, number of fits) or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    fits = run.rec.named("fit")
    if not fits:
        return None
    lo, hi = fits[0].start, fits[-1].end
    if obs.lost_since(lo):
        return None
    return [s for s in obs.spans() if lo <= s.start and s.end <= hi], len(fits)


def named(run, name: str):
    """(spans of ``name`` inside the window, their enclosing window's
    spans, number of fits) or None when there are none."""
    got = window(run)
    if got is None:
        return None
    spans, fits = got
    mine = [s for s in spans if s.name == name]
    return (mine, spans, fits) if mine else None


def ms_per_fit(run, name: str):
    """Milliseconds of the spans named ``name`` per fit."""
    got = named(run, name)
    if got is None:
        return None
    mine, _, fits = got
    return sum(s.seconds for s in mine) * 1e3 / fits


def phase1_sweeps(run):
    """(label sweeps of the window's phase-1 runs, those runs, fits) or
    None.  An emptied shard's span ran no phase 1 and has no ``sweeps``."""
    got = named(run, "ddc.phase1")
    if got is None:
        return None
    ran = [s for s in got[0] if "sweeps" in s.attrs]
    if not ran:
        return None
    return sum(s.attrs["sweeps"] for s in ran), len(ran), got[2]
