"""Phase-1 label propagation: min-label sweeps per fit of the lane that
swept most (the largest of the per-lane ``sweeps`` the program counts on
the chips and lists on each ``ddc.run`` span): the lane that sets the
pace at the first exchange."""
from bench import program_spans


def read(run):
    got = program_spans.named(run, "ddc.run")
    if got is None:
        return None
    spans, _, fits = got
    ran = [s for s in spans if s.attrs.get("sweeps")]
    if not ran:
        return None
    return sum(max(s.attrs["sweeps"]) for s in ran) / fits
