"""Phase-1 label propagation: pointer-doubling gathers run per fit,
summed over the shards (the ``doubling_steps`` the program counts on the
chip and sets on each ``ddc.phase1`` span).  None on a program whose
spans do not carry the attribute."""
from bench import program_spans


def read(run):
    got = program_spans.named(run, "ddc.phase1")
    if got is None:
        return None
    spans, _, fits = got
    ran = [s for s in spans if "doubling_steps" in s.attrs]
    if not ran:
        return None
    return sum(s.attrs["doubling_steps"] for s in ran) / fits
