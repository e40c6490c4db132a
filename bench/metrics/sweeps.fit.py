"""Phase-1 label propagation: min-label sweeps to convergence per fit,
summed over the shards (the ``sweeps`` the program counts on the chip
and sets on each ``ddc.phase1`` span)."""
from bench import program_spans


def read(run):
    got = program_spans.phase1_sweeps(run)
    if got is None:
        return None
    sweeps, _, fits = got
    return sweeps / fits
