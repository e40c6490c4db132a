"""Phase-1 kernels: device milliseconds of the phase-1 kernels per
label sweep, counting each phase-1 run's border sweep as one more
(the trace's ``phase1`` group over the program's sweep counts)."""
from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    secs = run.trace["groups"].get("phase1", 0.0)
    got = program_spans.phase1_sweeps(run)
    if not secs or got is None:
        return None
    sweeps, runs, _ = got
    return secs * 1e3 / (sweeps + runs)
