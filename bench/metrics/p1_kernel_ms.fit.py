"""Phase-1 kernels: device milliseconds of the neighbour-count and
min-label-sweep kernels per fit, per chip, in the traced window."""


def read(run):
    if run.trace is None:
        return None
    secs = run.trace["groups"].get("phase1", 0.0)
    fits = len(run.rec.named("fit"))
    if not secs or not fits:
        return None
    return secs * 1e3 / fits
