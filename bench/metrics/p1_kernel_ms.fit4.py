"""Phase-1 kernels: device milliseconds of the neighbour-count and
min-label-sweep kernels per fit, per chip (summed over the chips of the
traced window and divided by their number)."""


def read(run):
    if run.trace is None:
        return None
    secs = run.trace["groups"].get("phase1", 0.0)
    fits = len(run.rec.named("fit"))
    if not secs or not fits:
        return None
    return secs * 1e3 / fits
