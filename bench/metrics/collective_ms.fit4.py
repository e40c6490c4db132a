"""Cross-chip merge: device milliseconds per fit, per chip, of the
butterfly's ``ppermute`` rounds: the operations named
``collective-permute-start`` / ``-done``, the only collectives in a
traced run of the cell, summed over the chips and divided by their
number.  A lane's ``-start`` lasts until its partner is ready, so the
time holds the wait for the slower lane of each pair as well as the
transfer."""

PATTERN = "collective-permute"


def read(run):
    if run.trace is None:
        return None
    secs = sum(t for name, t in run.trace.get("op_s", {}).items()
               if PATTERN in name)
    fits = len(run.rec.named("fit"))
    if not secs or not fits:
        return None
    return secs * 1e3 / fits
