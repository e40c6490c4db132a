"""Device: the share of the traced window in which no operation ran,
averaged over the chips (1 - busy / window, busy the mean over the
chips of the union of their operations' intervals)."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
