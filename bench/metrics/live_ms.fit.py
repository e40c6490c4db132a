"""Facade read: milliseconds per fit in ``ShardControlPlane.live``, the
host copy behind ``DDC.labels_`` (the program's ``ddc.live`` spans)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_fit(run, "ddc.live")
