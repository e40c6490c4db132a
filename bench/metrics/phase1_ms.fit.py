"""Phase 1, local clustering: milliseconds per fit from ``local_phase``
to the end of each shard's host copy (the program's ``ddc.phase1``
spans), which holds the shard's device work."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_fit(run, "ddc.phase1")
