"""Stream ingest: host milliseconds per fit in ``ShardControlPlane.ingest``
(the program's ``ddc.ingest`` spans: host mirrors and append dispatch)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_fit(run, "ddc.ingest")
