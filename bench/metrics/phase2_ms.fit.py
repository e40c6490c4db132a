"""Phase 2, aggregation: milliseconds per fit of each refresh less its
phase-1 children (the program's ``ddc.refresh`` spans minus their
``ddc.phase1`` spans): the exchange gate, merge, global labels and
snapshot publish."""
from bench import program_spans


def read(run):
    got = program_spans.named(run, "ddc.refresh")
    if got is None:
        return None
    refreshes, spans, fits = got
    ids = {s.span_id for s in refreshes}
    children = sum(s.seconds for s in spans
                   if s.name == "ddc.phase1" and s.parent_id in ids)
    return (sum(s.seconds for s in refreshes) - children) * 1e3 / fits
