"""jit backend host side: milliseconds per fit of ``DDC.fit`` (the
program's ``ddc.fit`` spans: the split into shards) and of each refit
less its program run (``ddc.refit`` minus its ``ddc.run`` child:
padding, placement on the mesh, the label concat)."""
from bench import program_spans


def read(run):
    fit = program_spans.named(run, "ddc.fit")
    refit = program_spans.named(run, "ddc.refit")
    if fit is None or refit is None:
        return None
    refits, spans, fits = refit
    ids = {s.span_id for s in refits}
    children = sum(s.seconds for s in spans
                   if s.name == "ddc.run" and s.parent_id in ids)
    host = sum(s.seconds for s in fit[0]) + sum(s.seconds for s in refits)
    return (host - children) * 1e3 / fits
