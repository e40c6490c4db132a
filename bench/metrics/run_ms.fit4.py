"""jit pipeline, four chips: milliseconds per fit from the call of the
``make_ddc_fn`` program to the end of its one host read of labels and
stats (the program's ``ddc.run`` spans): phase 1 on every chip, the
butterfly merge, the global labels."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_fit(run, "ddc.run")
