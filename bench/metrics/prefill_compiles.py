"""Programs compiled or loaded from the compilation cache inside the
program's ``engine.prefill`` spans and their descendants, a round
(``bench/program_spans.py``)."""
from bench import program_spans


def read(ctx):
    return program_spans.compiles(ctx, program_spans.PREFILL)
