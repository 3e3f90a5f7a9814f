"""The chip's idle time inside the program's ``engine.decode`` spans, in
ms a round (``bench/program_spans.py``)."""
from bench import program_spans


def read(ctx):
    return program_spans.gap_ms(ctx, (program_spans.DECODE,))
