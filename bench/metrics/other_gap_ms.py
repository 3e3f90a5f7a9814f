"""The chip's idle time in the window outside the program's
``engine.prefill`` and ``engine.decode`` spans, in ms a round: the
harness and the host between calls (``bench/program_spans.py``)."""
from bench import program_spans


def read(ctx):
    return program_spans.other_gap_ms(ctx)
