"""One reader per metric of ``BENCHMARK.json``: ``read(ctx)`` returns the
metric's value, or ``None`` where the run holds nothing to read (the
harness then leaves the metric out).  ``ctx`` is ``bench.run.Context``."""
