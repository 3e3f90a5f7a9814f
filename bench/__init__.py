"""The chip benchmark: one cell (a configuration under a traffic mix) per
run of ``bench/run.py``.

Everything is found by name, one file per item:

* ``configs/<config>.json``: the configuration as it is run, with its
  source, the widths, what was cut and assumed, and its ``driver``;
* ``workloads/<cell>.json``: the cell's configuration, chips, traffic
  parameters and correctness limits;
* ``drivers/<driver>.py``: set-up, one closed-loop round and the
  correctness check for one entry point of the program;
* ``metrics/<metric>.py``: ``read(ctx)`` for one metric of
  ``BENCHMARK.json``;
* ``counts/<kernel>.py``: operations and bytes of one kernel call;
* ``reference/<config>.py``: the plain reference the check compares with;
* ``peaks.json``: the chip's peaks by ``device_kind``;
* ``trace.py``: the reduction of a profiler trace to busy time, kernel
  time and labelled idle gaps.
"""
