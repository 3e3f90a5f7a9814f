"""Read a cell's compared numbers for the program and for its control on
several seeds, in one process that holds the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 5

For each seed: the cell's set-up, a window of ``--seconds`` at the cell's
load, then the check on the window's sample twice: once as the benchmark
makes it, and once with the control, the reference in the next lower
precision, in the program's place.  Both are judged by the same
comparison with the cell's limits; ``control_correct`` has to come out
false.  Where the control computes its own answers from the inputs alone
(a driver with ``control_sample``), only the control is read, with no
set-up and no window.  Each seed prints one JSON line ``{"seed",
"correct", "checked", "control_correct", "control", "metrics"}``.  The
limits in the cells' files are set between the program's largest
reading over a dozen seeds or more and the control's smallest.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import run
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: JAX's backend is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    run.enable_compilation_cache(jax)
    import importlib
    cell, config = run.load_cell(args.workload)
    driver_mod = importlib.import_module(f"bench.drivers.{config['driver']}")
    for seed in args.seeds:
        driver = driver_mod.Driver(cell, config, seed, dev)
        if hasattr(driver, "control_sample"):
            compared, correct = run.compare(
                driver.check(driver.control_sample(), control=True),
                cell["limits"])
            print(json.dumps({"seed": seed, "control_correct": correct,
                              "control": compared}), flush=True)
            continue
        result, lines = run.run_cell(args.workload, seed, args.seconds, False,
                                     dev, control=True)
        for line in lines:
            print(line, file=sys.stderr, flush=True)
        gc.collect()       # the last seed's weights, before the next set-up
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checked": result["checked"],
                          "control_correct": result["control_correct"],
                          "control": result["control"],
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
