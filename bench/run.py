"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration,
``bench/configs/<config>.json``, which names its driver,
``bench/drivers/<driver>.py``.  One run, in one process that holds the
chip:

1. set-up (timed as ``setup_s``): the driver builds the program and its
   inputs from ``--seed`` and warms up every shape the window uses;
2. the window: closed-loop rounds of one request per client, each sent
   once the previous round's replies are all in, until ``--seconds``
   have passed; with ``--trace 1`` under the profiler;
3. the device's peak memory, then the program's state is dropped;
4. the check: the driver compares a seed-drawn sample of the window's
   answers with the plain reference, each number against its limit in
   the cell's file.

With ``--trace 0`` the metrics are the cell's end-to-end metrics of
``BENCHMARK.json``, with ``--trace 1`` its per-layer metrics; each is
read by ``bench/metrics/<metric>.py``.  The last line of standard output
is the result as one JSON object.  Off a TPU, or with fewer chips than
the cell asks for, the command prints no result and exits 2.

JAX's compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` where that is
set, else ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the monitoring events that mean a program was compiled or loaded from
# the persistent compilation cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict]:
    """(workload, configuration) of cell ``name``."""
    cell = load_json(BENCH, "workloads", f"{name}.json")
    return cell, load_json(BENCH, "configs", f"{cell['config']}.json")


def metrics_of(name: str, trace: bool) -> list[dict]:
    """The metrics of ``BENCHMARK.json`` a run of cell ``name`` reports:
    end-to-end without a trace, per-layer with one."""
    spec = load_json(ROOT, "BENCHMARK.json")
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


class CompileCounter:
    """Counts compilations and compilation-cache loads while active."""

    def __init__(self):
        import jax.monitoring as mon
        self.active, self.n = False, 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.active and name in COMPILE_EVENTS:
            self.n += 1

    def _duration(self, name, _secs, **_):
        self._event(name)


class Spans:
    """The harness's host spans: kept in memory, and written into the
    profiler's trace while one is taken."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, time.perf_counter()))


def enable_compilation_cache(jax) -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def compare(checked: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether every one is
    within it: the program's run and the control are judged alike."""
    compared = {k: {"value": v, "limit": limits[k]} for k, v in checked.items()}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def run_cell(name: str, seed: int, seconds: float, trace: bool, device, *,
             cell=None, config=None, window=None,
             control=False) -> tuple[dict, list]:
    """Set up, measure, check.  Returns (result, lines for stderr).
    ``cell``/``config`` replace the files' (tests run tiny sizes);
    ``window(driver)`` may wrap the driver before the window (tests break
    the timed path with it); ``control`` also judges the control on the
    same sample (``bench/control.py``)."""
    import jax
    from bench import peaks as peaks_mod
    from bench.common import Reservoir, sub_seed

    file_cell, file_config = load_cell(name)
    cell = cell or file_cell
    config = config or file_config
    driver_mod = importlib.import_module(f"bench.drivers.{config['driver']}")
    counter = CompileCounter()
    spans = Spans()
    lines: list[str] = []

    t0 = time.perf_counter()
    driver = driver_mod.Driver(cell, config, seed, device)
    driver.setup()
    setup_s = time.perf_counter() - t0
    lines += driver.log
    if window is not None:
        driver = window(driver)

    sample = Reservoir(cell["check_rounds"], sub_seed(seed, "sample"))
    latencies: list[float] = []
    before = driver.counters()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    counter.active = True
    k = 0
    with spans("bench.window"):
        start = time.perf_counter()
        while True:
            sent = time.perf_counter()
            sample.offer(driver.round(k, spans))
            done = time.perf_counter()
            latencies += [done - sent] * cell["clients"]
            k += 1
            if done - start >= seconds:
                break
    counter.active = False
    window_s = done - start
    reduction = None
    if trace:
        from bench import trace as trace_mod
        jax.profiler.stop_trace()
        reduction = trace_mod.reduce(trace_dir, driver.SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
    after = driver.counters()
    stats = device.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use", 0)

    ctx = Context(cell=cell, config=config, driver=driver, setup_s=setup_s,
                  latencies=latencies, window_s=window_s,
                  completed=len(latencies), rounds=k, compiles=counter.n,
                  counters={c: after[c] - before.get(c, 0) for c in after},
                  spans=[s for s in spans.items if s[0] != "bench.window"],
                  trace=reduction,
                  peaks=peaks_mod.for_kind(device.device_kind)
                  if device.platform == "tpu" else None)
    metrics = {}
    for m in metrics_of(name, trace):
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    driver.release()
    t0 = time.perf_counter()
    logged = len(driver.log)
    checked = driver.check(sample.items)
    lines += driver.log[logged:]
    lines.append(f"check of {len(sample.items)} rounds took "
                 f"{time.perf_counter() - t0:.2f}s")
    compared, correct = compare(checked, cell["limits"])
    lines += [f"check {k}={c['value']!r} limit={c['limit']!r}"
              for k, c in compared.items()]

    result = {"correct": correct, "attempted": len(latencies), "failed": 0,
              "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak_bytes}}
    if reduction is not None:
        result["device"]["busy_s"] = reduction.busy_s
        result["device"]["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": reduction.idle_gaps(10)}
    if control:
        result["control"], result["control_correct"] = compare(
            driver.check(sample.items, control=True), cell["limits"])
    result["checked"] = compared
    return result, lines


class Context:
    """What a metric's reader reads: the cell, the window's host-clock
    latencies and spans, the counters, the reduced trace (``None``
    without ``--trace 1``), the driver and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)

    cell, _ = load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX's backend is {devs[0].platform!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 2
    enable_compilation_cache(jax)
    result, lines = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), devs[0])
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
