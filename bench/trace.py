"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the metrics read.

The device planes (``/device:TPU:<n>``) hold one event per operation on
the line ``XLA Ops`` and one per program on ``XLA Modules``; the host
plane holds the harness's ``jax.profiler.TraceAnnotation`` spans, on the
same clock.  The window is the harness's ``bench.window`` span.

An operation event's name is its HLO text, ``%<op> = <shape> <kind>(...)``;
``Event.name`` keeps ``<op>`` and ``Event.detail`` the whole text.  A
loop's event encloses the events of its body.

* busy time: the union of the operation intervals inside the window, on
  each chip that ran any, averaged over those chips;
* a kernel's time: the summed durations of its custom calls, the
  operations named ``<kernel>`` or ``<kernel>.<n>``;
* the top operations: by self time, a loop's time less its body's;
* idle gaps: the stretches of the window with no operation on the chip,
  each labelled by the innermost harness span around its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "outside any span"


@dataclasses.dataclass
class Event:
    name: str
    start: float     # ns
    end: float       # ns
    detail: str      # the event's whole name (an operation's HLO text)


@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]          # ns
    ops: dict[str, list[Event]]          # device plane name -> op events
    modules: list[Event]
    spans: list[Event]                   # harness spans on the host

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _inside(self, events):
        lo, hi = self.window
        return [e for e in events if e.end > lo and e.start < hi]

    def busy_intervals(self, plane: str) -> list[tuple[float, float]]:
        """Merged intervals in which an operation ran on ``plane``,
        clipped to the window."""
        lo, hi = self.window
        out: list[list[float]] = []
        for e in sorted(self._inside(self.ops[plane]), key=lambda e: e.start):
            s, t = max(e.start, lo), min(e.end, hi)
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return [(s, t) for s, t in out]

    @property
    def chips(self) -> list[str]:
        return [p for p in sorted(self.ops) if self._inside(self.ops[p])]

    @property
    def busy_s(self) -> float:
        chips = self.chips
        if not chips:
            return 0.0
        total = sum(t - s for p in chips for s, t in self.busy_intervals(p))
        return total * 1e-9 / len(chips)

    def kernel_events(self, kernel: str) -> list[Event]:
        """The custom calls named ``kernel`` in the window, on any chip."""
        return [e for p in sorted(self.ops) for e in self._inside(self.ops[p])
                if e.name.split(".")[0] == kernel and "custom-call" in e.detail]

    def module_events(self, pattern: str) -> list[Event]:
        return [e for e in self._inside(self.modules) if pattern in e.detail]

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with the most self time in the window,
        summed over their events, as ``[name, seconds]``."""
        by: dict[str, float] = {}
        for p in sorted(self.ops):
            events = sorted(self._inside(self.ops[p]),
                            key=lambda e: (e.start, -e.end))
            stack: list[list] = []          # [event, self time]
            for e in events + [None]:
                while stack and (e is None or e.start >= stack[-1][0].end):
                    done, own = stack.pop()
                    by[done.name] = by.get(done.name, 0.0) + own * 1e-9
                if e is None:
                    break
                if stack:
                    stack[-1][1] -= e.end - e.start
                stack.append([e, e.end - e.start])
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest stretches of the window in which the first
        chip ran nothing, as ``[label, seconds]``, labelled by the
        innermost harness span around each gap's middle."""
        chips = self.chips
        if not chips:
            return []
        lo, hi = self.window
        busy = self.busy_intervals(chips[0])
        gaps, at = [], lo
        for s, t in busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, t)
        if hi > at:
            gaps.append((at, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label(0.5 * (s + t)), (t - s) * 1e-9]
                for s, t in gaps[:n]]

    def label(self, t: float) -> str:
        around = [e for e in self.spans
                  if e.start <= t <= e.end and e.name != WINDOW]
        if not around:
            return NO_SPAN
        return min(around, key=lambda e: e.end - e.start).name


def find(directory: str) -> str:
    """The one ``*.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one *.xplane.pb under {directory}, found {paths}")
    return paths[0]


def _op(ev) -> Event:
    """An operation event: ``<op>`` from ``%<op> = ...`` as its name."""
    text = ev.name
    name = text.split(" = ", 1)[0].lstrip("%") if " = " in text else text
    return Event(name, ev.start_ns, ev.start_ns + ev.duration_ns, text)


def reduce(path: str, span_names=()) -> Reduction:
    """Read the trace at ``path`` (a file, or a directory holding one);
    ``span_names`` are the harness's span names to keep from the host."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find(path)
    data = ProfileData.from_file(path)
    keep = set(span_names) | {WINDOW}
    ops: dict[str, list[Event]] = {}
    modules: list[Event] = []
    spans: list[Event] = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ops[plane.name] = [_op(e) for e in line.events]
            elif device and line.name == MODULES_LINE:
                modules += [Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            elif not device:
                spans += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                e.name)
                          for e in line.events if e.name in keep]
    windows = [e for e in spans if e.name == WINDOW]
    if windows:
        window = (windows[0].start, windows[0].end)
    else:
        every = [e for evs in ops.values() for e in evs]
        if not every:
            raise ValueError(f"{path}: no {WINDOW} span and no device "
                             "operation")
        window = (min(e.start for e in every), max(e.end for e in every))
    return Reduction(window=window, ops=ops, modules=modules, spans=spans)
