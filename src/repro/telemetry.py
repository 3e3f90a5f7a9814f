"""The program's one recorder of host spans and counters.

``span(name, **attrs)`` is a context manager around a block of host
code.  Its handle carries ``name``, a process-unique ``id``, the
``parent`` id (the innermost span open in the same thread when it
started), ``attrs``, ``t0_ns``/``t1_ns`` on ``time.perf_counter_ns()``
and ``compiles``: the compilations and compilation-cache loads that JAX
reported while this span was the innermost open one.  ``.seconds`` is
its duration, whether or not it is recorded, so a caller that needs a
duration reads it and keeps no timer of its own.

Each span also opens a ``jax.profiler.TraceAnnotation`` of the same
name, so it shows in a profiler trace on the device trace's clock.

There is no switch of its own: a span is kept only while a JAX profiler
trace is active (``jax.profiler.TraceAnnotation.is_enabled()``), so
starting the profiler turns recording on.  Otherwise a span costs two
clock reads, a push and a pop of the thread's stack and the inactive
annotation, and nothing is kept.  Kept spans go into a bounded buffer;
when it is full the oldest is dropped and ``counters()["dropped"]``
counts it.  A compile event with no span open in its thread counts
under ``counters()["compiles/none"]``.

``add(key, n)`` adds to a counter of the program's own and ``peak(key,
n)`` raises a high-water mark; both are counted whether or not a trace
is active, and ``counters()`` returns them beside the recorder's.  The
served model's MoE layers report through them once per
``Engine.generate``: ``moe.rows/<phase>``, the (token, expert) pairs that
landed on held experts, ``moe.experts/<phase>``, the held experts with
any, summed over layer calls, and ``moe.rows_max/<phase>``, the most
pairs on one held expert in one layer call; ``<phase>`` is ``prefill``
or ``decode``.

Spans inside a function that ``jax.jit`` traces fire once, at trace
time, and time the tracing; under ``jit`` a ``model.prefill.*`` span
therefore times how long its part of the program took to trace.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any

import jax
import jax.monitoring

CAPACITY = 65536
# JAX's monitoring events for a program compiled, or loaded from the
# persistent compilation cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")

_Annotation = jax.profiler.TraceAnnotation
_ids = itertools.count(1)
_open = threading.local()          # .stack: the thread's open spans


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


class Span:
    """One timed block; the context manager that ``span`` returns."""

    __slots__ = ("name", "id", "parent", "attrs", "t0_ns", "t1_ns",
                 "compiles", "kept", "_recorder", "_twin")

    def __init__(self, recorder: "Recorder", name: str, attrs: dict):
        self._recorder = recorder
        self.name = name
        self.attrs = attrs
        self.id = next(_ids)
        self.parent = None
        self.compiles = 0
        self.t0_ns = self.t1_ns = 0

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            self.parent = stack[-1].id
        self.kept = _Annotation.is_enabled()
        self._twin = _Annotation(self.name)
        self._twin.__enter__()
        stack.append(self)
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = time.perf_counter_ns()
        _stack().pop()
        self._twin.__exit__(*exc)
        self._twin = None
        if self.kept:
            self._recorder._keep(self)


class Recorder:
    """A bounded buffer of kept spans and the counters beside it."""

    def __init__(self, capacity: int = CAPACITY):
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._counts = {"dropped": 0, "compiles/none": 0}
        self._lock = threading.Lock()

    def span(self, name: str, **attrs: Any) -> Span:
        return Span(self, name, attrs)

    def _keep(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._counts["dropped"] += 1
            self._spans.append(s)

    def _count(self, key: str) -> None:
        with self._lock:
            self._counts[key] += 1

    def add(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + int(n)

    def peak(self, key: str, n: int) -> None:
        with self._lock:
            self._counts[key] = max(self._counts.get(key, 0), int(n))

    def spans(self) -> list[Span]:
        """The kept spans, oldest first."""
        with self._lock:
            return list(self._spans)

    def counters(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counts)


RECORDER = Recorder()
span = RECORDER.span
spans = RECORDER.spans
counters = RECORDER.counters
add = RECORDER.add
peak = RECORDER.peak


def _on_event(name: str, *_args, **_kw) -> None:
    if name not in COMPILE_EVENTS:
        return
    stack = _stack()
    if stack:
        if stack[-1].kept:
            stack[-1].compiles += 1
    elif _Annotation.is_enabled():
        RECORDER._count("compiles/none")


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_event)
