"""Named stages of the encode path in the profiler's trace.

``span(name)`` marks a host stage as ``gbatc.<name>`` with
``jax.profiler.TraceAnnotation``. The profiler stamps the span on the
clock of its device trace, so a trace shows which stage the host was in
while the device sat idle. A span records nothing unless a trace is being
taken (``jax.profiler.trace`` or ``start_trace``): with the profiler off it
costs one annotation enter and exit, and computes and keeps nothing.

While the profiler is on, every span also carries, as stats, what JAX
reported on this thread between its entry and exit, when nonzero:

* ``jit_s``: seconds spent tracing, lowering and compiling (or loading)
  programs;
* ``compiles``: XLA compilations, persistent-cache loads left out;
* ``cache_loads``: programs loaded from the persistent compilation cache.

The innermost span with ``compiles > 0`` is the stage that compiled. Code
may add counters of its own with the handle's ``count(**stats)``.

While the profiler is on, each span that ends is also kept in memory, on
the host's monotonic clock (``time.perf_counter_ns``), with its stats:
``stages()`` returns them, so a caller can read stage times and counters
of a traced run without parsing the trace file. The clock is read only
then, and nothing a span records reaches a cache key or a container byte.
Spans go in host code only, never inside a function JAX traces (that
would change the program and its cache key), and never inside a
per-species pool worker.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import NamedTuple

import jax
from jax.profiler import TraceAnnotation

PREFIX = "gbatc."

_DURATIONS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Totals(threading.local):
    """This thread's running totals of JAX's compile events."""

    def __init__(self):
        self.jit_s = 0.0
        self.compile_events = 0
        self.cache_loads = 0

    def snapshot(self) -> tuple[float, int, int]:
        return self.jit_s, self.compile_events, self.cache_loads


_totals = _Totals()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event in _DURATIONS and TraceAnnotation.is_enabled():
        _totals.jit_s += duration
        if event == _COMPILE:
            _totals.compile_events += 1


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT and TraceAnnotation.is_enabled():
        _totals.cache_loads += 1


# one pair of listeners per process, registered with the module
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


class Stage(NamedTuple):
    """One span kept while the profiler was on (``perf_counter_ns``)."""

    name: str
    start_ns: int
    end_ns: int
    stats: dict


# the spans that ended while the profiler was on, oldest first; bounded,
# so that a process left tracing for a long time keeps the newest
_stages: deque[Stage] = deque(maxlen=1 << 16)


def stages() -> list[Stage]:
    """The spans that ended while the profiler was on, oldest first."""
    return list(_stages)


class Span:
    """What a stage sees of its span: ``count`` attaches counters."""

    __slots__ = ("_ann", "on", "stats")

    def __init__(self, ann: TraceAnnotation, on: bool, meta: dict):
        self._ann = ann
        self.on = on
        self.stats = meta

    def count(self, **stats) -> None:
        """Attach ``stats`` to the span, when the profiler records it."""
        if self.on:
            self._ann.set_metadata(**stats)
            self.stats.update(stats)


@contextlib.contextmanager
def span(name: str, **meta):
    """Mark the enclosed host code as the stage ``gbatc.<name>``."""
    with TraceAnnotation(PREFIX + name, **meta) as ann:
        handle = Span(ann, TraceAnnotation.is_enabled(), dict(meta))
        if not handle.on:
            yield handle
            return
        jit0, ev0, hit0 = _totals.snapshot()
        t0 = time.perf_counter_ns()
        yield handle
        jit1, ev1, hit1 = _totals.snapshot()
        hits = hit1 - hit0
        stats = {"jit_s": jit1 - jit0, "compiles": ev1 - ev0 - hits,
                 "cache_loads": hits}
        handle.count(**{k: v for k, v in stats.items() if v})
        _stages.append(Stage(PREFIX + name, t0, time.perf_counter_ns(),
                             handle.stats))
