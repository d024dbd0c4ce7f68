"""From a profiler trace to the numbers the per-layer readers report.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain :class:`Event` lists, and the rest
of this module works on those alone, so a test can feed it events made
by hand.

* Device events are those of the ``/device:TPU:<n>`` planes. The op line
  (``XLA Ops``) gives the device's busy time: the union of the intervals
  in which an operation ran. The module line (``XLA Modules``) names the
  jitted programs.
* Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
  spans, whose names start with ``bench.``; each idle gap of the device is
  labelled by the innermost such span that covers its midpoint.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Device op and module events per chip, and the benchmark's spans."""

    ops: dict[int, list[Event]] = field(default_factory=dict)
    modules: dict[int, list[Event]] = field(default_factory=dict)
    spans: list[Event] = field(default_factory=list)

    @property
    def window(self) -> tuple[float, float]:
        """The traced window: the outermost ``bench.window`` span."""
        wins = [s for s in self.spans if s.name == SPAN_PREFIX + "window"]
        if not wins:
            raise ValueError("trace holds no bench.window span")
        return (min(s.start_ns for s in wins), max(s.end_ns for s in wins))

    def chips(self) -> list[int]:
        return sorted(self.ops)


def load(trace_dir: str | Path) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                dest = tr.ops if line.name == OPS_LINE else tr.modules
                dest.setdefault(int(m.group(2)), []).extend(
                    Event(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
            elif m is None:
                tr.spans.extend(
                    Event(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return tr


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of ``events`` clipped to ``[lo, hi]``, empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: Trace, chip: int) -> float:
    """Nanoseconds of the window in which some op ran on ``chip``."""
    lo, hi = trace.window
    return sum(b - a for a, b in union(clip(trace.ops.get(chip, []), lo, hi)))


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the chips traced."""
    chips = trace.chips()
    if not chips:
        return 0.0
    return sum(busy_ns(trace, c) for c in chips) / len(chips) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> float | None:
    """Percent of the window in which the device ran nothing."""
    if not trace.chips():
        return None
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def matching(events, pattern: str) -> list[Event]:
    """Events whose name matches ``pattern`` (a regex)."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def op_seconds(trace: Trace, pattern: str, chip: int = 0) -> tuple[float, int]:
    """(device seconds, event count) of the ops matching ``pattern``
    inside the window."""
    lo, hi = trace.window
    evs = matching(trace.ops.get(chip, []), pattern)
    spans = clip(evs, lo, hi)
    return sum(b - a for a, b in spans) / 1e9, len(spans)


def module_seconds(trace: Trace, pattern: str,
                   chip: int = 0) -> tuple[float, int]:
    """(device seconds, count) of the jitted programs matching
    ``pattern`` inside the window."""
    lo, hi = trace.window
    spans = clip(matching(trace.modules.get(chip, []), pattern), lo, hi)
    return sum(b - a for a, b in spans) / 1e9, len(spans)


def _label(trace: Trace, t: float) -> str:
    """The innermost benchmark span (other than the window) around ``t``."""
    best = None
    for s in trace.spans:
        if s.name == SPAN_PREFIX + "window":
            continue
        if s.start_ns <= t <= s.end_ns and (best is None
                                            or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else SPAN_PREFIX + "window"


def idle_gaps(trace: Trace, chip: int = 0) -> list[tuple[str, float]]:
    """Every idle gap of ``chip`` in the window, longest first, as
    (label of the host span it fell in, seconds)."""
    lo, hi = trace.window
    busy = union(clip(trace.ops.get(chip, []), lo, hi))
    gaps = []
    t = lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    out = [(_label(trace, (a + b) / 2), (b - a) / 1e9) for a, b in gaps]
    return sorted(out, key=lambda g: -g[1])


def short_name(name: str, width: int = 96) -> str:
    """An op's HLO text cut to its instruction and result shape."""
    head = name.split("{", 1)[0]
    return head if len(head) <= width else head[:width]


def top_ops(trace: Trace, chip: int = 0, n: int = 10
            ) -> list[tuple[str, float]]:
    """The ``n`` ops with the most device time in the window."""
    lo, hi = trace.window
    total: dict[str, float] = {}
    for e in trace.ops.get(chip, []):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            key = short_name(e.name)
            total[key] = total.get(key, 0.0) + (b - a) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The traced run's ``breakdown``: top device ops and longest gaps."""
    chips = trace.chips()
    if not chips:
        return {"device_ops": [], "idle_gaps": []}
    c = chips[0]
    return {
        "device_ops": [[k, v] for k, v in top_ops(trace, c, n)],
        "idle_gaps": [[k, v] for k, v in idle_gaps(trace, c)[:n]],
    }
