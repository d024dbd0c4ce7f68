"""The program's stages (``repro.tracing``) on the clock of the trace.

The program marks each stage of the encode path as a ``gbatc.*`` span in
the profiler's trace, and while the profiler is on it also keeps every
span it ran, with its stats, on the host's monotonic clock
(``repro.tracing.stages()``). :func:`spans` puts those on the clock of the
traced run's :class:`tracefile.Trace`: every job's two entry spans,
``gbatc.fit`` and ``gbatc.compress``, run just inside the driver's
``bench.fit`` and ``bench.compress``, so each such pair gives the offset
between the two clocks for the stages inside it, to within the few
microseconds between the two spans' entries and exits.

The stages found are added to the trace's spans as well, so that the
breakdown's idle gaps are labelled by the innermost program stage around
them (``tracefile.idle_gaps``) and no longer by the driver's span around
the call.

Against a program without ``repro.tracing`` nothing is read, and every
reader built on this returns ``None``.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from bench import tracefile

ENTRIES = {"gbatc.fit": "bench.fit", "gbatc.compress": "bench.compress"}


@dataclass
class Stage:
    """A program span on the trace's clock, with its stats."""

    name: str
    start_ns: float
    dur_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _record() -> list | None:
    """The program's kept spans, or None where it keeps none."""
    try:
        from repro.tracing import stages
    except ImportError:
        return None
    return stages()


def _offsets(trace, record) -> list[tuple[float, float]]:
    """(program-clock start, offset to the trace's clock) of each entry
    span matched to the driver's span around it, oldest first. The
    window's jobs are the newest the program kept, so the last entry
    spans of each name pair with the driver's spans in the window."""
    lo, hi = trace.window
    pairs = []
    for entry, outer in ENTRIES.items():
        bench = sorted((s for s in trace.spans
                        if s.name == outer and lo <= s.start_ns <= hi),
                       key=lambda s: s.start_ns)
        prog = [r for r in record if r.name == entry]
        n = min(len(bench), len(prog))
        for b, p in zip(bench[len(bench) - n:], prog[len(prog) - n:]):
            # the entry span lies inside b: the offset is between these
            a = b.start_ns - p.start_ns
            z = b.end_ns - p.end_ns
            pairs.append((p.start_ns, (a + z) / 2))
    return sorted(pairs)


def spans(ctx) -> list[Stage] | None:
    """The program's spans that start inside the traced window, on the
    trace's clock; None where the program kept none or no entry span
    could be matched. Computed once a run, and added to the trace's
    spans then."""
    if ctx.trace is None:
        return None
    if hasattr(ctx, "program_stages"):
        return ctx.program_stages
    ctx.program_stages = None
    record = _record()
    offsets = _offsets(ctx.trace, record) if record else []
    if not offsets:
        return None
    lo, hi = ctx.trace.window
    out = []
    for r in record:
        # the offset of the newest entry span that started by r's start
        off = offsets[0][1]
        for start, o in offsets:
            if start > r.start_ns:
                break
            off = o
        s = Stage(r.name, r.start_ns + off, float(r.end_ns - r.start_ns),
                  dict(r.stats))
        if lo <= s.start_ns <= hi:
            out.append(s)
    ctx.trace.spans.extend(tracefile.Event(s.name, s.start_ns, s.dur_ns)
                           for s in out)
    ctx.program_stages = out
    return out


def seconds_per_job(ctx, name: str) -> float | None:
    """Mean seconds per job in the spans called ``name``; None where the
    window holds none."""
    found = spans(ctx)
    if not found or ctx.units == 0:
        return None
    durs = [s.dur_ns for s in found if s.name == name]
    return sum(durs) / 1e9 / ctx.units if durs else None


def stat_per_job(ctx, names, key: str) -> float | None:
    """Mean per job of the stat ``key`` summed over the spans called any
    of ``names`` (a span without it counts 0); None where the window
    holds none of those spans."""
    found = spans(ctx)
    if not found or ctx.units == 0:
        return None
    hits = [s for s in found if s.name in names]
    if not hits:
        return None
    return sum(s.stats.get(key, 0) for s in hits) / ctx.units


def uncovered_idle_ns(trace, stages: list[Stage], chip: int = 0,
                      exclude=tuple(ENTRIES)) -> float:
    """Nanoseconds of the window in which ``chip`` ran nothing and no
    program span other than those named in ``exclude`` was open: idle
    time that no program stage accounts for."""
    lo, hi = trace.window
    inner = [s for s in stages if s.name not in exclude]
    covered = tracefile.union(tracefile.clip(trace.ops.get(chip, []), lo, hi)
                              + tracefile.clip(inner, lo, hi))
    return (hi - lo) - sum(b - a for a, b in covered)
