"""Device: percent of the window's device-idle time in which no program
stage was open (no ``gbatc.*`` span but the entry spans ``gbatc.fit``
and ``gbatc.compress``): host work that no span names yet. It says how
complete the program's instrumentation is."""

from bench import stages, tracefile


def read(ctx):
    found = stages.spans(ctx)
    if not found or not ctx.trace.chips():
        return None
    chip = ctx.trace.chips()[0]
    lo, hi = ctx.trace.window
    idle = (hi - lo) - tracefile.busy_ns(ctx.trace, chip)
    if idle <= 0:
        return None
    return 100.0 * stages.uncovered_idle_ns(ctx.trace, found, chip) / idle
