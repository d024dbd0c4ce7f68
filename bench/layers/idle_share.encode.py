"""Device: percent of the traced window in which no operation ran on the
chip (1 - the union of op intervals over the window)."""

from bench import tracefile


def read(ctx):
    if ctx.trace is None:
        return None
    return tracefile.idle_share(ctx.trace)
