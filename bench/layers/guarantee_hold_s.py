"""Guarantee layer: mean seconds per job in ``_hold_bound`` (the
program's ``gbatc.guarantee.hold_bound`` span in ``core/gae.py``), which
replays the fp32 correction and measures every block in fp64 until none
is over tau. Only the fp32 projection path runs it."""

from bench import stages


def read(ctx):
    return stages.seconds_per_job(ctx, "gbatc.guarantee.hold_bound")
