"""Guarantee layer: mean rounds per job of ``_hold_bound``'s replay and
measure loop (the ``rounds`` stat of the program's
``gbatc.guarantee.hold_bound`` span). One round means the cut held every
block on its first replay."""

from bench import stages


def read(ctx):
    return stages.stat_per_job(ctx, ("gbatc.guarantee.hold_bound",),
                               "rounds")
