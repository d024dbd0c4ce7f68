"""Guarantee layer: mean seconds per job in the engine's tau-independent
prepare (the program's ``gbatc.guarantee.prepare`` span around
``GuaranteeEngine.prepare`` in ``core/gae.py``: fp64 residual, PCA,
projection kernel and fetch, energy order, staging)."""

from bench import stages


def read(ctx):
    return stages.seconds_per_job(ctx, "gbatc.guarantee.prepare")
