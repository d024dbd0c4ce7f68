"""Trainer layer: mean seconds per job in the correction net's fit (the
program's ``gbatc.train.correction`` span in ``core/pipeline.py``: the
fused decode that feeds it, the pointwise transposes, the trainer)."""

from bench import stages


def read(ctx):
    return stages.seconds_per_job(ctx, "gbatc.train.correction")
