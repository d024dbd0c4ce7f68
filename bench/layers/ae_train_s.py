"""Trainer layer: mean seconds per job in the autoencoder's fit (the
program's ``gbatc.train.ae`` span around ``family.fit`` in
``core/pipeline.py``: trainer set-up, trace or cache load, the scan)."""

from bench import stages


def read(ctx):
    return stages.seconds_per_job(ctx, "gbatc.train.ae")
