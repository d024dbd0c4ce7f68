"""Whole job: mean seconds per job that JAX spent tracing, lowering and
compiling or loading programs (the ``jit_s`` stat of the program's two
entry spans, ``gbatc.fit`` and ``gbatc.compress``, which never nest, so
nothing counts twice). Fresh per-instance ``jax.jit`` objects re-trace
and reload their programs in every job; this is what that costs."""

from bench import stages


def read(ctx):
    return stages.stat_per_job(ctx, tuple(stages.ENTRIES), "jit_s")
