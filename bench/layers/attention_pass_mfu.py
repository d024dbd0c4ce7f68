"""The attention family's passes over all blocks: the encode after the
fit (the program's ``gbatc.fit.latents.encode`` span) and the decode the
guarantee is computed against (``gbatc.compress.latents.decode``), both
ending in their fetch. Their operations
(``attention_counts.pass_flops`` per job) over the two spans' time and
the chip's bf16 peak."""

from bench import attention_counts, roofline, stages

SPANS = ("gbatc.fit.latents.encode", "gbatc.compress.latents.decode")


def read(ctx):
    found = stages.spans(ctx)
    if not found or ctx.units == 0:
        return None
    seconds = sum(s.dur_ns for s in found if s.name in SPANS) / 1e9
    if seconds <= 0:
        return None
    flops = ctx.units * attention_counts.pass_flops(
        ctx.shapes, attention_counts.arch(ctx.config))
    return roofline.mfu(flops, seconds, ctx.peak, ctx.cell.chips)
