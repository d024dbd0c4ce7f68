"""Whole encode job: model operations of the window's jobs (``counts``:
training steps forward and backward, the passes over all blocks, the
guarantee kernels) over the window's time and the chip's bf16 peak."""

from bench import counts, roofline


def read(ctx):
    if ctx.units == 0 or ctx.window_s <= 0:
        return None
    flops = ctx.units * counts.encode_job_flops(ctx.shapes)
    return roofline.mfu(flops, ctx.window_s, ctx.peak, ctx.cell.chips)
