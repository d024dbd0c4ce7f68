"""Whole encode job of the attention family: its operations
(``attention_counts.encode_job_flops``: the fit forward and backward, the
encode and decode passes over all blocks, the guarantee kernels) for the
window's jobs, over the window's time and the chip's bf16 peak."""

from bench import attention_counts, roofline


def read(ctx):
    if ctx.units == 0 or ctx.window_s <= 0:
        return None
    flops = ctx.units * attention_counts.encode_job_flops(
        ctx.shapes, attention_counts.arch(ctx.config))
    return roofline.mfu(flops, ctx.window_s, ctx.peak, ctx.cell.chips)
