"""Projection kernel (``kernels/gbatc_project.py``): its share of the
roofline, from the device time of its events in the trace.

Each job projects every block of every species once (the guarantee
engine's prepare): 2 S NB D^2 operations over the residual read, the
basis read and the coefficients written, at D = 80.
"""

from bench import counts, roofline, tracefile

# the trace names a Pallas kernel by the HLO custom call that runs it,
# which takes the name of the jitted function around it: the guarantee
# engine's ``project_fn``
PATTERN = r"^%project_fn(\.\d+)? = \S+ custom-call\("


def read(ctx):
    if ctx.trace is None or ctx.units == 0:
        return None
    seconds, n = tracefile.op_seconds(ctx.trace, PATTERN)
    if n == 0:
        return None
    work = counts.guarantee_kernel(ctx.shapes)["project"]
    value, bound = roofline.share(ctx.units * work["flops"],
                                  ctx.units * work["bytes"], seconds,
                                  ctx.peak)
    ctx.note(f"gbatc_project: {n} events, {seconds:.6f} s, {bound} bound")
    return value
