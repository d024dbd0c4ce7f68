"""Trainer layer of the attention family: the fit's operations (the
``steps`` and ``batch`` stats of the program's ``gbatc.train.ae`` span,
``attention_counts.train_flops``) over that span's time less what JAX
spent tracing and compiling or loading in it (its ``jit_s``), and the
chip's bf16 peak."""

from bench import attention_counts, roofline, stages


def read(ctx):
    found = stages.spans(ctx)
    if not found:
        return None
    arch = attention_counts.arch(ctx.config)
    flops = seconds = 0.0
    for s in found:
        if s.name == "gbatc.train.ae" and "steps" in s.stats:
            flops += attention_counts.train_flops(
                ctx.shapes, arch, int(s.stats["steps"]),
                int(s.stats["batch"]))
            seconds += s.dur_ns / 1e9 - s.stats.get("jit_s", 0.0)
    if seconds <= 0:
        return None
    return roofline.mfu(flops, seconds, ctx.peak, ctx.cell.chips)
