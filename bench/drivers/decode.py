"""Full decodes back to back: the restart and visualisation reader.

Set-up fits and compresses one container from the field (the container
the traffic reads) and decodes it once. The window then runs whole
decodes, each a public ``codec.decompress(blob)`` of a container the
process has not decoded: the decode cache is cleared before each, so
each decode parses, entropy-decodes, runs the fused NN decode and the
guarantee replay, and finalizes the field. It ends with the first
decode that finishes at or after ``--seconds``.

End to end: ``decode_throughput``, raw field bytes (10^6 B of fp32)
reconstructed over the window's time.

Check: one decoded field of the window, drawn from the seed, held to the
guarantee against the original.
"""

from __future__ import annotations

import time

import numpy as np


def make_container(ctx):
    """Fit and compress the field once; returns the container bytes."""
    from repro import codec

    with ctx.span("make_container"):
        gcodec = codec.GBATCCodec(ctx.pipeline_config())
        gcodec.fit(ctx.field)
        blob, _ = gcodec.compress_report(
            target_nrmse=float(ctx.config["target_nrmse"]))
    ctx.note(f"container {len(blob)} bytes")
    return blob


def _decode(ctx, blob):
    from repro import codec

    codec.clear_decode_cache()
    with ctx.span("decode"):
        return codec.decompress(blob)


def setup(ctx):
    blob = make_container(ctx)
    if ctx.warm:
        _decode(ctx, blob)
    ctx.spans.clear()
    return {"blob": blob}


def window(ctx, state):
    outs = []
    t0 = time.perf_counter()
    while True:
        outs.append(_decode(ctx, state["blob"]))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    ctx.units = len(outs)
    state["outs"] = outs
    return {
        "attempted": len(outs),
        "failed": 0,
        "metrics": {
            "decode_throughput": len(outs) * ctx.field.nbytes / 1e6
            / elapsed,
        },
    }


def check(ctx, state, result):
    from repro import codec

    from bench import reference

    codec.clear_decode_cache()
    outs = state.pop("outs")
    pick = int(np.random.default_rng(ctx.seed).integers(len(outs)))
    return reference.field_checks(ctx, outs[pick])
