"""Encode jobs back to back: the time from a field to a verified container.

A job is what a simulation team runs per field: a fresh
``GBATCCodec(cfg)``, ``fit(field)``, then
``compress_report(target_nrmse=...)`` to container bytes. Every job in a
run encodes the same field. Set-up runs one job, which compiles or loads
every program a job uses; the window then runs whole jobs until one ends
at or after ``--seconds``.

End to end: ``encode_throughput``, the raw field bytes (10^6 B of fp32)
of all the window's jobs over the window's time, and
``compression_ratio``, their raw bytes over their container bytes.

Check: one job's container, drawn from the seed, decoded by the public
``codec.decompress`` and held to the guarantee against the original
field; and the same container decoded without the guarantee's
corrections (``codec.decode_artifact`` with its coefficient streams
left out, then ``codec.reconstruct``), the trained networks' own output,
held to ``net_nrmse``.
"""

from __future__ import annotations

import dataclasses
import resource
import time

import numpy as np


def _job(ctx, cfg):
    from repro import codec

    r0 = resource.getrusage(resource.RUSAGE_SELF)
    with ctx.span("job"):
        gcodec = codec.GBATCCodec(cfg)
        with ctx.span("fit"):
            gcodec.fit(ctx.field)
        with ctx.span("compress"):
            blob, _ = gcodec.compress_report(
                target_nrmse=float(ctx.config["target_nrmse"]))
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    ctx.note(f"job {ctx.spans['job'][-1]:.3f} s (fit "
             f"{ctx.spans['fit'][-1]:.3f}, compress "
             f"{ctx.spans['compress'][-1]:.3f}): host cpu user "
             f"{r1.ru_utime - r0.ru_utime:.3f} s, sys "
             f"{r1.ru_stime - r0.ru_stime:.3f} s, "
             f"{r1.ru_minflt - r0.ru_minflt} minor faults, "
             f"{r1.ru_nivcsw - r0.ru_nivcsw} involuntary switches; "
             f"container {len(blob)} bytes")
    return blob


def setup(ctx):
    cfg = ctx.pipeline_config()
    if ctx.warm:
        _job(ctx, cfg)
    ctx.spans.clear()
    return {"cfg": cfg}


def window(ctx, state):
    blobs = []
    t0 = time.perf_counter()
    while True:
        blobs.append(_job(ctx, state["cfg"]))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    ctx.units = len(blobs)
    raw = ctx.field.nbytes
    state["blobs"] = blobs
    return {
        "attempted": len(blobs),
        "failed": 0,
        "metrics": {
            "encode_throughput": len(blobs) * raw / 1e6 / elapsed,
            "compression_ratio": len(blobs) * raw / sum(map(len, blobs)),
        },
    }


def check(ctx, state, result):
    from repro import codec

    from bench import reference

    blobs = state.pop("blobs")
    pick = int(np.random.default_rng(ctx.seed).integers(len(blobs)))
    blob = blobs[pick]
    ctx.note(f"checking job {pick} of {len(blobs)}; containers "
             f"{[len(b) for b in blobs]} bytes, identical "
             f"{len(set(blobs)) == 1}")
    del blobs
    codec.clear_decode_cache()
    decoded = codec.decompress(blob)
    art = codec.decode_artifact(blob)
    art.species_guarantees = [dataclasses.replace(g, coeff_q=g.coeff_q[:0])
                              for g in art.species_guarantees]
    net = codec.reconstruct(art)
    del art
    codec.clear_decode_cache()
    return reference.field_checks(ctx, decoded, net)
