"""Encode jobs of a configuration that names its encoder family's arch
words (``pipeline.arch``), with the family's encoder and decoder held to
a plain reference.

The jobs and the guarantee checks are ``encode.py``'s, loaded from
beside this file; the program's configuration differs:
``PipelineConfig.arch`` is set from ``pipeline.arch``, which
``Ctx.pipeline_config`` does not read, so that the family runs at the
configuration's widths and not at its defaults. The window is
``encode.py``'s, but each job keeps, beside its container, the fitted
encoder's parameters and the float latents of its all-block encode pass:
references the program holds anyway, nothing copied.

Check: ``encode.py``'s (``nrmse``, ``block``, ``net_nrmse`` on one job's
container, drawn from the seed), then ``model`` and ``encoder`` on one
chunk of the fused-decode grid (512 block rows, or all of them where
there are fewer), drawn from the seed, of that job:

* ``model``, the decoder: the container decoded without its guarantee
  coefficients (``codec.decode_artifact``, then ``codec.reconstruct``),
  the program's network output, against ``attention_reference.decode_ref``
  on the container's own decoder parameters and dequantised latents;
  the range-normalised RMS of the difference.
* ``encoder``: the job's float latents of those rows against
  ``attention_reference.encode_ref`` on its encoder parameters and the
  rows' blocks, normalised by the container's species minima and ranges;
  the RMS of the difference over the reference latents' RMS.

Both references multiply at ``model_operands``, the precision the
configuration states for the program's matrix products. A run that reads
the control (``ctx.control``) puts in the program's place the reference
carried in bfloat16 throughout, the precision below the float32
activations the configuration states.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import numpy as np

from bench import harness

encode = harness.load_module(Path(__file__).with_name("encode.py"))

# rows per dispatch of the program's fused decode: every container
# decodes on this grid (``repro.codec.runtime``)
CHUNK = 512


def setup(ctx):
    cfg = dataclasses.replace(
        ctx.pipeline_config(),
        arch=tuple(int(w) for w in ctx.config["pipeline"]["arch"]))
    if ctx.warm:
        encode._job(ctx, cfg)
    ctx.spans.clear()
    return {"cfg": cfg}


def _job(ctx, cfg):
    """``encode.py``'s job; returns its container and its encoder's
    parameters and float latents."""
    from repro import codec

    with ctx.span("job"):
        gcodec = codec.GBATCCodec(cfg)
        with ctx.span("fit"):
            gcodec.fit(ctx.field)
        with ctx.span("compress"):
            blob, _ = gcodec.compress_report(
                target_nrmse=float(ctx.config["target_nrmse"]))
    ctx.note(f"job {ctx.spans['job'][-1]:.3f} s (fit "
             f"{ctx.spans['fit'][-1]:.3f}, compress "
             f"{ctx.spans['compress'][-1]:.3f}); container {len(blob)} "
             f"bytes")
    pipe = gcodec.pipeline
    params = {k: v for k, v in pipe._ae_params.items()
              if k.startswith("enc_")}
    return blob, {"params": params, "latents": pipe._latents}


def window(ctx, state):
    blobs, encoders = [], []
    t0 = time.perf_counter()
    while True:
        blob, enc = _job(ctx, state["cfg"])
        blobs.append(blob)
        encoders.append(enc)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    ctx.units = len(blobs)
    raw = ctx.field.nbytes
    state["blobs"], state["encoders"] = blobs, encoders
    return {
        "attempted": len(blobs),
        "failed": 0,
        "metrics": {
            "encode_throughput": len(blobs) * raw / 1e6 / elapsed,
            "compression_ratio": len(blobs) * raw / sum(map(len, blobs)),
        },
    }


def check(ctx, state, result):
    encoders = state.pop("encoders")
    # encode.check's draw: the job it checks
    pick = int(np.random.default_rng(ctx.seed).integers(len(encoders)))
    blob = state["blobs"][pick]
    checks = encode.check(ctx, state, result)
    checks.update(model_checks(ctx, blob, encoders[pick]))
    return checks


def _blocks(field: np.ndarray, rows: np.ndarray, block) -> np.ndarray:
    """Block rows ``rows`` of an (S, T, H, W) field, in the codec's order
    (time group, patch row, patch column), as (rows, S, bt, ph, pw)."""
    bt, ph, pw = block
    s, t, h, w = field.shape
    grid = (t // bt, h // ph, w // pw)
    it, ih, iw = np.unravel_index(rows, grid)
    x = field.reshape(s, grid[0], bt, grid[1], ph, grid[2], pw)
    return x[:, it, :, ih, :, iw, :]


def model_checks(ctx, blob: bytes, enc: dict) -> dict:
    import jax.numpy as jnp

    from repro import codec

    from bench import attention_reference, reference

    art = codec.decode_artifact(blob)
    art.species_guarantees = [dataclasses.replace(g, coeff_q=g.coeff_q[:0])
                              for g in art.species_guarantees]
    nb = art.latent_q.shape[0]
    chunk = min(CHUNK, nb)
    k = int(np.random.default_rng([ctx.seed, 1]).integers(-(-nb // chunk)))
    rows = np.arange(k * chunk, min((k + 1) * chunk, nb))
    s = len(art.norm_min)
    block = ctx.shapes.block
    heads = int(ctx.config["pipeline"]["arch"][1])
    operands = jnp.dtype(ctx.config["model_operands"])
    shape = (1, s, 1, 1, 1)
    mn = art.norm_min.astype(np.float64).reshape(shape)
    rng = art.norm_range.astype(np.float64).reshape(shape)

    # the program's latents: dequantised in float64, stored as float32
    lat = (art.latent_q[rows].astype(np.float64) * art.latent_bin).astype(
        np.float32)
    blocks = ((_blocks(ctx.field, rows, block) - mn) / rng).astype(np.float32)

    def decoder(**kw):
        return attention_reference.decode_ref(
            art.ae_params, lat, heads, (s,) + tuple(block), **kw)

    def encoder(**kw):
        return attention_reference.encode_ref(enc["params"], blocks, heads,
                                              **kw)

    dec_want, enc_want = decoder(operands=operands), encoder(operands=operands)
    if ctx.control:
        dec_got = decoder(dtype=jnp.bfloat16)
        enc_got = encoder(dtype=jnp.bfloat16)
    else:
        # the network's output, denormalised by the program in float32,
        # back in range-normalised units
        dec_got = (_blocks(codec.reconstruct(art), rows, block) - mn) / rng
        enc_got = np.asarray(enc["latents"])[rows]
    codec.clear_decode_cache()
    values = {"model": _rms(dec_got - dec_want),
              "encoder": _rms(enc_got - enc_want) / _rms(enc_want)}
    ctx.note(f"model, encoder: rows {rows[0]}-{rows[-1]} of {nb}, "
             f"{'control' if ctx.control else 'program'} against the "
             f"reference at {operands.name} operands {values['model']!r}, "
             f"{values['encoder']!r}")
    limits = ctx.config["limits"]
    return {name: reference.make_check(v, limits[name])
            for name, v in values.items()}


def _rms(diff: np.ndarray) -> float:
    value = float(np.sqrt(np.mean(np.square(diff.astype(np.float64)))))
    return value if np.isfinite(value) else float("inf")
