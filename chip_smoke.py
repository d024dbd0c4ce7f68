#!/usr/bin/env python3
"""Chip smoke run: the GBATC main path once, at the paper's width, on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the mesh path on four chips

One chip: fit the default conv pipeline (latent 36, channels (32, 64),
600 AE + 300 correction steps, batch 64) on 8 frames of the S3D
surrogate (58 species, 640x640, frames 20-28 across ignition, made from
the paper config's seed), compress at NRMSE 1e-3, decode the container,
and decode one (species, time group) window. It checks that the engine
runs compiled Pallas (no interpret mode) with the jit selection and that
the trainer runs its scan program, then that every species' NRMSE,
computed in fp64 on the host against the original field, is within the
bound; that every block meets the engine's per-block bound; that the
container decodes bitwise like ``pipeline.decompress``; and that the
window is bitwise the same slice of the full decode.

Four chips: fit and compress the same window over a 4-device mesh, then
compress the same fitted model with the single-device engine; the two
containers must be byte-identical, and the sharded one must meet the
bound.

Phase times are one cold run, compilation included: a smoke test, not a
benchmark. Off TPU, or without the repository's ``src/`` beside it, the
script exits nonzero before any work and prints no result. The last
line of a passing run is a JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
FRAMES = (20, 28)  # of the paper config's 50: two bt=4 groups mid-ignition
TARGET_NRMSE = 1e-3


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


class _Clock:
    """Wall time per phase, printed as each ends (compile included)."""

    def __init__(self):
        self.phases: dict[str, float] = {}

    def __call__(self, name, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.phases[name] = time.perf_counter() - t0
        print(f"phase {name}: {self.phases[name]:.2f} s (one cold run)",
              flush=True)
        return out


def _window():
    from repro.data import s3d

    cfg = s3d.PAPER_CONFIG
    data = s3d.generate_species_window(cfg, *FRAMES)
    print(f"data: S3D surrogate seed {cfg.seed}, {cfg.n_species} species x "
          f"frames {FRAMES[0]}-{FRAMES[1]} ({FRAMES[1] - FRAMES[0]} of "
          f"{cfg.n_time}) x {cfg.height}x{cfg.width}, shape {data.shape}, "
          f"{data.nbytes} bytes fp32", flush=True)
    return data


def _engine_failures(engine) -> list[str]:
    print(f"guarantee engine: {type(engine).__name__}, interpret "
          f"{engine.interpret}, select {engine.select_backend}, projection "
          f"{np.dtype(engine.coeff_dtype).name}", flush=True)
    bad = []
    if engine.interpret is not False:
        bad.append(f"guarantee engine in interpret mode ({engine.interpret})")
    if engine.select_backend != "jit":
        bad.append(f"guarantee select backend {engine.select_backend!r}")
    return bad


def _trainer_failures(pipe) -> list[str]:
    trainers = list(pipe.model._trainers.values())
    if pipe.corr_net is not None:
        trainers += list(pipe.corr_net.__dict__.get("_trainers", {}).values())
    modes = sorted({t.mode for t in trainers})
    print(f"trainer modes: {modes}", flush=True)
    return [] if modes == ["scan"] else [f"trainer modes {modes}, not scan"]


def _bound_failures(data, decoded, pipe, target) -> list[str]:
    """fp64 host checks: species NRMSE and the per-block l2 bound."""
    from repro.core import blocking, gae, metrics

    geom = pipe.cfg.geometry
    tau = target * np.sqrt(geom.block_size)
    mn, rng = pipe._norm
    per = np.empty(len(data))
    worst_block = 0.0
    blocks_bad = []
    for s in range(len(data)):
        per[s] = metrics.nrmse(data[s], decoded[s])
        norm = [
            blocking.blocks_as_vectors(blocking.to_blocks(
                (a[s : s + 1].astype(np.float64) - float(mn[s]))
                / float(rng[s]), geom))[0]
            for a in (data, decoded)
        ]
        err = np.linalg.norm(norm[0] - norm[1], axis=1)
        worst_block = max(worst_block, float(err.max()) / tau)
        if not gae.verify_guarantee(norm[0], norm[1], tau):
            blocks_bad.append(s)
    print(f"worst species NRMSE (fp64): {per.max():.6e} (bound "
          f"{target:g}), mean {per.mean():.6e}", flush=True)
    print(f"worst block l2 / tau: {worst_block:.6f}", flush=True)
    bad = []
    if not per.max() <= target:
        bad.append(f"species {int(per.argmax())} NRMSE {per.max():.6e} "
                   f"exceeds {target:g}")
    if blocks_bad:
        bad.append(f"species {blocks_bad} have blocks over the l2 bound")
    return bad


def run_one_chip(data, cfg, target=TARGET_NRMSE) -> list[str]:
    from repro import codec

    clock = _Clock()
    gcodec = codec.GBATCCodec(cfg)
    clock("fit", gcodec.fit, data)
    pipe = gcodec.pipeline
    bad = _engine_failures(pipe._gengine) + _trainer_failures(pipe)
    blob, rep = clock("compress", gcodec.compress_report,
                      target_nrmse=target)
    print(f"container: {len(blob)} bytes, CR {data.nbytes / len(blob):.3f}",
          flush=True)
    decoded = clock("decompress", gcodec.decompress, blob)
    bad += _bound_failures(data, decoded, pipe, target)

    if not np.array_equal(decoded, pipe.decompress(rep.artifact)):
        bad.append("container decode differs from pipeline.decompress")
    species, group = 7, 1  # a minor species, the second time group
    bt = cfg.geometry.bt
    window = clock("window_decode", codec.PartialDecoder(blob).decode,
                   species=species, time_range=(group * bt, (group + 1) * bt))
    same = np.array_equal(
        window, decoded[species, group * bt : (group + 1) * bt])
    print(f"window decode (species {species}, frames {group * bt}-"
          f"{(group + 1) * bt}) bitwise equal to the full decode: {same}",
          flush=True)
    if not same:
        bad.append("window decode differs from the full decode's slice")
    return bad


def run_four_chips(data, cfg, n_devices=4, target=TARGET_NRMSE) -> list[str]:
    from repro import codec
    from repro.core import gae
    from repro.parallel import mesh_fit

    clock = _Clock()
    mesh = mesh_fit.host_mesh(n_devices)
    gcodec = codec.GBATCCodec(cfg, mesh=mesh)
    clock("mesh_fit", gcodec.fit, data)
    pipe = gcodec.pipeline
    bad = _engine_failures(pipe._gengine)
    sharded, _ = clock("sharded_compress", gcodec.compress_report,
                       target_nrmse=target)
    pipe.set_guarantee_engine(gae.GuaranteeEngine())
    bad += _engine_failures(pipe._gengine)
    single, _ = clock("single_device_compress", gcodec.compress_report,
                      target_nrmse=target)
    same = sharded == single
    print(f"container: {len(sharded)} bytes, CR "
          f"{data.nbytes / len(sharded):.3f}; sharded engine byte-identical "
          f"to the single-device engine: {same}", flush=True)
    if not same:
        bad.append("sharded container differs from the single-device one")
    decoded = clock("decompress", gcodec.decompress, sharded)
    return bad + _bound_failures(data, decoded, pipe, target)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh fit/compress and its "
                         "single-device comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        _fail(f"JAX found no TPU (backend {backend!r}); nothing was run")
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        _fail(f"{need} devices wanted, {len(devices)} found")

    from repro import compile_cache
    from repro.core.pipeline import PipelineConfig

    cache = compile_cache.configure(ROOT)
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
          f"compile cache {cache}", flush=True)
    data = _window()
    cfg = PipelineConfig()
    print(f"model: {cfg.family} latent {cfg.latent} channels "
          f"{cfg.conv_channels} ae_steps {cfg.ae_steps} corr_steps "
          f"{cfg.corr_steps} batch {cfg.batch_size}; geometry "
          f"{cfg.geometry}", flush=True)
    if args.four_chips:
        bad = run_four_chips(data, cfg, n_devices=need)
    else:
        bad = run_one_chip(data, cfg)
    stats = dev.memory_stats() or {}
    print(f"device 0 peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
          flush=True)
    if bad:
        _fail("; ".join(bad))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
