import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build the model from its full config (ShapeDtypeStruct params/inputs —
    zero device allocation);
  * jit the production step (train_step incl. optimizer | prefill |
    serve_step) with explicit in/out shardings from repro.parallel.sharding;
  * ``.lower(...).compile()`` against the 16x16 (single-pod) and 2x16x16
    (multi-pod) meshes;
  * record memory_analysis(), cost_analysis(), and the collective-op bytes
    parsed from the post-SPMD optimized HLO into results/dryrun/<cell>.json
    (consumed by EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_2_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs.base import SHAPES, get_config, list_configs
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model, input_specs
from repro.parallel import sharding as sh
from repro.train import optimizer as opt
from repro.train.train_loop import TrainConfig, make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op in the optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    # matches: `= bf16[1,2,3]{...} all-gather(` and tuple forms
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        m = re.search(r"=\s+(.*?)\s+(" + "|".join(_COLLECTIVES) + r")[\.\(]",
                      line)
        if not m:
            continue
        shapes_str, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in shape_re.findall(shapes_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[op] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def depth_variants(cfg):
    """Reduced-depth override dicts for the affine cost fit.

    XLA's HloCostAnalysis counts a while-loop (lax.scan) body ONCE, so the
    scanned production artifact under-reports flops/bytes/collectives by the
    trip count. Costs are affine in stack depth, so we compile tiny unrolled
    variants (same widths, same remat, depth 1 and 2) and extrapolate:
        total(L) = f(1) + (L - 1) * (f(2) - f(1)).
    Whisper has two stacks (enc, dec) -> 3 points; recurrentgemma's depth
    unit is the (rec, rec, attn) period.
    """
    fam = cfg.family
    if fam == "audio":
        return (
            [dict(n_layers=1, n_encoder_layers=1, scan_layers=False),
             dict(n_layers=2, n_encoder_layers=1, scan_layers=False),
             dict(n_layers=1, n_encoder_layers=2, scan_layers=False)],
            ("dec", "enc"),
            (cfg.n_layers, cfg.n_encoder_layers),
        )
    if fam == "hybrid":
        tail = cfg.n_layers - 3 * (cfg.n_layers // 3)
        return (
            [dict(n_layers=3 + tail, scan_layers=False),
             dict(n_layers=6 + tail, scan_layers=False)],
            ("period",),
            (cfg.n_layers // 3,),
        )
    return (
        [dict(n_layers=1, scan_layers=False),
         dict(n_layers=2, scan_layers=False)],
        ("layer",),
        (cfg.n_layers,),
    )


def extrapolate(points: list[dict], depths: tuple[int, ...]) -> dict:
    """Affine extrapolation of every numeric metric to full depth.

    Slopes are clamped at 0: cost is non-decreasing in depth, and at tiny
    decode shapes compiler fusion noise can make f(2) < f(1) by epsilon."""
    keys = [k for k, v in points[0].items() if isinstance(v, (int, float))]
    out = {}
    for k in keys:
        base = points[0][k]
        total = base
        for i, full in enumerate(depths):
            slope = max(0.0, points[i + 1][k] - base)
            total += (full - 1) * slope
        out[k] = total
    return out


def train_state_specs(params_specs):
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jax.numpy.float32)
    return {
        "opt": {
            "m": jax.tree.map(f32, params_specs),
            "v": jax.tree.map(f32, params_specs),
            "step": jax.ShapeDtypeStruct((), jax.numpy.int32),
        }
    }


def _named(mesh, pspec_tree):
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _lower_compile(cfg, shape, mesh, grad_accum: int = 1):
    """Lower + compile one step for one config; returns metrics dict."""
    model = build_model(cfg)
    params_specs = model.specs()
    p_shard = _named(mesh, sh.param_pspecs(model, cfg, mesh))
    batch_specs = input_specs(cfg, shape)
    b_shard = {k: NamedSharding(mesh, v)
               for k, v in sh.batch_pspecs(cfg, shape, mesh).items()}

    t0 = time.time()
    if shape.kind == "train":
        tcfg = TrainConfig(optimizer=opt.AdamWConfig(lr=1e-4),
                           grad_accum=grad_accum)
        step = make_train_step(model, tcfg)
        state_specs = train_state_specs(params_specs)
        state_shard = {"opt": _named(mesh, sh.optimizer_pspecs(model, cfg, mesh))}
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, state_shard, b_shard),
                out_shardings=(p_shard, state_shard, None),
            ).lower(params_specs, state_specs, batch_specs)
    elif shape.kind == "prefill":
        def prefill(params, batch):
            return model.prefill(params, batch, max_len=shape.seq_len)

        with mesh:
            lowered = jax.jit(
                prefill, in_shardings=(p_shard, b_shard), out_shardings=None,
            ).lower(params_specs, batch_specs)
    else:  # decode
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        cache_shard = _named(mesh, sh.cache_pspecs(model, cfg, mesh, shape.global_batch))

        def serve_step(params, cache, tokens):
            return model.decode_step(params, cache, tokens)

        with mesh:
            lowered = jax.jit(
                serve_step,
                in_shardings=(p_shard, cache_shard,
                              NamedSharding(
                                  mesh,
                                  P(sh.dp_axes_for(mesh, shape.global_batch),
                                    None))),
                out_shardings=(None, cache_shard),
            ).lower(params_specs, cache_specs, batch_specs["tokens"])
    lower_s = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    mem_info = {}
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
            v = getattr(mem, field, None)
            if v is not None:
                mem_info[field] = int(v)
    cost = compiled.cost_analysis() or {}
    cost_info = {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float)) and not k.startswith("utilization")}
    coll = parse_collective_bytes(compiled.as_text())

    metrics = {
        "flops": cost_info.get("flops", 0.0),
        "bytes_accessed": cost_info.get("bytes accessed", 0.0),
        **{f"coll_{k}": float(v) for k, v in coll.items()},
    }
    return {
        "metrics": metrics,
        "memory": mem_info,
        "cost": cost_info,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun", overrides: dict | None = None,
             tag: str = "", grad_accum: int = 1) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"

    # 1. the production (scanned) artifact: memory analysis + compile proof
    prod = _lower_compile(cfg, shape, mesh, grad_accum=grad_accum)

    # 2. affine depth fit for scan-corrected flops/bytes/collectives
    variants, depth_names, full_depths = depth_variants(cfg)
    points = [_lower_compile(cfg.replace(**ov), shape, mesh,
                             grad_accum=grad_accum)["metrics"]
              for ov in variants]
    corrected = extrapolate(points, full_depths)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "ok": True,
        "lower_s": prod["lower_s"],
        "compile_s": prod["compile_s"],
        # scan-corrected totals (per device)
        "flops": corrected["flops"],
        "bytes_accessed": corrected["bytes_accessed"],
        "collectives": {k[5:]: v for k, v in corrected.items()
                        if k.startswith("coll_")},
        # raw production-artifact numbers (scan body counted once by XLA)
        "raw_scanned": prod["metrics"],
        "memory": prod["memory"],
        "cost_scanned": prod["cost"],
        "depth_fit": {"names": depth_names, "full": full_depths,
                      "points": points},
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "tag": tag,
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] OK {arch} {shape_name} {mesh_name} "
          f"flops={result['flops']:.3e} "
          f"coll={result['collectives']['total']:.3e}B "
          f"compile={prod['compile_s']:.0f}s")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list_configs() if (args.all or args.arch is None) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else cfg.shapes
        for shape_name in shapes:
            if shape_name not in cfg.shapes:
                print(f"[dryrun] SKIP {arch} {shape_name} (not applicable)")
                continue
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(
                    args.out, f"{arch}__{shape_name}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] cached {path}")
                    continue
                try:
                    run_cell(arch, shape_name, mp, args.out)
                except Exception as e:  # noqa: BLE001  # repro: allow[typed-errors] — record and continue
                    failures.append((arch, shape_name, mesh_name, repr(e)))
                    traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print("[dryrun] all cells green")


if __name__ == "__main__":
    main()
