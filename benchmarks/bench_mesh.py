"""Mesh-sharded fit/compress scaling: DP fit steps/s at 1/2/4/8 devices,
sharded-vs-default compress wall-clock, and gradient-exchange wire bytes.

All mesh work runs in ONE child subprocess with a forced 8-device host
platform (the device count is locked at first jax init, so the parent
process — which may already hold a 1-device runtime — cannot host it).
The forced devices are CPU slices, so the benchmark runs only where the
parent's backend is the CPU: on an accelerator the parent holds the
chips, and ``run`` returns None without starting a child.
The child asserts the bit-identity gates FIRST (P=1 DP fit bitwise the
scan fit; sharded-engine container byte-identical to the default engine;
parts-mode latent packing byte-identical to full-array packing) and only
then measures — a broken invariant can never hide behind a throughput
number.

On this CI host the 8 "devices" are XLA host-platform slices of the same
CPUs, so DP steps/s saturates at the physical core count; the JSON
records the full per-device-count curve plus ``cpu_cores`` so the curve
reads as a saturation measurement, not a regression.

Writes BENCH_mesh.json (repo root) + results/bench/mesh.csv.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
OUT_JSON = os.path.join(_REPO, "BENCH_mesh.json")
OUT_CSV = os.path.join(_REPO, "results", "bench", "mesh.csv")
_SENTINEL = "BENCH_MESH_JSON "

DEVICE_COUNTS = (1, 2, 4, 8)


# ---------------------------------------------------------------------------
# child: runs under the forced 8-device mesh
# ---------------------------------------------------------------------------
def _child(quick: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import gae
    from repro.codec import format as fmt
    from repro.core.pipeline import GBATCPipeline, PipelineConfig
    from repro.data import s3d
    from repro.parallel import mesh_fit
    from repro.train import train_loop

    assert len(jax.devices()) == 8, "child must run on 8 forced devices"
    summary: dict = {
        "quick": quick,
        "cpu_cores": os.cpu_count(),
        "n_devices_forced": 8,
        "gates": {},
    }

    # ---- trainer problem (linear AE, large enough to give the loss and
    # grad work per step some substance) --------------------------------
    rows_n, dim, lat = (2048, 96, 12) if quick else (8192, 128, 16)
    steps, batch = (30, 256) if quick else (60, 512)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((rows_n, dim)).astype(np.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w_enc": jax.random.normal(k1, (dim, lat)) * 0.1,
              "w_dec": jax.random.normal(k2, (lat, dim)) * 0.1}

    def loss_fn(p, b):
        rec = b @ p["w_enc"] @ p["w_dec"]
        return jnp.mean(jnp.square(rec - b))

    tr = train_loop.MiniBatchTrainer(
        loss_fn, train_loop.adamw_cfg(1e-3, steps), mode="scan")
    kw = dict(steps=steps, batch_size=batch, seed=0)

    # ---- gate 1: P=1 DP fit bitwise the plain scan fit ----------------
    p_ref, l_ref = tr.fit(params, (x,), **kw)
    p_1, l_1 = tr.fit(params, (x,), mesh=mesh_fit.host_mesh(1), **kw)
    bitwise = bool(np.array_equal(l_ref, l_1)) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_1))
    )
    summary["gates"]["p1_fit_bitwise"] = bitwise
    assert bitwise, "P=1 mesh fit drifted from the scan fit"

    # ---- DP fit steps/s per device count ------------------------------
    curve = []
    for n_dev in DEVICE_COUNTS:
        mesh = mesh_fit.host_mesh(n_dev)
        tr.fit(params, (x,), mesh=mesh, **kw)  # compile + warm
        t0 = time.perf_counter()
        tr.fit(params, (x,), mesh=mesh, **kw)
        dt = time.perf_counter() - t0
        curve.append({"n_devices": n_dev, "fit_s": dt,
                      "steps_per_s": steps / dt})
    base = curve[0]["steps_per_s"]
    for c in curve:
        c["speedup_vs_1dev"] = c["steps_per_s"] / base
    best = max(curve, key=lambda c: c["steps_per_s"])
    summary["dp_fit"] = {
        "steps": steps, "rows": rows_n, "batch": batch,
        "per_device_count": curve,
        "best_n_devices": best["n_devices"],
        "saturation_note": (
            f"forced host devices share {os.cpu_count()} physical core(s); "
            f"steps/s saturates at n_devices={best['n_devices']} "
            f"({best['speedup_vs_1dev']:.2f}x vs 1 device) — on real "
            f"multi-chip meshes the per-device batch shrinks P-fold instead"
        ),
    }

    # ---- gate 2 + compress wall-clock: sharded engine ------------------
    data = s3d.generate(s3d.S3DConfig(
        n_species=8 if not quick else 4, n_time=8, height=20, width=16,
        seed=5))["species"]
    cfg = PipelineConfig(ae_steps=40, corr_steps=20, conv_channels=(8, 16))
    pipe = GBATCPipeline(cfg, n_species=data.shape[0])
    pipe.fit(data)

    def compress_cold():
        # clear the tau-independent prepared cache so each timing pays the
        # full prepare+select path on its engine
        pipe._prepared.clear()
        pipe._last_prepared = None
        return pipe.compress(target_nrmse=1e-3).artifact.to_bytes()

    ref_bytes = compress_cold()
    t0 = time.perf_counter()
    compress_cold()
    t_default = time.perf_counter() - t0

    pipe.set_guarantee_engine(
        mesh_fit.ShardedGuaranteeEngine(mesh=mesh_fit.host_mesh()))
    got_bytes = compress_cold()
    identical = got_bytes == ref_bytes
    summary["gates"]["sharded_compress_byte_identical"] = identical
    assert identical, "sharded compress container drifted"
    t0 = time.perf_counter()
    compress_cold()
    t_sharded = time.perf_counter() - t0
    pipe.set_guarantee_engine(gae.default_engine())
    summary["compress"] = {
        "default_engine_s": t_default,
        "sharded_engine_s": t_sharded,
        "byte_identical": identical,
    }

    # ---- wire accounting: quantized vs fp32 exchange -------------------
    wire = {}
    for n_dev in (2, 8):
        rep = mesh_fit.dp_wire_report(p_ref, n_dev)
        wire[f"p{n_dev}"] = rep
    summary["wire"] = wire

    # ---- gate 3 + parts-mode latent packing ----------------------------
    lat_q = rng.integers(-40, 40, size=(960, 36)).astype(np.int32)
    shard_rows = 32
    full = fmt.pack_latent_stream(lat_q, shard_rows, parallel=False)
    bounds = [0, 250, 480, 730, 960]  # misaligned with the 32-row shards
    parts = [lat_q[a:b] for a, b in zip(bounds, bounds[1:])]
    streamed = fmt.pack_latent_stream(parts, shard_rows, parallel=False)
    parity = streamed == full
    summary["gates"]["pack_parts_bitwise"] = parity
    assert parity, "parts-mode latent packing drifted"
    t0 = time.perf_counter()
    fmt.pack_latent_stream(lat_q, shard_rows, parallel=False)
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    fmt.pack_latent_stream(parts, shard_rows, parallel=False)
    t_parts = time.perf_counter() - t0
    summary["pack_parts"] = {"full_ms": t_full * 1e3,
                             "parts_ms": t_parts * 1e3,
                             "rows": int(lat_q.shape[0])}
    return summary


# ---------------------------------------------------------------------------
# parent: spawn the forced-mesh child, persist the summary
# ---------------------------------------------------------------------------
def run(quick: bool = True) -> "dict | None":
    import jax

    if jax.default_backend() != "cpu":
        print(f"bench_mesh: skipped, the backend is "
              f"{jax.default_backend()!r} and the forced host devices need "
              f"the CPU")
        return None
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.bench_mesh", "--child"]
    if not quick:
        cmd.append("--full")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=_REPO, timeout=1800)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith(_SENTINEL):
            payload = json.loads(line[len(_SENTINEL):])
    assert out.returncode == 0 and payload is not None, (
        f"mesh benchmark child failed:\n{out.stdout}\n{out.stderr}"
    )
    assert all(payload["gates"].values()), f"gates failed: {payload['gates']}"

    with open(OUT_JSON, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    os.makedirs(os.path.dirname(OUT_CSV), exist_ok=True)
    with open(OUT_CSV, "w", encoding="utf-8") as f:
        f.write("n_devices,fit_s,steps_per_s,speedup_vs_1dev\n")
        for c in payload["dp_fit"]["per_device_count"]:
            f.write(f"{c['n_devices']},{c['fit_s']:.4f},"
                    f"{c['steps_per_s']:.2f},{c['speedup_vs_1dev']:.3f}\n")
    return payload


def main() -> None:
    if "--child" in sys.argv:
        summary = _child(quick="--full" not in sys.argv)
        print(_SENTINEL + json.dumps(summary))
        return
    summary = run(quick="--full" not in sys.argv)
    if summary is None:
        return
    best = max(summary["dp_fit"]["per_device_count"],
               key=lambda c: c["steps_per_s"])
    print(f"bench_mesh: gates {summary['gates']}; best DP fit "
          f"{best['steps_per_s']:.1f} steps/s at {best['n_devices']} "
          f"device(s) ({best['speedup_vs_1dev']:.2f}x vs 1); sharded "
          f"compress {summary['compress']['sharded_engine_s']:.2f}s vs "
          f"default {summary['compress']['default_engine_s']:.2f}s")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(_REPO, "src"))
    main()
