"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract) and
writes detailed CSVs under results/bench/.

  PYTHONPATH=src python -m benchmarks.run           # quick mode (default)
  PYTHONPATH=src python -m benchmarks.run --quick   # same, explicit
  PYTHONPATH=src python -m benchmarks.run --full    # 16x24x120x120 surrogate

Exits nonzero if any benchmark's internal assertion fails — in particular
the bit-identity gates (fused decompress vs the retained pre-change decode,
wire decode vs in-memory replay): a broken invariant can never hide behind
a pretty throughput number.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _timeit(fn, *args, repeat=3, **kw):
    fn(*args, **kw)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn(*args, **kw)
    return (time.perf_counter() - t0) / repeat * 1e6


def bench_kernels(rows):
    """Kernel micro-timings (CPU interpret mode — correctness path; TPU
    timings come only from a chip run, never from here)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 4, 512, 64), jnp.float32)
    f = jax.jit(lambda a: ref.flash_attention_ref(a, a, a))
    rows.append(("flash_attention_ref_512", _timeit(f, q), "oracle"))

    r = jax.random.normal(key, (1, 128, 2, 64), jnp.float32)
    w = jax.nn.sigmoid(r)
    u = jnp.zeros((2, 64))
    f = jax.jit(lambda a, b: ref.rwkv6_scan_ref(a, a, a, b, u)[0])
    rows.append(("rwkv6_scan_ref_T128", _timeit(f, r, w), "oracle"))

    x = jax.random.normal(key, (4096, 80), jnp.float32)
    basis = jnp.linalg.qr(jax.random.normal(key, (80, 80)))[0]
    f = jax.jit(ref.gbatc_project_ref)
    rows.append(("gbatc_project_4096x80", _timeit(f, x, basis), "oracle"))


def bench_gae(rows):
    """Table: guarantee post-process throughput + bytes at tau sweep."""
    from repro.core import gae

    rng = np.random.default_rng(0)
    x = rng.normal(size=(4096, 80)).astype(np.float32)
    xr = x + 0.05 * rng.normal(size=x.shape).astype(np.float32)
    for tau in (0.5, 0.2):
        us = _timeit(gae.guarantee, x, xr, tau, repeat=2)
        _, art = gae.guarantee(x, xr, tau)
        rows.append((f"gae_guarantee_tau{tau}", us,
                     f"bytes={art.total_bytes()}"))


def bench_guarantee_engine(rows):
    """Device-resident guarantee engine vs numpy oracle; emits the
    BENCH_guarantee.json perf trajectory for future PRs to regress
    against (harness CSV rows preserved alongside)."""
    from benchmarks import bench_guarantee

    summary = bench_guarantee.run()
    # steady-state = per-bound select cost with prepare amortized out,
    # matching the speedup_steady_state definition
    select_ms = [r["engine_select_ms"] for r in summary["sweep"]]
    rows.append((
        "guarantee_engine_steady_state",
        sum(select_ms) / len(select_ms) * 1e3,
        f"speedup={summary['speedup_steady_state']:.1f}x",
    ))
    rows.append((
        "guarantee_engine_sweep",
        summary["engine_sweep_ms"] * 1e3,
        f"speedup={summary['speedup_sweep']:.1f}x",
    ))


def bench_throughput_engine(rows, full=False):
    """Compiled trainer + fused decode vs the retained pre-change paths;
    emits BENCH_throughput.json. Bit-identity of the fused decode against
    the reference is asserted inside before any number is reported."""
    from benchmarks import bench_throughput

    summary = bench_throughput.run(quick=not full)
    rows.append((
        "throughput_fit_warm",
        summary["fit"]["engine_warm_s"] * 1e6,
        f"speedup={summary['fit']['speedup_warm']:.1f}x",
    ))
    rows.append((
        "throughput_decompress",
        summary["decompress"]["fused_ms"] * 1e3,
        f"MBps={summary['decompress']['fused_MBps']:.1f}"
        f" speedup={summary['decompress']['speedup']:.1f}x",
    ))


def bench_codec_wire(rows, full=False):
    """Container wire format: on-disk-verified ratios + codec throughput;
    emits BENCH_codec.json (harness CSV rows preserved alongside)."""
    from benchmarks import bench_codec

    summary = bench_codec.run(quick=not full)
    ser = [r["serialize_ms"] for r in summary["targets"]]
    deser = [r["deserialize_ms"] for r in summary["targets"]]
    crs = [r["on_disk_compression_ratio"] for r in summary["targets"]]
    rows.append((
        "codec_serialize",
        sum(ser) / len(ser) * 1e3,
        f"MBps={summary['serialize_MBps_mean']:.0f}",
    ))
    rows.append((
        "codec_deserialize",
        sum(deser) / len(deser) * 1e3,
        f"MBps={summary['deserialize_MBps_mean']:.0f}"
        " CR=" + "/".join(f"{c:.1f}" for c in crs),
    ))


def bench_partial_decode(rows, full=False):
    """Selective (per-species / time-window) decode vs full decode; emits
    BENCH_partial.json. Bitwise equivalence of every selective decode with
    the sliced full decode (and v1 container back-compat) is asserted
    inside before any number is reported."""
    from benchmarks import bench_partial

    summary = bench_partial.run(quick=not full)
    rows.append((
        "partial_decode_1_species",
        summary["decode_1_species_ms"] * 1e3,
        f"speedup={summary['speedup_1_species']:.1f}x"
        f" bytes={summary['bytes_parsed_fraction']:.0%}",
    ))
    rows.append((
        "partial_decode_1_species_window",
        summary["decode_1_species_window_ms"] * 1e3,
        f"speedup={summary['speedup_1_species_window']:.1f}x",
    ))


def bench_sharded_latents(rows, full=False):
    """Time-sharded (container v3) latent stream: O(window) latent entropy
    for window decodes + parallel shard encode; emits BENCH_shards.json.
    v2/v3 byte-identity and slice-equivalence gates are asserted inside
    before any number is reported."""
    from benchmarks import bench_shards

    summary = bench_shards.run(quick=not full)
    first = summary["per_shard_size"][0]
    rows.append((
        "shards_window_decode",
        first["window_decode_warm_ms"] * 1e3,
        f"latent_frac={summary['window_latent_fraction']:.0%}"
        f" v2_ms={summary['v2_window_decode_warm_ms']:.1f}",
    ))
    rows.append((
        "shards_parallel_encode",
        summary["shard_encode"]["parallel_ms"] * 1e3,
        f"MBps={summary['shard_encode']['parallel_MBps']:.0f}"
        f" speedup={summary['shard_encode']['parallel_speedup']:.1f}x",
    ))


def bench_integrity_v4(rows, full=False):
    """Integrity container (v4): digest overhead, verification budget,
    salvage throughput; emits BENCH_integrity.json. The clean-blob
    v4/v3 byte-identity gate, the verify-cost budget (< 3% of a warm
    full decode), salvage correctness, and the 100%-detection fault
    sweep are asserted inside before any number is reported."""
    from benchmarks import bench_integrity

    summary = bench_integrity.run(quick=not full)
    rows.append((
        "integrity_verify_blob",
        summary["verify_blob_ms"] * 1e3,
        f"frac_of_warm_decode="
        f"{summary['verify_fraction_of_warm_decode']:.1%}"
        f" digest_bytes={summary['digest_overhead_bytes']}",
    ))
    k1 = summary["salvage"][0]
    rows.append((
        "integrity_salvage_1_species",
        k1["salvage_ms"] * 1e3,
        f"MBps={k1['salvage_MBps']:.0f}"
        f" sweep_detect={summary['fault_sweep']['detection_rate']:.0%}",
    ))


def bench_serve_service(rows, full=False):
    """Continuous-batched decode service vs the naive serial
    PartialDecoder loop under synthetic traffic; emits BENCH_serve.json.
    Bitwise service-vs-serial equivalence for every distinct request and
    the >=2x-QPS-at-equal-p99 hot-mix gate are asserted inside before
    any number is reported."""
    from benchmarks import bench_serve

    summary = bench_serve.run(quick=not full)
    hot = summary["mixes"]["hot_zipf"]
    rows.append((
        "serve_hot_zipf_qps",
        1e6 / hot["service"]["qps"],
        f"speedup={hot['qps_ratio']:.1f}x"
        f" p99={hot['service']['p99_ms']:.0f}ms"
        f" shard_hits={hot['cache_hit_rates']['shard']:.0%}",
    ))
    churn = summary["mixes"]["churn"]
    rows.append((
        "serve_churn_qps",
        1e6 / churn["service"]["qps"],
        f"speedup={churn['qps_ratio']:.1f}x"
        f" p99={churn['service']['p99_ms']:.0f}ms",
    ))


def bench_encoder_families(rows, full=False):
    """Registered encoder families (conv AE, block attention) vs the SZ
    baseline: CR at 3 NRMSE bounds + fit/decode wall-clock; emits
    BENCH_families.json. The v1–v4 back-compat and conv-v5 ≡ v4 + tag
    byte-identity gates are asserted inside before any number is
    reported."""
    from benchmarks import bench_families

    summary = bench_families.run(quick=not full)
    by = {(r["method"], r["target_nrmse"]): r for r in summary["points"]}
    b0 = summary["bounds"][0]
    for fam in summary["families"]:
        r = by[(fam, b0)]
        crs = [by[(fam, b)]["compression_ratio"]
               for b in summary["bounds"]]
        rows.append((
            f"families_{fam}",
            r["decode_warm_ms"] * 1e3,
            f"fit_s={r['fit_s']:.1f}"
            " CR=" + "/".join(f"{c:.1f}" for c in crs),
        ))
    sz_crs = [by[("sz", b)]["compression_ratio"] for b in summary["bounds"]]
    rows.append((
        "families_sz_baseline",
        0.0,
        "CR=" + "/".join(f"{c:.1f}" for c in sz_crs),
    ))


def bench_mesh_scaling(rows, full=False):
    """Mesh-sharded fit/compress: DP fit steps/s at 1/2/4/8 forced host
    devices, sharded-vs-default compress wall-clock, quantized-vs-fp32
    wire bytes; emits BENCH_mesh.json. The P=1 fit bit-identity, the
    sharded-container byte-identity, and the parts-mode pack parity are
    asserted inside the child before any number is reported."""
    from benchmarks import bench_mesh

    summary = bench_mesh.run(quick=not full)
    if summary is None:
        rows.append(("mesh_dp_fit", 0.0, "skipped:forced-host-devices-need-cpu"))
        return
    best = max(summary["dp_fit"]["per_device_count"],
               key=lambda c: c["steps_per_s"])
    rows.append((
        "mesh_dp_fit",
        summary["dp_fit"]["per_device_count"][-1]["fit_s"] * 1e6,
        f"best={best['steps_per_s']:.0f}steps/s"
        f"@{best['n_devices']}dev cores={summary['cpu_cores']}",
    ))
    rows.append((
        "mesh_sharded_compress",
        summary["compress"]["sharded_engine_s"] * 1e6,
        f"default_s={summary['compress']['default_engine_s']:.3f}"
        f" byte_identical={summary['compress']['byte_identical']}",
    ))
    rows.append((
        "mesh_wire_quantized",
        0.0,
        f"ratio_p2={summary['wire']['p2']['wire_ratio']:.2f}"
        f" ratio_p8={summary['wire']['p8']['wire_ratio']:.2f}",
    ))


def bench_analysis_gate(rows):
    """Invariant checker (lint + wire schema + jaxpr audit) as a gate:
    zero non-baselined findings, or the whole run turns nonzero; emits
    BENCH_analysis.json with per-rule counts and tier wall-clocks."""
    from benchmarks import bench_analysis

    summary = bench_analysis.run()
    n_rules = sum(summary["rule_counts"].values())
    rows.append((
        "analysis_gate",
        (summary["lint_wall_clock_s"] + summary["schema_wall_clock_s"]
         + summary["audit_wall_clock_s"]) * 1e6,
        f"findings={n_rules} new={summary['new_findings']}"
        f" programs={len(summary['audited_programs'])}",
    ))


def bench_sz(rows):
    from repro.core import sz
    from repro.data import s3d

    ds = s3d.generate(s3d.S3DConfig(n_species=1, n_time=16, height=80,
                                    width=80, seed=0))
    field = ds["species"][0]
    for eb_rel in (1e-3, 1e-4):
        eb = eb_rel * float(field.max() - field.min())
        us = _timeit(sz.compress, field, eb, repeat=2)
        art = sz.compress(field, eb)
        rows.append((f"sz_compress_eb{eb_rel:g}", us,
                     f"CR={field.nbytes / art.payload_bytes():.1f}"))


def main() -> None:
    from repro import compile_cache

    compile_cache.configure(os.path.join(os.path.dirname(__file__), ".."))
    # --quick (the default) runs the small surrogates; --full the
    # 16x24x120x120 surrogate
    full = "--full" in sys.argv and "--quick" not in sys.argv
    rows: list[tuple] = []
    failures: list[str] = []

    def guarded(name, fn, *args, **kw):
        """Run one benchmark; a failed bit-identity (or any other)
        assertion is recorded and turns the whole run nonzero instead of
        silently dropping the benchmark."""
        try:
            fn(*args, **kw)
        except AssertionError as e:
            failures.append(f"{name}: {e}")
            rows.append((name, 0.0, f"ASSERTION FAILED: {e}"))

    guarded("bench_kernels", bench_kernels, rows)
    guarded("bench_gae", bench_gae, rows)
    guarded("guarantee_engine", bench_guarantee_engine, rows)
    guarded("throughput_engine", bench_throughput_engine, rows, full=full)
    guarded("codec_wire", bench_codec_wire, rows, full=full)
    guarded("partial_decode", bench_partial_decode, rows, full=full)
    guarded("sharded_latents", bench_sharded_latents, rows, full=full)
    guarded("integrity", bench_integrity_v4, rows, full=full)
    guarded("serve", bench_serve_service, rows, full=full)
    guarded("families", bench_encoder_families, rows, full=full)
    guarded("mesh", bench_mesh_scaling, rows, full=full)
    guarded("analysis", bench_analysis_gate, rows)
    guarded("bench_sz", bench_sz, rows)

    # paper-figure benchmarks (CR vs NRMSE + QoI + gradcomp)
    from benchmarks import bench_compression, bench_gradcomp, bench_qoi

    def timed(name, fn):
        t0 = time.time()
        out = fn(quick=not full)
        rows.append((f"{name}_total", (time.time() - t0) * 1e6,
                     f"rows={len(out)}"))

    guarded("bench_compression", timed, "bench_compression",
            bench_compression.run)
    guarded("bench_qoi", timed, "bench_qoi", bench_qoi.run)
    guarded("bench_gradcomp", timed, "bench_gradcomp", bench_gradcomp.run)

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failures:
        print("\nFAILED ASSERTIONS:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
