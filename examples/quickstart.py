"""Quickstart: compress a CFD snapshot series to *bytes on disk* with the
GBATC codec, decompress it standalone, and verify the error-bound guarantee —
the paper's pipeline end to end in ~1 minute on CPU.

  PYTHONPATH=src python examples/quickstart.py

The codec API is bytes in, bytes out: ``GBATCCodec.compress`` returns a
self-describing container blob, and ``repro.codec.decompress(blob)``
reconstructs the field from the blob alone — a fresh process with no fitted
model can decode the file this script writes. Subset consumers decode
randomly-accessed: ``decompress(blob, species=..., time_range=...)`` parses
only the header plus the requested streams and is bitwise equal to slicing
the full decode (step 4 below). Containers are written in the time-sharded
layout, so a time-window query entropy-decodes only the latent shards
covering the window — O(window), not O(T) (step 5 below) — and carry v4
integrity digests: every byte a decode reads is CRC-checked, corruption
raises a structured error, and ``on_error="salvage"`` decodes everything
that still verifies while quarantining the rest (step 6 below). The
encoder architecture itself is pluggable: containers are written in the
v5 family layout, whose meta stream names the encoder family, so a
block-attention codec rides the same wire format, guarantee engine, and
selective decode as the conv default (step 8 below). For fields that
outgrow one device, the whole fit/compress path shards over a
``("data",)`` mesh — DP trainer, species-sharded guarantee engine,
streamed sharded ingest — with byte-identical containers (step 9 below).

The 500-step fit below runs on the compiled mini-batch engine
(device-resident data, no per-step host sync), and *refitting* the same
codec is warm-start fast because the compiled training program is cached.
Standalone ``decompress`` runs the fused device-resident decode (one
dispatch for decoder+correction, batched guarantee replay). Speed is
measured on the chip only, by the cells under ``bench/``.

The invariants this pipeline rests on (decode reads only the blob, wire
errors carry stream/unit coordinates, hot programs never retrace, the
container layout matches its declarative schema) are machine-checked —
run the invariant checker before trusting a modified tree:

  PYTHONPATH=src python -m repro.analysis
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import codec
from repro.core import metrics
from repro.core.pipeline import PipelineConfig
from repro.data import s3d


def main():
    # 1. a small S3D-like dataset: 12 species, 16 frames, 80x80 grid
    #    (fixed overheads — decoder, PCA bases — amortize with data volume;
    #    the bench/ cells run the paper's width on the chip)
    ds = s3d.generate(s3d.S3DConfig(n_species=12, n_time=16, height=80,
                                    width=80, seed=0))
    data = ds["species"]
    print(f"data: {data.shape} ({data.nbytes / 1e6:.1f} MB), "
          f"species peak range {data.max(axis=(1,2,3)).min():.1e} .. "
          f"{data.max(axis=(1,2,3)).max():.1e}")

    # 2. fit the block AE + tensor-correction network once, then compress
    #    at the domain-expert bound (NRMSE 1e-3) straight to a file
    gbatc = codec.GBATCCodec(
        PipelineConfig(conv_channels=(16, 32), ae_steps=500, corr_steps=200)
    )
    gbatc.fit(data, verbose=True)
    blob, rep = gbatc.compress_report(target_nrmse=1e-3)

    fd, path = tempfile.mkstemp(suffix=".gbtc", prefix="quickstart_field_")
    with os.fdopen(fd, "wb") as f:
        f.write(blob)
    on_disk = os.path.getsize(path)
    print(f"\nwrote {path}: {on_disk} bytes "
          f"(compression ratio {data.nbytes / on_disk:.1f}x)")
    print(f"mean NRMSE        : {rep.mean_nrmse:.2e} (target 1e-3)")
    print(f"worst species     : {rep.per_species_nrmse.max():.2e}")
    print(f"bytes breakdown   : {rep.bytes_breakdown}")
    assert rep.bytes_breakdown["total"] == on_disk  # measured, not estimated

    # 3. decompress FROM THE FILE with no fitted state — everything the
    #    decoder needs (geometry, decoder params, correction net, guarantee
    #    streams, normalization) travels in the container
    with open(path, "rb") as f:
        decoded = codec.decompress(f.read())

    per = np.array([metrics.nrmse(data[s], decoded[s])
                    for s in range(data.shape[0])])
    assert per.max() <= 1e-3 * (1 + 1e-3), "bound violated!"
    assert np.array_equal(decoded, gbatc.pipeline.decompress(rep.artifact))
    print("\nguarantee verified: every species within the error bound; "
          "the on-disk container decodes bit-identically to the "
          "encoder-side reconstruction, with no fitted pipeline.")

    # 4. selective decode: analysts rarely want all S x T at once — pull ONE
    #    species (or a time window) straight from the on-disk blob. Only the
    #    header and that species' guarantee streams are parsed/entropy-
    #    decoded, and the result is bitwise equal to slicing a full decode.
    with open(path, "rb") as f:
        blob_on_disk = f.read()
    species_5 = codec.decompress(blob_on_disk, species=5)
    assert np.array_equal(species_5, decoded[5])
    pd = codec.PartialDecoder(blob_on_disk)  # reusable: head parsed once
    window = pd.decode(species=[2, 5], time_range=(4, 12))
    assert np.array_equal(window, decoded[[2, 5]][:, 4:12])
    touched = pd.bytes_parsed(species=[5])
    print(f"\nselective decode: species 5 alone touched {touched} of "
          f"{on_disk} container bytes ({touched / on_disk:.0%}) and came "
          "back bitwise equal to the full decode's slice.")

    # 5. sharded encode + window query: the container above is already the
    #    time-sharded v3 layout — the latent stream is partitioned into
    #    per-time-group Huffman chains under one shared codebook, so a
    #    window query entropy-decodes ONLY the shards covering it
    #    (O(window), where v1/v2 walk the whole sequential chain).
    #    `shard_tgroups` picks the granularity explicitly:
    coarse = codec.encode(rep.artifact, version=3, shard_tgroups=2)
    assert np.array_equal(codec.decompress(coarse), decoded)  # bit-equal
    lat_full = pd.latent_bytes_parsed()
    lat_win = pd.latent_bytes_parsed(time_range=(4, 8))
    print(f"\nwindow query: a 4-of-16-frame window entropy-decodes "
          f"{lat_win} of {lat_full} latent chain bytes "
          f"({lat_win / lat_full:.0%} ~ the window fraction). Fitting "
          "larger-than-memory series is the same API via time chunks: "
          "codec.GBATCCodec(cfg).fit_stream(s3d.S3DChunkLoader(...)).")

    # 6. integrity + salvage: the blob above is container v5 (the v4
    #    integrity layout + the meta family tag) — per-stream and
    #    per-random-access-unit CRC32 digests ride in an `integrity`
    #    stream (v1-v4 blobs still decode bit-identically). codec.write /
    #    codec.read are the atomic file path: tmp + fsync + rename on
    #    write, digest verification on read.
    codec.write(path, blob_on_disk)
    assert codec.read(path) == blob_on_disk  # verified round trip
    codec.verify_blob(blob_on_disk)  # every payload byte digest-checked
    # flip one bit in species 3's guarantee bytes: raise-mode decode
    # refuses with a structured error; salvage-mode quarantines species 3
    # and returns every other species bitwise clean, with a report
    from repro.core.container import ContainerFormatError
    from repro.testing.faults import FaultInjector, blob_regions

    regions = {r.label: r for r in blob_regions(blob_on_disk)}
    bad, _ = FaultInjector(seed=0).flip_bit(
        blob_on_disk, regions["guarantee:s3:coeff"]
    )
    try:
        codec.decompress(bad)
        raise SystemExit("corruption went undetected!")
    except ContainerFormatError as e:
        print(f"\ncorrupt blob refused: stream={e.stream} unit={e.unit}")
    field, report = codec.decompress(bad, on_error="salvage")
    assert report.quarantined == [3] and np.isnan(field[3]).all()
    healthy = [s for s in range(field.shape[0]) if s != 3]
    assert np.array_equal(field[healthy], decoded[healthy])  # bitwise clean
    print(f"salvage decode: quarantined species {report.quarantined}, "
          f"all {len(healthy)} healthy species bitwise equal to the clean "
          "decode.")

    # 7. decode service: many analysts, small queries — a scheduler thread
    #    coalesces concurrent (species, window) requests on one blob into
    #    shared fused dispatches and answers each from the multi-tier
    #    decode cache; every served slice is bitwise the serial
    #    PartialDecoder answer. cache_stats() surfaces the tiers.
    from repro.serve import DecodeService

    with DecodeService() as svc:
        svc.register("quickstart", blob_on_disk)
        futs = [svc.submit("quickstart", species=s % 12,
                           time_range=(4 * (s % 3), 4 * (s % 3) + 6))
                for s in range(9)]
        for s, fut in enumerate(futs):
            t0 = 4 * (s % 3)
            assert np.array_equal(fut.result(),
                                  decoded[s % 12, t0:t0 + 6])
    stats = codec.cache_stats()
    print(f"\ndecode service: {svc.stats.requests} mixed window queries in "
          f"{svc.stats.dispatches} fused dispatches "
          f"({svc.stats.coalesced} coalesced, {svc.stats.deduped} deduped); "
          "cache hit rates "
          + ", ".join(f"{tier}={stats[tier]['hit_rate']:.0%}"
                      for tier in ("head", "shard", "guarantee"))
          + ".")
    os.remove(path)

    # 8. a second encoder family, same container: `family="attention"`
    #    swaps the conv block AE for a patch-token block-attention
    #    autoencoder — the guarantee engine, wire format, selective
    #    decode, and integrity layer are untouched, so the bound holds
    #    the same way. The blob's meta stream carries the family tag;
    #    decompress dispatches on it with no fitted state, as always.
    attn = codec.GBATCCodec(PipelineConfig(
        family="attention", arch=(32, 2, 1, 64),  # d_model, heads, depth, mlp
        ae_steps=300, corr_steps=100,
    ))
    attn_blob, attn_rep = attn.compress_report(data, target_nrmse=1e-3)
    attn_decoded = codec.decompress(attn_blob)
    attn_per = np.array([metrics.nrmse(data[s], attn_decoded[s])
                         for s in range(data.shape[0])])
    assert attn_per.max() <= 1e-3 * (1 + 1e-3), "bound violated!"
    assert np.array_equal(codec.decompress(attn_blob, species=5),
                          attn_decoded[5])  # selective decode, same machinery
    print(f"\nattention family: CR "
          f"{data.nbytes / len(attn_blob):.1f}x at bound 1e-3 "
          f"(conv above: {data.nbytes / on_disk:.1f}x), worst species "
          f"NRMSE {attn_per.max():.2e} — same container, same guarantee.")

    # 9. mesh-sharded fit: the same pipeline over a ("data",) device mesh
    #    — DP trainer programs, a species/row-sharded guarantee engine,
    #    and streaming ingest that lands each chunk straight in a
    #    row-sharded device buffer, so each device holds only NB/P block
    #    rows and the full normalized field never exists on host. The
    #    demo prints the per-device ingest memory high-water against the
    #    single-device total and the sharded-compress NRMSE (container
    #    byte-identity with the single-device engine is asserted in
    #    tier-1, tests/test_mesh.py).
    #    On CPU the device count is locked at first jax init, so the demo
    #    runs in a child process with 8 forced host devices. On an
    #    accelerator this process already holds the devices and a child
    #    could not reach them, so the demo runs here, on the real ones.
    import jax

    if jax.default_backend() != "cpu":
        from repro.parallel import mesh_fit

        print(f"\nmesh-sharded fit ({len(jax.devices())} "
              f"{jax.default_backend()} device(s), in this process):")
        mesh_fit.demo()
        return
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    mesh_demo = subprocess.run(
        [sys.executable, "-m", "repro.parallel.mesh_fit"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert mesh_demo.returncode == 0, mesh_demo.stderr
    print("\nmesh-sharded fit (8 forced host devices, child process):")
    print(mesh_demo.stdout.strip())

if __name__ == "__main__":
    main()
