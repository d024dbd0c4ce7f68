"""Time-sharded container (v3) suite: segmented latents + streaming fit.

The acceptance contract for the sharded subsystem:

* a full v3 decode is **bitwise equal** to the v2 decode of the same fit,
  for every shard size — including a ragged last shard and shard sizes
  covering the whole series;
* every (species, time-window) slice of a v3 blob is bitwise equal to
  slicing the full decode, and a window's latent entropy work touches
  only the shards covering it (O(window), not O(T));
* corrupting one shard's latent chain raises
  :class:`ContainerFormatError` naming the shard, without poisoning
  sibling shards (windows over healthy shards still decode);
* the module-level decompress head cache serves repeat blobs without any
  cross-blob leakage and stays within its eviction bound;
* the streaming fit path (chunk loader -> ``fit_stream``) produces a
  container bit-identical to fitting on the fully materialized field.
"""

import numpy as np
import pytest

from repro import codec
from repro.codec import format as codec_format
from repro.codec import runtime as codec_runtime
from repro.core import entropy
from repro.core.container import (
    ContainerFormatError,
    ContainerReader,
    ContainerWriter,
)
from repro.core.pipeline import PipelineConfig
from repro.data import s3d


@pytest.fixture(scope="module")
def small_data():
    cfg = s3d.S3DConfig(n_species=6, n_time=16, height=40, width=32, seed=21)
    return s3d.generate(cfg)["species"]


@pytest.fixture(scope="module")
def fitted_codec(small_data):
    cfg = PipelineConfig(ae_steps=60, corr_steps=30, conv_channels=(16, 32))
    return codec.GBATCCodec(cfg).fit(small_data)


@pytest.fixture(scope="module")
def blob_and_report(fitted_codec):
    return fitted_codec.compress_report(target_nrmse=1e-3)


@pytest.fixture(scope="module")
def blob(blob_and_report):
    return blob_and_report[0]


@pytest.fixture(scope="module")
def full(blob):
    return codec.decompress(blob)


def _truncate_shard(latent_payload: bytes, k: int, keep: int) -> bytes:
    """Rebuild a v3 latent stream with shard ``k``'s chain cut to ``keep``
    bytes, directory record updated to match — the framing stays valid,
    only that one shard's chain is corrupt."""
    ldir = codec.LatentShardDirectory(latent_payload)
    payloads = [ldir.shard_payload(i) for i in range(ldir.n_shards)]
    payloads[k] = payloads[k][:keep]
    head_end = codec_format._LAT3_HEAD.size + codec_format._LAT3_CB.size \
        + 9 * len(ldir.symbols)
    parts = [latent_payload[:head_end]]
    parts.extend(codec_format._LAT3_LEN.pack(len(p)) for p in payloads)
    return b"".join(parts + payloads)


def _with_latent(blob: bytes, latent_payload: bytes) -> bytes:
    """Re-emit the container with a replacement latent stream, downgraded
    to v3 (integrity stream dropped): this suite pins the *structural*
    shard-corruption detection pre-digest containers rely on — on a v4
    blob the digests would (correctly) catch the same mutations first,
    which test_integrity.py covers."""
    r = ContainerReader(blob)
    w = ContainerWriter(version=min(r.version, 3))
    for name in r.names:
        if name == "integrity":
            continue
        payload = latent_payload if name == "latent" else r[name]
        if name == "meta" and r.version >= 5:
            payload = payload[1:]  # drop the family tag for v3
        w.add(name, payload)
    return w.to_bytes()


class TestShardedEncode:
    def test_default_version_is_sharded(self, blob):
        # v5 = the sharded v3 layout + integrity + a family tag
        r = ContainerReader(blob)
        assert r.version == 5
        assert "integrity" in r.names
        codec_format.LatentShardDirectory(r["latent"])  # sharded latents

    @pytest.mark.parametrize("tg", [1, 2, 3, 4, 99])
    def test_every_shard_size_decodes_bit_identical(
        self, blob_and_report, full, tg
    ):
        """Property sweep over shard sizes — 3 gives a ragged last shard
        (4 time groups), 4 is exactly one shard per group boundary, 99
        clamps to a single shard (shard_size >= T)."""
        _, rep = blob_and_report
        b = codec.encode(rep.artifact, version=3, shard_tgroups=tg)
        assert codec.decompress(b).tobytes() == full.tobytes()
        ldir = codec.LatentShardDirectory(ContainerReader(b)["latent"])
        nb = rep.artifact.latent_q.shape[0]
        assert ldir.n_shards == -(-nb // ldir.shard_rows)

    def test_v3_equals_v2_byte_for_byte(self, blob_and_report, full):
        _, rep = blob_and_report
        blob_v2 = codec.encode(rep.artifact, version=2)
        assert codec.decompress(blob_v2).tobytes() == full.tobytes()

    @pytest.mark.parametrize("n_shards", [5, 64])
    def test_parallel_and_serial_pack_identical(self, blob_and_report,
                                                n_shards):
        """Shard chains are pure functions of their rows — threading the
        pack must not change a byte, with fewer shards than the pool's
        workers or many more."""
        _, rep = blob_and_report
        lat = rep.artifact.latent_q
        rows = max(1, lat.shape[0] // n_shards)
        assert -(-lat.shape[0] // rows) >= n_shards
        a = codec.pack_latent_stream(lat, rows, parallel=True)
        b = codec.pack_latent_stream(lat, rows, parallel=False)
        assert a == b

    def test_default_shard_size_and_distinct_layouts(self, blob_and_report):
        """The writer's default shard size is DEFAULT_SHARD_TGROUPS, and
        each shard size writes its own bytes."""
        _, rep = blob_and_report
        blobs = {tg: codec.encode(rep.artifact, version=3, shard_tgroups=tg)
                 for tg in (1, 2, 4)}
        assert codec.encode(rep.artifact, version=3) == \
            blobs[codec.DEFAULT_SHARD_TGROUPS]
        assert len(set(blobs.values())) == len(blobs)

    def test_shard_tgroups_validation(self, blob_and_report):
        _, rep = blob_and_report
        with pytest.raises(ValueError, match="shard_tgroups"):
            codec.encode(rep.artifact, version=2, shard_tgroups=2)
        with pytest.raises(ValueError, match=">= 1"):
            codec.encode(rep.artifact, version=3, shard_tgroups=0)


class TestShardedSlices:
    def test_random_species_windows_bitwise(self, blob_and_report, full):
        """Every (species, window) slice of every shard size equals the
        sliced full decode bitwise."""
        _, rep = blob_and_report
        rng = np.random.default_rng(0)
        s, t = full.shape[:2]
        for tg in (1, 3, 99):
            b = codec.encode(rep.artifact, version=3, shard_tgroups=tg)
            pd = codec.PartialDecoder(b)
            for _ in range(5):
                k = int(rng.integers(1, s + 1))
                sel = sorted(rng.choice(s, size=k, replace=False).tolist())
                t0 = int(rng.integers(0, t))
                t1 = int(rng.integers(t0 + 1, t + 1))
                out = pd.decode(species=sel, time_range=(t0, t1))
                assert out.tobytes() == \
                    np.ascontiguousarray(full[sel][:, t0:t1]).tobytes()

    def test_window_latent_bytes_scale_with_window(self, blob, full):
        """The O(window) claim: latent chain bytes entropy-decoded grow
        with the window and a small window touches a commensurately small
        fraction — not O(T)."""
        pd = codec.PartialDecoder(blob)
        t = full.shape[1]
        total = pd.latent_bytes_parsed()
        b4 = pd.latent_bytes_parsed((4, 8))
        b8 = pd.latent_bytes_parsed((4, 12))
        assert b4 < b8 < total
        # 4 of 16 frames, plus slack for per-shard byte padding
        assert b4 <= total * (4 / t + 0.2)
        # windows from frame 0 grow monotonically up to the whole chain
        sizes = [pd.latent_bytes_parsed((0, f)) for f in (4, 8, t)]
        assert all(a <= b for a, b in zip(sizes, sizes[1:]))
        assert sizes[0] <= total * (4 / t + 0.2)
        # bytes_parsed with a window shrinks below the full-blob identity
        assert pd.bytes_parsed(time_range=(4, 8)) < pd.bytes_parsed()
        assert pd.bytes_parsed() == len(blob)
        with pytest.raises(ValueError, match="time_range"):
            pd.latent_bytes_parsed((3, 2))
        assert pd.latent_bytes_parsed((0, t)) == total

    def test_single_chain_versions_report_full_latent(self, blob_and_report):
        """v1/v2 carry one sequential chain: a window still walks it all,
        and the accounting must say so rather than pretend O(window)."""
        _, rep = blob_and_report
        for version in (1, 2):
            b = codec.encode(rep.artifact, version=version)
            pd = codec.PartialDecoder(b)
            assert pd.latent_bytes_parsed((4, 8)) == pd.latent_bytes_parsed()


class TestShardCorruption:
    @pytest.fixture()
    def bad_blob(self, blob):
        """v3 blob with shard 1's latent chain truncated (directory fixed
        up, so the stream framing itself stays valid)."""
        r = ContainerReader(blob)
        return _with_latent(blob, _truncate_shard(r["latent"], k=1, keep=3))

    def test_full_decode_raises_named_shard(self, bad_blob):
        with pytest.raises(ContainerFormatError, match="latent shard 1") \
                as ei:
            codec.decompress(bad_blob)
        # structured: the error names the stream and the shard unit
        assert (ei.value.stream, ei.value.unit) == ("latent", 1)

    def test_window_over_bad_shard_raises_named(self, bad_blob, full):
        pd = codec.PartialDecoder(bad_blob)
        geom_bt = 4  # paper geometry; shard 1 covers frames [4, 8)
        with pytest.raises(ContainerFormatError, match="latent shard 1"):
            pd.decode(time_range=(geom_bt, 2 * geom_bt))

    def test_healthy_shards_survive(self, bad_blob, full):
        """Windows over sibling shards decode bitwise from the same blob —
        the bad shard poisons only itself, before and after it raised."""
        pd = codec.PartialDecoder(bad_blob)
        np.testing.assert_array_equal(
            pd.decode(time_range=(0, 4)), full[:, 0:4]
        )
        with pytest.raises(ContainerFormatError, match="latent shard 1"):
            pd.decode(time_range=(2, 6))
        np.testing.assert_array_equal(
            pd.decode(species=[2], time_range=(8, 16)), full[[2]][:, 8:16]
        )

    def test_directory_payload_mismatch_raises(self, blob):
        """A shard table that disagrees with the stream's byte count must
        fail at parse, not mis-slice chains."""
        r = ContainerReader(blob)
        bad = _with_latent(blob, r["latent"][:-1])
        with pytest.raises(ContainerFormatError):
            codec.decompress(bad)

    def test_shard_count_mismatch_raises(self, blob):
        """n_shards inconsistent with n_rows/shard_rows must raise."""
        r = ContainerReader(blob)
        payload = bytearray(r["latent"])
        payload[4:8] = (1).to_bytes(4, "little")  # n_shards := 1
        with pytest.raises(ContainerFormatError):
            codec.decompress(_with_latent(blob, bytes(payload)))


class TestHeadCache:
    def test_no_cross_blob_leakage(self, fitted_codec, blob, full):
        """Interleaved queries against byte-different blobs must never
        serve each other's cached state."""
        blob_b, _ = fitted_codec.compress_report(target_nrmse=5e-3)
        assert blob_b != blob
        full_b = codec.decompress(blob_b)
        for _ in range(3):
            np.testing.assert_array_equal(
                codec.decompress(blob, species=1, time_range=(4, 8)),
                full[1, 4:8],
            )
            np.testing.assert_array_equal(
                codec.decompress(blob_b, species=1, time_range=(4, 8)),
                full_b[1, 4:8],
            )

    def test_eviction_bound(self, fitted_codec, blob, full):
        """The head memo is a bounded LRU: flooding it with distinct blobs
        evicts old entries instead of growing without bound, and evicted
        blobs still decode correctly (just cold)."""
        codec.clear_decode_cache()
        targets = (1e-3, 2e-3, 3e-3, 5e-3, 8e-3)
        blobs = [fitted_codec.compress_report(target_nrmse=tn)[0]
                 for tn in targets]
        assert len(set(blobs)) == len(blobs)
        for b in blobs:
            codec.decompress(b, species=0, time_range=(0, 4))
        assert len(codec_runtime._HEADS) <= codec_runtime._HEADS_MAX
        # the first (evicted) blob still decodes, bitwise
        np.testing.assert_array_equal(
            codec.decompress(blobs[0]), codec.decompress(blobs[0])
        )

    def test_repeat_queries_hit_cache(self, blob):
        codec.clear_decode_cache()
        pd1 = codec.PartialDecoder(blob)
        pd2 = codec.PartialDecoder(blob)
        assert pd1._head is pd2._head  # one parse serves both
        assert len(codec_runtime._HEADS) == 1


class TestSegmentedEntropyPrimitives:
    def test_payload_matches_inline_encode(self):
        rng = np.random.default_rng(3)
        vals = (rng.integers(-30, 30, size=4000) ** 3 // 400).astype(np.int64)
        blob = entropy.huffman_encode(vals)
        n, symbols, lengths, off = entropy._parse_header(blob)
        sym, lens = entropy.huffman_codebook(vals)
        np.testing.assert_array_equal(symbols, sym)
        np.testing.assert_array_equal(lengths, lens)
        assert blob[off:] == entropy.huffman_payload(vals, sym, lens)

    def test_segmented_round_trip_ragged(self):
        rng = np.random.default_rng(4)
        vals = rng.integers(0, 9, size=1111).astype(np.int64)
        sym, lens = entropy.huffman_codebook(vals)
        cuts = [0, 1, 128, 129, 1000, 1111]
        segs = [vals[a:b] for a, b in zip(cuts, cuts[1:])]
        payloads = [entropy.huffman_payload(s, sym, lens) for s in segs]
        outs = entropy.huffman_decode_payloads(
            payloads, [len(s) for s in segs], sym, lens
        )
        for s, o in zip(segs, outs):
            np.testing.assert_array_equal(s, o)

    def test_value_outside_codebook_raises(self):
        sym, lens = entropy.huffman_codebook(np.array([1, 2, 3]))
        with pytest.raises(ValueError, match="codebook"):
            entropy.huffman_payload(np.array([4]), sym, lens)

    def test_truncated_and_padded_payloads_raise(self):
        vals = np.arange(512, dtype=np.int64) % 7
        sym, lens = entropy.huffman_codebook(vals)
        payload = entropy.huffman_payload(vals, sym, lens)
        with pytest.raises(ValueError):
            entropy.huffman_decode_payload(payload[:-2], len(vals), sym, lens)
        with pytest.raises(ValueError):
            entropy.huffman_decode_payload(
                payload + b"\x00\x00", len(vals), sym, lens
            )
        with pytest.raises(ValueError):  # empty chain carrying bytes
            entropy.huffman_decode_payload(payload, 0, sym, lens)


class TestStreamingFit:
    def test_chunk_loader_bitwise_matches_generate(self):
        cfg = s3d.S3DConfig(n_species=5, n_time=12, height=40, width=32,
                            seed=13)
        full = s3d.generate(cfg)["species"]
        win = s3d.generate_species_window(cfg, 3, 9)
        assert win.tobytes() == np.ascontiguousarray(full[:, 3:9]).tobytes()
        loader = s3d.S3DChunkLoader(cfg, chunk_frames=5)  # ragged tail
        cat = np.concatenate(list(loader.chunks()), axis=1)
        assert cat.tobytes() == full.tobytes()
        assert loader.shape == full.shape
        assert loader.n_chunks == 3
        # re-iterable (fit_stream runs two passes)
        assert sum(c.shape[1] for c in loader.chunks()) == cfg.n_time

    def test_fit_stream_blob_bit_identical_to_full_fit(self):
        """The whole point of the streaming path: consuming time chunks
        must yield the same trained codec — container bytes and all — as
        materializing the field."""
        scfg = s3d.S3DConfig(n_species=4, n_time=8, height=40, width=32,
                             seed=17)
        data = s3d.generate(scfg)["species"]
        pcfg = PipelineConfig(ae_steps=25, corr_steps=12,
                              conv_channels=(16, 32))
        blob_full, rep_full = codec.GBATCCodec(pcfg).fit(
            data).compress_report(target_nrmse=2e-3)
        loader = s3d.S3DChunkLoader(scfg, chunk_frames=4)
        c = codec.GBATCCodec(pcfg).fit_stream(loader)
        blob_stream, rep_stream = c.compress_report(target_nrmse=2e-3)
        assert blob_stream == blob_full
        # normalized-vector NRMSE equals the data-space metric up to
        # float rounding (range is exactly 1 under min/max normalization)
        np.testing.assert_allclose(
            rep_stream.per_species_nrmse, rep_full.per_species_nrmse,
            rtol=1e-4,
        )
        assert rep_stream.compression_ratio == rep_full.compression_ratio

    def test_fit_stream_rejects_misaligned_chunks(self):
        scfg = s3d.S3DConfig(n_species=4, n_time=8, height=40, width=32,
                             seed=17)
        pcfg = PipelineConfig(ae_steps=5, corr_steps=5,
                              conv_channels=(16, 32))
        with pytest.raises(ValueError, match="block depth"):
            codec.GBATCCodec(pcfg).fit_stream(
                s3d.S3DChunkLoader(scfg, chunk_frames=3)
            )
