"""Codec / container-format tests: the wire contract.

The acceptance bar for the serialization layer:

* ``decompress(compress(...))`` matches the in-memory reconstruction
  **bitwise** (not just within tolerance);
* a container decodes through the standalone module path — no fitted
  pipeline, no codec instance state;
* ``len(blob)`` equals the reported byte total exactly (accounting is a
  view over the stream table, not an estimate);
* corrupted / truncated / wrong-version blobs raise
  :class:`ContainerFormatError` with a useful message.
"""

import numpy as np
import pytest

from repro import codec
from repro.core import container as container_format
from repro.core import gae, metrics
from repro.core.container import (
    ContainerFormatError,
    ContainerReader,
    ContainerWriter,
)
from repro.core.pipeline import CompressedArtifact, GBATCPipeline, PipelineConfig
from repro.data import s3d


@pytest.fixture(scope="module")
def small_data():
    cfg = s3d.S3DConfig(n_species=8, n_time=8, height=40, width=32, seed=3)
    return s3d.generate(cfg)["species"]


@pytest.fixture(scope="module")
def fitted_codec(small_data):
    cfg = PipelineConfig(ae_steps=60, corr_steps=30, conv_channels=(16, 32))
    return codec.GBATCCodec(cfg).fit(small_data)


@pytest.fixture(scope="module")
def blob_and_report(fitted_codec):
    return fitted_codec.compress_report(target_nrmse=1e-3)


def _truncate_species_coeff(payload: bytes, sidx: int, keep: int) -> bytes:
    """Rebuild a combined (v2) guarantee stream with species ``sidx``'s
    coeff payload cut to ``keep`` bytes, directory record updated to match
    — the framing stays valid, only that one stream is corrupt."""
    head, rec = codec._GDIR_HEAD, codec._GDIR_REC
    (s,) = head.unpack_from(payload, 0)
    recs = [
        list(r)
        for r in rec.iter_unpack(payload[head.size : head.size + s * rec.size])
    ]
    off = head.size + s * rec.size
    parts: dict[int, list[bytes]] = {0: [], 1: [], 2: []}
    for kind in range(3):
        for i in range(s):
            ln = recs[i][4 + kind]
            parts[kind].append(payload[off : off + ln])
            off += ln
    parts[0][sidx] = parts[0][sidx][:keep]
    recs[sidx][4] = keep
    return b"".join(
        [head.pack(s)] + [rec.pack(*r) for r in recs]
        + parts[0] + parts[1] + parts[2]
    )


class TestContainer:
    def test_round_trip(self):
        w = ContainerWriter()
        w.add("alpha", b"12345")
        w.add("beta", b"")
        w.add("gamma", bytes(range(256)))
        blob = w.to_bytes()
        r = ContainerReader(blob)
        assert r.names == ["alpha", "beta", "gamma"]
        assert r["alpha"] == b"12345"
        assert r["beta"] == b""
        assert r["gamma"] == bytes(range(256))
        assert r.total_bytes == len(blob)
        assert r.header_bytes + sum(r.stream_sizes().values()) == len(blob)

    def test_duplicate_stream_rejected(self):
        w = ContainerWriter()
        w.add("x", b"1")
        with pytest.raises(ValueError):
            w.add("x", b"2")

    def test_missing_stream_raises(self):
        w = ContainerWriter()
        w.add("x", b"1")
        with pytest.raises(ContainerFormatError):
            ContainerReader(w.to_bytes())["y"]

    @pytest.mark.parametrize("cut", [0, 3, 7, 12, -1])
    def test_truncation_raises(self, cut):
        w = ContainerWriter()
        w.add("stream", b"payload-bytes")
        blob = w.to_bytes()
        with pytest.raises(ContainerFormatError):
            ContainerReader(blob[:cut] if cut >= 0 else blob[: len(blob) - 1])

    def test_trailing_garbage_raises(self):
        w = ContainerWriter()
        w.add("stream", b"payload")
        with pytest.raises(ContainerFormatError, match="trailing"):
            ContainerReader(w.to_bytes() + b"x")

    def test_bad_magic_raises(self):
        w = ContainerWriter()
        w.add("stream", b"payload")
        blob = w.to_bytes()
        with pytest.raises(ContainerFormatError, match="magic"):
            ContainerReader(b"NOPE" + blob[4:])

    def test_unknown_version_raises(self):
        w = ContainerWriter(version=73)
        w.add("stream", b"payload")
        with pytest.raises(ContainerFormatError, match="version"):
            ContainerReader(w.to_bytes())


class TestCodecRoundTrip:
    def test_bitwise_matches_in_memory_reconstruction(
        self, fitted_codec, blob_and_report
    ):
        blob, rep = blob_and_report
        dec = codec.decompress(blob)
        inmem = fitted_codec.pipeline.decompress(rep.artifact)
        np.testing.assert_array_equal(dec, inmem)
        assert dec.dtype == np.float32

    def test_standalone_decode_meets_bound(self, small_data, blob_and_report):
        blob, _ = blob_and_report
        dec = codec.decompress(blob)
        per = np.array(
            [metrics.nrmse(small_data[s], dec[s]) for s in range(small_data.shape[0])]
        )
        assert per.max() <= 1e-3 * (1 + 1e-3)

    def test_fresh_codec_instance_decodes(self, blob_and_report):
        """Decoding needs zero fitted state — a brand-new codec (and the
        module-level function) must reconstruct the same field."""
        blob, _ = blob_and_report
        fresh = codec.GBATCCodec()
        np.testing.assert_array_equal(fresh.decompress(blob),
                                      codec.decompress(blob))

    def test_artifact_fields_survive_wire(self, blob_and_report):
        blob, rep = blob_and_report
        art = CompressedArtifact.from_bytes(blob)
        src = rep.artifact
        np.testing.assert_array_equal(art.latent_q, src.latent_q)
        assert art.latent_bin == src.latent_bin
        np.testing.assert_array_equal(art.norm_min, src.norm_min)
        np.testing.assert_array_equal(art.norm_range, src.norm_range)
        assert art.shape == src.shape
        assert art.cfg.geometry == src.cfg.geometry
        assert art.cfg.latent == src.cfg.latent
        assert tuple(art.cfg.conv_channels) == tuple(src.cfg.conv_channels)
        for g_dec, g_src in zip(art.species_guarantees, src.species_guarantees):
            np.testing.assert_array_equal(g_dec.coeff_q, g_src.coeff_q)
            np.testing.assert_array_equal(g_dec.index_offsets, g_src.index_offsets)
            np.testing.assert_array_equal(g_dec.index_flat, g_src.index_flat)
            np.testing.assert_array_equal(g_dec.basis, g_src.basis)
            assert g_dec.tau == g_src.tau
            assert g_dec.coeff_bin == g_src.coeff_bin
        # decoder params round-trip bitwise (fp32 storage is lossless)
        dec_keys = sorted(k for k in src.ae_params if k.startswith("dec"))
        assert sorted(art.ae_params) == dec_keys
        for k in dec_keys:
            for leaf_name in sorted(art.ae_params[k]):
                np.testing.assert_array_equal(
                    np.asarray(art.ae_params[k][leaf_name]),
                    np.asarray(src.ae_params[k][leaf_name]),
                )

    def test_target_sweep_round_trips(self, small_data, fitted_codec):
        """Property-style sweep: every error bound's container must decode
        standalone to a bound-satisfying field, bitwise-matching the
        in-memory replay."""
        for target in (5e-3, 1e-3, 3e-4):
            blob, rep = fitted_codec.compress_report(target_nrmse=target)
            dec = codec.decompress(blob)
            np.testing.assert_array_equal(
                dec, fitted_codec.pipeline.decompress(rep.artifact)
            )
            per = np.array(
                [metrics.nrmse(small_data[s], dec[s])
                 for s in range(small_data.shape[0])]
            )
            assert per.max() <= target * (1 + 1e-3)
            assert len(blob) == rep.bytes_breakdown["total"]

    def test_version_back_compat(self, blob_and_report):
        """v1 (per-species nested guarantee), v2 (single-chain latent),
        v3 (sharded, no digests), and v4 (integrity) containers must
        decode bit-identically to the default v5 family layout through
        the same entry point; all five versions stay writable so
        round-trips cover each, and a conv-family v5 blob's payload
        streams are byte-identical to the v4 encoding of the same fit
        apart from the one-byte family tag (and the digests it shifts)."""
        blob, rep = blob_and_report
        blob_v1 = codec.encode(rep.artifact, version=1)
        blob_v2 = codec.encode(rep.artifact, version=2)
        blob_v3 = codec.encode(rep.artifact, version=3)
        blob_v4 = codec.encode(rep.artifact, version=4)
        assert ContainerReader(blob_v1).version == 1
        assert ContainerReader(blob_v2).version == 2
        assert ContainerReader(blob_v3).version == 3
        assert ContainerReader(blob_v4).version == 4
        r5, r4 = ContainerReader(blob), ContainerReader(blob_v4)
        assert r5.version == 5
        # conv v5 meta = family tag (conv=1) + the exact v4 meta bytes;
        # every other payload stream except the digests is byte-identical
        assert r5["meta"][:1] == b"\x01"
        assert r5["meta"][1:] == r4["meta"]
        for name in r4.names:
            if name not in ("meta", "integrity"):
                assert r5[name] == r4[name]
        assert len(blob_v2) < len(blob_v1)  # combined layout shaves framing
        full = codec.decompress(blob)
        # v5 decode == v4 == v3 == v2 decode BYTE for byte on one fit
        assert codec.decompress(blob_v4).tobytes() == full.tobytes()
        assert codec.decompress(blob_v3).tobytes() == full.tobytes()
        assert codec.decompress(blob_v2).tobytes() == full.tobytes()
        np.testing.assert_array_equal(codec.decompress(blob_v1), full)
        bb1 = codec.stream_breakdown(blob_v1)
        bb2 = codec.stream_breakdown(blob_v2)
        bb3 = codec.stream_breakdown(blob_v3)
        bb4 = codec.stream_breakdown(blob_v4)
        bb5 = codec.stream_breakdown(blob)
        for key in ("decoder", "correction", "coeff", "index", "basis"):
            assert bb1[key] == bb2[key] == bb3[key] == bb4[key] == bb5[key]
        # v1/v2 count the latent stream whole (inline Huffman header); v3+
        # buckets only the shard chain payloads as latent, the shared
        # codebook + shard table land in meta — parts still sum exactly
        assert bb1["latent"] == bb2["latent"] >= bb3["latent"]
        assert bb3["latent"] == bb4["latent"] == bb5["latent"]
        # the v4 digests are the only delta vs v3 and land in meta; the
        # v5 family tag adds exactly one more byte there
        assert bb4["meta"] > bb3["meta"]
        assert bb5["meta"] == bb4["meta"] + 1
        assert bb1["total"] == len(blob_v1)
        assert bb2["total"] == len(blob_v2)
        assert bb3["total"] == len(blob_v3)
        assert bb4["total"] == len(blob_v4)
        assert bb5["total"] == len(blob)

    def test_compress_with_data_fits_first(self, small_data):
        c = codec.GBATCCodec(
            PipelineConfig(ae_steps=40, corr_steps=20, conv_channels=(16, 32))
        )
        assert not c.fitted
        blob = c.compress(small_data, target_nrmse=2e-3)
        assert c.fitted
        dec = codec.decompress(blob)
        assert dec.shape == small_data.shape

    def test_unfitted_compress_raises(self):
        with pytest.raises(RuntimeError):
            codec.GBATCCodec().compress(target_nrmse=1e-3)

    def test_non_4d_data_raises_clearly(self, fitted_codec):
        """compress(1e-3) — a float where data goes — must fail with a
        clear ValueError, not an AttributeError deep inside fit."""
        with pytest.raises(ValueError, match="expected \\(S, T, H, W\\)"):
            fitted_codec.compress(1e-3)

    def test_unrepresentable_conv_channels_raise_at_encode(
        self, blob_and_report
    ):
        import dataclasses

        _, rep = blob_and_report
        bad_cfg = dataclasses.replace(
            rep.artifact.cfg, conv_channels=(70000, 32)
        )
        bad_art = dataclasses.replace(
            rep.artifact, cfg=bad_cfg, _wire=None
        )
        with pytest.raises(ValueError, match="u16"):
            codec.encode(bad_art)
        bad_cfg = dataclasses.replace(rep.artifact.cfg, latent=70000)
        bad_art = dataclasses.replace(rep.artifact, cfg=bad_cfg, _wire=None)
        with pytest.raises(ValueError, match="u16"):
            codec.encode(bad_art)


class TestByteAccounting:
    def test_len_equals_reported_total_exactly(self, blob_and_report):
        blob, rep = blob_and_report
        bb = rep.bytes_breakdown
        assert bb["total"] == len(blob)
        parts = (bb["latent"] + bb["decoder"] + bb["correction"] + bb["coeff"]
                 + bb["index"] + bb["basis"] + bb["meta"])
        assert parts == bb["total"]

    def test_breakdown_matches_stream_table(self, blob_and_report):
        blob, rep = blob_and_report
        r = ContainerReader(blob)
        sizes = r.stream_sizes()
        bb = rep.bytes_breakdown
        # v3 buckets the shard chain payloads as latent; the shard head
        # (shared codebook + extents table) is framing and lands in meta
        ldir = codec.LatentShardDirectory(r["latent"])
        assert bb["latent"] == ldir.payload_total
        assert bb["latent"] + ldir.header_bytes == sizes["latent"]
        assert bb["decoder"] == sizes["decoder"]
        assert bb["correction"] == sizes["correction"]
        # meta is measured framing + metadata, not the seed's 8*S + 64 guess
        assert bb["meta"] >= r.header_bytes + sizes["meta"] + ldir.header_bytes

    def test_gba_container_has_no_correction_stream(self, fitted_codec):
        blob, rep = fitted_codec.compress_report(
            target_nrmse=2e-3, skip_correction=True
        )
        assert "correction" not in ContainerReader(blob)
        assert rep.bytes_breakdown["correction"] == 0
        assert rep.bytes_breakdown["total"] == len(blob)
        dec = codec.decompress(blob)
        art = CompressedArtifact.from_bytes(blob)
        assert art.corr_params is None
        np.testing.assert_array_equal(dec, codec.reconstruct(art))


class TestCorruption:
    def test_truncated_raises(self, blob_and_report):
        blob, _ = blob_and_report
        for cut in (0, 5, len(blob) // 2, len(blob) - 1):
            with pytest.raises(ContainerFormatError):
                codec.decompress(blob[:cut])

    def test_wrong_magic_raises(self, blob_and_report):
        blob, _ = blob_and_report
        with pytest.raises(ContainerFormatError, match="magic"):
            codec.decompress(b"ZSTD" + blob[4:])

    def test_wrong_version_raises(self, blob_and_report):
        blob, _ = blob_and_report
        bad = blob[:4] + (99).to_bytes(2, "little") + blob[6:]
        with pytest.raises(ContainerFormatError, match="version"):
            codec.decompress(bad)

    def test_trailing_garbage_raises(self, blob_and_report):
        blob, _ = blob_and_report
        with pytest.raises(ContainerFormatError, match="trailing"):
            codec.decompress(blob + b"\x00\x01\x02")

    @pytest.mark.parametrize(
        "offset,value",
        [
            (0, 0),    # cleared correction flag with a correction stream present
            (0, 0xFF), # unknown flag bits set (newer writer or bit flip)
            (1, 3),    # param_dtype_bytes neither 2 nor 4
            (4, 0),    # geometry bt == 0 (would ZeroDivide downstream)
            (10, 0),   # n_conv == 0 (mis-frames the rest of the meta stream)
            (12, 0),   # conv_channels[0] == 0
        ],
    )
    def test_corrupt_meta_fields_raise(self, blob_and_report, offset, value):
        """Bit-flipped meta fields must surface as ContainerFormatError, not
        ZeroDivisionError / model-construction crashes downstream."""
        blob, _ = blob_and_report

        def mutate(name, payload):
            if name == "meta":
                return (payload[:offset] + bytes([value])
                        + payload[offset + 1:])
            return payload

        with pytest.raises(ContainerFormatError) as ei:
            codec.decompress(self._rebuild(blob, mutate).to_bytes())
        # structured: meta parse errors name the stream; a cleared/forged
        # correction flag instead surfaces as a stream-set mismatch (the
        # whole-container check, attributed to no single stream)
        assert ei.value.stream in ("meta", None)

    def _rebuild(self, blob, mutate):
        """Re-emit the outer container with ``mutate(name, payload)``,
        downgraded to v3 (integrity stream dropped, v5 meta family tag
        stripped back to the legacy layout): these tests pin the
        *structural* validation layer that pre-digest containers rely on
        — on a v4+ blob the digests would (correctly) catch the same
        mutations first, which test_integrity.py covers."""
        r = ContainerReader(blob)
        w = ContainerWriter(version=min(r.version, 3))
        family_ver = container_format.FORMAT_VERSION_FAMILY
        for name in r.names:
            if name == "integrity":
                continue
            payload = r[name]
            if name == "meta" and r.version >= family_ver:
                payload = payload[1:]  # drop the tag; v3 meta is the body
            res = mutate(name, payload)
            if res is not None:
                w.add(name, res)
        return w

    def test_truncated_nested_coeff_raises_format_error(self, blob_and_report):
        """A coeff payload cut inside its Huffman header must raise
        ContainerFormatError, not leak struct.error (v2: the species'
        directory record is shrunk to match, so only that stream is bad)."""
        blob, _ = blob_and_report

        def mutate(name, payload):
            if name == "guarantee":
                return _truncate_species_coeff(payload, sidx=0, keep=8)
            return payload

        with pytest.raises(ContainerFormatError) as ei:
            codec.decompress(self._rebuild(blob, mutate).to_bytes())
        assert ei.value.stream == "guarantee"
        assert ei.value.unit == 0

    def test_stray_stream_raises(self, blob_and_report):
        """Unknown streams must be rejected — every byte on the wire is
        accounted for by purpose, nothing rides along silently."""
        blob, _ = blob_and_report
        w = self._rebuild(blob, lambda name, payload: payload)
        w.add("padding", b"\x00" * 1024)
        with pytest.raises(ContainerFormatError, match="unexpected stream"):
            codec.decompress(w.to_bytes())

    def test_nan_coeff_bin_raises(self, blob_and_report):
        """A NaN coefficient bin in a guarantee directory record must
        raise, not scatter NaN corrections into the decoded field."""
        import struct

        blob, _ = blob_and_report

        def mutate(name, payload):
            if name == "guarantee":
                # record 0 starts after the u32 species count: <ddII...>
                off = 4 + 8  # skip count + tau
                return (payload[:off] + struct.pack("<d", float("nan"))
                        + payload[off + 8:])
            return payload

        with pytest.raises(ContainerFormatError, match="coeff bin"):
            codec.decompress(self._rebuild(blob, mutate).to_bytes())

    def test_basis_dimension_mismatch_raises(self, blob_and_report):
        """A guarantee basis whose row dimension disagrees with the block
        size must fail validation, not crash in the decode replay."""
        blob, rep = blob_and_report
        arts = rep.artifact.species_guarantees
        nb = arts[0].n_blocks
        wrong_d = codec.pack_guarantee_stream(
            [gae.GuaranteeArtifact.empty(nb=nb, d=40, tau=1.0)
             for _ in arts]
        )
        w = self._rebuild(
            blob,
            lambda name, payload: wrong_d if name == "guarantee" else payload,
        )
        with pytest.raises(ContainerFormatError, match="block size"):
            codec.decompress(w.to_bytes())

    def test_corrupt_guarantee_directory_raises(self, blob_and_report):
        """A guarantee stream whose directory disagrees with its payload
        bytes must surface as ContainerFormatError, not a mis-slice."""
        blob, _ = blob_and_report

        def mutate(name, payload):
            if name == "guarantee":
                # inflate the species count: directory now overruns
                return (99).to_bytes(4, "little") + payload[4:]
            return payload

        with pytest.raises(ContainerFormatError) as ei:
            codec.decompress(self._rebuild(blob, mutate).to_bytes())
        assert ei.value.stream == "guarantee"

    def test_corrupt_nested_guarantee_raises_v1(self, blob_and_report):
        """v1 layout: corrupting a nested guarantee container's magic must
        surface as a ContainerFormatError through the same entry point."""
        _, rep = blob_and_report
        blob = codec.encode(rep.artifact, version=1)
        r = ContainerReader(blob)
        w = ContainerWriter(version=r.version)
        for name in r.names:
            payload = r[name]
            if name == "guarantee0":
                payload = b"NOPE" + payload[4:]
            w.add(name, payload)
        with pytest.raises(ContainerFormatError):
            codec.decompress(w.to_bytes())


class TestConfigShadowingFix:
    """decompress must derive structure from the artifact, not the pipeline."""

    def test_gba_pipeline_applies_gbatc_correction(
        self, small_data, fitted_codec, blob_and_report
    ):
        blob, rep = blob_and_report
        cfg_gba = PipelineConfig(
            ae_steps=60, corr_steps=30, conv_channels=(16, 32),
            use_correction=False,
        )
        pipe_gba = GBATCPipeline(cfg_gba, n_species=small_data.shape[0])
        out = pipe_gba.decompress(rep.artifact)  # seed silently skipped corr
        np.testing.assert_array_equal(out, codec.decompress(blob))

    def test_structural_mismatch_raises(self, small_data, blob_and_report):
        _, rep = blob_and_report
        for bad_cfg in (
            PipelineConfig(conv_channels=(16, 32), latent=20),
            PipelineConfig(conv_channels=(8, 16)),
        ):
            pipe = GBATCPipeline(bad_cfg, n_species=small_data.shape[0])
            with pytest.raises(ValueError, match="does not match"):
                pipe.decompress(rep.artifact)

    def test_species_count_mismatch_raises(self, blob_and_report):
        _, rep = blob_and_report
        pipe = GBATCPipeline(
            PipelineConfig(conv_channels=(16, 32)), n_species=3
        )
        with pytest.raises(ValueError, match="does not match"):
            pipe.decompress(rep.artifact)


class TestGuaranteeArtifactWire:
    @pytest.mark.parametrize("tau", [0.2, 0.8])
    def test_round_trip(self, tau):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(200, 64)).astype(np.float32)
        x_rec = x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
        _, art = gae.guarantee(x, x_rec, tau)
        back = gae.GuaranteeArtifact.from_bytes(art.to_bytes())
        np.testing.assert_array_equal(back.coeff_q, art.coeff_q)
        np.testing.assert_array_equal(back.index_offsets, art.index_offsets)
        np.testing.assert_array_equal(back.index_flat, art.index_flat)
        np.testing.assert_array_equal(back.basis, art.basis)
        assert back.tau == art.tau and back.coeff_bin == art.coeff_bin
        # the replayed correction is bit-identical through the wire
        np.testing.assert_array_equal(
            gae.apply_correction(x_rec, back), gae.apply_correction(x_rec, art)
        )

    def test_empty_artifact_round_trip(self):
        art = gae.GuaranteeArtifact.empty(nb=37, d=80, tau=1.5)
        back = gae.GuaranteeArtifact.from_bytes(art.to_bytes())
        assert back.n_blocks == 37
        assert back.coeff_q.size == 0 and back.basis.shape == (80, 0)
        assert back.tau == 1.5

    def test_out_of_range_index_raises(self):
        """A well-framed index stream whose flat indices exceed the stored
        basis columns must raise at decode, not silently scatter into
        zero/absent columns at replay time."""
        art = gae.GuaranteeArtifact(
            basis=np.zeros((8, 2), np.float32),
            coeff_q=np.array([5], np.int64),
            index_offsets=np.array([0, 1, 1], np.int64),
            index_flat=np.array([5], np.int64),  # >= n_store == 2
            coeff_bin=0.1,
            tau=0.5,
        )
        with pytest.raises(ContainerFormatError, match="basis column"):
            gae.GuaranteeArtifact.from_bytes(art.to_bytes())

    def test_stream_size_memos_match_measured(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(150, 48)).astype(np.float32)
        x_rec = x + 0.2 * rng.normal(size=x.shape).astype(np.float32)
        _, art = gae.guarantee(x, x_rec, 0.3)
        back = gae.GuaranteeArtifact.from_bytes(art.to_bytes())
        assert back.coeff_bytes() == art.coeff_bytes()
        assert back.index_bytes() == art.index_bytes()


class TestFp16ParamStorage:
    def test_honest_fp16_container(self, small_data):
        """fp16 storage halves the parameter streams AND keeps the bound:
        fit() rounds params through the storage dtype before anything
        downstream uses them, so the serialized decoder is exactly the one
        the guarantee was computed against."""
        mk = lambda pdb: PipelineConfig(
            ae_steps=40, corr_steps=20, conv_channels=(16, 32),
            param_dtype_bytes=pdb,
        )
        target = 2e-3
        blob32, _ = codec.GBATCCodec(mk(4)).fit(small_data).compress_report(
            target_nrmse=target
        )
        blob16, rep16 = codec.GBATCCodec(mk(2)).fit(small_data).compress_report(
            target_nrmse=target
        )
        bb32 = codec.stream_breakdown(blob32)
        bb16 = codec.stream_breakdown(blob16)
        assert bb16["decoder"] * 2 == bb32["decoder"]
        assert bb16["correction"] * 2 == bb32["correction"]
        dec = codec.decompress(blob16)
        np.testing.assert_array_equal(dec, codec.reconstruct(rep16.artifact))
        per = np.array(
            [metrics.nrmse(small_data[s], dec[s])
             for s in range(small_data.shape[0])]
        )
        assert per.max() <= target * (1 + 1e-3)


def _serial_guarantee_stream(arts) -> bytes:
    """The combined guarantee stream assembled one species at a time from
    ``wire_parts``: count, directory records, then the coeff, index and
    basis payloads, each group in species order."""
    wires = [g.wire_parts() for g in arts]
    records = [
        codec._GDIR_REC.pack(g.tau, g.coeff_bin, *g.basis.shape,
                             *(len(p) for p in w))
        for g, w in zip(arts, wires)
    ]
    payloads = [w[kind] for kind in range(3) for w in wires]
    return codec._GDIR_HEAD.pack(len(arts)) + b"".join(records + payloads)


class TestGuaranteeStreamPacking:
    @staticmethod
    def _artifacts(n_species: int) -> list:
        """``n_species`` artifacts over 90 blocks of 48 values; every third
        one empty, the rest from the guarantee at varied tolerances."""
        rng = np.random.default_rng(n_species)
        arts = []
        for sidx in range(n_species):
            if sidx % 3 == 1:
                arts.append(gae.GuaranteeArtifact.empty(nb=90, d=48, tau=0.4))
                continue
            x = rng.normal(size=(90, 48)).astype(np.float32)
            x_rec = x + 0.2 * rng.normal(size=x.shape).astype(np.float32)
            arts.append(gae.guarantee(x, x_rec, 0.2 + 0.05 * sidx)[1])
        return arts

    @pytest.mark.parametrize("n_species", [1, 3, 13])
    def test_pooled_pack_matches_serial_assembly(self, n_species):
        arts = self._artifacts(n_species)
        assert any(g.coeff_q.size == 0 for g in arts) == (n_species > 1)
        assert codec.pack_guarantee_stream(arts) == \
            _serial_guarantee_stream(arts)

    def test_encode_matches_serial_guarantee_stream(
        self, blob_and_report, monkeypatch
    ):
        """A whole container with the pooled guarantee stream is the blob
        whose guarantee stream was assembled serially."""
        from repro.codec import format as wire

        blob, rep = blob_and_report
        pooled = codec.encode(rep.artifact)
        monkeypatch.setattr(wire, "pack_guarantee_stream",
                            _serial_guarantee_stream)
        assert codec.encode(rep.artifact) == pooled == blob
