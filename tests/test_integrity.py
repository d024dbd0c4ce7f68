"""Integrity container (v4) suite: digests, salvage decode, fault sweep.

The acceptance contract for the integrity subsystem:

* a clean v4 container decodes **bitwise identical** to the v3 container
  of the same fit, through every entry point (full ``decompress``,
  ``PartialDecoder`` windows, the streaming-fit path) — the digests
  change no payload byte, and stripping them yields exactly the v3 blob;
* *detected or harmless, never a silent wrong decode*: a fault-injection
  sweep (seeded bit flips, zero runs, splices, truncations — thousands
  of corruptions) over every addressable region must either raise
  :class:`ContainerFormatError` or decode bitwise equal to clean. On v4,
  **100% of single-bit payload flips are detected**; v1–v3 carry no
  digests, so their coverage is structural-only — measured and pinned
  here, not asserted at 100%;
* ``on_error="salvage"`` quarantines corrupt units, returns every
  non-quarantined species bitwise equal to the clean decode, NaN-fills
  the rest, and reports exactly what happened in a
  :class:`~repro.codec.DecodeReport`;
* salvage is cache-isolated: it never reads from or writes into the
  decode head cache, and raise-mode corruption evicts the poisoned head;
* :func:`repro.codec.write`/:func:`repro.codec.read` publish atomically
  (tmp+fsync+rename) and digest-verify on read;
* ``fit_stream`` retries transient loader faults with backoff and the
  recovered fit stays bit-identical to a clean run.
"""

import os

import numpy as np
import pytest

from repro import codec
from repro.codec import format as codec_format
from repro.codec import runtime as codec_runtime
from repro.core.container import ContainerFormatError, ContainerReader, \
    ContainerWriter
from repro.core.pipeline import PipelineConfig
from repro.data import s3d
from repro.testing.faults import FaultInjector, blob_regions
from repro.train.fault_tolerance import retry_with_backoff


@pytest.fixture(scope="module")
def small_cfg():
    return s3d.S3DConfig(n_species=6, n_time=16, height=20, width=16, seed=3)


@pytest.fixture(scope="module")
def small_data(small_cfg):
    return s3d.generate(small_cfg)["species"]


@pytest.fixture(scope="module")
def pipe_cfg():
    return PipelineConfig(ae_steps=8, corr_steps=4, conv_channels=(8, 16),
                          seed=0)


@pytest.fixture(scope="module")
def fitted(small_data, pipe_cfg):
    return codec.GBATCCodec(pipe_cfg).fit(small_data)


@pytest.fixture(scope="module")
def blob_and_report(fitted):
    return fitted.compress_report(target_nrmse=1e-2)


@pytest.fixture(scope="module")
def blob(blob_and_report):
    return blob_and_report[0]


@pytest.fixture(scope="module")
def blob_v3(blob_and_report):
    return codec.encode(blob_and_report[1].artifact, version=3)


@pytest.fixture(scope="module")
def clean(blob):
    return codec.decompress(blob)


@pytest.fixture(scope="module")
def regions(blob):
    return blob_regions(blob)


def _region(regions, label):
    return next(r for r in regions if r.label == label)


class TestV4Wire:
    def test_default_is_v5_and_verifies(self, blob):
        assert ContainerReader(blob).version == 5
        assert codec.verify_blob(blob) == 5

    def test_below_v4_structural_only(self, blob_v3):
        # no digests to check: verify_blob is just the structural parse
        assert codec.verify_blob(blob_v3) == 3

    def test_stripping_digests_yields_exact_v3_blob(self, blob, blob_v3):
        """The v5 additions are strictly additive on a conv fit: dropping
        the integrity stream, the meta family-tag byte, and the version
        bump reproduces the v3 container byte for byte."""
        r = ContainerReader(blob)
        w = ContainerWriter(version=3)
        for name in r.names:
            if name == "integrity":
                continue
            payload = r[name]
            if name == "meta":
                payload = payload[1:]  # the conv family tag
            w.add(name, payload)
        assert w.to_bytes() == blob_v3

    def test_full_decode_bit_identical_to_v3(self, blob, blob_v3, clean):
        assert codec.decompress(blob_v3).tobytes() == clean.tobytes()

    def test_partial_decode_bit_identical_to_v3(self, blob, blob_v3):
        pd4 = codec.PartialDecoder(blob)
        pd3 = codec.PartialDecoder(blob_v3)
        for sel, win in (([1, 4], (4, 12)), (2, (0, 4)), (None, (8, 16))):
            a = pd4.decode(species=sel, time_range=win)
            b = pd3.decode(species=sel, time_range=win)
            assert a.tobytes() == b.tobytes()

    def test_fit_stream_writes_identical_blob(self, small_cfg, pipe_cfg,
                                              fitted):
        """The streaming-fit path lands on the same container bytes as
        the materialized fit — the integrity layer is orthogonal to how
        the model was trained."""
        loader = s3d.S3DChunkLoader(small_cfg, chunk_frames=4)
        c = codec.GBATCCodec(pipe_cfg).fit_stream(loader)
        blob_stream = c.compress(target_nrmse=1e-2)
        blob_full = fitted.compress(target_nrmse=1e-2)
        assert ContainerReader(blob_stream).version == 5
        assert blob_stream == blob_full

    def test_digest_overhead_is_marginal(self, blob, blob_v3):
        # a few CRCs per stream/unit: well under 1% on any real container
        assert len(blob) - len(blob_v3) < 0.01 * len(blob_v3)

    def test_every_byte_is_digest_covered(self, blob, regions):
        """The regions partition proof: header + stream extents tile the
        blob exactly, so the sweep's per-region coverage is whole-blob
        coverage."""
        coarse = [r for r in regions
                  if r.label == "header" or r.label.startswith("stream:")]
        spans = sorted((r.lo, r.hi) for r in coarse)
        assert spans[0][0] == 0 and spans[-1][1] == len(blob)
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))


class TestFaultSweepV4:
    """The headline property: detected or harmless, never silent."""

    def test_thousands_of_bit_flips_all_detected(self, blob, regions):
        """verify_blob digest-checks 100% of the blob's bytes: a sweep of
        seeded single-bit flips across every region must raise for every
        one (CRC32 detects all single-bit errors)."""
        inj = FaultInjector(seed=101)
        flips = 0
        for reg in regions:
            for _ in range(40):
                bad, fault = inj.flip_bit(blob, reg)
                with pytest.raises(ContainerFormatError):
                    codec.verify_blob(bad)
                flips += 1
        assert flips >= 1000  # "thousands": ~31 regions x 40 flips

    def test_decode_paths_never_silently_wrong(self, blob, regions, clean):
        """End to end through ``decompress``: every payload flip must
        raise (v4 detects 100% of single-bit payload flips); header
        flips raise too (the outer digest covers the framing)."""
        inj = FaultInjector(seed=202)
        for reg in regions:
            for _ in range(4):
                bad, fault = inj.flip_bit(blob, reg)
                with pytest.raises(ContainerFormatError):
                    codec.decompress(bad)

    def test_detection_names_the_unit(self, blob, regions):
        """A flip inside a fine-grained unit is attributed to that unit
        (stream + index), not just 'corrupt blob'."""
        inj = FaultInjector(seed=303)
        for label, stream, unit in (
            ("latent:shard1", "latent", 1),
            ("guarantee:s2:coeff", "guarantee", 2),
            ("guarantee:s4:basis", "guarantee", 4),
        ):
            bad, _ = inj.flip_bit(blob, _region(regions, label))
            with pytest.raises(ContainerFormatError) as ei:
                codec.decompress(bad)
            assert ei.value.stream == stream
            assert ei.value.unit == unit

    def test_zero_runs_and_splices_detected(self, blob, regions):
        inj = FaultInjector(seed=404)
        payload_regions = [r for r in regions if r.stream is not None]
        for reg in payload_regions:
            bad, _ = inj.zero_run(blob, reg, length=16)
            if bad == blob:
                continue  # zeroed an already-zero run: genuinely harmless
            with pytest.raises(ContainerFormatError):
                codec.verify_blob(bad)
        # splice shard payloads / species extents crosswise: every byte
        # is individually plausible, only the digests can tell
        for dst, src in (
            ("latent:shard0", "latent:shard2"),
            ("guarantee:s1:coeff", "guarantee:s3:coeff"),
        ):
            bad, _ = inj.splice(blob, _region(regions, dst),
                                _region(regions, src))
            if bad == blob:
                continue
            with pytest.raises(ContainerFormatError):
                codec.verify_blob(bad)

    def test_truncations_detected(self, blob):
        inj = FaultInjector(seed=505)
        for _ in range(20):
            bad, _ = inj.truncate(blob)
            with pytest.raises(ContainerFormatError):
                codec.verify_blob(bad)

    def test_window_decode_checks_what_it_reads(self, blob, regions, clean):
        """A corrupt shard outside the requested window must not block
        the window (lazy verification), but a window over it must raise."""
        inj = FaultInjector(seed=606)
        bad, _ = inj.flip_bit(blob, _region(regions, "latent:shard2"))
        d = codec_format.LatentShardDirectory(ContainerReader(blob)["latent"])
        bt = 4  # paper geometry: 4 frames per time block-group
        per_frame = d.n_rows * bt // clean.shape[1]
        t_lo = d.shard_row_extent(2)[0] // per_frame * bt
        pd = codec.PartialDecoder(bad)
        # shard 2 covers frames [t_lo, ...); frames [0, 4) live in shard 0
        np.testing.assert_array_equal(
            pd.decode(time_range=(0, 4)), clean[:, 0:4]
        )
        with pytest.raises(ContainerFormatError) as ei:
            pd.decode(time_range=(t_lo, t_lo + bt))
        assert (ei.value.stream, ei.value.unit) == ("latent", 2)


class TestFaultSweepLegacy:
    """v1–v3 carry no digests: structural-only coverage, documented by
    measurement. The property that must still hold everywhere: *typed*
    failure — corruption raises ContainerFormatError or decodes, never
    leaks struct.error/ValueError or crashes."""

    @pytest.fixture(scope="class")
    def legacy_blobs(self, blob_and_report):
        art = blob_and_report[1].artifact
        return {v: codec.encode(art, version=v) for v in (1, 2, 3)}

    def test_structural_faults_detected_payload_flips_typed(
        self, legacy_blobs
    ):
        for version, b in legacy_blobs.items():
            clean = codec.decompress(b)
            inj = FaultInjector(seed=700 + version)
            silent = detected = harmless = 0
            for reg in blob_regions(b):
                for _ in range(3):
                    bad, fault = inj.flip_bit(b, reg)
                    try:
                        out = codec.decompress(bad)
                    except ContainerFormatError:
                        detected += 1
                        continue
                    # no digests below v4: a flip may decode — it must do
                    # so cleanly (typed), and we pin how often it is wrong
                    if np.array_equal(out, clean):
                        harmless += 1
                    else:
                        silent += 1
            # structural framing (header) faults are always caught even
            # without digests — re-sweep the header alone to pin that
            hdr = blob_regions(b, fine=False)[0]
            assert hdr.label == "header"
            for _ in range(10):
                bad, _ = inj.flip_bit(b, hdr)
                try:
                    out = codec.decompress(bad)
                except ContainerFormatError:
                    pass
                else:
                    assert np.array_equal(out, clean)
            # documented gap: payload flips CAN decode silently wrong on
            # pre-digest containers (this is precisely what v4 closes)
            assert detected > 0
            assert silent + harmless + detected > 0

    def test_truncation_always_detected_below_v4(self, legacy_blobs):
        inj = FaultInjector(seed=808)
        for b in legacy_blobs.values():
            for _ in range(10):
                bad, _ = inj.truncate(b)
                with pytest.raises(ContainerFormatError):
                    codec.decompress(bad)


class TestSalvage:
    def _inj(self, seed=11):
        return FaultInjector(seed=seed)

    def test_clean_blob_salvage_is_clean_decode(self, blob, clean):
        field, rep = codec.decompress(blob, on_error="salvage")
        assert rep.ok and rep.integrity and rep.version == 5
        assert rep.quarantined == []
        assert field.tobytes() == clean.tobytes()
        for i, sr in rep.species.items():
            assert sr.status == "verified"
            # tau = target * sqrt(D) at compress, so the per-species
            # bound round-trips to the compression target exactly
            assert sr.nrmse_bound == pytest.approx(1e-2)
            assert sr.damaged_frames == []

    def test_corrupt_species_quarantined_siblings_bitwise(
        self, blob, regions, clean
    ):
        bad, fault = self._inj().flip_bit(
            blob, _region(regions, "guarantee:s2:index")
        )
        field, rep = codec.decompress(bad, on_error="salvage")
        assert not rep.ok
        assert rep.quarantined == [2]
        assert rep.species[2].status == "missing"
        assert np.isnan(field[2]).all()
        for i in (0, 1, 3, 4, 5):
            assert rep.species[i].status == "verified"
            assert field[i].tobytes() == clean[i].tobytes()
        assert [(f.stream, f.unit) for f in rep.failures] \
            == [("guarantee", 2)]

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_k_corrupt_species_quarantined_exactly(
        self, blob, regions, clean, k
    ):
        """A coefficient flip in each of species 0..k-1: salvage
        quarantines exactly those, NaN-fills them, and returns every other
        species bitwise equal to the clean decode."""
        inj = self._inj(seed=5)
        bad = blob
        for i in range(k):
            bad, _ = inj.flip_bit(bad,
                                  _region(regions, f"guarantee:s{i}:coeff"))
        field, rep = codec.decompress(bad, on_error="salvage")
        assert rep.quarantined == list(range(k))
        for i in range(field.shape[0]):
            if i < k:
                assert np.isnan(field[i]).all()
            else:
                assert field[i].tobytes() == clean[i].tobytes()

    def test_corrupt_shard_salvaged_with_damage_map(
        self, blob, regions, clean
    ):
        bad, _ = self._inj(22).flip_bit(
            blob, _region(regions, "latent:shard1")
        )
        field, rep = codec.decompress(bad, on_error="salvage")
        assert not rep.ok and rep.quarantined == []
        d = codec_format.LatentShardDirectory(ContainerReader(blob)["latent"])
        r0, r1 = d.shard_row_extent(1)
        per_frame = d.n_rows * 4 // clean.shape[1]  # bt=4 block rows/frame
        want = [(r0 // per_frame * 4, r1 // per_frame * 4)]
        for i, sr in rep.species.items():
            # the AE decodes species jointly: shard damage is species-wide
            assert sr.status == "salvaged"
            assert sr.damaged_frames == want
        dmg = np.zeros(clean.shape[1], bool)
        for lo, hi in want:
            dmg[lo:hi] = True
        assert np.isnan(field[:, dmg]).all()
        assert field[:, ~dmg].tobytes() == clean[:, ~dmg].tobytes()

    def test_corrupt_shared_stream_all_missing(self, blob, regions, clean):
        for label in ("stream:decoder", "stream:correction", "latent:head"):
            bad, _ = self._inj(33).flip_bit(blob, _region(regions, label))
            field, rep = codec.decompress(bad, on_error="salvage")
            assert rep.quarantined == list(range(clean.shape[0]))
            assert np.isnan(field).all()
            assert field.shape == clean.shape

    def test_corrupt_integrity_stream_downgrades_to_unverified(
        self, blob, regions, clean
    ):
        """A corrupt digest table indicts itself: the data decodes via
        the structural parse, honestly reported as unverified."""
        bad, _ = self._inj(44).flip_bit(
            blob, _region(regions, "stream:integrity")
        )
        field, rep = codec.decompress(bad, on_error="salvage")
        assert not rep.integrity
        assert all(sr.status == "unverified" for sr in rep.species.values())
        assert field.tobytes() == clean.tobytes()

    def test_meta_corruption_still_raises(self, blob, regions):
        bad, _ = self._inj(55).flip_bit(blob, _region(regions, "stream:meta"))
        with pytest.raises(ContainerFormatError):
            codec.decompress(bad, on_error="salvage")

    def test_salvage_respects_selection(self, blob, regions, clean):
        bad, _ = self._inj(66).flip_bit(
            blob, _region(regions, "guarantee:s2:coeff")
        )
        field, rep = codec.decompress(
            bad, species=[1, 2], time_range=(4, 12), on_error="salvage"
        )
        assert field.shape == (2, 8) + clean.shape[2:]
        assert sorted(rep.species) == [1, 2]
        assert rep.species[1].status == "verified"
        assert rep.species[2].status == "missing"
        assert field[0].tobytes() == clean[1, 4:12].tobytes()
        assert np.isnan(field[1]).all()
        # corruption outside the selection is not even read
        field2, rep2 = codec.decompress(
            bad, species=[0, 3], on_error="salvage"
        )
        assert rep2.ok
        assert field2.tobytes() == clean[[0, 3]].tobytes()

    def test_salvage_on_partial_decoder(self, blob, regions, clean):
        bad, _ = self._inj(77).flip_bit(
            blob, _region(regions, "guarantee:s0:basis")
        )
        pd = codec.PartialDecoder(bad)
        field, rep = pd.decode(on_error="salvage")
        assert rep.quarantined == [0]
        assert field[1:].tobytes() == clean[1:].tobytes()
        # raise mode on the same decoder still raises
        with pytest.raises(ContainerFormatError):
            pd.decode(species=[0])

    def test_salvage_below_v4_is_unverified(self, blob_v3):
        field, rep = codec.decompress(blob_v3, on_error="salvage")
        assert rep.version == 3 and not rep.integrity
        assert all(sr.status == "unverified" for sr in rep.species.values())
        assert field.tobytes() == codec.decompress(blob_v3).tobytes()

    def test_invalid_on_error_rejected(self, blob):
        with pytest.raises(ValueError, match="on_error"):
            codec.decompress(blob, on_error="ignore")
        with pytest.raises(ValueError, match="on_error"):
            codec.PartialDecoder(blob).decode(on_error="ignore")


class TestCacheIsolation:
    def test_salvage_never_touches_head_cache(self, blob, regions):
        codec.clear_decode_cache()
        bad, _ = FaultInjector(seed=1).flip_bit(
            blob, _region(regions, "guarantee:s2:coeff")
        )
        codec.decompress(bad, on_error="salvage")
        # salvage parsed the head itself — nothing may remain cached
        assert bytes(bad) not in codec_runtime._HEADS

    def test_raise_mode_corruption_evicts_poisoned_head(
        self, blob, regions
    ):
        codec.clear_decode_cache()
        bad, _ = FaultInjector(seed=2).flip_bit(
            blob, _region(regions, "guarantee:s3:coeff")
        )
        # head parse succeeds (guarantee digests check lazily), decode
        # raises — the poisoned head must not linger in the cache
        with pytest.raises(ContainerFormatError):
            codec.decompress(bad)
        assert bytes(bad) not in codec_runtime._HEADS

    def test_salvage_leaves_clean_entries_alone(self, blob, regions, clean):
        codec.clear_decode_cache()
        np.testing.assert_array_equal(codec.decompress(blob), clean)
        assert bytes(blob) in codec_runtime._HEADS
        bad, _ = FaultInjector(seed=3).flip_bit(
            blob, _region(regions, "guarantee:s1:coeff")
        )
        codec.decompress(bad, on_error="salvage")
        # the CLEAN blob's entry survives; only the bad blob's key (had
        # one existed) is evicted
        assert bytes(blob) in codec_runtime._HEADS
        np.testing.assert_array_equal(codec.decompress(blob), clean)

    def test_salvage_evicts_own_key_on_entry(self, blob):
        """Salvaging a blob that was previously decoded clean must not be
        served from (or leave) its cached head."""
        codec.clear_decode_cache()
        codec.decompress(blob)
        assert bytes(blob) in codec_runtime._HEADS
        field, rep = codec.decompress(blob, on_error="salvage")
        assert rep.ok
        assert bytes(blob) not in codec_runtime._HEADS


class TestAtomicIO:
    def test_write_read_round_trip(self, blob, tmp_path):
        p = tmp_path / "field.gbtc"
        codec.write(p, blob)
        assert codec.read(p) == blob
        # no tmp litter
        assert os.listdir(tmp_path) == ["field.gbtc"]

    def test_write_replaces_atomically(self, blob, tmp_path):
        p = tmp_path / "field.gbtc"
        p.write_bytes(b"previous contents")
        codec.write(p, blob)
        assert p.read_bytes() == blob

    def test_read_verifies_by_default(self, blob, tmp_path, regions):
        p = tmp_path / "field.gbtc"
        bad, _ = FaultInjector(seed=9).flip_bit(
            blob, _region(regions, "stream:decoder")
        )
        p.write_bytes(bad)
        with pytest.raises(ContainerFormatError) as ei:
            codec.read(p)
        assert ei.value.stream == "decoder"
        assert codec.read(p, verify=False) == bad

    def test_codec_facade_write_read(self, fitted, tmp_path):
        p = tmp_path / "x.gbtc"
        blob = fitted.write(p, target_nrmse=1e-2)
        assert codec.GBATCCodec.read(p) == blob
        field = codec.decompress(blob)
        assert field.shape[0] == 6


class _FlakyLoader:
    """Wraps a chunk loader; raises OSError mid-iteration a set number of
    times, then behaves cleanly — the transient-I/O model fit_stream's
    retry must absorb."""

    def __init__(self, inner, fail_times):
        self._inner = inner
        self._fails = fail_times
        self.shape = inner.shape

    def chunks(self):
        n = 0
        for c in self._inner.chunks():
            yield c
            n += 1
            if self._fails > 0 and n == 2:
                self._fails -= 1
                raise OSError("transient read fault")


class TestLoaderRetry:
    def test_retry_with_backoff_unit(self):
        calls = []
        sleeps = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("flaky")
            return "done"

        out = retry_with_backoff(fn, max_retries=3, backoff=0.5,
                                 sleep=sleeps.append)
        assert out == "done" and len(calls) == 3
        assert sleeps == [0.5, 1.0]  # exponential: backoff * 2**attempt

    def test_retry_exhaustion_reraises(self):
        def fn():
            raise OSError("always")

        with pytest.raises(OSError, match="always"):
            retry_with_backoff(fn, max_retries=2, backoff=0,
                               sleep=lambda s: None)

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def fn():
            calls.append(1)
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            retry_with_backoff(fn, max_retries=5, sleep=lambda s: None)
        assert len(calls) == 1

    def test_flaky_loader_yields_bit_identical_container(
        self, small_cfg, pipe_cfg
    ):
        """One transient fault in each pass: the restart re-reads from
        the top, and the final container matches a clean run byte for
        byte."""
        sleeps = []
        flaky = _FlakyLoader(
            s3d.S3DChunkLoader(small_cfg, chunk_frames=4), fail_times=2
        )
        c_flaky = codec.GBATCCodec(pipe_cfg).fit_stream(
            flaky, _sleep=sleeps.append
        )
        c_clean = codec.GBATCCodec(pipe_cfg).fit_stream(
            s3d.S3DChunkLoader(small_cfg, chunk_frames=4)
        )
        assert c_flaky.compress(target_nrmse=1e-2) \
            == c_clean.compress(target_nrmse=1e-2)
        assert sleeps == [0.1, 0.2]  # one backoff per pass restart

    def test_persistent_faults_exhaust_retries(self, small_cfg, pipe_cfg):
        flaky = _FlakyLoader(
            s3d.S3DChunkLoader(small_cfg, chunk_frames=4), fail_times=99
        )
        with pytest.raises(OSError, match="transient"):
            codec.GBATCCodec(pipe_cfg).fit_stream(
                flaky, loader_retries=2, _sleep=lambda s: None
            )

    def test_validation_errors_never_retried(self, small_cfg, pipe_cfg):
        class Misaligned:
            shape = (6, 16, 20, 16)

            def __init__(self):
                self.iterations = 0

            def chunks(self):
                self.iterations += 1
                yield np.zeros((6, 3, 20, 16), np.float32)

        loader = Misaligned()
        with pytest.raises(ValueError, match="block depth"):
            codec.GBATCCodec(pipe_cfg).fit_stream(
                loader, _sleep=lambda s: None
            )
        assert loader.iterations == 1
