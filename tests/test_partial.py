"""Selective-decode suite: random access by species / time window.

The acceptance contract for the partial-decode subsystem:

* for ANY species subset and time window, the selective path is **bitwise
  equal** to slicing the full decode — same entry point, same bytes out;
* a corrupted/truncated individual sub-stream raises
  :class:`ContainerFormatError` naming the species, without poisoning
  sibling species (they remain decodable from the same blob);
* v1 (per-species nested guarantee) blobs round-trip bit-identically
  through the same entry points, selective decode included;
* selective decode genuinely parses fewer bytes (``bytes_parsed``) than a
  full decode on the v2 layout.
"""

import numpy as np
import pytest

from repro import codec
from repro.core.container import ContainerFormatError, ContainerReader, ContainerWriter
from repro.core.pipeline import PipelineConfig
from repro.data import s3d
# no tests/__init__.py: pytest puts each test file's directory on
# sys.path, so the shared helper imports by module name under both
# `pytest` and `python -m pytest`
from test_codec import _truncate_species_coeff


@pytest.fixture(scope="module")
def small_data():
    cfg = s3d.S3DConfig(n_species=8, n_time=8, height=40, width=32, seed=11)
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
def blob_v1(blob_and_report):
    return codec.encode(blob_and_report[1].artifact, version=1)


@pytest.fixture(scope="module")
def full(blob):
    return codec.decompress(blob)


def _sliced(full, species, time_range):
    t0, t1 = time_range if time_range is not None else (0, full.shape[1])
    if species is None:
        return full[:, t0:t1]
    if isinstance(species, int):
        return full[species, t0:t1]
    return full[list(species)][:, t0:t1]


class TestSelectiveEqualsFullSlice:
    @pytest.mark.parametrize(
        "species,time_range",
        [
            ([0], None),            # single species, all frames
            ([7], None),            # last species
            ([1, 4, 6], None),      # subset, preserving order
            ([5, 2], None),         # non-monotone order
            (None, (0, 4)),         # block-aligned window
            (None, (3, 7)),         # unaligned window (straddles blocks)
            (None, (5, 6)),         # single frame
            ([3], (2, 8)),          # species x window
            ([0, 7], (1, 5)),       # subset x unaligned window
        ],
    )
    def test_bitwise_equal(self, blob, full, species, time_range):
        out = codec.decompress(blob, species=species, time_range=time_range)
        np.testing.assert_array_equal(out, _sliced(full, species, time_range))
        assert out.dtype == np.float32

    def test_random_subsets_and_windows(self, blob, full):
        rng = np.random.default_rng(0)
        pd = codec.PartialDecoder(blob)
        s, t = full.shape[:2]
        for _ in range(6):
            k = int(rng.integers(1, s + 1))
            sel = sorted(rng.choice(s, size=k, replace=False).tolist())
            t0 = int(rng.integers(0, t))
            t1 = int(rng.integers(t0 + 1, t + 1))
            out = pd.decode(species=sel, time_range=(t0, t1))
            np.testing.assert_array_equal(out, full[sel][:, t0:t1])

    def test_int_species_squeezes_axis(self, blob, full):
        out = codec.decompress(blob, species=3)
        assert out.shape == full.shape[1:]
        np.testing.assert_array_equal(out, full[3])

    def test_negative_species_index(self, blob, full):
        np.testing.assert_array_equal(
            codec.decompress(blob, species=-1), full[-1]
        )

    def test_full_selection_equals_full_decode(self, blob, full):
        out = codec.decompress(blob, species=list(range(full.shape[0])))
        np.testing.assert_array_equal(out, full)

    def test_empty_species_matches_full_byte_for_byte(self, blob_and_report):
        """A species with NO stored corrections must still ride the replay
        kernel when any sibling has corrections — the full decode applies
        (x + 0 @ U^T) to it, and the selective output must be *byte*
        identical to that slice (array_equal would mask a -0.0 flip)."""
        import dataclasses

        from repro.core import gae

        _, rep = blob_and_report
        arts = list(rep.artifact.species_guarantees)
        nb = arts[0].n_blocks
        d = arts[0].basis.shape[0]
        arts[3] = gae.GuaranteeArtifact.empty(nb=nb, d=d, tau=arts[3].tau)
        art = dataclasses.replace(
            rep.artifact, species_guarantees=arts, _wire=None
        )
        for version in (2, 1):
            mixed_blob = codec.encode(art, version=version)
            full_mixed = codec.decompress(mixed_blob)
            out = codec.decompress(mixed_blob, species=3)
            assert out.tobytes() == full_mixed[3].tobytes()

    def test_gba_partial_decode(self, fitted_codec, small_data):
        """The no-correction (GBA) variant rides the same selective path."""
        gba_blob, _ = fitted_codec.compress_report(
            target_nrmse=2e-3, skip_correction=True
        )
        gba_full = codec.decompress(gba_blob)
        out = codec.decompress(gba_blob, species=[2, 5], time_range=(2, 6))
        np.testing.assert_array_equal(out, gba_full[[2, 5]][:, 2:6])


class TestPartialDecoder:
    def test_reuse_and_memoization(self, blob, full):
        pd = codec.PartialDecoder(blob)
        a = pd.decode(species=[1], time_range=(0, 4))
        b = pd.decode(species=[1], time_range=(4, 8))
        np.testing.assert_array_equal(
            np.concatenate([a, b], axis=1), full[[1]]
        )
        assert pd.shape == full.shape
        assert pd.n_species == full.shape[0]
        assert pd.version == 5  # writers default to the family layout

    def test_bytes_parsed_shrinks_with_selection(self, blob):
        pd = codec.PartialDecoder(blob)
        one = pd.bytes_parsed(species=[0])
        all_ = pd.bytes_parsed()
        assert one < all_
        # every byte of a v2 container is accounted to a purpose: the
        # full selection touches exactly the blob
        assert all_ == len(blob)
        # growing the selection strictly grows the touched extent, up to
        # exactly the blob length (CSR-of-CSR: extents partition the bytes)
        sizes = [pd.bytes_parsed(species=list(range(k + 1)))
                 for k in range(pd.n_species)]
        assert all(a < b for a, b in zip(sizes, sizes[1:]))
        assert sizes[-1] == len(blob)

    def test_invalid_selections_raise(self, blob):
        pd = codec.PartialDecoder(blob)
        s, t = pd.shape[0], pd.shape[1]
        with pytest.raises(ValueError, match="out of range"):
            pd.decode(species=s)
        with pytest.raises(ValueError, match="out of range"):
            pd.decode(species=[-s - 1])
        with pytest.raises(ValueError, match="duplicate"):
            pd.decode(species=[1, 1])
        with pytest.raises(ValueError, match="empty"):
            pd.decode(species=[])
        for bad in ((0, 0), (3, 2), (-1, 4), (0, t + 1)):
            with pytest.raises(ValueError, match="time_range"):
                pd.decode(time_range=bad)


@pytest.fixture(scope="module")
def scale_report():
    """A 12-species, 16-frame, 80x80 field at NRMSE 3e-4: enough data
    that the shared streams (latents, decoder) no longer dominate."""
    data = s3d.generate(s3d.S3DConfig(n_species=12, n_time=16, height=80,
                                      width=80, seed=1))["species"]
    cfg = PipelineConfig(ae_steps=150, corr_steps=80, conv_channels=(16, 32))
    return codec.GBATCCodec(cfg).fit(data).compress_report(
        target_nrmse=3e-4)[1]


class TestBytesParsedAtScale:
    @pytest.mark.parametrize("version", [2, 5])
    def test_one_species_touches_under_six_tenths(self, scale_report,
                                                  version):
        b = codec.encode(scale_report.artifact, version=version)
        pd = codec.PartialDecoder(b)
        assert pd.bytes_parsed() == len(b)  # every byte has a purpose
        assert pd.bytes_parsed(species=[0]) < 0.6 * len(b)


class TestCorruptionIsolation:
    @pytest.fixture()
    def bad_blob(self, blob):
        """v3 blob with species 2's coeff stream truncated mid-header
        (directory updated, so the framing itself stays valid).

        Emitted without the v4 integrity stream: these tests pin the
        *structural* detection path that pre-digest containers rely on
        (the digest path is covered in test_integrity.py)."""
        r = ContainerReader(blob)
        w = ContainerWriter(version=min(r.version, 3))
        for name in r.names:
            if name == "integrity":
                continue
            payload = r[name]
            if name == "meta" and r.version >= 5:
                payload = payload[1:]  # drop the family tag for v3
            if name == "guarantee":
                payload = _truncate_species_coeff(payload, sidx=2, keep=8)
            w.add(name, payload)
        return w.to_bytes()

    def test_corrupt_species_raises_named(self, bad_blob):
        with pytest.raises(ContainerFormatError, match="guarantee stream 2") \
                as ei:
            codec.decompress(bad_blob, species=[2])
        # the error is structured, not just a string: it names the stream
        # and the random-access unit at fault
        assert ei.value.stream == "guarantee"
        assert ei.value.unit == 2

    def test_full_decode_of_corrupt_blob_raises(self, bad_blob):
        with pytest.raises(ContainerFormatError):
            codec.decompress(bad_blob)

    def test_siblings_survive_corruption(self, bad_blob, full):
        """Sibling species decode from the same blob, bit-identical to the
        uncorrupted full decode — the bad stream poisons only itself."""
        pd = codec.PartialDecoder(bad_blob)
        for sidx in (0, 1, 3, 7):
            np.testing.assert_array_equal(
                pd.decode(species=[sidx]), full[[sidx]]
            )
        with pytest.raises(ContainerFormatError, match="guarantee stream 2") \
                as ei:
            pd.decode(species=[2])
        assert (ei.value.stream, ei.value.unit) == ("guarantee", 2)
        # a mixed request containing the bad species raises too ...
        with pytest.raises(ContainerFormatError, match="guarantee stream 2"):
            pd.decode(species=[1, 2])
        # ... and does not wedge later healthy requests on the same decoder
        np.testing.assert_array_equal(pd.decode(species=[1]), full[[1]])


class TestV1BackCompat:
    def test_full_round_trip_bit_identical(self, blob, blob_v1, full):
        assert ContainerReader(blob_v1).version == 1
        np.testing.assert_array_equal(codec.decompress(blob_v1), full)

    def test_selective_on_v1(self, blob_v1, full):
        pd = codec.PartialDecoder(blob_v1)
        assert pd.version == 1
        np.testing.assert_array_equal(
            pd.decode(species=[4], time_range=(3, 7)), full[[4]][:, 3:7]
        )
        assert pd.bytes_parsed(species=[4]) < len(blob_v1)

    def test_v1_artifact_round_trips_wire(self, blob, blob_v1):
        a2 = codec.decode_artifact(blob)
        a1 = codec.decode_artifact(blob_v1)
        np.testing.assert_array_equal(a1.latent_q, a2.latent_q)
        for g1, g2 in zip(a1.species_guarantees, a2.species_guarantees):
            np.testing.assert_array_equal(g1.coeff_q, g2.coeff_q)
            np.testing.assert_array_equal(g1.index_offsets, g2.index_offsets)
            np.testing.assert_array_equal(g1.index_flat, g2.index_flat)
            np.testing.assert_array_equal(g1.basis, g2.basis)
            assert g1.tau == g2.tau and g1.coeff_bin == g2.coeff_bin

    def test_reference_decode_handles_both_layouts(self, blob, blob_v1, full):
        """The retained pre-change orchestration reads both layouts and
        stays the fused path's bit-identity oracle."""
        np.testing.assert_array_equal(codec.decompress_reference(blob), full)
        np.testing.assert_array_equal(
            codec.decompress_reference(blob_v1), full
        )
