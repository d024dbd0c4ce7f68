"""Integration tests: full GBA/GBATC pipeline on a small S3D surrogate.

Kept deliberately small (few AE steps) — these check *invariants* (guarantee,
decode consistency, accounting), not compression quality. The compression
ratio at the paper's width is measured on the chip by the ``bench/`` cells.
"""

import numpy as np
import pytest

from repro.core import blocking, gae, metrics
from repro.core.pipeline import GBATCPipeline, PipelineConfig
from repro.data import s3d


@pytest.fixture(scope="module")
def small_data():
    cfg = s3d.S3DConfig(n_species=8, n_time=8, height=40, width=32, seed=3)
    return s3d.generate(cfg)["species"]


@pytest.fixture(scope="module")
def fitted_gbatc(small_data):
    cfg = PipelineConfig(ae_steps=60, corr_steps=30, conv_channels=(16, 32))
    pipe = GBATCPipeline(cfg, n_species=small_data.shape[0])
    pipe.fit(small_data)
    return pipe


class TestPipeline:
    def test_error_bound_guaranteed(self, small_data, fitted_gbatc):
        target = 1e-3
        rep = fitted_gbatc.compress(target_nrmse=target)
        # the l2-per-block bound implies per-species NRMSE <= target
        assert rep.per_species_nrmse.max() <= target * (1 + 1e-3)
        assert rep.mean_nrmse <= target

    def test_decompress_bit_consistent(self, small_data, fitted_gbatc):
        rep = fitted_gbatc.compress(target_nrmse=2e-3)
        dec = fitted_gbatc.decompress(rep.artifact)
        np.testing.assert_allclose(dec, rep.recon, atol=1e-6)

    def test_block_level_guarantee(self, small_data, fitted_gbatc):
        target = 1e-3
        rep = fitted_gbatc.compress(target_nrmse=target)
        geom = fitted_gbatc.cfg.geometry
        tau = target * np.sqrt(geom.block_size)
        normed, _, rngs = GBATCPipeline._normalize(small_data)
        rec_normed = (
            rep.recon - fitted_gbatc._norm[0][:, None, None, None]
        ) / rngs[:, None, None, None]
        vo = blocking.blocks_as_vectors(blocking.to_blocks(normed, geom))
        vr = blocking.blocks_as_vectors(blocking.to_blocks(rec_normed.astype(np.float32), geom))
        for s in range(small_data.shape[0]):
            assert gae.verify_guarantee(vo[s], vr[s], tau)

    def test_tighter_target_lower_cr(self, fitted_gbatc):
        loose = fitted_gbatc.compress(target_nrmse=5e-3)
        tight = fitted_gbatc.compress(target_nrmse=2e-4)
        assert tight.compression_ratio < loose.compression_ratio
        assert tight.mean_nrmse < loose.mean_nrmse

    def test_byte_accounting_complete(self, fitted_gbatc):
        rep = fitted_gbatc.compress(target_nrmse=1e-3)
        bb = rep.bytes_breakdown
        parts = bb["latent"] + bb["decoder"] + bb["correction"] + bb["coeff"] \
            + bb["index"] + bb["basis"] + bb["meta"]
        assert parts == bb["total"]
        assert bb["total"] > 0
        assert rep.compression_ratio > 0

    def test_gba_variant_runs(self, small_data):
        cfg = PipelineConfig(
            ae_steps=40, use_correction=False, conv_channels=(16, 32)
        )
        pipe = GBATCPipeline(cfg, n_species=small_data.shape[0])
        rep = pipe.fit_compress(small_data, target_nrmse=1e-3)
        assert rep.bytes_breakdown["correction"] == 0
        assert rep.mean_nrmse <= 1e-3

    def test_compress_before_fit_raises(self, small_data):
        pipe = GBATCPipeline(PipelineConfig(), n_species=small_data.shape[0])
        with pytest.raises(RuntimeError):
            pipe.compress()


class TestSurrogateData:
    def test_shapes_and_finiteness(self, small_data):
        assert small_data.shape == (8, 8, 40, 32)
        assert np.isfinite(small_data).all()
        assert (small_data >= 0).all()  # mass fractions

    def test_species_span_decades(self):
        ds = s3d.generate(s3d.S3DConfig(n_species=16, n_time=8, height=40, width=40))
        peaks = ds["species"].max(axis=(1, 2, 3))
        assert peaks.max() / peaks.min() > 1e3  # majors vs minors

    def test_temporal_correlation_present(self, small_data):
        a, b = small_data[:, 0], small_data[:, 1]
        corr = np.corrcoef(a.ravel(), b.ravel())[0, 1]
        assert corr > 0.9  # adjacent frames strongly correlated


class TestRoundTripAfterCSR:
    """decompress(compress(...)) must satisfy the guarantee end to end —
    the CSR index/coefficient streams are the only carrier of corrections."""

    def test_decompressed_output_meets_guarantee(self, small_data, fitted_gbatc):
        target = 1e-3
        rep = fitted_gbatc.compress(target_nrmse=target)
        dec = fitted_gbatc.decompress(rep.artifact)
        geom = fitted_gbatc.cfg.geometry
        tau = target * np.sqrt(geom.block_size)
        normed, mn, rngs = GBATCPipeline._normalize(small_data)
        dec_normed = (
            (dec - fitted_gbatc._norm[0][:, None, None, None])
            / rngs[:, None, None, None]
        )
        vo = blocking.blocks_as_vectors(blocking.to_blocks(normed, geom))
        vr = blocking.blocks_as_vectors(
            blocking.to_blocks(dec_normed.astype(np.float32), geom)
        )
        for s in range(small_data.shape[0]):
            assert gae.verify_guarantee(vo[s], vr[s], tau)
        # per-species NRMSE of the decompressed tensor also meets the target
        per = np.array([
            metrics.nrmse(small_data[s], dec[s])
            for s in range(small_data.shape[0])
        ])
        assert per.max() <= target * (1 + 1e-3)

    def test_artifact_streams_survive_wire_round_trip(self, fitted_gbatc):
        """Index sets re-encoded through the Fig. 2 bitstream decode to the
        same CSR arrays the artifact carries."""
        from repro.core import index_coding

        rep = fitted_gbatc.compress(target_nrmse=1e-3)
        for art in rep.artifact.species_guarantees:
            blob = index_coding.encode_indices(art.index_offsets, art.index_flat)
            off, flat = index_coding.decode_indices(blob)
            np.testing.assert_array_equal(off, art.index_offsets)
            np.testing.assert_array_equal(flat, art.index_flat)
            assert len(blob) == art.index_bytes()

    def test_target_sweep_reuses_prepared_state(self, fitted_gbatc):
        """Sweeping error bounds must hit the cached tau-independent state
        (one prepared entry per (latent_bin, correction) key) and still
        produce bound-satisfying reports."""
        fitted_gbatc._prepared.clear()
        for target in (5e-3, 1e-3, 3e-4):
            rep = fitted_gbatc.compress(target_nrmse=target)
            assert rep.per_species_nrmse.max() <= target * (1 + 1e-3)
        assert len(fitted_gbatc._prepared) == 1
