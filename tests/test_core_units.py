"""Unit tests for the compression substrate: blocking, quantization, entropy
coding, index coding, PCA, and metrics."""

import numpy as np
import pytest

from repro.core import blocking, entropy, index_coding, metrics, pca
from repro.core.quantization import dequantize, quantize


class TestBlocking:
    @pytest.mark.parametrize(
        "shape,geom",
        [
            ((6, 8, 20, 12), blocking.BlockGeometry(4, 5, 4)),
            ((3, 4, 10, 8), blocking.BlockGeometry(2, 5, 2)),
            ((1, 4, 5, 4), blocking.PAPER_GEOMETRY),
            ((58, 8, 10, 8), blocking.PAPER_GEOMETRY),
        ],
    )
    def test_round_trip(self, shape, geom):
        rng = np.random.default_rng(1)
        data = rng.normal(size=shape).astype(np.float32)
        b = blocking.to_blocks(data, geom)
        s, t, h, w = shape
        nb = (t // geom.bt) * (h // geom.ph) * (w // geom.pw)
        assert b.shape == (nb, s, geom.bt, geom.ph, geom.pw)
        assert np.array_equal(blocking.from_blocks(b, shape, geom), data)

    def test_vector_round_trip(self):
        rng = np.random.default_rng(2)
        data = rng.normal(size=(5, 8, 10, 8)).astype(np.float32)
        b = blocking.to_blocks(data, blocking.PAPER_GEOMETRY)
        v = blocking.blocks_as_vectors(b)
        assert v.shape == (5, b.shape[0], 80)
        assert np.array_equal(
            blocking.vectors_as_blocks(v, blocking.PAPER_GEOMETRY), b
        )

    def test_indivisible_raises(self):
        data = np.zeros((2, 7, 20, 12), np.float32)
        with pytest.raises(ValueError):
            blocking.to_blocks(data, blocking.PAPER_GEOMETRY)

    def test_block_locality(self):
        """A block must contain exactly one spatiotemporal patch."""
        geom = blocking.BlockGeometry(2, 2, 2)
        data = np.arange(1 * 4 * 4 * 4, dtype=np.float32).reshape(1, 4, 4, 4)
        b = blocking.to_blocks(data, geom)
        # first block = t 0:2, h 0:2, w 0:2 of species 0
        assert np.array_equal(b[0, 0], data[0, 0:2, 0:2, 0:2])


class TestQuantization:
    @pytest.mark.parametrize("bin_size", [1e-4, 0.01, 0.5, 3.0])
    def test_error_bound(self, bin_size):
        rng = np.random.default_rng(3)
        x = rng.normal(scale=10.0, size=10000).astype(np.float64)
        q, xhat = quantize(x, bin_size), dequantize(quantize(x, bin_size), bin_size)
        assert np.abs(x - xhat).max() <= bin_size / 2 + 1e-12
        assert q.dtype == np.int64

    def test_bad_bin(self):
        with pytest.raises(ValueError):
            quantize(np.ones(3), 0.0)


class TestHuffman:
    @pytest.mark.parametrize("seed", range(4))
    def test_round_trip_random(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 20000))
        vals = (rng.integers(-40, 40, size=n) ** 3) // rng.integers(1, 50)
        blob = entropy.huffman_encode(vals)
        assert np.array_equal(entropy.huffman_decode(blob), vals)
        assert entropy.huffman_size_bytes(vals) == len(blob)

    def test_empty_and_single_symbol(self):
        for vals in [np.zeros(0, np.int64), np.full(777, -3, np.int64)]:
            blob = entropy.huffman_encode(vals)
            assert np.array_equal(entropy.huffman_decode(blob), vals)

    def test_skewed_beats_raw(self):
        rng = np.random.default_rng(9)
        vals = np.rint(rng.normal(scale=1.5, size=100000)).astype(np.int64)
        assert entropy.huffman_size_bytes(vals) < vals.size  # << 8 bytes/sym

    def test_zstd_round_trip(self):
        data = np.arange(1000, dtype=np.int32).tobytes()
        assert entropy.zstd_unbytes(entropy.zstd_bytes(data)) == data

    def test_long_codes_beyond_table(self):
        """Fibonacci frequencies force code lengths past the 16-bit lookup
        table — the vectorized decoder's long-code path must stay exact."""
        fib = [1, 1]
        while len(fib) < 26:
            fib.append(fib[-1] + fib[-2])
        vals = np.concatenate(
            [np.full(f, i, np.int64) for i, f in enumerate(fib)]
        )
        np.random.default_rng(0).shuffle(vals)
        blob = entropy.huffman_encode(vals)
        k = int(np.frombuffer(blob, dtype="<u4", count=1, offset=12)[0])
        lengths = np.frombuffer(blob, dtype="<u1", count=k, offset=16 + 8 * k)
        assert lengths.max() > 16  # the premise: codes exceed the table
        assert np.array_equal(entropy.huffman_decode(blob), vals)

    def test_large_stream_round_trip(self):
        """Speculative chunk decode across many chunks, wide alphabet."""
        rng = np.random.default_rng(42)
        vals = np.rint(rng.normal(scale=25.0, size=300000)).astype(np.int64)
        blob = entropy.huffman_encode(vals)
        assert np.array_equal(entropy.huffman_decode(blob), vals)

    def test_truncated_stream_raises(self):
        vals = np.rint(np.random.default_rng(7).normal(
            scale=2.0, size=5000)).astype(np.int64)
        blob = entropy.huffman_encode(vals)
        with pytest.raises(ValueError):
            entropy.huffman_decode(blob[: len(blob) // 2])

    _I64 = np.iinfo(np.int64)
    _RNG = np.random.default_rng(11)
    # (values, takes the linear-time codebook)
    CODEBOOK_CASES = {
        "narrow_laplacian": (np.rint(_RNG.laplace(
            scale=20.0, size=60000)).astype(np.int64), True),
        "all_negative": (-1 - _RNG.integers(0, 300, size=4000), True),
        "single_symbol": (np.full(513, -9, np.int64), True),
        "empty": (np.zeros(0, np.int64), False),
        "int32": (np.rint(_RNG.laplace(scale=4.0, size=2500)).astype(
            np.int32), True),
        "sparse_wide_range": (_RNG.integers(-10**15, 10**15, size=700),
                              False),
        "int64_min_edge": (_I64.min + _RNG.integers(0, 12, size=900), True),
        "int64_max_edge": (_I64.max - _RNG.integers(0, 12, size=900), True),
        "int64_both_extremes": (np.array(
            [_I64.min, _I64.max, 0, 0, -1, _I64.min], np.int64), False),
    }

    @staticmethod
    def _unique_reference(vals):
        """The sort-based construction: ``np.unique`` symbols, frequencies
        through the inverse, per-value lookups through the inverse."""
        vals = np.asarray(vals).ravel()
        if vals.size == 0:
            return (b"HUF1" + bytes(12), (np.zeros(0, np.int64),) * 2, 16)
        symbols, inverse = np.unique(vals, return_inverse=True)
        freqs = np.bincount(inverse)
        lengths = entropy._code_lengths(freqs)
        codes = entropy._canonical_codes(lengths)
        sym_lengths, sym_codes = lengths[inverse], codes[inverse]
        offsets = np.concatenate(([0], np.cumsum(sym_lengths)[:-1]))
        total_bits = int(sym_lengths.sum())
        blob = (
            b"HUF1" + np.array([vals.size], "<u8").tobytes()
            + np.array([len(symbols)], "<u4").tobytes()
            + symbols.astype("<i8").tobytes() + lengths.astype("<u1").tobytes()
            + entropy._pack_payload(sym_codes, sym_lengths, offsets,
                                    total_bits)
        )
        size = 16 + 9 * len(symbols) + (total_bits + 7) // 8
        return blob, (symbols.astype(np.int64), lengths), size

    @pytest.mark.parametrize("case", sorted(CODEBOOK_CASES))
    def test_codebook_matches_unique_construction(self, case):
        """Encode, codebook and size are bitwise the sort-based
        construction's whether the codebook is counted (narrow integer
        ranges) or sorted (the fallback), and the stream round-trips."""
        vals, dense = self.CODEBOOK_CASES[case]
        assert entropy.dense_codebook(vals) is dense
        blob, (symbols, lengths), size = self._unique_reference(vals)
        assert entropy.huffman_encode(vals) == blob
        got_symbols, got_lengths = entropy.huffman_codebook(vals)
        assert got_symbols.dtype == symbols.dtype
        assert np.array_equal(got_symbols, symbols)
        assert np.array_equal(got_lengths, lengths)
        assert entropy.huffman_size_bytes(vals) == size == len(blob)
        assert np.array_equal(entropy.huffman_decode(blob), vals)

    @pytest.mark.parametrize("seed", range(5))
    def test_packed_encoder_parity_with_bitloop(self, seed):
        """The table-driven batched pack must be bit-identical to the
        retained per-code-bit reference on every payload, including codes
        that straddle 64-bit word boundaries."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 150000))
        vals = np.rint(rng.normal(scale=3.0 ** rng.integers(0, 4),
                                  size=n)).astype(np.int64)
        symbols, inverse = np.unique(vals, return_inverse=True)
        lengths = entropy._code_lengths(np.bincount(inverse))
        codes = entropy._canonical_codes(lengths)
        sym_lengths, sym_codes = lengths[inverse], codes[inverse]
        offsets = np.concatenate(([0], np.cumsum(sym_lengths)[:-1]))
        total_bits = int(sym_lengths.sum())
        assert entropy._pack_payload(
            sym_codes, sym_lengths, offsets, total_bits
        ) == entropy._pack_payload_bitloop(
            sym_codes, sym_lengths, offsets, total_bits
        )

    def test_packed_encoder_parity_long_codes(self):
        """Fibonacci frequencies push code lengths past 16 bits — the
        word-spill path of the packed encoder must stay exact."""
        fib = [1, 1]
        while len(fib) < 26:
            fib.append(fib[-1] + fib[-2])
        vals = np.concatenate(
            [np.full(f, i, np.int64) for i, f in enumerate(fib)]
        )
        np.random.default_rng(3).shuffle(vals)
        symbols, inverse = np.unique(vals, return_inverse=True)
        lengths = entropy._code_lengths(np.bincount(inverse))
        codes = entropy._canonical_codes(lengths)
        sym_lengths, sym_codes = lengths[inverse], codes[inverse]
        offsets = np.concatenate(([0], np.cumsum(sym_lengths)[:-1]))
        total_bits = int(sym_lengths.sum())
        assert lengths.max() > 16
        assert entropy._pack_payload(
            sym_codes, sym_lengths, offsets, total_bits
        ) == entropy._pack_payload_bitloop(
            sym_codes, sym_lengths, offsets, total_bits
        )


class TestIndexCoding:
    @pytest.mark.parametrize("seed", range(3))
    def test_round_trip(self, seed):
        rng = np.random.default_rng(seed)
        sets = []
        for _ in range(200):
            m = int(rng.integers(0, 30))
            sets.append(
                np.sort(rng.choice(80, size=m, replace=False)).astype(np.int64)
            )
        offsets, flat = index_coding.sets_to_csr(sets)
        blob = index_coding.encode_indices(offsets, flat)
        out_off, out_flat = index_coding.decode_indices(blob)
        np.testing.assert_array_equal(out_off, offsets)
        np.testing.assert_array_equal(out_flat, flat)
        assert index_coding.encoded_size_bytes(offsets, flat) == len(blob)

    def test_csr_set_conversion_round_trip(self):
        sets = [np.array([0, 3, 7]), np.zeros(0, np.int64), np.array([79]),
                np.zeros(0, np.int64)]
        offsets, flat = index_coding.sets_to_csr(sets)
        back = index_coding.csr_to_sets(offsets, flat)
        assert len(back) == len(sets)
        for a, b in zip(sets, back):
            np.testing.assert_array_equal(a, b)

    def test_empty_blocks_only_cost_length_fields(self):
        offsets = np.zeros(101, np.int64)
        flat = np.zeros(0, np.int64)
        blob = index_coding.encode_indices(offsets, flat)
        assert len(blob) == 4 + 2 * 100  # header + u16 lengths, zero bits
        out_off, out_flat = index_coding.decode_indices(blob)
        np.testing.assert_array_equal(out_off, offsets)
        assert out_flat.size == 0

    def test_prefix_property(self):
        """Leading-index selections must cost fewer bits than trailing ones."""
        lead = index_coding.sets_to_csr(
            [np.arange(5, dtype=np.int64) for _ in range(100)]
        )
        trail = index_coding.sets_to_csr(
            [np.arange(75, 80, dtype=np.int64) for _ in range(100)]
        )
        assert index_coding.encoded_size_bytes(*lead) < \
            index_coding.encoded_size_bytes(*trail)


class TestPCA:
    def test_orthonormal_and_sorted(self):
        rng = np.random.default_rng(5)
        r = rng.normal(size=(400, 32)) @ np.diag(np.linspace(3, 0.1, 32))
        u, ev = pca.pca_basis(r)
        assert np.allclose(u.T @ u, np.eye(32), atol=1e-10)
        assert np.all(np.diff(ev) <= 1e-9)

    def test_projection_reconstructs(self):
        rng = np.random.default_rng(6)
        r = rng.normal(size=(100, 16))
        u, _ = pca.pca_basis(r)
        c = pca.project(r, u)
        assert np.allclose(c @ u.T, r, atol=1e-10)


@pytest.mark.filterwarnings("error")
class TestMetrics:
    """Runs with warnings-as-errors: the next silent ``log10(0)`` /
    divide-by-zero in a metric fails loudly instead of leaking ``-inf``
    with a RuntimeWarning into a benchmark table."""

    def test_nrmse_zero(self):
        x = np.random.default_rng(0).normal(size=(4, 5))
        assert metrics.nrmse(x, x) == 0.0

    def test_nrmse_scale_invariant(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=1000)
        noise = rng.normal(size=1000) * 0.01
        a = metrics.nrmse(x, x + noise)
        b = metrics.nrmse(1e6 * x, 1e6 * (x + noise))
        assert np.isclose(a, b, rtol=1e-6)

    def test_nrmse_constant_field(self):
        x = np.full((6, 7), 2.5)
        assert metrics.nrmse(x, x.copy()) == 0.0
        assert metrics.nrmse(x, x + 1.0) == float("inf")

    def test_psnr_monotone(self):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(64, 64))
        small = x + 1e-4 * rng.normal(size=x.shape)
        big = x + 1e-2 * rng.normal(size=x.shape)
        assert metrics.psnr(x, small) > metrics.psnr(x, big)

    def test_psnr_constant_field(self):
        """rng == 0 with nonzero MSE must be handled explicitly (like
        nrmse), not reach log10(0) and warn its way to -inf."""
        x = np.full((8, 8), 3.0)
        assert metrics.psnr(x, x.copy()) == float("inf")
        assert metrics.psnr(x, x + 0.5) == float("-inf")

    def test_psnr_exact_match_any_range(self):
        x = np.random.default_rng(4).normal(size=(16, 16))
        assert metrics.psnr(x, x.copy()) == float("inf")

    def test_ssim_identity_and_noise(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(48, 48))
        assert metrics.ssim2d(x, x) == pytest.approx(1.0, abs=1e-9)
        assert metrics.ssim2d(x, x + rng.normal(size=x.shape)) < 0.9
