"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.block_quant import block_quant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gbatc_project import (
    gbatc_correct,
    gbatc_correct_batched,
    gbatc_project,
    gbatc_project_batched,
    gbatc_select_accumulate,
)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,tq,tk,d", [
        (1, 1, 128, 128, 64),
        (2, 3, 256, 256, 64),
        (1, 2, 128, 384, 128),   # cross: longer K
        (1, 1, 200, 200, 64),    # non-multiple of block
        (2, 2, 64, 64, 32),      # small everything
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, b, h, tq, tk, d, dtype):
        keys = jax.random.split(jax.random.PRNGKey(hash((b, tq, tk)) % 2**31), 3)
        q = _rand(keys[0], (b, h, tq, d), dtype)
        k = _rand(keys[1], (b, h, tk, d), dtype)
        v = _rand(keys[2], (b, h, tk, d), dtype)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    @pytest.mark.parametrize("window", [16, 64, 1000])
    def test_sliding_window(self, window):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(keys[0], (1, 2, 256, 64), jnp.float32)
        k = _rand(keys[1], (1, 2, 256, 64), jnp.float32)
        v = _rand(keys[2], (1, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_non_causal(self):
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(keys[0], (1, 1, 128, 64), jnp.float32)
        k = _rand(keys[1], (1, 1, 256, 64), jnp.float32)
        v = _rand(keys[2], (1, 1, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=False, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 256), (32, 128)])
    def test_block_shape_invariance(self, block_q, block_k):
        keys = jax.random.split(jax.random.PRNGKey(2), 3)
        q = _rand(keys[0], (1, 2, 256, 64), jnp.float32)
        k = _rand(keys[1], (1, 2, 256, 64), jnp.float32)
        v = _rand(keys[2], (1, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=block_q,
                              block_k=block_k, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestRWKV6Scan:
    @pytest.mark.parametrize("b,t,h,n,chunk", [
        (1, 32, 1, 16, 8),
        (2, 64, 2, 32, 16),
        (1, 100, 2, 64, 32),   # non-multiple of chunk
        (1, 128, 4, 64, 64),
    ])
    def test_matches_scan_ref(self, b, t, h, n, chunk):
        keys = jax.random.split(jax.random.PRNGKey(t + n), 5)
        r = _rand(keys[0], (b, t, h, n), jnp.float32)
        k = _rand(keys[1], (b, t, h, n), jnp.float32)
        v = _rand(keys[2], (b, t, h, n), jnp.float32)
        # decays in (0,1), including very small values (stability check)
        w = jax.nn.sigmoid(3.0 * _rand(keys[3], (b, t, h, n), jnp.float32))
        w = jnp.clip(w, 1e-6, 1.0 - 1e-6)
        u = 0.5 * _rand(keys[4], (h, n), jnp.float32)
        out, sT = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
        want, sT_want = ref.rwkv6_scan_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_want),
                                   rtol=2e-4, atol=2e-4)

    def test_extreme_decay_stable(self):
        """Near-zero decays (w -> 0) must not produce inf/nan (the chunked
        form's pairwise exponents are always <= 0)."""
        b, t, h, n = 1, 64, 1, 16
        keys = jax.random.split(jax.random.PRNGKey(9), 4)
        r = _rand(keys[0], (b, t, h, n), jnp.float32)
        k = _rand(keys[1], (b, t, h, n), jnp.float32)
        v = _rand(keys[2], (b, t, h, n), jnp.float32)
        w = jnp.full((b, t, h, n), 1e-30, jnp.float32)
        u = _rand(keys[3], (h, n), jnp.float32)
        out, sT = rwkv6_scan(r, k, v, w, u, chunk=16, interpret=True)
        assert bool(jnp.isfinite(out).all() & jnp.isfinite(sT).all())
        want, _ = ref.rwkv6_scan_ref(r, k, v, w, u)
        # log-decays of ~-69 per step push the chunked form's fp32 cumsum to
        # ~-1e3 where one ulp is ~1e-4; agreement is precision-bound there
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_initial_state_carried(self):
        b, t, h, n = 1, 32, 1, 16
        keys = jax.random.split(jax.random.PRNGKey(3), 6)
        r = _rand(keys[0], (b, t, h, n), jnp.float32)
        k = _rand(keys[1], (b, t, h, n), jnp.float32)
        v = _rand(keys[2], (b, t, h, n), jnp.float32)
        w = jax.nn.sigmoid(_rand(keys[3], (b, t, h, n), jnp.float32))
        u = _rand(keys[4], (h, n), jnp.float32)
        s0 = _rand(keys[5], (b, h, n, n), jnp.float32)
        out, sT = rwkv6_scan(r, k, v, w, u, s0, chunk=8, interpret=True)
        want, sT_want = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_want),
                                   rtol=2e-4, atol=2e-4)


class TestRGLRUScan:
    @pytest.mark.parametrize("b,t,w,chunk", [
        (1, 64, 32, 16),
        (2, 128, 256, 64),
        (1, 100, 130, 32),  # non-multiples everywhere
    ])
    def test_matches_scan_ref(self, b, t, w, chunk):
        keys = jax.random.split(jax.random.PRNGKey(t + w), 3)
        a = jax.nn.sigmoid(2.0 + _rand(keys[0], (b, t, w), jnp.float32))
        bb = _rand(keys[1], (b, t, w), jnp.float32)
        h0 = _rand(keys[2], (b, w), jnp.float32)
        h, hT = rglru_scan(a, bb, h0, chunk=chunk, interpret=True)
        want, hT_want = ref.rglru_scan_ref(a, bb, h0)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_want),
                                   rtol=2e-4, atol=2e-4)

    def test_tiny_decay_stable(self):
        a = jnp.full((1, 32, 16), 1e-25, jnp.float32)
        bb = jnp.ones((1, 32, 16), jnp.float32)
        h, hT = rglru_scan(a, bb, chunk=8, interpret=True)
        assert bool(jnp.isfinite(h).all())
        want, _ = ref.rglru_scan_ref(a, bb)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestBlockQuant:
    @pytest.mark.parametrize("shape,block", [
        ((64, 256), 64),
        ((3, 7, 128), 32),
        ((1024, 64), 64),
    ])
    @pytest.mark.parametrize("n_bits", [4, 8])
    def test_matches_ref(self, shape, block, n_bits):
        x = _rand(jax.random.PRNGKey(sum(shape)), shape, jnp.float32)
        out, scale = block_quant(x, n_bits=n_bits, block=block, interpret=True)
        want, scale_want = ref.block_quant_ref(x, n_bits=n_bits, block=block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(scale), np.asarray(scale_want),
                                   rtol=1e-6, atol=1e-6)

    def test_quant_error_bounded(self):
        x = _rand(jax.random.PRNGKey(5), (128, 128), jnp.float32)
        out, scale = block_quant(x, n_bits=8, block=64, interpret=True)
        err = jnp.abs(out - x)
        # half-bin bound plus fp32 round-off: a value landing exactly on a
        # .5 quantization boundary has error == scale/2, and the dequant
        # multiply q*scale rounds relative to |x| (not the bound), adding
        # up to ~ulp(|x|) ~ 1e-7 * |x| on top
        bound = jnp.repeat(scale, 64, axis=-1) * 0.5 + 2e-7 * jnp.abs(x) + 1e-9
        assert bool((err <= bound).all())


class TestGBATCKernels:
    @pytest.mark.parametrize("nb,d", [(100, 80), (1000, 80), (64, 64), (513, 80)])
    def test_project_matches_ref(self, nb, d):
        keys = jax.random.split(jax.random.PRNGKey(nb), 2)
        r = _rand(keys[0], (nb, d), jnp.float32)
        q, _ = jnp.linalg.qr(_rand(keys[1], (d, d), jnp.float32))
        c = gbatc_project(r, q, interpret=True)
        want = ref.gbatc_project_ref(r, q)
        np.testing.assert_allclose(np.asarray(c), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_correct_matches_ref_and_guarantee_math(self):
        nb, d = 200, 80
        keys = jax.random.split(jax.random.PRNGKey(7), 3)
        x = _rand(keys[0], (nb, d), jnp.float32)
        xr = x + 0.1 * _rand(keys[1], (nb, d), jnp.float32)
        q, _ = jnp.linalg.qr(_rand(keys[2], (d, d), jnp.float32))
        c = gbatc_project(x - xr, q, interpret=True)
        mask = (jnp.abs(c) > jnp.quantile(jnp.abs(c), 0.5)).astype(jnp.float32)
        out = gbatc_correct(xr, c, mask, q, interpret=True)
        want = ref.gbatc_correct_ref(xr, c, mask, q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # keeping ALL coefficients must reconstruct x exactly (orthonormal U)
        full = gbatc_correct(xr, c, jnp.ones_like(c), q, interpret=True)
        np.testing.assert_allclose(np.asarray(full), np.asarray(x),
                                   rtol=1e-4, atol=1e-4)


class TestGBATCBatchedKernels:
    """Batched-over-species variants: one dispatch, per-species basis."""

    @pytest.mark.parametrize("s,nb,d,spt,rpt,lane", [
        (3, 100, 80, None, None, None),   # single grid step (engine/CPU mode)
        (2, 513, 130, 1, 256, 128),       # padding on every axis, MXU lanes
        (1, 7, 4, None, None, None),      # tiny everything
        (5, 64, 80, 2, 16, 8),            # species tiling + row tiling
    ])
    def test_project_matches_ref(self, s, nb, d, spt, rpt, lane):
        keys = jax.random.split(jax.random.PRNGKey(s * 1000 + nb), 2)
        r = _rand(keys[0], (s, nb, d), jnp.float32)
        u = jnp.stack([
            jnp.linalg.qr(_rand(k, (d, d), jnp.float32))[0]
            for k in jax.random.split(keys[1], s)
        ])
        c = gbatc_project_batched(r, u, species_per_tile=spt, rows_per_tile=rpt,
                                  interpret=True, lane=lane)
        want = ref.gbatc_project_batched_ref(r, u)
        np.testing.assert_allclose(np.asarray(c), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("s,nb,d,spt,rpt,lane", [
        (3, 100, 80, None, None, None),
        (2, 513, 130, 1, 256, 128),
    ])
    def test_correct_matches_ref(self, s, nb, d, spt, rpt, lane):
        keys = jax.random.split(jax.random.PRNGKey(s * 77 + nb), 3)
        x = _rand(keys[0], (s, nb, d), jnp.float32)
        c = _rand(keys[1], (s, nb, d), jnp.float32)
        u = jnp.stack([
            jnp.linalg.qr(_rand(k, (d, d), jnp.float32))[0]
            for k in jax.random.split(keys[2], s)
        ])
        out = gbatc_correct_batched(x, c, u, species_per_tile=spt,
                                    rows_per_tile=rpt, interpret=True, lane=lane)
        want = ref.gbatc_correct_batched_ref(x, c, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("s,nb,d,spt,rpt,lane", [
        (3, 100, 80, None, None, None),
        (2, 513, 130, 1, 256, 128),
    ])
    def test_select_accumulate_matches_ref(self, s, nb, d, spt, rpt, lane):
        keys = jax.random.split(jax.random.PRNGKey(s + nb + d), 4)
        x = _rand(keys[0], (s, nb, d), jnp.float32)
        c = _rand(keys[1], (s, nb, d), jnp.float32)
        u = jnp.stack([
            jnp.linalg.qr(_rand(k, (d, d), jnp.float32))[0]
            for k in jax.random.split(keys[2], s)
        ])
        # a valid rank field: per-row permutation of 0..d-1
        rank = jnp.argsort(jnp.argsort(-jnp.abs(c), axis=-1), axis=-1).astype(
            jnp.int32)
        m = jax.random.randint(keys[3], (s, nb), 0, d + 1, jnp.int32)
        out = gbatc_select_accumulate(x, c, rank, m, u, species_per_tile=spt,
                                      rows_per_tile=rpt, interpret=True,
                                      lane=lane)
        want = ref.gbatc_select_accumulate_ref(x, c, rank, m, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_select_accumulate_m_zero_is_identity(self):
        """m == 0 must leave x_rec untouched (the non-needs-row contract)."""
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        x = _rand(keys[0], (2, 64, 80), jnp.float32)
        c = _rand(keys[1], (2, 64, 80), jnp.float32)
        u = jnp.stack([jnp.eye(80, dtype=jnp.float32)] * 2)
        rank = jnp.broadcast_to(jnp.arange(80, dtype=jnp.int32), (2, 64, 80))
        m = jnp.zeros((2, 64), jnp.int32)
        out = gbatc_select_accumulate(x, c, rank, m, u, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0)

    def test_fp64_project_in_interpret(self):
        """The guarantee engine's selection math runs the projection in
        fp64 under interpret mode — dtype must be honored end to end."""
        rng = np.random.default_rng(0)
        r = rng.normal(size=(2, 50, 80))
        u = np.stack([np.linalg.qr(rng.normal(size=(80, 80)))[0]
                      for _ in range(2)])
        with jax.enable_x64(True):
            c = gbatc_project_batched(jnp.asarray(r), jnp.asarray(u),
                                      interpret=True)
            assert c.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(c), np.matmul(r, u),
                                   rtol=1e-12, atol=1e-12)
