"""Tests for Algorithm 1 — the error-bound guarantee is the paper's core claim."""

import numpy as np
import pytest

from repro.core import gae


def _make_case(seed, nb=300, d=80, noise=0.1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(nb, d)).astype(np.float32)
    x_rec = x + noise * rng.normal(size=(nb, d)).astype(np.float32)
    return x, x_rec


def _fp32_case(case):
    rng = np.random.default_rng(11)
    if case == "gaussian":
        x = rng.normal(size=(3, 400, 80)).astype(np.float32)
        return x, x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
    x = rng.standard_t(df=1.5, size=(3, 400, 80)).astype(np.float32)
    return x, np.zeros_like(x)  # terrible reconstruction


def _fp32_prepared(x, x_rec, rel_err=0.0):
    """The state an MXU engine prepares: fp32 projections of the fp32
    residual onto the fp32 basis, through the projection kernel, with
    ``rel_err`` of each block's ||r|| added as noise when asked."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.kernels.gbatc_project import gbatc_project_batched

    engine = gae.GuaranteeEngine(select_backend="jit")
    prep = engine.prepare(x, x_rec)
    r32 = (x.astype(np.float64) - x_rec).astype(np.float32)
    c = np.array(gbatc_project_batched(
        r32, prep.basis.astype(np.float32), interpret=True))
    assert c.dtype == np.float32
    if rel_err:
        noise = np.random.default_rng(3).normal(size=c.shape)
        noise *= rel_err * np.sqrt(prep.norms2 / c.shape[2])[..., None]
        c += noise.astype(np.float32)
    order = gae._stable_desc_order(c * c)
    c_sorted = np.take_along_axis(c, order, axis=-1)
    inv_rank = np.empty(c.shape, np.int32)
    iota = np.broadcast_to(np.arange(c.shape[2], dtype=np.int32), c.shape)
    np.put_along_axis(inv_rank, order, iota, axis=-1)
    with jax.enable_x64(True):
        return engine, dataclasses.replace(
            prep, coeffs=c, coeffs_sorted=c_sorted, inv_rank=inv_rank,
            coeffs_dev=jnp.asarray(c), coeffs_sorted_dev=jnp.asarray(c_sorted),
            inv_rank_dev=jnp.asarray(inv_rank),
        )


def _assert_held_and_replayed(engine, x, x_rec, corrected, arts, tau):
    """Every block within tau in fp64, with no slack, and the decode
    replay bitwise what the engine checked."""
    r = x.astype(np.float64) - corrected
    assert np.sum(r * r, axis=2).max() <= tau * tau
    np.testing.assert_array_equal(engine.apply_batched(x_rec, arts), corrected)


class TestGuarantee:
    @pytest.mark.parametrize("tau", [0.1, 0.5, 2.0])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_bound_holds_every_block(self, tau, seed):
        x, x_rec = _make_case(seed)
        corrected, art = gae.guarantee(x, x_rec, tau)
        assert gae.verify_guarantee(x, corrected, tau)
        r = np.linalg.norm(x.astype(np.float64) - corrected, axis=1)
        assert r.max() <= tau + 1e-4

    def test_bound_holds_with_heavy_tailed_residuals(self):
        rng = np.random.default_rng(7)
        x = rng.standard_t(df=1.5, size=(200, 80)).astype(np.float32)
        x_rec = np.zeros_like(x)  # terrible reconstruction
        corrected, art = gae.guarantee(x, x_rec, 0.25)
        assert gae.verify_guarantee(x, corrected, 0.25)

    @pytest.mark.parametrize("case", ["gaussian", "heavy_tailed"])
    @pytest.mark.parametrize("tau", [0.05, 0.5])
    def test_fp32_projection_holds_bound_every_block(self, case, tau):
        """The MXU path projects in fp32; its cut must hold the real
        residual, checked in fp64, and not only the coefficient-space
        count, and decode must replay exactly what was checked."""
        x, x_rec = _fp32_case(case)
        engine, prep = _fp32_prepared(x, x_rec)
        corrected, arts = engine.select(prep, tau)
        _assert_held_and_replayed(engine, x, x_rec, corrected, arts, tau)

    def test_fp32_cut_that_falls_short_is_held(self):
        """A projection off by far more than fp32 rounding makes the cut
        count too little energy left in some blocks; the engine must find
        them by their real residual and keep more coefficients there."""
        import jax

        tau = 0.05
        x, x_rec = _fp32_case("gaussian")
        engine, prep = _fp32_prepared(x, x_rec, rel_err=1e-3)
        bin_size = gae._effective_bin(0.0, tau, x.shape[2])
        with jax.enable_x64(True):
            cut_only = np.asarray(engine._select_jit(
                prep.coeffs_dev, prep.coeffs_sorted_dev, prep.inv_rank_dev,
                prep.norms2_dev, prep.x_rec_dev, prep.basis32_dev,
                np.float64(tau * tau), np.float64(bin_size),
            )[0])
        r = x.astype(np.float64) - cut_only
        assert np.sum(r * r, axis=2).max() > tau * tau
        corrected, arts = engine.select(prep, tau)
        _assert_held_and_replayed(engine, x, x_rec, corrected, arts, tau)

    def test_fp32_hold_bound_counts_its_rounds(self, tmp_path):
        """Traced, the hold loop's span says how many blocks its first
        replay found over tau and how many rounds it took to hold them."""
        import glob

        import jax

        x, x_rec = _fp32_case("gaussian")
        engine, prep = _fp32_prepared(x, x_rec, rel_err=1e-3)
        jax.profiler.start_trace(str(tmp_path))
        try:
            engine.select(prep, 0.05)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
        (stats,) = [dict(e.stats)
                    for plane in jax.profiler.ProfileData.from_file(
                        path).planes
                    for line in plane.lines for e in line.events
                    if e.name == "gbatc.guarantee.hold_bound"]
        assert stats["blocks_over"] > 0 and stats["rounds"] >= 2

    def test_decode_replay_matches(self):
        x, x_rec = _make_case(2)
        corrected, art = gae.guarantee(x, x_rec, 0.4)
        replay = gae.apply_correction(x_rec, art)
        np.testing.assert_allclose(replay, corrected, atol=1e-6)

    def test_loose_bound_stores_nothing(self):
        x, x_rec = _make_case(3, noise=0.01)
        corrected, art = gae.guarantee(x, x_rec, 1e6)
        assert art.coeff_q.size == 0
        assert art.basis.shape[1] == 0
        np.testing.assert_array_equal(corrected, x_rec.astype(np.float32))

    def test_tighter_bound_costs_more(self):
        x, x_rec = _make_case(4)
        _, loose = gae.guarantee(x, x_rec, 1.0)
        _, tight = gae.guarantee(x, x_rec, 0.1)
        assert tight.total_bytes() > loose.total_bytes()

    def test_coefficients_prefer_leading_basis(self):
        """Energy-sorted selection should concentrate on leading PCA vectors
        when the residual is low-rank — the premise of the Fig. 2 coding."""
        rng = np.random.default_rng(5)
        d, rank = 64, 4
        factors = rng.normal(size=(rank, d))
        weights = rng.normal(size=(500, rank))
        x_rec = np.zeros((500, d), np.float32)
        x = (weights @ factors).astype(np.float32)
        _, art = gae.guarantee(x, x_rec, 0.05)
        used = np.concatenate([s for s in art.index_sets if s.size])
        # ~all selected indices within the true rank (+ tiny noise margin)
        assert np.quantile(used, 0.99) <= rank + 1

    def test_custom_coeff_bin_clamped_for_guarantee(self):
        x, x_rec = _make_case(6)
        # absurdly coarse bin must be clamped so the bound still holds
        corrected, art = gae.guarantee(x, x_rec, 0.3, coeff_bin=100.0)
        assert gae.verify_guarantee(x, corrected, 0.3)
        assert art.coeff_bin <= 1.8 * 0.3 / np.sqrt(80) + 1e-12


class TestGuaranteeProperties:
    """Property-style sweeps (hypothesis unavailable offline): random shapes,
    scales, noise levels — the bound must hold unconditionally."""

    @pytest.mark.parametrize("trial", range(10))
    def test_random_cases(self, trial):
        rng = np.random.default_rng(100 + trial)
        nb = int(rng.integers(1, 400))
        d = int(rng.integers(4, 128))
        scale = 10.0 ** rng.uniform(-6, 4)
        noise = 10.0 ** rng.uniform(-3, 0)
        tau = scale * 10.0 ** rng.uniform(-3, 0.5)
        x = (scale * rng.normal(size=(nb, d))).astype(np.float32)
        x_rec = x + (scale * noise * rng.normal(size=(nb, d))).astype(np.float32)
        corrected, art = gae.guarantee(x, x_rec, tau)
        assert gae.verify_guarantee(x, corrected, tau)
        replay = gae.apply_correction(x_rec, art)
        np.testing.assert_allclose(replay, corrected, rtol=1e-5, atol=1e-6 * scale)


def _assert_artifact_equal(a, b):
    """Bit-identical artifact contract (the engine's byte-accounting claim)."""
    np.testing.assert_array_equal(a.coeff_q, b.coeff_q)
    np.testing.assert_array_equal(a.index_offsets, b.index_offsets)
    np.testing.assert_array_equal(a.index_flat, b.index_flat)
    np.testing.assert_array_equal(a.basis, b.basis)
    assert a.coeff_bin == b.coeff_bin
    assert a.tau == b.tau
    assert a.total_bytes() == b.total_bytes()


class TestEngineOracleParity:
    """Device engine vs the retained numpy oracle (gae_ref): identical byte
    accounting, matching corrections, on adversarial geometries."""

    def _parity(self, x, xr, taus, engine=None):
        from repro.core import gae_ref

        engine = engine or gae.default_engine()
        prep = engine.prepare(x, xr)
        for tau in taus:
            corrected, arts = engine.select(prep, tau)
            for s in range(x.shape[0]):
                c_ref, a_ref = gae_ref.guarantee(x[s], xr[s], tau)
                _assert_artifact_equal(arts[s], a_ref)
                np.testing.assert_allclose(corrected[s], c_ref,
                                           atol=2e-5, rtol=1e-5)
                assert gae.verify_guarantee(x[s], corrected[s], tau)
                replay = gae.apply_correction(xr[s], arts[s])
                np.testing.assert_allclose(replay, gae_ref.apply_correction(
                    xr[s], a_ref), atol=2e-6)
            dec = gae.apply_correction_batched(xr, arts, engine)
            np.testing.assert_allclose(dec, corrected, atol=1e-6)

    def test_no_block_needs_fixing(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(3, 120, 80)).astype(np.float32)
        xr = x + 1e-5 * rng.normal(size=x.shape).astype(np.float32)
        self._parity(x, xr, [10.0])

    def test_every_block_needs_fixing(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(3, 120, 80)).astype(np.float32)
        xr = np.zeros_like(x)  # terrible reconstruction everywhere
        self._parity(x, xr, [0.8, 0.3])

    def test_mixed_species_some_empty(self):
        """One species within bound, one far out — batched dispatch must
        keep the clean species byte-free and untouched."""
        rng = np.random.default_rng(2)
        x = rng.normal(size=(2, 150, 64)).astype(np.float32)
        xr = x.copy()
        xr[1] += 0.5 * rng.normal(size=x.shape[1:]).astype(np.float32)
        prep = gae.default_engine().prepare(x, xr)
        corrected, arts = gae.default_engine().select(prep, 1.0)
        assert arts[0].coeff_q.size == 0 and arts[0].basis.shape[1] == 0
        assert arts[1].coeff_q.size > 0
        np.testing.assert_array_equal(corrected[0], xr[0])
        self._parity(x, xr, [1.0])

    def test_d_not_multiple_of_lane(self):
        """D=130 crosses the 128-lane boundary; force MXU-style padding."""
        engine = gae.GuaranteeEngine(interpret=True, lane=128)
        rng = np.random.default_rng(3)
        x = rng.normal(size=(2, 90, 130)).astype(np.float32)
        xr = x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
        self._parity(x, xr, [0.9, 0.4], engine=engine)

    def test_nb_not_multiple_of_rows_per_tile(self):
        engine = gae.GuaranteeEngine(
            interpret=True, species_per_tile=1, rows_per_tile=256
        )
        rng = np.random.default_rng(4)
        x = rng.normal(size=(3, 513, 80)).astype(np.float32)
        xr = x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
        self._parity(x, xr, [0.7], engine=engine)

    def test_float64_reconstructions_keep_oracle_parity(self):
        """The seed API accepted float64 x_rec; the engine must not narrow
        it before forming the residual, or byte accounting drifts."""
        rng = np.random.default_rng(9)
        x = rng.normal(size=(2, 120, 80))  # float64, as the seed allowed
        xr = x + 0.1 * rng.normal(size=x.shape)
        self._parity(x, xr, [0.8, 0.3])

    def test_jit_selection_backend_matches(self):
        """The jnp selection backend (accelerator path) must produce the
        same artifacts as the default host backend and the oracle."""
        engine = gae.GuaranteeEngine(interpret=True, select_backend="jit")
        rng = np.random.default_rng(6)
        x = rng.normal(size=(2, 160, 80)).astype(np.float32)
        xr = x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
        self._parity(x, xr, [0.8, 0.35], engine=engine)
        host = gae.GuaranteeEngine(interpret=True, select_backend="host")
        pj = engine.prepare(x, xr)
        ph = host.prepare(x, xr)
        for tau in (0.8, 0.35):
            cj, aj = engine.select(pj, tau)
            ch, ah = host.select(ph, tau)
            np.testing.assert_allclose(cj, ch, atol=1e-6)
            for a, b in zip(aj, ah):
                _assert_artifact_equal(a, b)

    def test_prepared_state_reused_across_taus(self):
        """The tau sweep off one prepare must equal fresh per-tau runs."""
        rng = np.random.default_rng(5)
        x = rng.normal(size=(2, 200, 80)).astype(np.float32)
        xr = x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
        engine = gae.default_engine()
        prep = engine.prepare(x, xr)
        for tau in (1.0, 0.5, 0.25):
            corr_sweep, arts_sweep = engine.select(prep, tau)
            corr_fresh, arts_fresh = gae.guarantee_batched(x, xr, tau)
            np.testing.assert_array_equal(corr_sweep, corr_fresh)
            for a, b in zip(arts_sweep, arts_fresh):
                _assert_artifact_equal(a, b)


class TestCSRArtifact:
    def test_csr_layout_consistent(self):
        x, x_rec = _make_case(11)
        _, art = gae.guarantee(x, x_rec, 0.3)
        assert art.index_offsets.shape == (x.shape[0] + 1,)
        assert art.index_offsets[0] == 0
        assert art.index_offsets[-1] == art.index_flat.size == art.coeff_q.size
        counts = np.diff(art.index_offsets)
        assert (counts >= 0).all()
        # ascending indices within each block
        for ids in art.index_sets:
            assert np.all(np.diff(ids) > 0) or ids.size <= 1

    def test_size_memoization_stable(self):
        x, x_rec = _make_case(12)
        _, art = gae.guarantee(x, x_rec, 0.3)
        first = (art.coeff_bytes(), art.index_bytes(), art.total_bytes())
        assert (art.coeff_bytes(), art.index_bytes(), art.total_bytes()) == first
        assert art._coeff_bytes is not None  # memo actually populated
