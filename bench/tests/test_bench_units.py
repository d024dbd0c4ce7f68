"""Units of the benchmark's yardstick, on the CPU: trace reduction, counts,
roofline, traffic generation, the reference check and the refusal off a
chip. Nothing here runs the codec."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import counts, reference, roofline, surrogate, tracefile
from bench import harness
from bench.tests import tiny

ROOT = tiny.ROOT


def _ev(name, start, dur):
    return tracefile.Event(name, float(start), float(dur))


def _hand_trace():
    """Window 0-100 ns; ops overlap at 10-30 and 25-40, one at 60-70;
    a fit span over 0-50 and a compress span over 50-100."""
    tr = tracefile.Trace()
    tr.ops[0] = [
        _ev("fusion.1", 10, 20),
        _ev("%project_fn.1 = f32[2,3,128]{2,1,0} custom-call(f32[2,3,128]",
            25, 15),
        _ev("fusion.1", 60, 10),
        _ev("late", 95, 20),  # runs past the window's end
    ]
    tr.modules[0] = [_ev("jit_fused(7)", 10, 30), _ev("jit_other", 60, 10)]
    tr.spans = [_ev("bench.window", 0, 100), _ev("bench.fit", 0, 50),
                _ev("bench.compress", 50, 50)]
    return tr


class TestTraceReduction:
    def test_busy_union_and_idle_share(self):
        tr = _hand_trace()
        # union: 10-40 (30) + 60-70 (10) + 95-100 (5) = 45 ns of 100
        assert tracefile.busy_ns(tr, 0) == 45
        assert tracefile.busy_s(tr) == pytest.approx(45e-9)
        assert tracefile.window_s(tr) == pytest.approx(100e-9)
        assert tracefile.idle_share(tr) == pytest.approx(55.0)

    def test_union_merges_touching_and_nested(self):
        assert tracefile.union([(5, 9), (0, 2), (2, 4), (6, 7)]) == [
            (0, 4), (5, 9)]

    def test_kernel_time_by_name(self):
        tr = _hand_trace()
        pattern = harness.load_module(
            tiny.BENCH / "layers" / "gbatc_project_roofline.py").PATTERN
        secs, n = tracefile.op_seconds(tr, pattern)
        assert (secs, n) == (pytest.approx(15e-9), 1)
        secs, n = tracefile.op_seconds(tr, r"^fusion")
        assert (secs, n) == (pytest.approx(30e-9), 2)
        assert tracefile.module_seconds(tr, r"jit_fused") == (
            pytest.approx(30e-9), 1)
        assert tracefile.op_seconds(tr, r"absent") == (0.0, 0)

    def test_gaps_labelled_by_innermost_span(self):
        gaps = tracefile.idle_gaps(_hand_trace())
        # 0-10 (fit), 40-60 (midpoint 50: the shorter of fit/compress
        # covering it, compress ties at its start), 70-95 (compress)
        assert [g[1] for g in gaps] == pytest.approx([25e-9, 20e-9, 10e-9])
        assert gaps[0][0] == "bench.compress"
        assert gaps[2][0] == "bench.fit"

    def test_breakdown_lists_top_ops_and_gaps(self):
        b = tracefile.breakdown(_hand_trace(), n=2)
        assert b["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
        assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2

    def test_no_device_reads_none(self):
        tr = tracefile.Trace(spans=[_ev("bench.window", 0, 10)])
        assert tracefile.idle_share(tr) is None
        assert tracefile.busy_s(tr) == 0.0
        assert tracefile.breakdown(tr) == {"device_ops": [], "idle_gaps": []}

    def test_recorded_cpu_trace(self, tmp_path):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((64, 64))
        f(x).block_until_ready()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                with jax.profiler.TraceAnnotation("bench.fit"):
                    f(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        tr = tracefile.load(tmp_path)
        names = [s.name for s in tr.spans]
        assert "bench.window" in names and "bench.fit" in names
        lo, hi = tr.window
        fit = [s for s in tr.spans if s.name == "bench.fit"][0]
        assert lo <= fit.start_ns and fit.end_ns <= hi
        assert tracefile.window_s(tr) > 0
        # the CPU has no device plane: nothing to read, never a 0
        assert tracefile.idle_share(tr) is None


def _shapes(**kw):
    base = dict(n_species=2, n_blocks=3, block=(1, 2, 2), latent=3,
                channels=(4,), correction_widths=(2,), ae_steps=5,
                corr_steps=7, batch=11, corr_batch=13)
    base.update(kw)
    return counts.Shapes(**base)


class TestCounts:
    def test_autoencoder_by_hand(self):
        sh = _shapes()
        # conv 2->4 over 4 voxels, 27 taps: 2*27*2*4*4 = 1728; dense
        # 4*4 -> 3: 2*16*3 = 96
        assert counts.encoder_flops(sh) == 1728 + 96
        assert counts.decoder_flops(sh) == 1728 + 96

    def test_correction_by_hand(self):
        sh = _shapes()
        # 2 -> 4 -> 2: 2*2*4 + 2*4*2
        assert counts.correction_flops_per_point(sh) == 32
        assert counts.correction_flops_per_point(
            _shapes(correction_widths=())) == 0

    def test_guarantee_kernels_at_block_size(self):
        sh = _shapes()
        k = counts.guarantee_kernel(sh)
        d = 4  # 1x2x2, never a padded lane width
        assert k["project"]["flops"] == 2 * 2 * 3 * d * d
        assert k["project"]["bytes"] == 4 * (2 * 2 * 3 * d + 2 * d * d)
        assert k["correct"]["bytes"] == 4 * (3 * 2 * 3 * d + 2 * d * d)

    def test_job_and_decode_by_hand(self):
        sh = _shapes()
        enc = dec = 1824
        kern = 2 * 2 * 3 * 16
        train = 3 * 5 * 11 * (enc + dec) + 3 * 7 * 13 * 32
        passes = 3 * enc + 3 * dec + 3 * (dec + 4 * 32) + 2 * kern
        assert counts.encode_job_flops(sh) == train + passes
        gba = _shapes(correction_widths=())
        assert counts.encode_job_flops(gba) == (
            3 * 5 * 11 * (enc + dec) + 3 * enc + 3 * dec + 2 * kern)

    def test_paper_shapes(self):
        cfg = json.loads((tiny.BENCH / "configs" / "s3d_gbatc.json")
                         .read_text())
        sh = counts.shapes_from_config(cfg)
        assert (sh.n_species, sh.n_blocks, sh.voxels) == (58, 20480, 80)


class TestRoofline:
    def test_unknown_device_is_an_error(self):
        with pytest.raises(KeyError):
            roofline.peaks("TPU v99")

    def test_v5e_peaks(self):
        p = roofline.peaks("TPU v5 lite")
        assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9

    def test_share_and_binding_bound(self):
        p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
        assert roofline.share(100.0, 50.0, 10.0, p) == (50.0, "memory")
        assert roofline.share(1000.0, 5.0, 20.0, p) == (50.0, "compute")
        assert roofline.mfu(100.0, 2.0, p) == 50.0


def _mix():
    return json.loads((tiny.BENCH / "traffic" / "query_open.json")
                      .read_text())


class TestTraffic:
    def _schedule(self):
        mod = harness.load_module(tiny.BENCH / "drivers" / "query.py")
        return mod.schedule

    def test_seeded_and_deterministic(self):
        schedule = self._schedule()
        seed = 2**31 + 12345  # more than 32 signed bits
        a = schedule(_mix(), 58, 4, 30.0, seed)
        assert a == schedule(_mix(), 58, 4, 30.0, seed)
        assert a != schedule(_mix(), 58, 4, 30.0, seed + 1)

    def test_shapes_of_queries(self):
        mix = _mix()
        plan = self._schedule()(mix, 58, 4, 200.0, 3)
        times = [t for t, _, _ in plan]
        assert times == sorted(times) and times[-1] < 200.0
        assert len(plan) == round(200 * mix["rate_per_s"])
        singles = 0
        for _, species, (t0, t1) in plan:
            assert 0 <= t0 < t1 <= 4 and 1 <= t1 - t0 <= 4
            if isinstance(species, int):
                singles += 1
                assert 0 <= species < 58
            else:
                assert 1 <= len(species) <= 3
                assert len(set(species)) == len(species)
        assert singles == round(mix["single_share"] * len(plan))

    def test_every_seed_the_same_load(self):
        """Seeds reorder the queries; the load is the same."""
        schedule = self._schedule()

        def load(plan):
            gaps = np.diff([0.0] + [t for t, _, _ in plan])
            return (sorted(np.round(gaps, 9)),
                    sorted(1 if isinstance(s, int) else len(s)
                           for _, s, _ in plan),
                    sorted(t1 - t0 for _, _, (t0, t1) in plan))

        a = schedule(_mix(), 58, 4, 51.0, 1)
        b = schedule(_mix(), 58, 4, 51.0, 2**31 + 5)
        assert a != b and load(a) == load(b)

    def test_send_times_ignore_completions(self):
        """Open loop: a service that answers slowly, one request at a
        time, gets each request when its schedule says."""
        import threading
        import time
        from concurrent.futures import Future

        mod = harness.load_module(tiny.BENCH / "drivers" / "query.py")

        class SlowService:
            def __init__(self):
                self.stats = type("S", (), {"as_dict": lambda s: {}})()
                self.lock = threading.Lock()

            def submit(self, blob_id, species=None, time_range=None):
                fut = Future()

                def answer():
                    with self.lock:  # serial, 50 ms each
                        time.sleep(0.05)
                    fut.set_result(np.zeros(1))
                threading.Thread(target=answer, daemon=True).start()
                return fut

        mix = dict(_mix(), rate_per_s=100.0, drain_s=10)
        ctx = type("C", (), {})()
        ctx.traffic, ctx.seconds, ctx.seed = mix, 0.5, 9
        ctx.counters, ctx.units = {}, 0
        ctx.span = harness.Ctx.span.__get__(ctx)
        ctx.spans = {}
        ctx.note = lambda msg: None
        plan = mod.schedule(mix, 58, 4, 0.5, 9)
        state = {"svc": SlowService(), "plan": plan, "sample": set()}
        out = mod.window(ctx, state)
        assert out["attempted"] == len(plan) > 20
        assert out["failed"] == 0
        # answers took len(plan) * 50 ms serially; sends kept to time
        assert max(state["late"]) < 0.05
        assert out["metrics"]["query_p95_ms"] > 500


class TestSurrogate:
    def test_seed_reorders_the_same_blocks(self):
        cfg = json.loads((tiny.BENCH / "configs" / "s3d_gbatc.json")
                         .read_text())["data"]
        cfg = dict(cfg, n_species=5, height=20, width=16)
        block = (4, 5, 4)

        def blocks(f):
            s, t, h, w = f.shape
            b = f.reshape(s, t, h // 5, 5, w // 4, 4).transpose(
                0, 2, 4, 1, 3, 5).reshape(-1, 80)
            return sorted(map(bytes, b))

        a = surrogate.field_for(cfg, 1, block)
        b = surrogate.field_for(cfg, 2**33 + 1, block)
        assert not np.array_equal(a, b)
        assert blocks(a) == blocks(b)
        assert np.array_equal(a, surrogate.field_for(cfg, 1, block))

    def test_bitwise_the_programs_generator(self):
        from repro.data import s3d

        cfg = s3d.S3DConfig(n_species=6, n_time=10, height=20, width=20,
                            seed=2**33 + 7)
        want = s3d.generate_species_window(cfg, 3, 7)
        got = surrogate.species_window(
            seed=cfg.seed, n_species=6, n_series=10, height=20, width=20,
            beta=cfg.beta, major_frac=cfg.major_frac, t0=3, t1=7)
        assert got.dtype == np.float32 and np.array_equal(got, want)


class TestReference:
    def _field(self):
        rng = np.random.default_rng(0)
        return rng.random((3, 4, 10, 8)).astype(np.float32)

    def test_exact_answer_reads_zero(self):
        f = self._field()
        mn, rng = reference.species_scale(f)
        r = reference.guarantee_readings(f, f.copy(), target=1e-3,
                                         block=(4, 5, 4), mn=mn, rng=rng)
        assert r == {"nrmse": 0.0, "block": 0.0}

    def test_one_block_over_tau(self):
        f = self._field()
        mn, rng = reference.species_scale(f)
        bad = f.copy()
        tau = 1e-3 * np.sqrt(80)
        # one value of species 1 off by 2 tau of its range: one block
        bad[1, 2, 3, 4] += np.float32(2 * tau * rng[1])
        r = reference.guarantee_readings(f, bad, target=1e-3,
                                         block=(4, 5, 4), mn=mn, rng=rng)
        assert r["block"] == pytest.approx(2.0, rel=1e-4)
        assert r["nrmse"] == pytest.approx(2 * tau / np.sqrt(320), rel=1e-4)

    def test_window_blocks_start_at_the_group(self):
        f = self._field()
        mn, rng = reference.species_scale(f)
        win = f[:, 1:3].copy()
        win[0, 0, 0, 0] += np.float32(rng[0] * 0.5)
        r = reference.guarantee_readings(f[:, 1:3], win, target=1e-3,
                                         block=(4, 5, 4), mn=mn, rng=rng,
                                         frame0=1)
        assert r["block"] == pytest.approx(0.5 / (1e-3 * np.sqrt(80)),
                                           rel=1e-4)

    def test_bf16_control_rounds_to_eight_bits(self):
        f = self._field()
        mn, rng = reference.species_scale(f)
        c = reference.bf16_control(f, mn, rng)
        norm = (c.astype(np.float64) - mn[:, None, None, None]) / rng[
            :, None, None, None]
        err = np.abs(norm - (f - mn[:, None, None, None])
                     / rng[:, None, None, None])
        assert err.max() <= 2.0**-8 and err.max() > 2.0**-12

    def test_wrong_shape_or_nan_fails(self):
        f = self._field()
        mn, rng = reference.species_scale(f)
        assert reference.guarantee_readings(
            f, f[:, :2], target=1e-3, block=(4, 5, 4), mn=mn,
            rng=rng)["block"] == float("inf")
        bad = f.copy()
        bad[0, 0, 0, 0] = np.nan
        assert reference.guarantee_readings(
            f, bad, target=1e-3, block=(4, 5, 4), mn=mn,
            rng=rng)["nrmse"] == float("inf")


def _run_cli(cwd, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gbatc.encode",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


class TestRefusal:
    def test_refuses_off_tpu(self):
        p = _run_cli(ROOT)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "no TPU" in p.stderr

    def test_refuses_without_the_program(self, tmp_path):
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(tiny.BENCH, tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        p = _run_cli(tmp_path)
        assert p.returncode != 0
        assert p.stdout.strip() == ""


class TestLayout:
    def test_every_metric_has_its_reader_and_every_cell_its_files(self):
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        for m in spec["per_layer"]:
            mod = harness.load_module(tiny.BENCH / "layers"
                                      / f"{m['name']}.py")
            assert callable(mod.read)
        for w in spec["workloads"]:
            cell = harness.Cell(ROOT / "BENCHMARK.json", w["name"])
            assert cell.driver_path.is_file()
            assert cell.per_layer() and cell.end_to_end()
            assert "setup_s" in {m["name"] for m in cell.end_to_end()}

    def test_a_cell_added_by_files_and_an_entry_is_found(self, tmp_path):
        spec = tiny.make(tmp_path)
        cell = harness.Cell(spec, "tiny.encode", tmp_path)
        assert cell.config["name"] == "tiny"
        assert cell.traffic["driver"] == "encode"
        names = {m["name"] for m in cell.per_layer()}
        assert {"fit_s", "compress_s", "encode_mfu"} <= names
        assert {m["name"] for m in cell.end_to_end()} == {
            "encode_throughput", "compression_ratio", "setup_s"}

    def test_configs_match_the_program(self):
        """The configuration files state what the program runs."""
        from repro.core import correction
        from repro.core.pipeline import PipelineConfig
        from repro.data import s3d

        for name, corr in (("s3d_gbatc", True), ("s3d_gba", False)):
            cfg = json.loads((tiny.BENCH / "configs" / f"{name}.json")
                             .read_text())
            ctx = harness.Ctx(harness.Cell(ROOT / "BENCHMARK.json",
                                           "gbatc.encode"), 0, 1)
            ctx.config = cfg
            pc = ctx.pipeline_config()
            want = PipelineConfig(use_correction=corr)
            assert pc == want
            d = cfg["data"]
            paper = s3d.PAPER_CONFIG
            assert (d["n_species"], d["height"], d["width"],
                    d["n_series"], d["beta"], d["major_frac"]) == (
                paper.n_species, paper.height, paper.width, paper.n_time,
                paper.beta, paper.major_frac)
            assert tuple(cfg["correction_widths"]) == (
                correction.CorrectionConfig(n_species=1).widths)
