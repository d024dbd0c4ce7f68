"""The attention cell's files on the CPU: a tiny attention configuration
added to the tiny benchmark by files and entries alone, run through the
harness as the chip runs it; the control it has to fail; its four
readers; and the family's operation counts against a count by hand.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

from bench import attention_counts, counts, faults, harness
from bench.tests import tiny

SEED = 2**31 + 99  # more than 32 signed bits, as the benchmark's are
CELL = "tiny.attention"
ARCH = [16, 2, 1, 32]
READERS = ("attention_encode_mfu", "attention_train_mfu",
           "attention_pass_mfu", "decoder_share")


def tiny_attention_config() -> dict:
    cfg = json.loads((tiny.BENCH / "configs" / "s3d_attention.json")
                     .read_text())
    cfg["name"] = "tiny_attention"
    cfg["data"].update(n_species=4, height=20, width=20, n_series=8,
                       first_frame=2)
    cfg["pipeline"].update(arch=ARCH, ae_steps=100, batch_size=16, latent=6)
    # the CPU's DEFAULT precision multiplies float32 operands whole
    cfg["model_operands"] = "float32"
    # trained 100 steps the tiny attention AE reads 0.227-0.254 on its
    # own, left at its initialisation 1.03-1.08 (seeds 1-3 on the CPU)
    cfg["limits"]["net_nrmse"] = 0.5
    return cfg


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    dest = tmp_path_factory.mktemp("tinyattention")
    path = tiny.make(dest)
    (dest / "configs" / "tiny_attention.json").write_text(
        json.dumps(tiny_attention_config()))
    spec = json.loads(path.read_text())
    spec["workloads"].append({"name": CELL, "config": "tiny_attention",
                              "traffic": "encode_jobs_family", "chips": 1,
                              "why": "tiny CPU cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "attention.encode" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    path.write_text(json.dumps(spec))
    return path


def run_cell(spec, *, trace=False, control=False):
    return harness.run(tiny.ROOT, CELL, SEED, 0.5, trace,
                       t_start=time.perf_counter(), require_tpu=False,
                       persist=False, bench_dir=spec.parent, spec=spec,
                       control=control)


@pytest.fixture(scope="module")
def sound(spec):
    return run_cell(spec)


def test_attention_cell_is_correct(sound):
    out = sound
    assert out["correct"] is True
    assert set(out["metrics"]) == {"encode_throughput", "compression_ratio",
                                   "setup_s"}
    assert set(out["checks"]) == {"nrmse", "block", "net_nrmse", "model",
                                  "encoder"}
    # on the CPU the program multiplies in float32, as the reference does
    assert out["checks"]["model"]["value"] < 1e-5
    assert out["checks"]["encoder"]["value"] < 1e-5


def test_attention_control_fails(spec):
    """In the program's place: the answers carried in bfloat16 break the
    block bound, and the references carried in bfloat16 throughout the
    model and encoder limits."""
    out = run_cell(spec, control=True)
    assert out["correct"] is False
    checks = out["checks"]
    for name in ("block", "model", "encoder"):
        assert checks[name]["value"] > checks[name]["limit"], name


def positions_left_out(mp, cfg):
    """The family's fixed token positions are all zero: a program that
    trains, encodes and decodes consistently, off the equations."""
    from repro.codec import runtime
    from repro.models import common

    mp.setattr(common, "sinusoidal_positions",
               lambda n, d: np.zeros((n, d), np.float32))
    # decode runtimes built before the fault would decode with positions
    mp.setattr(runtime, "_RUNTIMES", {})


# test id, the fault's name: (fault, the checks it has to fail; empty for
# any)
FAULTS = {
    "trainer_frozen": (faults.trainer_frozen, {"net_nrmse"}),
    "blocks_uncorrected": (faults.blocks_uncorrected, set()),
    "latent_altered": (faults.latent_altered, set()),
    "positions_left_out": (positions_left_out, {"model", "encoder"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_attention_fault_makes_correct_false(spec, fault, monkeypatch):
    plant, must = FAULTS[fault]
    plant(monkeypatch, tiny_attention_config())
    out = run_cell(spec)
    assert out["correct"] is False
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert failed and must <= failed, failed


def test_attention_half_batch_trainer_reads_as_sound(spec, sound,
                                                     monkeypatch):
    """A trainer that leaves half of each batch out is no case of the test
    above: its network comes within a few percent of a sound one's, its
    container meets the guarantee, and its encoder and decoder keep to
    the equations, so no number of the check tells it apart (as in the
    conv cells). Read here so that the blind spot is seen."""
    faults.trainer_half_batch(monkeypatch, tiny_attention_config())
    out = run_cell(spec)
    assert out["correct"] is True
    net = out["checks"]["net_nrmse"]["value"]
    assert net == pytest.approx(sound["checks"]["net_nrmse"]["value"],
                                rel=0.05)


def test_attention_readers_read_a_traced_run(spec):
    out = run_cell(spec, trace=True)
    assert out["correct"] is True
    for name in READERS:
        assert out["metrics"][name]["unit"] == "%"
        assert 0 < out["metrics"][name]["value"] < 100, name
    assert {"fit_s", "ae_train_s", "container_encode_s"} <= set(
        out["metrics"])
    assert "encode_mfu" not in out["metrics"]


def test_setup_hands_the_arch_words_to_the_program(spec):
    cell = harness.Cell(spec, CELL, spec.parent)
    ctx = harness.Ctx(cell, SEED, 0.5)
    ctx.warm = False
    driver = harness.load_module(cell.driver_path)
    assert driver.setup(ctx)["cfg"].arch == tuple(ARCH)


def test_attention_counts_by_hand():
    """S=2 species, 1x2x3 blocks (T=2 tokens of 6 values), d_model 4,
    depth 2, SwiGLU 8, latent 3, 5 blocks."""
    sh = counts.Shapes(n_species=2, n_blocks=5, block=(1, 2, 3), latent=3,
                       channels=(1,), correction_widths=(), ae_steps=7,
                       corr_steps=0, batch=2, corr_batch=1)
    arch = (4, 2, 2, 8)
    # a layer: q,k,v,o 4*2*2*4*4 = 256, scores and values 2*2*2*2*4 = 64,
    # SwiGLU 3*2*2*4*8 = 384
    assert attention_counts.layer_flops(2, 4, 8) == 704
    # in 2*2*6*4 = 96, two layers 1408, head 2*2*4*3 = 48
    assert attention_counts.encoder_flops(sh, arch) == 1552
    assert attention_counts.decoder_flops(sh, arch) == 1552
    assert attention_counts.train_flops(sh, arch, 7, 2) == 3 * 7 * 2 * 3104
    assert attention_counts.pass_flops(sh, arch) == 5 * 3104
    # projection and replay: 2 * (2 * 2 * 5 * 6 * 6)
    assert attention_counts.encode_job_flops(sh, arch) == (
        3 * 7 * 2 * 3104 + 5 * 3104 + 2 * 720)


def test_bench_reference_copies_the_family_reference():
    """``bench/attention_reference.py`` at float32 is the family's own
    reference; bfloat16 operands move it less than bfloat16 throughout."""
    import jax
    import jax.numpy as jnp

    from bench import attention_reference as bench_ref
    from repro.models import block_attention, block_attention_ref

    cfg = block_attention.BlockAttentionConfig(
        n_species=3, block=(4, 5, 4), latent=6, d_model=16, n_heads=2,
        depth=2, mlp_hidden=32)
    params = block_attention.BlockAttentionAE(cfg).init(
        jax.random.PRNGKey(3))
    blocks = np.random.default_rng(3).random((5, 3, 4, 5, 4), np.float32)
    z = np.asarray(block_attention_ref.encode_ref(params, blocks, 2))
    want = np.asarray(block_attention_ref.decode_ref(params, z, 2,
                                                     (3, 4, 5, 4)))
    # the same operations in the same order: float32 rounding alone
    np.testing.assert_allclose(bench_ref.encode_ref(params, blocks, 2), z,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        bench_ref.decode_ref(params, z, 2, (3, 4, 5, 4)), want,
        rtol=1e-5, atol=1e-5)

    def off(**kw):
        got = bench_ref.decode_ref(params, z, 2, (3, 4, 5, 4), **kw)
        return float(np.sqrt(np.mean(np.square(got - want))))

    operands, carried = off(operands=jnp.bfloat16), off(dtype=jnp.bfloat16)
    assert 0 < operands < carried
