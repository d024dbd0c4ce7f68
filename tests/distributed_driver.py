"""Subprocess driver for multi-device tests (8 fake CPU devices).

Run as:  python tests/distributed_driver.py <scenario>
Prints "SCENARIO_OK <name>" on success; any exception exits non-zero.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import get_config, SHAPES, ShapeSpec
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model, make_batch
from repro.parallel import sharding as sh
from repro.parallel.gradient_compression import (
    CompressionConfig, quantized_all_reduce)
from repro.train import optimizer as opt
from repro.train.checkpoint import CheckpointManager
from repro.train.train_loop import (
    TrainConfig, init_train_state, make_train_step)


def _small_setup(arch="llama3_2_1b", mesh_shape=(4, 2)):
    cfg = get_config(arch).smoke()
    mesh = make_mesh(mesh_shape, ("data", "model"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    p_shard = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                           sh.param_pspecs(model, cfg, mesh))
    params = jax.tree.map(jax.device_put, params, p_shard)
    return cfg, mesh, model, params, p_shard


def scenario_sharded_train_step():
    """Sharded train step on a (4, 2) mesh must match single-device numerics."""
    cfg, mesh, model, params, p_shard = _small_setup()
    tcfg = TrainConfig(optimizer=opt.AdamWConfig(lr=1e-3))
    step = make_train_step(model, tcfg)
    state = init_train_state(model, params, tcfg)
    batch = make_batch(cfg, batch=8, seq=16, kind="train")
    shape = ShapeSpec("t", 16, 8, "train")
    b_shard = sh.batch_shardings(cfg, shape, mesh)
    batch_sharded = {k: jax.device_put(v, b_shard[k]) for k, v in batch.items()}

    with mesh:
        p2, s2, m2 = jax.jit(step)(params, state, batch_sharded)
    # reference: plain single-device execution
    params_host = jax.device_get(params)
    state_host = jax.device_get(state)
    p1, s1, m1 = jax.jit(step)(params_host, state_host, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4, atol=1e-5)
    flat1 = jax.tree.leaves(p1)
    flat2 = jax.tree.leaves(jax.device_get(p2))
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-4)
    print("SCENARIO_OK sharded_train_step")


def scenario_quantized_all_reduce():
    mesh = make_mesh((8,), ("data",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    got = quantized_all_reduce(xs, mesh, axis="data")
    want = jnp.broadcast_to(x.sum(axis=0, keepdims=True) * 0 + x.sum(axis=0),
                            x.shape)  # full sum on every row? no:
    # quantized_all_reduce sums *shards* -> every shard holds the total
    total = np.asarray(x).sum(axis=0)
    got_host = jax.device_get(got)
    for row in got_host.reshape(8, 64):
        np.testing.assert_allclose(row, total, rtol=0.05, atol=0.05)
    print("SCENARIO_OK quantized_all_reduce")


def scenario_checkpoint_elastic():
    """Save under a (4,2) mesh, restore under (2,4) and (8,1) — elastic."""
    cfg, mesh, model, params, _ = _small_setup(mesh_shape=(4, 2))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_write=False)
        mgr.save(3, params, wait=True)
        for new_shape in [(2, 4), (8, 1), (1, 8)]:
            mesh2 = make_mesh(new_shape, ("data", "model"))
            shard2 = jax.tree.map(lambda ps: NamedSharding(mesh2, ps),
                                  sh.param_pspecs(model, cfg, mesh2))
            restored, step = mgr.restore(model.specs(), shardings=shard2)
            assert step == 3
            for a, b in zip(jax.tree.leaves(jax.device_get(params)),
                            jax.tree.leaves(jax.device_get(restored))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("SCENARIO_OK checkpoint_elastic")


def scenario_dryrun_small_mesh():
    """Full dry-run mechanics on an 8-device (4,2) mesh for one arch."""
    cfg = get_config("llama3_2_1b")
    mesh = make_mesh((4, 2), ("data", "model"))
    model = build_model(cfg)
    from repro.models.registry import input_specs
    from repro.train.train_loop import make_train_step
    shape = SHAPES["train_4k"]
    specs = model.specs()
    p_shard = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                           sh.param_pspecs(model, cfg, mesh))
    b_shard = {k: NamedSharding(mesh, v)
               for k, v in sh.batch_pspecs(cfg, shape, mesh).items()}
    tcfg = TrainConfig(optimizer=opt.AdamWConfig(lr=1e-4))
    step = make_train_step(model, tcfg)
    from repro.launch.dryrun import train_state_specs, parse_collective_bytes
    st_specs = train_state_specs(specs)
    st_shard = {"opt": jax.tree.map(
        lambda ps: NamedSharding(mesh, ps),
        sh.optimizer_pspecs(model, cfg, mesh),
        is_leaf=lambda x: isinstance(x, P))}
    with mesh:
        lowered = jax.jit(step, in_shardings=(p_shard, st_shard, b_shard),
                          out_shardings=(p_shard, st_shard, None)).lower(
            specs, st_specs, input_specs(cfg, shape))
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0
    coll = parse_collective_bytes(compiled.as_text())
    assert coll["total"] > 0, "sharded train step must communicate"
    print("SCENARIO_OK dryrun_small_mesh")


def scenario_moe_ep_sharded():
    """MoE forward under EP sharding matches unsharded numerics."""
    cfg = get_config("qwen3_moe_30b_a3b").smoke().replace(n_experts=8,
                                                          moe_top_k=2)
    mesh = make_mesh((2, 4), ("data", "model"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch(cfg, batch=4, seq=16, kind="train")
    loss1 = float(jax.jit(model.loss)(params, batch))
    p_shard = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                           sh.param_pspecs(model, cfg, mesh))
    params_s = jax.tree.map(jax.device_put, params, p_shard)
    with mesh:
        loss2 = float(jax.jit(model.loss)(params_s, batch))
    np.testing.assert_allclose(loss1, loss2, rtol=1e-4)
    print("SCENARIO_OK moe_ep_sharded")


def _mesh_fit_problem():
    """Tiny linear-AE trainer problem shared by the mesh-fit scenarios."""
    from repro.train import train_loop

    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 12)).astype(np.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w_enc": jax.random.normal(k1, (12, 4)) * 0.1,
              "w_dec": jax.random.normal(k2, (4, 12)) * 0.1}

    def loss_fn(p, b):
        rec = b @ p["w_enc"] @ p["w_dec"]
        return jnp.mean(jnp.square(rec - b))

    tr = train_loop.MiniBatchTrainer(
        loss_fn, train_loop.adamw_cfg(5e-3, 16), mode="scan")
    return tr, params, x


def scenario_mesh_dp_fit():
    """DP fit over all 8 devices trains; a 1-device sub-mesh fit stays
    bitwise the plain scan fit (the P=1 identity gate, on a real forced
    mesh rather than the suite's default single device)."""
    from repro.parallel import mesh_fit

    tr, params, x = _mesh_fit_problem()
    kw = dict(steps=16, batch_size=16, seed=0)
    mesh8 = mesh_fit.host_mesh()
    assert mesh_fit.mesh_size(mesh8) == 8
    _, l8 = tr.fit(params, (x,), mesh=mesh8, **kw)
    assert np.isfinite(l8).all() and l8[-1] < l8[0]
    p_ref, l_ref = tr.fit(params, (x,), **kw)
    p1, l1 = tr.fit(params, (x,), mesh=mesh_fit.host_mesh(1), **kw)
    np.testing.assert_array_equal(l_ref, l1)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("SCENARIO_OK mesh_dp_fit")


def scenario_mesh_quantized_fit():
    """DP fit with the int8 quantized gradient exchange on 8 devices:
    trains to a finite decreasing loss, and the static wire accounting
    shows the exchange is the cheaper one for realistically-sized params."""
    from repro.parallel import mesh_fit

    tr, params, x = _mesh_fit_problem()
    mesh8 = mesh_fit.host_mesh()
    _, losses = tr.fit(params, (x,), steps=16, batch_size=16, seed=0,
                       mesh=mesh8, quantized_exchange=True)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    big = {"w": np.zeros((256, 256), np.float32)}
    # the int8 all-gather beats the fp32 ring all-reduce at small P (the
    # ring moves ~2n bytes regardless of P, the gather P*n/4); at P=8 the
    # two are a wash — assert each regime where it actually holds, plus
    # the ~4x win over an fp32 all-gather of the same pattern
    rep2 = mesh_fit.dp_wire_report(big, 2)
    assert rep2["wire_ratio"] > 3.5
    rep8 = mesh_fit.dp_wire_report(big, 8)
    fp32_gather = 7 * rep8["grad_fp32_bytes"]
    assert rep8["quantized_bytes_per_step"] < fp32_gather / 3.5
    print("SCENARIO_OK mesh_quantized_fit")


def scenario_mesh_sharded_compress():
    """Sharded guarantee engine with chunks placed across all 8 devices:
    the serialized container is byte-identical to the default engine's."""
    from repro.core.pipeline import GBATCPipeline, PipelineConfig
    from repro.data import s3d
    from repro.parallel import mesh_fit

    data = s3d.generate(s3d.S3DConfig(
        n_species=4, n_time=8, height=20, width=16, seed=5))["species"]
    cfg = PipelineConfig(ae_steps=40, corr_steps=20, conv_channels=(8, 16))
    pipe = GBATCPipeline(cfg, n_species=4)
    pipe.fit(data)
    ref = pipe.compress(target_nrmse=1e-3).artifact.to_bytes()
    pipe.set_guarantee_engine(
        mesh_fit.ShardedGuaranteeEngine(mesh=mesh_fit.host_mesh()))
    got = pipe.compress(target_nrmse=1e-3).artifact.to_bytes()
    assert got == ref, "sharded compress drifted from the default engine"
    print("SCENARIO_OK mesh_sharded_compress")


def scenario_mesh_fit_stream():
    """Mesh fit_stream on 8 devices: ingest lands row-sharded across the
    full mesh, the compressed output meets the bound, and re-compressing
    the same fitted state on the default engine is byte-identical."""
    from repro.core import gae
    from repro.core.pipeline import GBATCPipeline, PipelineConfig
    from repro.data import s3d
    from repro.parallel import mesh_fit

    scfg = s3d.S3DConfig(n_species=4, n_time=8, height=20, width=16, seed=5)
    loader = s3d.S3DChunkLoader(scfg, chunk_frames=4)
    cfg = PipelineConfig(ae_steps=30, corr_steps=15, conv_channels=(8, 16))
    pipe = GBATCPipeline(cfg, n_species=4, mesh=mesh_fit.host_mesh())
    pipe.fit_stream(loader)
    devs = {int(s.device.id) for s in pipe._blocks.addressable_shards}
    assert len(devs) == 8, f"ingest store only spans devices {devs}"
    rep = pipe.compress(target_nrmse=1e-3)
    assert rep.mean_nrmse <= 1e-3 * (1 + 1e-3)
    ref = rep.artifact.to_bytes()
    pipe.set_guarantee_engine(gae.default_engine())
    assert pipe.compress(target_nrmse=1e-3).artifact.to_bytes() == ref
    print("SCENARIO_OK mesh_fit_stream")


SCENARIOS = {
    "sharded_train_step": scenario_sharded_train_step,
    "quantized_all_reduce": scenario_quantized_all_reduce,
    "checkpoint_elastic": scenario_checkpoint_elastic,
    "dryrun_small_mesh": scenario_dryrun_small_mesh,
    "moe_ep_sharded": scenario_moe_ep_sharded,
    "mesh_dp_fit": scenario_mesh_dp_fit,
    "mesh_quantized_fit": scenario_mesh_quantized_fit,
    "mesh_sharded_compress": scenario_mesh_sharded_compress,
    "mesh_fit_stream": scenario_mesh_fit_stream,
}

if __name__ == "__main__":
    SCENARIOS[sys.argv[1]]()
