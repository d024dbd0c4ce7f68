"""Compile rehearsal: the chip programs of the main path, compiled for a
described (not attached) TPU v5e at the widths ``chip_smoke.py`` runs.

Nothing runs, so nothing here is a result or a time: a pass says the TPU
compiler accepts each program at the S3D paper width (S=58 species,
D=80 block vectors, NB=40,960 blocks: 8 frames of 640x640 in 4x5x4
blocks) and that it fits one chip's 16 GB. The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker running this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.codec import runtime as codec_runtime
from repro.core import autoencoder as ae
from repro.core import correction, gae
from repro.core.pipeline import GBATCPipeline, PipelineConfig
from repro.kernels import gbatc_project as gp
from repro.parallel import mesh_fit
from repro.train import optimizer, train_loop

S, NB, D = 58, 40960, 80
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


@pytest.fixture(scope="module")
def pipe():
    return GBATCPipeline(PipelineConfig(), n_species=S)


def _tree_shapes(shape, tree):
    return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)


def _check(compiled, *, kernel: bool):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"
    assert ("tpu_custom_call" in compiled.as_text()) == kernel


_TILES = dict(species_per_tile=1, rows_per_tile=512, interpret=False)


def test_project_batched_compiles(shape):
    fn = jax.jit(lambda r, u: gp.gbatc_project_batched(r, u, **_TILES))
    _check(fn.lower(shape((S, NB, D), jnp.float32),
                    shape((S, D, D), jnp.float32)).compile(), kernel=True)


def test_correct_batched_compiles(shape):
    fn = jax.jit(lambda x, c, u: gp.gbatc_correct_batched(x, c, u, **_TILES))
    args = (shape((S, NB, D), jnp.float32), shape((S, NB, D), jnp.float32),
            shape((S, D, D), jnp.float32))
    _check(fn.lower(*args).compile(), kernel=True)


def test_select_accumulate_compiles(shape):
    fn = jax.jit(lambda x, c, r, m, u: gp.gbatc_select_accumulate(
        x, c, r, m, u, **_TILES))
    args = (shape((S, NB, D), jnp.float32), shape((S, NB, D), jnp.float32),
            shape((S, NB, D), jnp.int32), shape((S, NB), jnp.int32),
            shape((S, D, D), jnp.float32))
    _check(fn.lower(*args).compile(), kernel=True)


def test_engine_select_compiles(shape):
    engine = gae.GuaranteeEngine(interpret=False)
    assert engine.coeff_dtype == np.float32
    assert engine.select_backend == "jit"
    engine._build_jits()
    with jax.enable_x64(True):
        args = (
            shape((S, NB, D), jnp.float32),  # coeffs
            shape((S, NB, D), jnp.float32),  # coeffs_sorted
            shape((S, NB, D), jnp.int32),  # inv_rank
            shape((S, NB), jnp.float64),  # norms2
            shape((S, NB, D), jnp.float32),  # x_rec
            shape((S, D, D), jnp.float32),  # basis32
            shape((), jnp.float64),  # tau2
            shape((), jnp.float64),  # bin_size
        )
        compiled = engine._select_jit.lower(*args).compile()
    _check(compiled, kernel=True)


def test_conv_fused_decode_compiles(shape, pipe):
    cfg = pipe.cfg
    rt = codec_runtime._runtime(cfg, S, True)
    params = jax.eval_shape(pipe.model.init, jax.random.PRNGKey(0))
    corr = jax.eval_shape(pipe.corr_net.init, jax.random.PRNGKey(1))
    lat = shape((codec_runtime._FUSED_CHUNK, cfg.latent), jnp.float32)
    compiled = rt.jit_fused.lower(
        _tree_shapes(shape, params), _tree_shapes(shape, corr), lat
    ).compile()
    _check(compiled, kernel=False)


def test_scan_trainer_step_compiles(shape, pipe):
    cfg = pipe.cfg
    trainer = train_loop.MiniBatchTrainer(
        ae._ae_loss(pipe.model), train_loop.adamw_cfg(cfg.lr, cfg.ae_steps),
        mode="scan",
    )
    run = trainer._scan_program(cfg.ae_steps, NB, cfg.batch_size, 0)
    params = jax.eval_shape(pipe.model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(optimizer.init_state, params)
    bkey = jax.eval_shape(train_loop.batch_key, 0)
    g = cfg.geometry
    blocks = shape((NB, S, g.bt, g.ph, g.pw), jnp.float32)
    compiled = run.lower(
        _tree_shapes(shape, params), _tree_shapes(shape, state),
        _tree_shapes(shape, bkey), blocks,
    ).compile()
    _check(compiled, kernel=False)


@pytest.mark.parametrize("net", ["ae", "correction"])
def test_mesh_dp_trainer_compiles(topo, pipe, net):
    """The four-chip path's data-parallel scan program: the gradient
    exchange is an all-reduce and each chip's share fits it."""
    cfg = pipe.cfg
    mesh = Mesh(np.array(topo.devices), (mesh_fit.DATA_AXIS,))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(mesh_fit.DATA_AXIS))
    g = cfg.geometry
    if net == "ae":
        model, loss, steps, bs = (pipe.model, ae._ae_loss(pipe.model),
                                  cfg.ae_steps, cfg.batch_size)
        n, data = NB, [(NB, S, g.bt, g.ph, g.pw)]
    else:
        model, loss, steps, bs = (pipe.corr_net,
                                  correction._corr_loss(pipe.corr_net),
                                  cfg.corr_steps, 4096)
        n = NB * g.block_size
        data = [(n, S), (n, S)]
    trainer = train_loop.MiniBatchTrainer(
        loss, train_loop.adamw_cfg(cfg.lr, steps), mode="scan")
    run = mesh_fit.dp_scan_program(trainer, steps, n, bs, 0, mesh, False,
                                   len(data))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(optimizer.init_state, params)
    bkey = jax.eval_shape(train_loop.batch_key, 0)

    def on(tree, sharding):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)

    compiled = run.lower(
        on(params, rep), on(state, rep), on(bkey, rep),
        *[jax.ShapeDtypeStruct(d, jnp.float32, sharding=rows) for d in data],
    ).compile()
    _check(compiled, kernel=False)
    assert "all-reduce" in compiled.as_text()
