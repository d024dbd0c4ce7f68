"""Error-bounded gradient compression with error feedback.

The paper's residual machinery applied *temporally*: each step, the gradient
plus the carried quantization residual is block-quantized (int8/int4 with
per-block scales — the same primitive as repro/kernels/block_quant); the
quantization error is fed back into the next step's residual, so the method
is unbiased over time (EF-SGD family) and the per-step l-inf error is bounded
by scale/2 per block.

Three integration modes:
  * ``compress_tree`` — post-allreduce quantization inside the jit'd train
    step (models the numerics; SPMD collectives unchanged);
  * ``quantized_psum`` — the per-shard exchange body (quantize, all-gather
    int payload + scales, dequant-sum locally), callable *inside* an
    enclosing ``shard_map`` — this is what the mesh DP trainer
    (``parallel/mesh_fit.py``) routes its gradient exchange through when
    ``quantized_exchange=True``;
  * ``quantized_all_reduce`` — standalone ``shard_map(quantized_psum)``:
    the actual 4x wire saving for DP gradient exchange, validated in tests
    on an 8-device CPU mesh.

Wire accounting for the DP exchange lives in
``mesh_fit.dp_wire_report`` (static, from the gradient leaf shapes).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    n_bits: int = 8
    block: int = 64
    enabled: bool = True


def _quant_dequant(x: jax.Array, n_bits: int, block: int):
    """Per-block symmetric quantize->dequantize on a flattened tensor."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    xb = flat.reshape(-1, block)
    qmax = float(2 ** (n_bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1, keepdims=True), 1e-30) / qmax
    q = jnp.clip(jnp.round(xb / scale), -qmax - 1, qmax)
    out = (q * scale).reshape(-1)[: x.size].reshape(x.shape)
    return out.astype(x.dtype)


def init_residuals(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_tree(grads, residuals, cfg: CompressionConfig):
    """Returns (compressed_grads, new_residuals). Error feedback:
    g_hat = Q(g + r);  r' = (g + r) - g_hat."""
    if not cfg.enabled:
        return grads, residuals

    def one(g, r):
        total = g.astype(jnp.float32) + r
        g_hat = _quant_dequant(total, cfg.n_bits, cfg.block)
        return g_hat.astype(g.dtype), total - g_hat.astype(jnp.float32)

    flat_g, tree = jax.tree.flatten(grads)
    flat_r = tree.flatten_up_to(residuals)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (tree.unflatten([o[0] for o in outs]),
            tree.unflatten([o[1] for o in outs]))


def quantized_psum(local: jax.Array, axis: str = "data",
                   n_bits: int = 8, block: int = 64) -> jax.Array:
    """Quantized psum of a per-shard value, inside an enclosing shard_map.

    The shard quantizes its local tensor (int payload + one fp32 scale per
    ``block`` values), all-gathers the quantized payloads over ``axis``,
    and sums the dequantized contributions locally — the wire carries
    int8 + scales instead of fp32. Every shard returns the same full sum,
    so this is a drop-in for ``jax.lax.psum`` (up to quantization error;
    the mesh DP trainer's convergence test covers the numerics).
    """
    qmax = float(2 ** (n_bits - 1) - 1)
    flat = local.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    xb = flat.reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), -1, keepdims=True), 1e-30) / qmax
    q = jnp.clip(jnp.round(xb / scale), -qmax - 1, qmax).astype(jnp.int8)
    q_all = jax.lax.all_gather(q, axis)  # (P, nb, block) int8 on the wire
    s_all = jax.lax.all_gather(scale, axis)
    total = jnp.sum(q_all.astype(jnp.float32) * s_all, axis=0)
    return total.reshape(-1)[: local.size].reshape(local.shape).astype(
        local.dtype)


def quantized_all_reduce(x: jax.Array, mesh: Mesh, axis: str = "data",
                         n_bits: int = 8, block: int = 64) -> jax.Array:
    """All-reduce over `axis` with int8 wire format.

    Each device quantizes its local shard (int + fp32 scales), all-gathers
    the quantized payload, and sums dequantized contributions locally.
    Wire volume: n*(P-1)/P bytes int8 + scales vs 2*n*(P-1)/P * 4 bytes for
    a ring all-reduce in fp32 -> ~8x reduction at 8 bits.
    """
    inner = functools.partial(quantized_psum, axis=axis, n_bits=n_bits,
                              block=block)
    # input sharded on dim 0 over `axis`; every shard returns the full sum
    return jax.shard_map(
        inner, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )(x)
