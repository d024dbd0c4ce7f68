"""Pallas chunked RG-LRU scan (TPU target; validated with interpret=True).

Diagonal linear recurrence h_t = a_t * h_{t-1} + b_t (Griffin's RG-LRU after
gating), per channel. Within a chunk of C steps with la = log a (<= 0),
cum_t = sum_{j<=t} la_j:

  h_t = e^{cum_t} h_0 + sum_{s<=t} e^{cum_t - cum_s} b_s

All exponents are pairwise differences <= 0 -> unconditionally stable.
Grid = (B, W/bw); chunks walked sequentially with the (bw,) carry; the (C, C)
pairwise weight tensor per channel block stays in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rglru_kernel(la_ref, b_ref, h0_ref, h_ref, hT_ref, *, chunk, t):
    n_chunks = t // chunk
    tri = (
        jax.lax.iota(jnp.int32, chunk)[:, None]
        >= jax.lax.iota(jnp.int32, chunk)[None, :]
    )

    def body(ci, h0):
        sl = (0, pl.ds(ci * chunk, chunk), slice(None))
        la = la_ref[sl].astype(jnp.float32)  # (C, bw)
        bb = b_ref[sl].astype(jnp.float32)
        cum = jnp.cumsum(la, axis=0)
        # pairwise decay weights e^{cum_t - cum_s} for s <= t
        pair = cum[:, None, :] - cum[None, :, :] + la[None, :, :] * 0.0
        # note: sum_{j=s+1..t} la_j = cum_t - cum_s
        w = jnp.where(tri[:, :, None], jnp.exp(pair), 0.0)  # (C, C, bw)
        h = jnp.exp(cum) * h0[None, :] + jnp.einsum("tsw,sw->tw", w, bb)
        h_ref[sl] = h.astype(h_ref.dtype)
        return h[-1]

    hT = jax.lax.fori_loop(0, n_chunks, body, h0_ref[0].astype(jnp.float32))
    hT_ref[0] = hT.astype(hT_ref.dtype)


def rglru_scan(
    a: jax.Array,  # (B, T, W) decay in (0, 1]
    b: jax.Array,  # (B, T, W) input term
    h0: jax.Array | None = None,  # (B, W)
    *,
    chunk: int = 64,
    block_w: int = 128,
    interpret: bool = False,
):
    """Returns (h (B, T, W), h_T (B, W))."""
    bb_, t, w = a.shape
    if h0 is None:
        h0 = jnp.zeros((bb_, w), jnp.float32)
    pad_t = -t % chunk
    if pad_t:
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, 0)))
    pad_w = -w % block_w
    if pad_w:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_w)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    tp, wp = t + pad_t, w + pad_w
    bw = min(block_w, wp)

    la = jnp.log(jnp.clip(a.astype(jnp.float32), 1e-37, 1.0))
    h, hT = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk, t=tp),
        grid=(bb_, wp // bw),
        in_specs=[
            pl.BlockSpec((1, tp, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, tp, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bw), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tp, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bw), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bb_, tp, wp), a.dtype),
            jax.ShapeDtypeStruct((bb_, wp), jnp.float32),
        ],
        interpret=interpret,
    )(la, b, h0)
    return h[:, :t, :w], hT[:, :w]
