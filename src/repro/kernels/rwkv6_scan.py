"""Pallas chunked WKV6 scan (TPU target; validated with interpret=True).

TPU adaptation of the RWKV-6 recurrence (the reference CUDA kernel is a
per-timestep serial loop; on TPU we use the *chunked matrix form* so the MXU
does the work):

Within a chunk of C tokens (per head, head dim N), with per-channel decays
w_t in (0,1] and logs lw_t = log w_t <= 0, cum_t = sum_{j<=t} lw_j:

  out_t = r_t diag(exp(cum_{t-1})) S_0                      (state term)
        + sum_{s<t} [sum_i r_t[i] e^{cum_{t-1}[i]-cum_s[i]} k_s[i]] v_s
        + (sum_i r_t[i] u[i] k_t[i]) v_t                    (bonus diagonal)
  S_C   = diag(exp(cum_C)) S_0 + sum_s diag(e^{cum_C-cum_s}) k_s v_s^T

Every exponent is <= 0 (pairwise differences along the decay), so the chunked
form is *unconditionally* stable — no division by vanishing cumulative decay
(the failure mode of the naive k/P formulation).

Grid = (B*H,); each program walks its chunks sequentially carrying the (N, N)
fp32 state in the fori_loop carry (VMEM-resident); parallelism comes from the
B*H grid axis and the MXU within chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sT_ref,
                 *, chunk, t):
    u = u_ref[0].astype(jnp.float32)  # (N,)
    n_chunks = t // chunk
    tri = (
        jax.lax.iota(jnp.int32, chunk)[:, None]
        > jax.lax.iota(jnp.int32, chunk)[None, :]
    )

    def body(ci, s):
        sl = (0, pl.ds(ci * chunk, chunk), slice(None))
        r = r_ref[sl].astype(jnp.float32)  # (C, N)
        k = k_ref[sl].astype(jnp.float32)
        v = v_ref[sl].astype(jnp.float32)
        lw = lw_ref[sl].astype(jnp.float32)
        cum = jnp.cumsum(lw, axis=0)  # inclusive prefix
        cum_prev = cum - lw  # exclusive prefix (cum_{t-1})

        # state term: (r * e^{cum_prev}) @ S
        out = jax.lax.dot_general(
            r * jnp.exp(cum_prev), s, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (C, N_v)
        # intra-chunk pairwise-decay scores (all exponents <= 0 where used)
        pair = cum_prev[:, None, :] - cum[None, :, :]  # (C, C, N)
        weights = jnp.where(tri[:, :, None], jnp.exp(pair), 0.0)
        scores = jnp.einsum("ti,tsi,si->ts", r, weights, k)
        diag = jnp.sum(r * u[None, :] * k, axis=1)  # (C,) bonus term
        out = out + jax.lax.dot_general(
            scores, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + diag[:, None] * v
        o_ref[sl] = out.astype(o_ref.dtype)

        # chunk-boundary state update (exponents <= 0)
        k_w = k * jnp.exp(cum[-1][None, :] - cum)  # (C, N)
        s_new = jnp.exp(cum[-1])[:, None] * s + jax.lax.dot_general(
            k_w, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return s_new

    sT = jax.lax.fori_loop(0, n_chunks, body, s0_ref[0].astype(jnp.float32))
    sT_ref[0] = sT.astype(sT_ref.dtype)


def rwkv6_scan(
    r: jax.Array,  # (B, T, H, N)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decays in (0, 1]
    u: jax.Array,  # (H, N)
    s0: jax.Array | None = None,  # (B, H, N, N)
    *,
    chunk: int = 32,
    interpret: bool = False,
):
    """Returns (out (B, T, H, N), final state (B, H, N, N))."""
    b, t, h, n = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)
    pad = -t % chunk
    if pad:
        zeros = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = (jnp.pad(a, zeros) for a in (r, k, v))
        w = jnp.pad(w, zeros, constant_values=1.0)
    tp = t + pad

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, tp, n)

    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-37, 1.0))
    rr, kk, vv, lww = to_bh(r), to_bh(k), to_bh(v), to_bh(lw)
    uu = jnp.tile(u.astype(jnp.float32), (b, 1)).reshape(b * h, n)
    ss = s0.reshape(b * h, n, n)

    seq_spec = pl.BlockSpec((1, tp, n), lambda i: (i, 0, 0))
    out, sT = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk, t=tp),
        grid=(b * h,),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tp, n), r.dtype),
            jax.ShapeDtypeStruct((b * h, n, n), jnp.float32),
        ],
        interpret=interpret,
    )(rr, kk, vv, lww, uu, ss)
    out = out.reshape(b, h, tp, n).transpose(0, 2, 1, 3)[:, :t]
    return out, sT.reshape(b, h, n, n)
