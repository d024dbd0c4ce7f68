"""The benchmark's own plain reference of the block-attention family.

A copy of ``repro.models.block_attention_ref`` (the family's equations in
straightforward ``jax.numpy``, every matrix product at
``Precision.HIGHEST``), so that the comparisons deciding ``correct``
import nothing of the program. It adds two arguments:

* ``operands``: the dtype every matrix product's two operands are first
  rounded to (ties to even), before an exact product with float32 sums.
  ``bfloat16`` is the rounding the v5e's DEFAULT precision makes in its
  one MXU pass; ``float32`` leaves them whole.
* ``dtype``: the dtype the whole computation is carried in: parameters,
  inputs, activations and every result. ``bfloat16`` is the control, the
  precision below the float32 activations a configuration states.

The encoder, for ``T = S * bt`` tokens of ``ph * pw`` values:
``h = x W_in + b_in + P`` with fixed sinusoidal positions ``P``;
``depth`` pre-norm layers ``h += Attn(N1(h))``,
``h += (silu(N2(h) W_g) * N2(h) W_u) W_d``; then
``z = flatten(N(h)) W_head + b_head``. The decoder is its mirror:
``h = unflatten(z W_in + b_in) + P``, ``depth`` layers of its own, then
``N(h) W_out + b_out``. ``N`` is RMSNorm with a learned scale (epsilon
1e-6) and ``Attn`` multi-head softmax attention over all tokens of the
block, unmasked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def positions(n: int, d: int) -> np.ndarray:
    """(n, d) sinusoidal positions: sin on even channels, cos on odd."""
    ang = np.arange(n)[:, None] / (10000 ** (np.arange(0, d, 2)[None, :] / d))
    out = np.zeros((n, d), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return out


def _rms_norm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def _stack(params, prefix, h, n_heads, mm):
    """``depth`` pre-norm layers ``prefix``_block0.. over (N, T, d) ``h``,
    then the final RMSNorm."""

    def attention(p, x):
        n, t, dm = x.shape
        hd = dm // n_heads

        def heads(w):  # (N, T, d_model) -> (N, heads, T, head size)
            return mm(x, w).reshape(n, t, n_heads, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
        scores = mm(q, k.transpose(0, 1, 3, 2)) / np.sqrt(hd)
        e = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        probs = e / e.sum(axis=-1, keepdims=True)
        o = mm(probs, v).transpose(0, 2, 1, 3).reshape(n, t, dm)
        return mm(o, p["wo"])

    depth = sum(1 for k in params if k.startswith(prefix + "_block"))
    for i in range(depth):
        p = params[f"{prefix}_block{i}"]
        h = h + attention(p["attn"], _rms_norm(h, p["ln1"]["scale"]))
        u = _rms_norm(h, p["ln2"]["scale"])
        g = mm(u, p["ffn"]["wg"])
        h = h + mm(g / (1 + jnp.exp(-g)) * mm(u, p["ffn"]["wu"]),
                   p["ffn"]["wd"])
    return _rms_norm(h, params[f"{prefix}_norm"]["scale"])


def _run(fn, params, x, operands, dtype) -> np.ndarray:
    """``fn(params, x, mm, pos)`` at ``Precision.HIGHEST``, carried in
    ``dtype``, with ``mm`` rounding its operands to ``operands``; the
    result as float32 numpy."""
    dtype = jnp.dtype(dtype)
    p = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)

    def mm(a, b):
        a = a.astype(operands).astype(dtype)
        b = b.astype(operands).astype(dtype)
        return a @ b

    def pos(n, d):
        return jnp.asarray(positions(n, d), dtype)

    with jax.default_matmul_precision("highest"):
        out = fn(p, jnp.asarray(x, dtype), mm, pos)
    return np.asarray(out.astype(jnp.float32))


def encode_ref(params, blocks, n_heads: int, operands=jnp.float32,
               dtype=jnp.float32) -> np.ndarray:
    """(N, S, bt, ph, pw) blocks -> (N, latent) latents, as float32 numpy.

    ``params`` is the family's tree (its ``enc_*`` entries are read)."""

    def fn(p, x, mm, pos):
        n, s, bt, ph, pw = x.shape
        dm = p["enc_proj"]["w"].shape[1]
        h = (mm(x.reshape(n, s * bt, ph * pw), p["enc_proj"]["w"])
             + p["enc_proj"]["b"] + pos(s * bt, dm))
        h = _stack(p, "enc", h, n_heads, mm).reshape(n, -1)
        return mm(h, p["enc_head"]["w"]) + p["enc_head"]["b"]

    return _run(fn, params, blocks, operands, dtype)


def decode_ref(params, z, n_heads: int, block_shape, operands=jnp.float32,
               dtype=jnp.float32) -> np.ndarray:
    """(N, latent) latents -> (N, S, bt, ph, pw) blocks, as float32 numpy.

    ``params`` is the decoder's tree as a container carries it (the
    ``dec_*`` entries); ``block_shape`` is (S, bt, ph, pw)."""
    s, bt, ph, pw = block_shape

    def fn(p, z, mm, pos):
        n = z.shape[0]
        dm = p["dec_head"]["w"].shape[0]
        h = (mm(z, p["dec_proj"]["w"]) + p["dec_proj"]["b"]).reshape(
            n, s * bt, dm) + pos(s * bt, dm)
        h = _stack(p, "dec", h, n_heads, mm)
        return (mm(h, p["dec_head"]["w"]) + p["dec_head"]["b"]).reshape(
            n, s, bt, ph, pw)

    return _run(fn, params, z, operands, dtype)
