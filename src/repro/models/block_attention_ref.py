"""Plain reference of the block-attention encoder family, in float32.

The forward passes of :class:`repro.models.block_attention.BlockAttentionAE`
written out from the family's equations in straightforward ``jax.numpy``:
no trainer, no batching into chunks, no fused decode, nothing imported
from ``models/common.py``. Every matrix product runs under
``jax.default_matmul_precision("highest")``, so on a TPU, too, the
reference computes in float32 (the default there would round each
operand to bfloat16).

The equations, for one block of ``S`` species, ``bt`` frames and
``ph x pw`` points, with ``T = S * bt`` tokens of ``ph * pw`` values:

* patch tokens: the block's values, one token per species per frame;
* encoder: ``h = x W_in + b_in + P``, with ``P`` the fixed sinusoidal
  positions (sine on even, cosine on odd channels, base 10000); then
  ``depth`` pre-norm layers ``h += Attn(N1(h))``, ``h += FFN(N2(h))``;
  then ``z = flatten(N(h)) W_head + b_head``, the latent;
* ``N`` is RMSNorm with a learned scale (epsilon 1e-6), ``Attn`` is
  multi-head softmax attention over all ``T`` tokens of the block (no
  mask; heads of ``d_model / n_heads``, scores scaled by the inverse
  square root of the head size), ``FFN(h) = (silu(h W_g) * h W_u) W_d``
  (SwiGLU);
* decoder, the mirror: ``h = unflatten(z W_in + b_in) + P``, ``depth``
  layers with their own weights, ``N``, then ``h W_out + b_out`` back to
  patch values.

Departures from arXiv 2409.05357, as far as the repository holds it
(``PAPERS.md`` keeps the abstract alone, which describes attention over
blocks of the field with the guarantee of 2404.18063 behind it): the
tokenisation (one token per species and frame), the fixed positions,
RMSNorm, SwiGLU, the single dense latent head over the flattened tokens
and the decoder that mirrors the encoder are this repository's choices,
not taken from the paper's text.

The parameters are the family's own tree (``model.init``): the encoder
reads the ``enc_*`` entries, the decoder the ``dec_*`` entries that a
container carries. The depth is read from the tree; the number of heads
is not in it and is passed.
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


def _attention(p, x, n_heads: int):
    n, t, dm = x.shape
    hd = dm // n_heads

    def heads(w):  # (N, T, d_model) -> (N, heads, T, head size)
        return (x @ w).reshape(n, t, n_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(hd)
    e = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = e / e.sum(axis=-1, keepdims=True)
    o = (probs @ v).transpose(0, 2, 1, 3).reshape(n, t, dm)
    return o @ p["wo"]


def _layer(p, h, n_heads: int):
    h = h + _attention(p["attn"], _rms_norm(h, p["ln1"]["scale"]), n_heads)
    u = _rms_norm(h, p["ln2"]["scale"])
    g = u @ p["ffn"]["wg"]
    return h + (g / (1 + jnp.exp(-g)) * (u @ p["ffn"]["wu"])) @ p["ffn"]["wd"]


def _depth(params, prefix: str) -> int:
    return sum(1 for k in params if k.startswith(prefix + "_block"))


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def encode_ref(params, blocks, n_heads: int):
    """(N, S, bt, ph, pw) blocks -> (N, latent) latents."""
    p = _f32(params)
    n, s, bt, ph, pw = blocks.shape
    x = jnp.asarray(blocks, jnp.float32).reshape(n, s * bt, ph * pw)
    with jax.default_matmul_precision("highest"):
        dm = p["enc_proj"]["w"].shape[1]
        h = x @ p["enc_proj"]["w"] + p["enc_proj"]["b"] + positions(s * bt, dm)
        for i in range(_depth(p, "enc")):
            h = _layer(p[f"enc_block{i}"], h, n_heads)
        h = _rms_norm(h, p["enc_norm"]["scale"]).reshape(n, -1)
        return h @ p["enc_head"]["w"] + p["enc_head"]["b"]


def decode_ref(params, z, n_heads: int, block_shape):
    """(N, latent) latents -> (N, S, bt, ph, pw) blocks; ``block_shape`` is
    (S, bt, ph, pw)."""
    p = _f32(params)
    s, bt, ph, pw = block_shape
    z = jnp.asarray(z, jnp.float32)
    n = z.shape[0]
    with jax.default_matmul_precision("highest"):
        dm = p["dec_head"]["w"].shape[0]
        h = (z @ p["dec_proj"]["w"] + p["dec_proj"]["b"]).reshape(
            n, s * bt, dm) + positions(s * bt, dm)
        for i in range(_depth(p, "dec")):
            h = _layer(p[f"dec_block{i}"], h, n_heads)
        h = _rms_norm(h, p["dec_norm"]["scale"])
        return (h @ p["dec_head"]["w"] + p["dec_head"]["b"]).reshape(
            n, s, bt, ph, pw)
