"""Operations of the block-attention encoder family, from shapes alone.

The yardstick of the attention cell's utilisations, as ``counts.py`` is
the conv cells'. A block of ``S`` species, ``bt`` frames and ``ph x pw``
points is ``T = S bt`` tokens of ``ph pw`` values; ``d`` is d_model,
``m`` the SwiGLU width, ``L`` the latent. A multiply-add is two
operations, and only the matrix products are counted (norms, softmax,
SiLU and residual adds are a few operations per value, next to
``d`` or ``m`` per value in the products):

* in projection ``2 T (ph pw) d`` (encoder) or ``2 L T d`` (decoder);
* each layer: q, k, v and o projections ``4 * 2 T d^2``, scores and
  their weighted sum of values ``2 * 2 T^2 d`` (over all heads), the
  SwiGLU ``3 * 2 T d m``;
* out projection ``2 T d L`` (encoder) or ``2 T d (ph pw)`` (decoder).

The encoder and the decoder are mirrors, so they count the same.
"""

from __future__ import annotations

from bench import counts


def arch(cfg: dict) -> tuple[int, int, int, int]:
    """(d_model, n_heads, depth, mlp_hidden) of a configuration file."""
    return tuple(int(w) for w in cfg["pipeline"]["arch"])


def layer_flops(tokens: int, d: int, mlp: int) -> int:
    """One pre-norm layer over one block's tokens."""
    return 8 * tokens * d * d + 4 * tokens * tokens * d + 6 * tokens * d * mlp


def encoder_flops(sh: counts.Shapes, arch) -> int:
    """Forward operations of the encoder for one block."""
    d, _, depth, mlp = arch
    bt, ph, pw = sh.block
    t = sh.n_species * bt
    return (2 * t * ph * pw * d + depth * layer_flops(t, d, mlp)
            + 2 * t * d * sh.latent)


def decoder_flops(sh: counts.Shapes, arch) -> int:
    """Forward operations of the decoder for one block (the mirror)."""
    return encoder_flops(sh, arch)


def train_flops(sh: counts.Shapes, arch, steps: int, batch: int) -> int:
    """A fit of ``steps`` steps of ``batch`` blocks, forward and backward
    (three forward passes) through the encoder and the decoder."""
    return 3 * steps * batch * (encoder_flops(sh, arch)
                                + decoder_flops(sh, arch))


def pass_flops(sh: counts.Shapes, arch) -> int:
    """The two passes over all blocks a job makes: the encode after the
    fit and the decode the guarantee is computed against."""
    return sh.n_blocks * (encoder_flops(sh, arch) + decoder_flops(sh, arch))


def encode_job_flops(sh: counts.Shapes, arch) -> int:
    """Operations of one encode job: the fit, the two passes, and the
    guarantee's projection and correction replay."""
    kern = counts.guarantee_kernel(sh)
    return (train_flops(sh, arch, sh.ae_steps, sh.batch) + pass_flops(sh, arch)
            + kern["project"]["flops"] + kern["correct"]["flops"])
