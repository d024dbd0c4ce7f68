"""Operations and bytes the algorithm needs, computed from shapes alone.

These are the yardstick for every roofline share and utilisation the
benchmark reports. They count what the algorithm asks for at its own
sizes, never what an implementation happens to do: the guarantee
kernels are counted at the block size D (80 at the paper's 4x5x4
geometry), not at a lane-padded width, and a multiply-add is two
operations.

The conv autoencoder (paper Fig. 1) convolves each block with 3x3x3
kernels at stride 1, every voxel of the block an output; the encoder
ends in one dense layer to the latent, the decoder starts with one.
The tensor-correction network is pointwise over the block's voxels:
S -> w1 S -> w2 S -> w3 S -> S dense layers.
"""

from __future__ import annotations

from dataclasses import dataclass

F32 = 4  # bytes per value on the chip path


@dataclass(frozen=True)
class Shapes:
    """The sizes a configuration runs at."""

    n_species: int
    n_blocks: int
    block: tuple[int, int, int]
    latent: int
    channels: tuple[int, ...]
    correction_widths: tuple[int, ...]  # () for GBA
    ae_steps: int
    corr_steps: int
    batch: int
    corr_batch: int

    @property
    def voxels(self) -> int:
        bt, ph, pw = self.block
        return bt * ph * pw


def shapes_from_config(cfg: dict) -> Shapes:
    """Shapes of a configuration file (see ``configs/``)."""
    data, pipe = cfg["data"], cfg["pipeline"]
    block = tuple(int(v) for v in pipe["geometry"])
    bt, ph, pw = block
    nb = (int(data["n_time"]) // bt) * (int(data["height"]) // ph) * (
        int(data["width"]) // pw)
    return Shapes(
        n_species=int(data["n_species"]),
        n_blocks=nb,
        block=block,
        latent=int(pipe["latent"]),
        channels=tuple(int(c) for c in pipe["conv_channels"]),
        correction_widths=(tuple(int(w) for w in cfg["correction_widths"])
                           if pipe["use_correction"] else ()),
        ae_steps=int(pipe["ae_steps"]),
        corr_steps=int(pipe["corr_steps"]),
        batch=int(pipe["batch_size"]),
        corr_batch=int(cfg["correction_batch"]),
    )


def encoder_flops(sh: Shapes) -> int:
    """Forward operations of the encoder for one block."""
    v = sh.voxels
    chans = (sh.n_species,) + sh.channels
    conv = sum(2 * 27 * chans[i] * chans[i + 1] * v
               for i in range(len(sh.channels)))
    return conv + 2 * sh.channels[-1] * v * sh.latent


def decoder_flops(sh: Shapes) -> int:
    """Forward operations of the decoder for one block (the mirror)."""
    return encoder_flops(sh)


def correction_flops_per_point(sh: Shapes) -> int:
    """Forward operations of the correction network for one voxel."""
    if not sh.correction_widths:
        return 0
    s = sh.n_species
    dims = (s,) + tuple(w * s for w in sh.correction_widths) + (s,)
    return sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))


def guarantee_kernel(sh: Shapes) -> dict:
    """One projection or one correction replay over all S x NB blocks.

    Both are a (NB, D) x (D, D) product per species: 2 S NB D^2
    operations. The projection reads the residual and the basis and
    writes the coefficients; the replay reads the reconstruction, the
    coefficients and the basis and writes the corrected vectors.
    """
    s, nb, d = sh.n_species, sh.n_blocks, sh.voxels
    flops = 2 * s * nb * d * d
    rows = s * nb * d * F32
    basis = s * d * d * F32
    return {
        "project": {"flops": flops, "bytes": 2 * rows + basis},
        "correct": {"flops": flops, "bytes": 3 * rows + basis},
    }


def encode_job_flops(sh: Shapes) -> int:
    """Model operations of one encode job (fit, then compress).

    Training steps count forward and backward (three forward passes)
    over their batch. Then the passes the job makes over all blocks:
    one encode, the decode without correction that feeds the correction
    fit (GBATC only), the decode with correction that the guarantee is
    computed against, the projection and the selected replay.
    """
    ae_fwd = encoder_flops(sh) + decoder_flops(sh)
    train = 3 * sh.ae_steps * sh.batch * ae_fwd
    point = correction_flops_per_point(sh)
    train += 3 * sh.corr_steps * sh.corr_batch * point
    nb = sh.n_blocks
    passes = nb * encoder_flops(sh)
    if point:
        passes += nb * decoder_flops(sh)
    passes += nb * (decoder_flops(sh) + sh.voxels * point)
    kern = guarantee_kernel(sh)
    passes += kern["project"]["flops"] + kern["correct"]["flops"]
    return train + passes
