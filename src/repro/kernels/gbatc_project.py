"""Pallas GBATC residual-projection kernels (TPU target; interpret-validated).

The guarantee post-process is dominated by two tall-skinny GEMMs over
millions of D=80 blocks per species:

  project: C   = R @ U            (coefficients, eq. 1)
  correct: x^G = x^R + (C.mask) @ U^T   (eq. 2)

TPU adaptation: D=80 is padded to 128 (MXU lane width); U (128x128 fp32 =
64 KiB) is VMEM-resident and reused across all row tiles — the kernels are
then purely bandwidth-bound on R, which is the roofline optimum for this
shape.

Two tiers of API:

* 2D single-species (``gbatc_project`` / ``gbatc_correct``) — the original
  kernels, kept for checkpoint compression and as the simplest contract.
* 3D batched-over-species (``*_batched``) — one dispatch for the whole
  (S, NB, D) problem with a per-species basis stack (S, D, D). The grid is
  (species tiles, row tiles); on CPU interpret mode the guarantee engine
  collapses it to a single step (species_per_tile=S, rows_per_tile=NB) so
  the interpreter overhead is paid once per call.

``gbatc_select_accumulate`` is Algorithm 1's masked select-and-accumulate:
given quantized coefficient values, each element's rank in the per-block
energy order, and the per-block cut M, it applies the keep mask inside the
XLA fusion that writes the kernel's lane-padded coefficient operand, so the
mask costs no HBM pass of its own and the per-block cut never enters the
kernel (an (S, NB) int32 block of (1, rows) is not a tile Mosaic accepts).

All kernels compute in the dtype of their inputs: fp32 on the MXU, where
every contraction asks for ``Precision.HIGHEST`` (the default would round
the operands to bf16, far coarser than the error bound), and fp64 under
interpret mode, where the guarantee engine needs bit-stable quantization
against the fp64 numpy oracle. Mosaic has no fp64.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST
# block-index zero for the index maps: a bare 0 traced under
# jax.enable_x64 (the guarantee engine's select) is an i64 Mosaic refuses
_Z = np.int32(0)


def _pad_to(x, target, axis):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _lane(d: int, interpret: bool, lane: int | None) -> int:
    """Padded feature width: MXU lane width on TPU, sublane-aligned under
    interpret (where any shape works and padding only wastes flops)."""
    if lane is None:
        lane = 128 if not interpret else 8
    return max(lane, _round_up(d, lane))


# ---------------------------------------------------------------------------
# 2D single-species kernels (original contract)
# ---------------------------------------------------------------------------


def _project_kernel(r_ref, u_ref, c_ref):
    r = r_ref[...]
    u = u_ref[...]
    c_ref[...] = jax.lax.dot_general(
        r, u, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=c_ref.dtype,
    ).astype(c_ref.dtype)


def _correct_kernel(x_ref, c_ref, m_ref, u_ref, o_ref):
    x = x_ref[...]
    cm = c_ref[...] * m_ref[...].astype(c_ref.dtype)
    u = u_ref[...]
    o_ref[...] = (
        x + jax.lax.dot_general(
            cm, u, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=o_ref.dtype,
        ).astype(o_ref.dtype)
    )


def gbatc_project(
    residual: jax.Array,  # (NB, D)
    basis: jax.Array,  # (D, D) orthonormal columns
    *,
    rows_per_tile: int = 512,
    interpret: bool = False,
    lane: int | None = None,
) -> jax.Array:
    """c = R @ U, blocked over rows; returns (NB, D) in the input dtype."""
    nb, d = residual.shape
    dtype = jnp.result_type(residual.dtype, basis.dtype)
    dp = _lane(d, interpret, lane)
    r = _pad_to(_pad_to(residual.astype(dtype), dp, 1),
                _round_up(nb, rows_per_tile), 0)
    u = _pad_to(_pad_to(basis.astype(dtype), dp, 0), dp, 1)
    rp = r.shape[0]
    rt = min(rows_per_tile, rp)

    c = pl.pallas_call(
        _project_kernel,
        grid=(rp // rt,),
        in_specs=[
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((dp, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rt, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, dp), dtype),
        interpret=interpret,
    )(r, u)
    return c[:nb, :d]


def gbatc_correct(
    x_rec: jax.Array,  # (NB, D)
    coeffs: jax.Array,  # (NB, D)
    mask: jax.Array,  # (NB, D) 0/1 keep mask
    basis: jax.Array,  # (D, D)
    *,
    rows_per_tile: int = 512,
    interpret: bool = False,
    lane: int | None = None,
) -> jax.Array:
    """x^G = x^R + (coeffs * mask) @ U^T."""
    nb, d = x_rec.shape
    dtype = jnp.result_type(x_rec.dtype, coeffs.dtype, basis.dtype)
    dp = _lane(d, interpret, lane)
    rp = _round_up(nb, rows_per_tile)
    x = _pad_to(_pad_to(x_rec.astype(dtype), dp, 1), rp, 0)
    c = _pad_to(_pad_to(coeffs.astype(dtype), dp, 1), rp, 0)
    m = _pad_to(_pad_to(mask, dp, 1), rp, 0)
    u = _pad_to(_pad_to(basis.astype(dtype), dp, 0), dp, 1)
    rt = min(rows_per_tile, rp)

    out = pl.pallas_call(
        _correct_kernel,
        grid=(rp // rt,),
        in_specs=[
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((dp, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rt, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, dp), dtype),
        interpret=interpret,
    )(x, c, m, u)
    return out[:nb, :d]


# ---------------------------------------------------------------------------
# Batched-over-species kernels: one dispatch for (S, NB, D)
# ---------------------------------------------------------------------------

_BATCH_DIMS = (((2,), (1,)), ((0,), (0,)))  # (s,n,d) @ (s,d,k) -> (s,n,k)
_BATCH_DIMS_T = (((2,), (2,)), ((0,), (0,)))  # (s,n,k) @ (s,d,k) -> (s,n,d)


def _project_batched_kernel(r_ref, u_ref, c_ref):
    c_ref[...] = jax.lax.dot_general(
        r_ref[...], u_ref[...], _BATCH_DIMS, precision=_HIGHEST,
        preferred_element_type=c_ref.dtype,
    ).astype(c_ref.dtype)


def _correct_batched_kernel(x_ref, c_ref, u_ref, o_ref):
    o_ref[...] = x_ref[...] + jax.lax.dot_general(
        c_ref[...], u_ref[...], _BATCH_DIMS_T, precision=_HIGHEST,
        preferred_element_type=o_ref.dtype,
    ).astype(o_ref.dtype)


def _batched_tiles(s, nb, species_per_tile, rows_per_tile):
    spt = s if species_per_tile is None else min(species_per_tile, s)
    rpt = nb if rows_per_tile is None else min(rows_per_tile, nb)
    return spt, rpt, _round_up(s, spt), _round_up(nb, rpt)


def gbatc_project_batched(
    residual: jax.Array,  # (S, NB, D)
    basis: jax.Array,  # (S, D, D) per-species orthonormal columns
    *,
    species_per_tile: int | None = None,
    rows_per_tile: int | None = None,
    interpret: bool = False,
    lane: int | None = None,
) -> jax.Array:
    """Per-species c_s = R_s @ U_s in one dispatch; returns (S, NB, D)."""
    s, nb, d = residual.shape
    dtype = jnp.result_type(residual.dtype, basis.dtype)
    dp = _lane(d, interpret, lane)
    spt, rpt, sp, rp = _batched_tiles(s, nb, species_per_tile, rows_per_tile)
    r = _pad_to(_pad_to(_pad_to(residual.astype(dtype), dp, 2), rp, 1), sp, 0)
    u = _pad_to(_pad_to(_pad_to(basis.astype(dtype), dp, 1), dp, 2), sp, 0)

    c = pl.pallas_call(
        _project_batched_kernel,
        grid=(sp // spt, rp // rpt),
        in_specs=[
            pl.BlockSpec((spt, rpt, dp), lambda i, j: (i, j, _Z)),
            pl.BlockSpec((spt, dp, dp), lambda i, j: (i, _Z, _Z)),
        ],
        out_specs=pl.BlockSpec((spt, rpt, dp), lambda i, j: (i, j, _Z)),
        out_shape=jax.ShapeDtypeStruct((sp, rp, dp), dtype),
        interpret=interpret,
    )(r, u)
    return c[:s, :nb, :d]


def gbatc_correct_batched(
    x_rec: jax.Array,  # (S, NB, D)
    coeffs: jax.Array,  # (S, NB, D) — already masked/dequantized
    basis: jax.Array,  # (S, D, D)
    *,
    species_per_tile: int | None = None,
    rows_per_tile: int | None = None,
    interpret: bool = False,
    lane: int | None = None,
) -> jax.Array:
    """Per-species x^G_s = x^R_s + C_s @ U_s^T in one dispatch."""
    s, nb, d = x_rec.shape
    dtype = jnp.result_type(x_rec.dtype, coeffs.dtype, basis.dtype)
    dp = _lane(d, interpret, lane)
    spt, rpt, sp, rp = _batched_tiles(s, nb, species_per_tile, rows_per_tile)
    x = _pad_to(_pad_to(_pad_to(x_rec.astype(dtype), dp, 2), rp, 1), sp, 0)
    c = _pad_to(_pad_to(_pad_to(coeffs.astype(dtype), dp, 2), rp, 1), sp, 0)
    u = _pad_to(_pad_to(_pad_to(basis.astype(dtype), dp, 1), dp, 2), sp, 0)

    out = pl.pallas_call(
        _correct_batched_kernel,
        grid=(sp // spt, rp // rpt),
        in_specs=[
            pl.BlockSpec((spt, rpt, dp), lambda i, j: (i, j, _Z)),
            pl.BlockSpec((spt, rpt, dp), lambda i, j: (i, j, _Z)),
            pl.BlockSpec((spt, dp, dp), lambda i, j: (i, _Z, _Z)),
        ],
        out_specs=pl.BlockSpec((spt, rpt, dp), lambda i, j: (i, j, _Z)),
        out_shape=jax.ShapeDtypeStruct((sp, rp, dp), dtype),
        interpret=interpret,
    )(x, c, u)
    return out[:s, :nb, :d]


def gbatc_select_accumulate(
    x_rec: jax.Array,  # (S, NB, D)
    coeff_vals: jax.Array,  # (S, NB, D) dequantized coefficient values
    rank: jax.Array,  # (S, NB, D) int32 energy-order rank of each element
    m: jax.Array,  # (S, NB) int32 per-block cut: keep rank < m
    basis: jax.Array,  # (S, D, D)
    *,
    species_per_tile: int | None = None,
    rows_per_tile: int | None = None,
    interpret: bool = False,
    lane: int | None = None,
) -> jax.Array:
    """Algorithm-1 tail: x^G = x^R + (C * [rank < m]) @ U^T.

    The keep mask rides the fusion that pads ``coeff_vals`` to the kernel's
    tile shape, so the masked coefficients cost no HBM pass of their own.
    """
    keep = rank < m.astype(rank.dtype)[..., None]
    return gbatc_correct_batched(
        x_rec, coeff_vals * keep.astype(coeff_vals.dtype), basis,
        species_per_tile=species_per_tile, rows_per_tile=rows_per_tile,
        interpret=interpret, lane=lane,
    )
