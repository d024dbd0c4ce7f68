"""The comparison that decides ``correct``: the codec's guarantee, in fp64.

GBATC promises two things for a container compressed at a target NRMSE
(paper eq. 3 and Algorithm 1), both held here against the original field
that the benchmark made, with numpy alone:

* every species' range-normalised RMSE is at most the target;
* every block of the block geometry (bt x ph x pw values of one species),
  in units normalised by that species' range, is within
  ``tau = target * sqrt(bt * ph * pw)`` in l2.

Nothing here imports the program or takes anything it made: the range
and minimum of each species come from the original field. A window answer
(a slice of species and frames) is held to the block bound over the part
of each block that lies inside the window, which is at most that block's
whole error.

An encode job also trains the networks, and the guarantee holds whatever
they learned, so an encode run also compares how far the trained
networks came on their own: the range-normalised RMSE over all species
of the same container decoded without the guarantee's corrections
(``net_nrmse``). A trainer that leaves its state unchanged fails it.

``bf16_control`` is the control the check has to fail: the same answer
carried in bfloat16 (the normalised values rounded to 8 mantissa bits),
the precision below the fp32 the configuration states. A run that reads
the control (``ctx.control``) puts it in the program's place and checks
it against the same limits.
"""

from __future__ import annotations

import numpy as np


def species_scale(field: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-species (min, range) of the original field, in fp64."""
    s = field.shape[0]
    flat = field.reshape(s, -1)
    mn = flat.min(axis=1).astype(np.float64)
    rng = flat.max(axis=1).astype(np.float64) - mn
    return mn, np.maximum(rng, 1e-30)


def _block_norms(err: np.ndarray, block: tuple[int, int, int]) -> np.ndarray:
    """l2 norm of each (possibly partial) block of a (T, H, W) error field.

    Partial blocks occur only along time, where a window starts or ends
    inside a block; their norm is over the frames the window holds.
    """
    bt, ph, pw = block
    t, h, w = err.shape
    sq = np.square(err)
    # pad time to whole blocks with zeros: a partial block's norm is the
    # norm over the frames it has
    pad = (-t) % bt
    if pad:
        sq = np.concatenate([sq, np.zeros((pad, h, w))], axis=0)
    tt = sq.shape[0]
    sums = sq.reshape(tt // bt, bt, h // ph, ph, w // pw, pw).sum(
        axis=(1, 3, 5))
    return np.sqrt(sums)


def guarantee_readings(original: np.ndarray, answer: np.ndarray, *,
                       target: float, block: tuple[int, int, int],
                       mn: np.ndarray, rng: np.ndarray,
                       frame0: int = 0) -> dict:
    """Worst species NRMSE and worst block l2 over tau of one answer.

    ``original`` and ``answer`` are (S', T', H, W) over the same species
    and frames; ``mn``/``rng`` are those species' scales. ``frame0`` is
    the window's first frame in the container, which sets where the
    time-block boundaries fall.
    """
    if answer.shape != original.shape:
        return {"nrmse": float("inf"), "block": float("inf")}
    bt = block[0]
    tau = target * np.sqrt(float(np.prod(block)))
    worst_nrmse = 0.0
    worst_block = 0.0
    for s in range(original.shape[0]):
        a = answer[s].astype(np.float64)
        if not np.all(np.isfinite(a)):
            return {"nrmse": float("inf"), "block": float("inf")}
        err = (a - original[s].astype(np.float64)) / rng[s]
        worst_nrmse = max(worst_nrmse, float(np.sqrt(np.mean(err * err))))
        lead = frame0 % bt
        if lead:
            err = np.concatenate(
                [np.zeros((lead,) + err.shape[1:]), err], axis=0)
        worst_block = max(worst_block,
                          float(_block_norms(err, block).max()) / tau)
    return {"nrmse": worst_nrmse, "block": worst_block}


def bf16_control(answer: np.ndarray, mn: np.ndarray, rng: np.ndarray
                 ) -> np.ndarray:
    """The answer with its normalised values carried in bfloat16.

    Rounds the fp32 normalised value to the nearest bfloat16 (8 bits of
    mantissa, ties to even) and maps it back, species by species.
    """
    out = np.empty(answer.shape, np.float32)
    for s in range(answer.shape[0]):
        norm = ((answer[s].astype(np.float64) - mn[s]) / rng[s]).astype(
            np.float32)
        bits = norm.view(np.uint32).astype(np.uint64)
        bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
        rounded = bits.astype(np.uint32).view(np.float32).astype(np.float64)
        out[s] = (rounded * rng[s] + mn[s]).astype(np.float32)
    return out


def network_nrmse(original: np.ndarray, net: np.ndarray, mn: np.ndarray,
                  rng: np.ndarray) -> float:
    """Range-normalised RMSE over all species of the networks' own output
    ``net`` (S, T, H, W) against the original."""
    if net.shape != original.shape:
        return float("inf")
    sq = 0.0
    for s in range(original.shape[0]):
        err = (net[s].astype(np.float64) - original[s]) / rng[s]
        sq += float(np.mean(err * err))
    value = float(np.sqrt(sq / original.shape[0]))
    return value if np.isfinite(value) else float("inf")


def field_checks(ctx, decoded: np.ndarray, net: np.ndarray | None = None
                 ) -> dict:
    """The guarantee over a whole decoded field of a run (``ctx``) and,
    given ``net``, the same container decoded without the guarantee's
    corrections, how far the trained networks came. A run that reads the
    control checks both carried in bfloat16."""
    limits = ctx.config["limits"]
    target = float(ctx.config["target_nrmse"])
    mn, rng = species_scale(ctx.field)
    if ctx.control:
        decoded = bf16_control(decoded, mn, rng)
        if net is not None:
            net = bf16_control(net, mn, rng)
    r = guarantee_readings(ctx.field, decoded, target=target,
                           block=ctx.shapes.block, mn=mn, rng=rng)
    checks = {
        "nrmse": make_check(r["nrmse"], limits["nrmse"]),
        "block": make_check(r["block"], limits["block"]),
    }
    if net is not None:
        checks["net_nrmse"] = make_check(
            network_nrmse(ctx.field, net, mn, rng), limits["net_nrmse"])
    return checks


def checks_line(checks: dict) -> str:
    """One line per number compared: ``name value (limit L)``."""
    return "\n".join(
        f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
        f"{'pass' if c['ok'] else 'FAIL'})"
        for name, c in checks.items()
    )


def make_check(value: float, limit: float) -> dict:
    """A number compared with its limit, which it may not pass."""
    return {"value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}
