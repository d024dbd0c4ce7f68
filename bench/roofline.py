"""Roofline shares against the chip's published peaks (``peaks.json``).

The least time a piece of work can take on a chip is the larger of its
operations over the peak operation rate and its bytes over the peak
memory bandwidth; its share of the roofline is that least time over the
time it took. The operations and bytes come from ``counts.py`` at the
algorithm's own sizes. The guarantee kernels compute in fp32 at the
highest matmul precision, for which no peak is published: the compute
roof is the bf16 peak, which no fp32 product can beat, so a share never
passes 100% for want of a lower roof.
"""

from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = _PEAKS) -> dict:
    """The peaks of one device kind; an unknown device is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, which bound binds: "compute" or "memory")."""
    t_compute = flops / peak["bf16_flops"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


def share(flops: float, nbytes: float, seconds: float, peak: dict
          ) -> tuple[float, str]:
    """(percent of the roofline, binding bound) for work done in
    ``seconds`` of device time."""
    t, bound = least_time(flops, nbytes, peak)
    return 100.0 * t / seconds, bound


def mfu(flops: float, seconds: float, peak: dict, chips: int = 1) -> float:
    """Percent of the chips' bf16 peak that ``flops`` in ``seconds`` is."""
    return 100.0 * flops / (seconds * chips * peak["bf16_flops"])
