"""The benchmark's own copy of the S3D HCCI surrogate field generator.

The benchmark makes its inputs itself, from ``--seed``, and imports
nothing of the program to do so: this is a copy of the frame-window
generator in ``repro.data.s3d`` (``_base_fields``, ``_frame_fields``,
``_species_responses``), numpy only. The same ``(seed, frames)`` give
the same float32 field, bit for bit, as that module does.

The field: ``n_species`` mass fractions on a ``height x width`` grid
over a window of a ``n_series``-frame run, built from advected Gaussian
random fields with an ignition front (majors O(1e-1), minors down to
O(1e-8)). Its fields are periodic in both grid directions.
"""

from __future__ import annotations

import numpy as np


def _grf(rng: np.random.Generator, h: int, w: int, beta: float) -> np.ndarray:
    """Gaussian random field with a k^-beta spectrum, unit std."""
    kx = np.fft.fftfreq(h)[:, None]
    ky = np.fft.fftfreq(w)[None, :]
    k = np.sqrt(kx**2 + ky**2)
    k[0, 0] = 1.0
    amp = k ** (-beta / 2.0)
    amp[0, 0] = 0.0
    noise = rng.normal(size=(h, w)) + 1j * rng.normal(size=(h, w))
    field = np.fft.ifft2(noise * amp).real
    field -= field.mean()
    std = field.std()
    return field / (std if std > 0 else 1.0)


def _advect(field: np.ndarray, shift_y: float, shift_x: float) -> np.ndarray:
    """Periodic sub-pixel advection by a Fourier phase shift."""
    h, w = field.shape
    fy = np.fft.fftfreq(h)[:, None]
    fx = np.fft.fftfreq(w)[None, :]
    phase = np.exp(-2j * np.pi * (fy * shift_y + fx * shift_x))
    return np.fft.ifft2(np.fft.fft2(field) * phase).real


def species_window(*, seed: int, n_species: int, n_series: int, height: int,
                   width: int, beta: float, major_frac: float, t0: int,
                   t1: int) -> np.ndarray:
    """(n_species, t1 - t0, height, width) float32 mass fractions."""
    if not 0 <= t0 < t1 <= n_series:
        raise ValueError(f"frames ({t0}, {t1}) outside [0, {n_series})")
    rng = np.random.default_rng(seed)
    h, w = height, width
    mixture = _grf(rng, h, w, beta)
    strain = _grf(rng, h, w, beta)
    modulation = _grf(rng, h, w, beta - 0.5)
    delay = 0.5 + 0.12 * mixture + 0.08 * strain
    width_ign = 0.06 * (1.0 + 0.3 * np.tanh(modulation))
    drift = rng.normal(scale=0.8, size=(2,))

    times = np.linspace(0.0, 1.0, n_series)[t0:t1]
    shape = (len(times), h, w)
    c = np.empty(shape)
    z = np.empty(shape)
    st = np.empty(shape)
    md = np.empty(shape)
    for i, tt in enumerate(times):
        z[i] = _advect(mixture, drift[0] * tt * h * 0.02,
                       drift[1] * tt * w * 0.02)
        st[i] = _advect(strain, -drift[1] * tt * h * 0.015,
                        drift[0] * tt * w * 0.015)
        md[i] = _advect(modulation, drift[0] * tt * h * 0.01,
                        -drift[0] * tt * w * 0.02)
        c[i] = 1.0 / (1.0 + np.exp(-(tt - delay) / width_ign))

    n_major = max(2, int(round(major_frac * n_species)))
    out = np.empty((n_species, *shape), dtype=np.float32)
    for j in range(n_species):
        rj = np.random.default_rng(seed * 1000 + 17 + j)
        if j == 0:  # fuel, consumed through ignition
            y = 0.06 * (1.0 - c) * (1.0 + 0.25 * z)
        elif j == 1:  # oxidizer
            y = 0.22 * (1.0 - 0.85 * c) * (1.0 - 0.1 * z)
        elif j < n_major:  # products grow with progress
            a = rj.uniform(0.02, 0.12)
            y = a * c * (1.0 + 0.2 * np.tanh(z + 0.3 * md))
        else:  # minors: exponential bumps around a progress point
            logamp = rj.uniform(-8.0, -2.5)
            c0 = rj.uniform(0.15, 0.9)
            sig = rj.uniform(0.05, 0.25)
            sens = rj.uniform(1.0, 4.0)
            y = (10.0**logamp) * np.exp(
                -(((c - c0) / sig) ** 2) + sens * 0.3 * z + 0.2 * st
            )
        out[j] = y.astype(np.float32)
    return out


def field_for(data_cfg: dict, seed: int,
              block: tuple[int, int, int]) -> np.ndarray:
    """The field of a run: the configuration's field, in an order drawn
    from ``seed``.

    The configuration's ``data`` block fixes the field (its own
    ``seed`` is the surrogate's). A run's ``seed`` permutes the species
    and rolls the periodic grid by whole blocks (``block`` is the
    codec's (bt, ph, pw)), so every run codes the same set of blocks,
    the same work, in another order.
    """
    t0 = int(data_cfg["first_frame"])
    base = species_window(
        seed=int(data_cfg["seed"]), n_species=int(data_cfg["n_species"]),
        n_series=int(data_cfg["n_series"]), height=int(data_cfg["height"]),
        width=int(data_cfg["width"]), beta=float(data_cfg["beta"]),
        major_frac=float(data_cfg["major_frac"]),
        t0=t0, t1=t0 + int(data_cfg["n_time"]),
    )
    s, _, h, w = base.shape
    _, ph, pw = block
    rng = np.random.default_rng([int(seed), 0x5EED])
    perm = rng.permutation(s)
    dy = ph * int(rng.integers(h // ph))
    dx = pw * int(rng.integers(w // pw))
    return np.roll(base[perm], (dy, dx), axis=(2, 3))
