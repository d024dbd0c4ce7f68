"""Faults planted in the program underneath a run: each has to make the
check's ``correct`` come out false.

A fault is a function of a patcher (anything with ``setattr(obj, name,
value)`` that undoes itself, such as ``pytest.MonkeyPatch``) and of the
cell's configuration that breaks one step of the timed path for as long
as the patch holds. ``FAULTS`` maps a fault's name to the kind of cell
it can occur in (the driver: ``encode``, ``decode`` or ``query``) and
the function that plants it. ``bench/tests/test_bench_faults.py`` drives
tiny runs on the CPU with each; ``bench/control.py --fault <name>``
reads a fault on the chip at a cell's own size.
"""

from __future__ import annotations

import numpy as np


def trainer_frozen(mp, cfg):
    """Every trainer step returns its state unchanged: the AE (and the
    correction net) keep their initialisation."""
    from repro.train import train_loop

    def frozen(self, params, state, batch):
        return params, state, self._loss_fn(params, *batch)

    mp.setattr(train_loop.MiniBatchTrainer, "_step", frozen)


def trainer_half_batch(mp, cfg):
    """Every trainer step leaves half of its batch out and takes the mean
    over the rest."""
    from repro.train import train_loop

    orig = train_loop.MiniBatchTrainer._step

    def half(self, params, state, batch):
        return orig(self, params, state,
                    tuple(a[: a.shape[0] // 2] for a in batch))

    mp.setattr(train_loop.MiniBatchTrainer, "_step", half)


def blocks_uncorrected(mp, cfg):
    """The guarantee engine's batch of blocks: half keep no correction."""
    from repro.core import gae

    orig = gae.GuaranteeEngine._build_artifacts

    def half(prep, m_eff, cq, needs, bin_size, tau):
        m_eff, needs = m_eff.copy(), needs.copy()
        nb = m_eff.shape[1]
        m_eff[:, nb // 2:] = 0
        needs[:, nb // 2:] = False
        return orig(prep, m_eff, cq, needs, bin_size, tau)

    mp.setattr(gae.GuaranteeEngine, "_build_artifacts", staticmethod(half))


def latent_altered(mp, cfg):
    """A latent changed after the guarantee was computed against it."""
    from repro.core.pipeline import GBATCPipeline

    orig = GBATCPipeline._prepare_guarantee

    def altered(self, *a, **kw):
        entry = orig(self, *a, **kw)
        entry[1][0] += 1000  # the quantized latents the container stores
        return entry

    mp.setattr(GBATCPipeline, "_prepare_guarantee", altered)


def rows_undecoded(mp, cfg):
    """The full decode's batch of block rows: half left at zero."""
    from repro.codec import decode

    orig = decode._fused_vecs

    def half(*a, **kw):
        out = orig(*a, **kw)
        return out.at[:, out.shape[1] // 2:].set(0.0)

    mp.setattr(decode, "_fused_vecs", half)


def field_altered(mp, cfg):
    """One value of the decoded field changed where it is finalized."""
    from repro.codec import decode

    orig = decode._finalize_field

    def altered(*a, **kw):
        out = orig(*a, **kw)
        out[0, 0, 0, 0] += np.float32(1.0)
        return out

    mp.setattr(decode, "_finalize_field", altered)


def tick_half_dropped(mp, cfg):
    """Half of the requests the scheduler drains are left out, from the
    window on (set-up's warm-up requests, one per species-union size,
    are all served)."""
    import itertools

    from repro.serve import decode_service

    warm = int(cfg["data"]["n_species"]) + 1
    orig = decode_service.DecodeService._tick
    seq = itertools.count()

    def half(self, batch):
        kept = [r for r in batch if (i := next(seq)) < warm or i % 2 == 0]
        if kept:
            orig(self, kept)

    mp.setattr(decode_service.DecodeService, "_tick", half)


def answer_altered(mp, cfg):
    """One value of each answer changed where it is finalized."""
    from repro.serve import decode_service

    orig = decode_service.finalize_slice

    def altered(*a, **kw):
        out = np.array(orig(*a, **kw))
        out.reshape(-1)[0] += np.float32(1.0)
        return out

    mp.setattr(decode_service, "finalize_slice", altered)


FAULTS = {
    "trainer_frozen": ("encode", trainer_frozen),
    "trainer_half_batch": ("encode", trainer_half_batch),
    "blocks_uncorrected": ("encode", blocks_uncorrected),
    "latent_altered": ("encode", latent_altered),
    "rows_undecoded": ("decode", rows_undecoded),
    "field_altered": ("decode", field_altered),
    "tick_half_dropped": ("query", tick_half_dropped),
    "answer_altered": ("query", answer_altered),
}
