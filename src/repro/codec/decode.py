"""Full-field decode paths: blob/artifact -> (S, T, H, W) float32.

The hot path (:func:`decompress`) is device-resident: the container head
(meta, latents, parameters) parses first — served from the content-keyed
head cache on repeat blobs — and one fused jit (dequantized latents → AE
decoder → pointwise correction → (S, NB, D) vectors) is dispatched
asynchronously; the per-species guarantee streams entropy-decode while
the NN decode runs, and a single batched Pallas replay applies the
corrections. The pre-throughput-engine orchestration is retained as
:func:`reconstruct_reference` / :func:`decompress_reference` — the fused
path must match it **bit for bit** (asserted in tests and gating
``benchmarks/bench_throughput.py``).
"""

from __future__ import annotations

import numpy as np

from repro.codec.runtime import (
    _cached_head,
    _decode_guarantees,
    _decode_head,
    _evict_head,
    _fused_vecs,
    _latents32,
    _runtime,
    _runtime_reference,
)
from repro.core.container import ContainerFormatError
from repro.core import blocking, correction, entropy, gae
from repro.codec.artifact import CompressedArtifact, _batched
from repro.core.quantization import dequantize


def _finish_artifact(head, *, huffman=None) -> CompressedArtifact:
    return CompressedArtifact(
        latent_q=head.latents.full(),
        latent_bin=head.latent_bin,
        ae_params=head.ae_params,
        corr_params=head.corr_params,
        species_guarantees=_decode_guarantees(head, huffman=huffman),
        norm_min=head.norm_min,
        norm_range=head.norm_range,
        shape=head.shape,
        cfg=head.cfg,
        _latent_blob=head.latent_stream,
        _wire=head.blob,
    )


def decode_artifact(blob: bytes) -> CompressedArtifact:
    """Rebuild a :class:`CompressedArtifact` from a container blob alone.

    The returned artifact carries only what the wire format does: the AE
    *decoder* parameters (the encoder never ships), the correction network
    if present, and the per-species guarantee streams (entropy-decoded
    species-parallel, decode tables memoized per codebook). Always parses
    fresh — deserialize timing stays honest; the head cache serves
    :func:`decompress` and :class:`~repro.codec.PartialDecoder`.
    """
    return _finish_artifact(_decode_head(blob))


def decode_artifact_reference(blob: bytes) -> CompressedArtifact:
    """Pre-change deserialize, retained as the throughput baseline:
    sequential per-species guarantee decode with per-call table builds and
    the reference per-code-bit window pass. Bitwise the same artifact as
    :func:`decode_artifact`."""
    return _finish_artifact(
        _decode_head(blob, huffman=entropy.huffman_decode_ref),
        huffman=entropy.huffman_decode_ref,
    )


def _finalize_field(corrected: np.ndarray, artifact: CompressedArtifact
                    ) -> np.ndarray:
    """(S, NB, D) corrected vectors -> denormalized (S, T, H, W) field.

    Host numpy in both the fused and the reference path: the multiply/add
    stays un-fused (no FMA contraction), keeping the two paths bit-identical.
    """
    geom = artifact.cfg.geometry
    rec_blocks = blocking.vectors_as_blocks(corrected, geom)
    rec_normed = blocking.from_blocks(rec_blocks, artifact.shape, geom)
    return (
        rec_normed * artifact.norm_range[:, None, None, None]
        + artifact.norm_min[:, None, None, None]
    ).astype(np.float32)


def _apply_guarantees_and_finalize(vecs_dev, artifact: CompressedArtifact
                                   ) -> np.ndarray:
    """Post-dispatch tail of the fused decode: batched guarantee replay on
    the (possibly still in-flight) NN-decoded vectors, then host
    finalization. The single implementation behind both ``reconstruct``
    and ``decompress``."""
    import jax.numpy as jnp

    engine = gae.default_engine()
    arts = artifact.species_guarantees
    if any(a.coeff_q.size for a in arts):
        s, nb, d = vecs_dev.shape
        # host-side CSR scatter overlaps the in-flight async NN decode
        dense, basis = engine.dense_corrections(arts, (s, nb, d))
        vecs_dev = engine.apply_device(
            vecs_dev, jnp.asarray(dense), jnp.asarray(basis)
        )
    return _finalize_field(np.asarray(vecs_dev), artifact)


def _fused_reconstruct(rt, artifact: CompressedArtifact) -> np.ndarray:
    """The device-resident decode hot path (see :func:`decompress`)."""
    vecs_dev = _fused_vecs(
        rt, artifact.ae_params, artifact.corr_params,
        _latents32(artifact.latent_q, artifact.latent_bin),
        row0=0, n_rows=artifact.latent_q.shape[0],
    )
    return _apply_guarantees_and_finalize(vecs_dev, artifact)


def reconstruct(artifact: CompressedArtifact) -> np.ndarray:
    """Decode an in-memory artifact to the full (S, T, H, W) field.

    Derives every structural decision — geometry, AE shape, whether the
    tensor-correction network runs — from the artifact itself, never from
    ambient pipeline state (the seed's config-shadowing hazard). Runs the
    fused device-resident hot path; :func:`reconstruct_reference` retains
    the staged pre-change orchestration as the bit-identity oracle.
    """
    cfg = artifact.cfg
    has_corr = artifact.corr_params is not None
    rt = _runtime(cfg, len(artifact.norm_min), has_corr)
    return _fused_reconstruct(rt, artifact)


def reconstruct_reference(artifact: CompressedArtifact,
                          conv_impl: str = "2d") -> np.ndarray:
    """The seed's decode *orchestration*, retained as baseline and oracle:
    host-chunked ``_batched`` stages with a numpy round-trip between
    dequantize, decoder, correction, and guarantee replay.

    With the default ``conv_impl="2d"`` the staged path shares the fused
    path's layer implementations, and ``reconstruct`` must match it **bit
    for bit** — the gate asserted by the test suite and by
    ``benchmarks/bench_throughput.py`` before any number is reported (it
    proves the hot-path reorganization is semantically transparent).
    ``conv_impl="xla"`` additionally retains the seed's convolution
    lowering — the true pre-change cost profile used as the benchmark's
    timing baseline; its output differs from the 2d formulation only by
    float-summation reassociation inside the convolutions (ulp-level,
    bound-checked in the benchmark)."""
    cfg = artifact.cfg
    has_corr = artifact.corr_params is not None
    builder = _runtime if conv_impl == "2d" else _runtime_reference
    rt = builder(cfg, len(artifact.norm_min), has_corr)
    lat = dequantize(artifact.latent_q, artifact.latent_bin)
    x_rec = _batched(rt.jit_decode, artifact.ae_params, lat)
    if has_corr:
        vecs = correction.blocks_to_pointwise(x_rec)
        fixed = _batched(rt.jit_corr, artifact.corr_params, vecs, batch=1 << 16)
        x_rec = correction.pointwise_to_blocks(fixed, x_rec)
    vecs_rec = blocking.blocks_as_vectors(x_rec)
    corrected = gae.apply_correction_batched(
        vecs_rec, artifact.species_guarantees
    )
    return _finalize_field(corrected, artifact)


def decompress(blob: bytes, *, species=None, time_range=None,
               on_error: str = "raise"):
    """Standalone decode: container bytes -> (S, T, H, W) float32 field.

    Needs no codec instance and no fitted model — everything is
    reconstructed from the blob (the acceptance contract for the wire
    format). Raises :class:`ContainerFormatError` on malformed input.

    ``species`` (an index or a sequence of indices) and/or ``time_range``
    (a half-open ``(t0, t1)`` frame window) select a slice to decode
    randomly-accessed: only the requested guarantee streams are parsed and
    entropy-decoded, the fused NN decode covers only the chunks of the
    container's fixed decode grid (``runtime._FUSED_CHUNK`` block rows)
    that the window touches — and on a v3+ (time-sharded) container only
    the latent shards
    covering the window entropy-decode, making a window query O(window)
    end to end — with the result bitwise equal to slicing a full decode:
    ``decompress(b, species=s, time_range=(t0, t1))
    == decompress(b)[s, t0:t1]``. An integer ``species`` drops the species
    axis, like numpy indexing.

    On a v4 container every byte the decode reads is digest-checked
    (CRC32) before it is interpreted; a mismatch raises
    :class:`ContainerFormatError` with structured context (stream,
    offset, unit). ``on_error="salvage"`` switches to degraded-but-honest
    decoding: corrupt species/latent shards are quarantined instead of
    aborting, everything verifiable decodes (bitwise equal to the clean
    decode), damaged regions come back NaN, and the call returns a
    ``(field, DecodeReport)`` tuple — see
    :func:`repro.codec.integrity.salvage_decompress`.

    Parsed container heads are served from a content-keyed bounded cache,
    so repeated (window) queries on one blob skip the head parse and every
    already-decoded stream; :func:`repro.codec.clear_decode_cache` drops
    the memo (benchmarks use it to time cold decodes). A raise-mode
    decode that hits corruption evicts the blob's cached head, and
    salvage never touches the cache — a salvaged parse can never be
    served later as a clean head.
    """
    if on_error not in ("raise", "salvage"):
        raise ValueError(
            f"on_error must be 'raise' or 'salvage', got {on_error!r}"
        )
    if on_error == "salvage":
        from repro.codec.integrity import salvage_decompress

        return salvage_decompress(
            blob, species=species, time_range=time_range
        )
    if species is not None or time_range is not None:
        from repro.codec.partial import PartialDecoder

        return PartialDecoder(blob).decode(
            species=species, time_range=time_range
        )
    head = _cached_head(blob)
    try:
        vecs_dev = _fused_vecs(
            head.runtime, head.ae_params, head.corr_params,
            _latents32(head.latents.full(), head.latent_bin),
            row0=0, n_rows=head.nb,
        )
        # the guarantee streams entropy-decode while the dispatched NN runs
        artifact = _finish_artifact(head)
        return _apply_guarantees_and_finalize(vecs_dev, artifact)
    except ContainerFormatError:
        # corruption discovered after the head parse (lazy shard/species
        # digest or entropy failure): drop the poisoned cached head
        _evict_head(blob)
        raise


def decompress_reference(blob: bytes, conv_impl: str = "2d") -> np.ndarray:
    """Retained pre-change standalone decode: sequential per-species
    deserialize with per-call Huffman table builds, then the staged
    host-chunked reconstruct. With the default ``conv_impl="2d"`` this is
    the fused path's bit-identity oracle; with ``"xla"`` it is the seed's
    full cost profile (the throughput benchmark's timing baseline)."""
    return reconstruct_reference(decode_artifact_reference(blob), conv_impl)
