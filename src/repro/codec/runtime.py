"""Cached decode runtimes + container-head parsing for :mod:`repro.codec`.

Two caches make repeated decoding cheap without any codec instance state:

* **runtime cache** — model instances, jitted callables (including the
  fused decode program), and Huffman decode tables, keyed by structural
  signature; a fresh ``decompress`` call on a structurally familiar blob
  never re-traces.
* **head cache** — fully parsed container heads (meta, latent store,
  network parameters, guarantee directory/artifact memos), keyed by blob
  content with a bounded LRU: repeated window queries against the same
  blob skip the parse, the parameter unpack, and every already-decoded
  latent shard / guarantee stream. Distinct blobs can never alias — the
  key compares by content, not object id.

The latent stream is abstracted as a *store*: container v1/v2 carry one
sequential Huffman chain (decoded whole, as any row needs the full walk),
v3 carries independent per-shard chains under a shared codebook, decoded
lazily and only for the block rows a query touches.
"""

from __future__ import annotations

import dataclasses
import itertools
import struct
import threading
from typing import Any, Optional

import numpy as np

from repro import tracing
from repro.codec import cache as tier_cache
from repro.codec import families
from repro.codec import format as wire
from repro.codec.families import make_fused_decode  # noqa: F401  (canonical home moved; re-exported for the public codec API)
from repro.codec.latents import _ChainLatents, _ShardedLatents
from repro.codec.params import unpack_params
from repro.core import correction, entropy, gae
from repro.core import container as container_format
from repro.core.container import ContainerFormatError, ContainerReader
from repro.core.quantization import dequantize


# ---------------------------------------------------------------------------
# decode runtime (cached per structural signature; never re-traces)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _DecodeRuntime:
    family: families.EncoderFamily
    model: Any
    corr_net: Optional[correction.TensorCorrectionNetwork]
    jit_decode: Any
    jit_corr: Any
    # fused device-resident hot path: dequantized latents -> AE decode ->
    # pointwise correction -> (S, NB, D) block vectors, one dispatch
    jit_fused: Any
    # per-runtime Huffman decode-table memo (codebooks repeat across calls)
    table_cache: entropy.DecodeTableCache


_RUNTIMES: dict[tuple, _DecodeRuntime] = {}
_RUNTIMES_REF: dict[tuple, _DecodeRuntime] = {}
_RUNTIMES_MAX = 8
# the decode service issues concurrent decodes: runtime construction and
# eviction must not interleave (a half-built runtime must never be
# observable, and two threads racing a miss must agree on ONE runtime —
# the cache is also an identity cache, `rt is rt` matters to jit reuse)
_RUNTIMES_LOCK = threading.RLock()


def _runtime_key(cfg: Any, n_species: int, has_corr: bool) -> tuple:
    """Structural signature a decode runtime is cached under.

    ``cfg`` is anything :func:`families.structural` accepts; the family
    name leads the key, so two families sharing geometry/latent/arch can
    never alias one runtime (or each other's jitted programs)."""
    scfg = families.structural(cfg)
    geom = scfg.geometry
    return (
        scfg.family,
        n_species,
        (geom.bt, geom.ph, geom.pw),
        scfg.latent,
        tuple(scfg.arch),
        has_corr,
    )


def _build_runtime(scfg: families.StructuralConfig, n_species: int,
                   has_corr: bool, backend: str) -> _DecodeRuntime:
    import jax

    fam = families.get(scfg.family)
    model = fam.build_model(scfg, n_species, backend)
    corr_net = (
        correction.TensorCorrectionNetwork(
            correction.CorrectionConfig(n_species=n_species)
        )
        if has_corr
        else None
    )
    return _DecodeRuntime(
        family=fam,
        model=model,
        corr_net=corr_net,
        jit_decode=jax.jit(model.decode),
        jit_corr=jax.jit(corr_net.__call__) if corr_net is not None else None,
        jit_fused=jax.jit(fam.make_fused(model, corr_net)),
        table_cache=entropy.DecodeTableCache(),
    )


def _cached_runtime(cache: dict, cfg: Any, n_species: int,
                    has_corr: bool, backend: str) -> _DecodeRuntime:
    scfg = families.structural(cfg)
    key = _runtime_key(scfg, n_species, has_corr)
    with _RUNTIMES_LOCK:
        hit = cache.get(key)
        if hit is not None:
            return hit
        rt = _build_runtime(scfg, n_species, has_corr, backend)
        while len(cache) >= _RUNTIMES_MAX:
            cache.pop(next(iter(cache)))
        cache[key] = rt
        return rt


def _runtime(cfg: Any, n_species: int,
             has_corr: bool) -> _DecodeRuntime:
    return _cached_runtime(_RUNTIMES, cfg, n_species, has_corr, "2d")


def _runtime_reference(cfg: Any, n_species: int,
                       has_corr: bool) -> _DecodeRuntime:
    """Runtime for the retained pre-change decode path: XLA conv impl,
    staged host-chunked orchestration (see ``reconstruct_reference``)."""
    return _cached_runtime(_RUNTIMES_REF, cfg, n_species, has_corr, "xla")


# ---------------------------------------------------------------------------
# container-head parsing
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _DecodedHead:
    """Everything the NN decode needs, parsed before guarantee streams."""

    reader: ContainerReader
    blob: bytes
    cfg: families.StructuralConfig
    shape: tuple[int, int, int, int]
    nb: int
    latent_bin: float
    norm_min: np.ndarray
    norm_range: np.ndarray
    latents: Any  # _ChainLatents | _ShardedLatents
    latent_stream: Optional[bytes]  # v1/v2 single chain (None for v3)
    ae_params: Any
    corr_params: Any
    runtime: _DecodeRuntime
    version: int = container_format.FORMAT_VERSION
    # parsed + self-verified v4 integrity digests (None below v4): head
    # regions were digest-checked during the head parse; lazily read units
    # (latent shards, species guarantee extents, the guarantee directory)
    # digest-check on first access through this handle
    integrity: Optional[wire.IntegrityDirectory] = None
    # lazily parsed combined guarantee directory (see _gdir)
    gdir: Optional[wire.GuaranteeDirectory] = None
    # memoized artifact-wide "any species has corrections" bit (a pure
    # function of the blob; see partial._any_corrections)
    any_corrections: Optional[bool] = None
    # per-species guarantee artifacts already decoded from this blob —
    # the local memo for uncached heads (fresh parses, salvage); cached
    # heads migrate into the shared guarantee tier (see _attach_cache)
    arts_memo: dict = dataclasses.field(default_factory=dict)
    # unique per-parse token: the shard/guarantee tier key prefix (content
    # alone must not alias entries across re-parses of one blob, and a
    # head eviction cascades by token)
    token: int = dataclasses.field(default_factory=lambda: next(_TOKENS))
    # the shared DecodeCache once this head is admitted to the head tier
    # (None for fresh/salvage parses — those stay cache-isolated)
    cache: Optional[tier_cache.DecodeCache] = None
    # guards the lazy single-assignment memos (gdir, any_corrections)
    # against concurrent decode threads; reentrant because the
    # any_corrections probe holds it across a _gdir call
    lock: threading.RLock = dataclasses.field(
        default_factory=threading.RLock, repr=False
    )


_TOKENS = itertools.count()


def _artifact_nbytes(art) -> int:
    """Resident cost of a decoded guarantee artifact (array bytes)."""
    return int(
        art.basis.nbytes + art.coeff_q.nbytes
        + art.index_offsets.nbytes + art.index_flat.nbytes
    )


def _memo_art_get(head: _DecodedHead, sidx: int):
    if head.cache is not None:
        return head.cache.guarantees.get((head.token, sidx))
    return head.arts_memo.get(sidx)


def _memo_art_put(head: _DecodedHead, sidx: int, art) -> None:
    if head.cache is not None:
        head.cache.guarantees.put(
            (head.token, sidx), art, _artifact_nbytes(art)
        )
    else:
        head.arts_memo[sidx] = art


def _decode_head(blob: bytes, *, huffman=None,
                 check_integrity: bool = True) -> _DecodedHead:
    """Parse/validate the container head: meta, stream set, latents,
    network parameters — everything except the guarantee streams, so the
    fused NN decode can be dispatched while those entropy-decode.
    ``huffman`` overrides the latent decoder (reference path).

    On a v4 container the integrity stream is parsed (and self-verified)
    first, then every region this parse consumes is digest-checked
    *before* its bytes are interpreted: the outer header/table, the meta
    stream, the latent stream's head region, and the decoder/correction
    parameter streams. Lazily read units (latent shards, guarantee
    directory and species extents) digest-check on first access.
    ``check_integrity=False`` skips all digest work (salvage uses it to
    decode structurally when the integrity stream itself is corrupt);
    v1–v3 containers carry no digests and parse exactly as before."""
    r = ContainerReader(blob)
    integ = None
    if (check_integrity
            and r.version >= container_format.FORMAT_VERSION_INTEGRITY):
        integ = wire.IntegrityDirectory(r["integrity"])
        integ.verify_outer(r._blob, r.header_bytes)
        integ.verify_stream("meta", r["meta"])
    cfg, shape, latent_bin, norm_min, norm_range = wire._unpack_meta(
        r["meta"], version=r.version
    )
    if cfg.use_correction != ("correction" in r):
        # a flipped correction flag must not silently decode without the
        # shipped network (or with a phantom one)
        raise ContainerFormatError(
            f"meta correction flag is {cfg.use_correction} but the "
            f"container {'carries' if 'correction' in r else 'lacks'} a "
            f"correction stream",
            stream="meta",
        )
    s, t, h, w = shape
    geom = cfg.geometry
    if t % geom.bt or h % geom.ph or w % geom.pw:
        raise ContainerFormatError(
            f"shape {shape} not divisible by block geometry "
            f"({geom.bt}, {geom.ph}, {geom.pw})",
            stream="meta",
        )
    nb = (t // geom.bt) * (h // geom.ph) * (w // geom.pw)

    expected_streams = wire.expected_stream_set(
        r.version, s, cfg.use_correction
    )
    if set(r.names) != expected_streams:
        # strictness: every stream must be accounted for by purpose — no
        # stray payloads hiding in the blob, no silently absent streams.
        # Name the first offending stream so the error locates itself.
        odd = sorted(set(r.names) ^ expected_streams)[0]
        raise ContainerFormatError(
            f"unexpected stream set {sorted(r.names)} "
            f"(expected {sorted(expected_streams)})",
            stream=odd,
        )

    # the runtime cache is the single construction site for the decode
    # models — decode_artifact and reconstruct cannot drift apart
    rt = _runtime(cfg, s, cfg.use_correction)
    latent_stream: Optional[bytes] = r["latent"]
    if r.version >= container_format.FORMAT_VERSION_SHARDED:
        if integ is not None:
            # the head region digest-checks against its *stored* length
            # before any framing field is interpreted
            integ.verify_latent_head(latent_stream)
        latents = _ShardedLatents(
            wire.LatentShardDirectory(latent_stream), nb, cfg.latent,
            rt.table_cache, reference=huffman is not None, integrity=integ,
        )
        latent_stream = None  # not the single-chain wire form
    else:
        latents = _ChainLatents(
            latent_stream, nb, cfg.latent, rt.table_cache, huffman=huffman
        )

    def _params(name: str, defs):
        if integ is not None:
            integ.verify_stream(name, r[name])
        try:
            return unpack_params(r[name], defs, cfg.param_dtype_bytes)
        except ContainerFormatError as e:
            raise ContainerFormatError(
                f"{name} stream: {e}", stream=name, offset=e.offset
            ) from e

    ae_params = _params("decoder", rt.family.decoder_defs(rt.model))
    corr_params = None
    if cfg.use_correction:
        corr_params = _params("correction", rt.corr_net.defs)
    return _DecodedHead(
        reader=r, blob=bytes(blob), cfg=cfg, shape=shape, nb=nb,
        latent_bin=latent_bin, norm_min=norm_min, norm_range=norm_range,
        latents=latents, latent_stream=latent_stream,
        ae_params=ae_params, corr_params=corr_params, runtime=rt,
        version=r.version, integrity=integ,
    )


# the shared multi-tier decode cache: head / latent-shard / guarantee
# tiers with byte budgets, LRU eviction, and stats (see codec/cache.py);
# _HEADS aliases the head tier — the PR-5 name the suite pins eviction
# and isolation behaviour against
_CACHE = tier_cache.DecodeCache()
_HEADS = _CACHE.heads
_HEADS_MAX = tier_cache.DEFAULT_HEAD_ENTRIES
# serializes head *parses* per blob so N concurrent first queries on one
# blob pay one parse, not N (decode work after the parse runs unlocked)
_HEADS_PARSE_LOCK = threading.Lock()
_HEADS_PARSING: dict[bytes, threading.Event] = {}


def _attach_cache(head: _DecodedHead) -> None:
    """Admit a head's sub-memos to the shared tiers (migrating anything
    already decoded through the local memos)."""
    head.cache = _CACHE
    for sidx, art in list(head.arts_memo.items()):
        _CACHE.guarantees.put(
            (head.token, sidx), art, _artifact_nbytes(art)
        )
    head.arts_memo.clear()
    attach = getattr(head.latents, "attach_cache", None)
    if attach is not None:
        attach(_CACHE.shards, head.token)


def _cached_head(blob: bytes) -> _DecodedHead:
    """Content-keyed head tier of the shared decode cache.

    Repeated ``decompress``/window queries on the same blob skip the head
    parse, the parameter unpack, and every latent shard or guarantee
    stream already entropy-decoded through this head. The key is the blob
    *bytes* themselves — content equality, so byte-different blobs can
    never share an entry — and CPython caches a bytes object's hash, so a
    caller re-presenting the same object pays O(1) per query rather than
    re-hashing the container (the entry pins the blob anyway). Entry cost
    is the blob size (the head pins its blob); decoded latent shards and
    guarantee artifacts are accounted in their own tiers and cascade out
    when the head evicts. Concurrent first queries on one blob coalesce
    onto a single parse.
    """
    key = bytes(blob)
    while True:
        hit = _CACHE.heads.get(key)
        if hit is not None:
            return hit
        with _HEADS_PARSE_LOCK:
            # re-check under the lock: the parser that beat us published
            hit = _CACHE.heads.get(key)
            if hit is not None:
                return hit
            waiter = _HEADS_PARSING.get(key)
            if waiter is None:
                _HEADS_PARSING[key] = threading.Event()
                break  # we are the parser
        waiter.wait()
    try:
        head = _decode_head(key)
        _attach_cache(head)
        _CACHE.heads.put(key, head, len(key))
        return head
    finally:
        with _HEADS_PARSE_LOCK:
            _HEADS_PARSING.pop(key).set()


def configure_decode_cache(*, head_bytes: Optional[int] = None,
                           shard_bytes: Optional[int] = None,
                           guarantee_bytes: Optional[int] = None,
                           head_entries: Optional[int] = None) -> None:
    """Re-budget the decode cache tiers (contents are dropped — a budget
    change invalidates every admission decision already made). ``None``
    keeps a tier's current budget; the head tier's entry bound can be
    lifted entirely with ``head_entries=0``."""
    global _HEADS_MAX
    if head_bytes is not None:
        _CACHE.heads.capacity_bytes = int(head_bytes)
    if head_entries is not None:
        _CACHE.heads.max_entries = int(head_entries) or None
        _HEADS_MAX = _CACHE.heads.max_entries or (1 << 62)
    if shard_bytes is not None:
        _CACHE.shards.capacity_bytes = int(shard_bytes)
    if guarantee_bytes is not None:
        _CACHE.guarantees.capacity_bytes = int(guarantee_bytes)
    clear_decode_cache()


def cache_stats() -> dict:
    """Hit/miss/eviction counters + occupancy for every decode cache
    tier, plus the per-runtime Huffman decode-table memos (aggregated
    over the cached decode runtimes)."""
    stats = _CACHE.stats()
    with _RUNTIMES_LOCK:
        runtimes = list(_RUNTIMES.values()) + list(_RUNTIMES_REF.values())
    hits = misses = entries = 0
    for rt in runtimes:
        d = rt.table_cache.stats()
        hits += d["hits"]
        misses += d["misses"]
        entries += d["entries"]
    total = hits + misses
    stats["decode_table"] = {
        "hits": hits,
        "misses": misses,
        "hit_rate": (hits / total) if total else 0.0,
        "entries": entries,
    }
    return stats


def clear_decode_cache() -> None:
    """Drop every decode-cache tier: memoized parsed heads (and with
    them the latent shards / guarantee artifacts their tiers hold), plus
    the Huffman decode-table memos on the cached decode runtimes.
    Tests use it to run a genuinely cold decode."""
    _CACHE.clear()
    with _RUNTIMES_LOCK:
        runtimes = list(_RUNTIMES.values()) + list(_RUNTIMES_REF.values())
    for rt in runtimes:
        rt.table_cache.clear()


def _evict_head(blob: bytes) -> None:
    """Drop ONE blob's cached head. Raise-mode decodes call this when
    corruption surfaces *after* the head parse (a bad latent shard or
    guarantee stream discovered lazily): the head must not stay serveable
    as if the blob were clean, and salvage must never be answered from —
    or write into — the clean-head cache. Cascades to the head's shard
    and guarantee tier entries."""
    _CACHE.heads.discard(bytes(blob))


# ---------------------------------------------------------------------------
# guarantee stream decode (either layout), per species
# ---------------------------------------------------------------------------
def _gdir(head: _DecodedHead) -> wire.GuaranteeDirectory:
    """Parse (once) the combined guarantee stream's directory (v2+).

    On v4 the directory region digest-checks (against its stored length)
    before any record is interpreted. Concurrent callers serialize on the
    head lock so the directory parses exactly once."""
    with head.lock:
        if head.gdir is None:
            payload = head.reader["guarantee"]
            if head.integrity is not None:
                head.integrity.verify_gdir(payload)
            gdir = wire.GuaranteeDirectory(payload)
            if gdir.n_species != head.shape[0]:
                raise ContainerFormatError(
                    f"guarantee directory covers {gdir.n_species} species, "
                    f"meta stream declares {head.shape[0]}",
                    stream="guarantee",
                )
            if (head.integrity is not None
                    and len(head.integrity.species_crcs) != gdir.n_species):
                raise ContainerFormatError(
                    f"integrity stream carries "
                    f"{len(head.integrity.species_crcs)} species digests, "
                    f"guarantee directory has {gdir.n_species}",
                    stream="integrity",
                )
            head.gdir = gdir
        return head.gdir


def _coeff_streams(head: _DecodedHead, indices) -> "Optional[list[bytes]]":
    """Selected species' coefficient payloads, sliced without parsing any
    sibling payload; ``None`` when the per-species framing cannot be
    pre-parsed (the per-species path then surfaces the canonical error)."""
    if head.version >= container_format.FORMAT_VERSION_SELECTIVE:
        gdir = _gdir(head)
        return [gdir.coeff_stream(sidx) for sidx in indices]
    try:
        return [
            ContainerReader(head.reader[f"guarantee{sidx}"])["coeff"]
            for sidx in indices
        ]
    except (ContainerFormatError, KeyError):
        return None


def _species_guarantee(
    head: _DecodedHead, sidx: int, *, huffman=None, coeff_q=None
) -> gae.GuaranteeArtifact:
    """Parse + validate ONE species' guarantee artifact (either layout).

    Touches only that species' streams, so a corrupt sibling cannot poison
    it; errors carry the species index (structured: ``stream``/``unit``).
    On v4 the species' guarantee byte extent digest-checks before any of
    it is parsed. ``coeff_q`` injects pre-decoded coefficient symbols
    from the batched lockstep walk."""
    cache = head.runtime.table_cache
    selective = head.version >= container_format.FORMAT_VERSION_SELECTIVE
    sname = "guarantee" if selective else f"guarantee{sidx}"
    try:
        if selective:
            gdir = _gdir(head)
            if head.integrity is not None:
                head.integrity.verify_species(
                    sidx, head.reader["guarantee"], gdir.species_spans(sidx)
                )
            tau, coeff_bin, d, n_store, coeff, index, basis = \
                gdir.species_parts(sidx)
            g = gae.GuaranteeArtifact.from_parts(
                tau, coeff_bin, d, n_store, coeff, index, basis,
                table_cache=cache, huffman=huffman, coeff_q=coeff_q,
            )
        else:
            if coeff_q is not None:
                huffman = lambda _blob, _out=coeff_q: _out  # noqa: E731
            g = gae.GuaranteeArtifact.from_bytes(
                head.reader[sname],
                table_cache=cache, huffman=huffman,
            )
    except ContainerFormatError as e:
        if e.unit == sidx and e.stream == sname:
            raise  # already canonically framed (a failed species digest)
        raise ContainerFormatError(
            f"guarantee stream {sidx}: {e}",
            stream=sname, unit=sidx, offset=e.offset,
        ) from e
    if g.n_blocks != head.nb:
        raise ContainerFormatError(
            f"guarantee stream {sidx} covers {g.n_blocks} blocks, "
            f"expected {head.nb}",
            stream=sname, unit=sidx,
        )
    if g.basis.shape[0] != head.cfg.geometry.block_size:
        raise ContainerFormatError(
            f"guarantee stream {sidx} basis has dimension "
            f"{g.basis.shape[0]}, expected block size "
            f"{head.cfg.geometry.block_size}",
            stream=sname, unit=sidx,
        )
    return g


def _decode_species_guarantees(
    head: _DecodedHead, indices: "list[int]", *, huffman=None
) -> list:
    """Entropy-decode the guarantee streams of ``indices`` only.

    The selected coefficient streams decode in one lockstep chunk-parallel
    chain walk (:func:`entropy.huffman_decode_many`) with codebook tables
    served from the runtime cache; per-species parsing/validation then
    consumes the pre-decoded symbols. Successful artifacts land in the
    guarantee cache tier keyed under the head's token (cached heads serve
    repeated queries without re-walking; a custom ``huffman`` bypasses
    the shared tier entirely). When the batch walk cannot read a stream,
    every species re-parses individually so the canonical per-species
    ContainerFormatError surfaces (and healthy siblings are still
    decodable)."""
    shared = huffman is None
    got: dict = {}
    if shared:
        for s in indices:
            art = _memo_art_get(head, s)
            if art is not None:
                got[s] = art
    todo = [s for s in indices if s not in got]
    if todo:
        coeffs: "Optional[list]" = None
        if shared and len(todo) > 1:
            streams = _coeff_streams(head, todo)
            if streams is not None:
                try:
                    coeffs = entropy.huffman_decode_many(
                        streams, table_cache=head.runtime.table_cache
                    )
                except (ValueError, struct.error):
                    coeffs = None  # per-species path raises canonically
        for k, sidx in enumerate(todo):
            art = _species_guarantee(
                head, sidx, huffman=huffman,
                coeff_q=None if coeffs is None else coeffs[k],
            )
            got[sidx] = art  # local ref: immune to immediate eviction
            if shared:
                _memo_art_put(head, sidx, art)
    return [got[s] for s in indices]


def _decode_guarantees(head: _DecodedHead, *, huffman=None) -> list:
    """Entropy-decode every species' guarantee stream (full decode)."""
    return _decode_species_guarantees(
        head, list(range(head.shape[0])), huffman=huffman
    )


# ---------------------------------------------------------------------------
# fused NN decode over latents
# ---------------------------------------------------------------------------
def _latents32(latent_q: np.ndarray, latent_bin: float) -> np.ndarray:
    """f64 dequantize then one f32 round — exactly the cast the staged path
    performs when the f64 latents enter the jitted decoder."""
    return dequantize(latent_q, latent_bin).astype(np.float32)


# Blocks per fused-decode dispatch, and the grid every container decodes
# on. It is part of the format: the encoder holds its guarantee against the
# reconstruction decoded on this grid, so changing it changes the decoded
# bits of every container already written. 512 is also the staged
# reference path's batch (`artifact._batched`).
_FUSED_CHUNK = 512


@tracing.span("decode.fused")
def _fused_vecs(rt: _DecodeRuntime, ae_params, corr_params,
                lat32: np.ndarray, *, row0: int, n_rows: int):
    """Run the fused NN decode over block rows ``[row0, row0 + len(lat32))``
    of a container with ``n_rows`` rows.

    The rows decode on one grid of chunks per container, ``[k C, (k+1) C)``
    with ``C = min(_FUSED_CHUNK, n_rows)``, each dispatched at the full
    chunk shape with every row at its own offset (absent rows are zero
    latents). XLA picks kernels, and so summation orders, by shape: on
    XLA:CPU a dense layer over a few dozen rows rounds differently from
    the same layer over a few hundred. The fixed grid makes a row's bits
    independent of the window that asked for it, so a window decode is
    bitwise the slice of the full decode, and it decodes at most one
    partial chunk beyond the window at each end. Dispatches are
    asynchronous; results concatenate on device.
    """
    import jax.numpy as jnp

    n = lat32.shape[0]
    chunk = min(_FUSED_CHUNK, int(n_rows))
    r1 = row0 + n
    outs = []
    for c0 in range(row0 - row0 % chunk, r1, chunk):
        lo, hi = max(c0, row0), min(c0 + chunk, r1)
        part = lat32[lo - row0 : hi - row0]
        if hi - lo < chunk:
            part = np.zeros((chunk,) + lat32.shape[1:], np.float32)
            part[lo - c0 : hi - c0] = lat32[lo - row0 : hi - row0]
        out = rt.jit_fused(ae_params, corr_params, part)
        outs.append(out if hi - lo == chunk else out[:, lo - c0 : hi - c0])
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
