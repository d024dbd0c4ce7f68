"""Cross-family container suite: the v5 encoder-family seam.

The registry contract, exercised end to end:

* a conv-family v5 blob decodes **bitwise identical** to the v4 blob of
  the same fit through every entry point (``decompress``,
  ``PartialDecoder``, ``DecodeService``), and v1–v4 blobs keep decoding
  unchanged — the family seam costs legacy containers nothing;
* the attention family round-trips through the same container, the same
  guarantee engine, and the same selective-decode machinery: slices are
  bitwise equal to the corresponding full-decode slices and every
  species meets its NRMSE bound;
* wire strictness: an unregistered family tag and a family/param-stream
  mismatch both raise :class:`ContainerFormatError` with stream
  coordinates (never a silent wrong-family decode);
* isolation: two families sharing geometry/latent can never alias a
  decode runtime or a cached head;
* the v4 integrity contract survives the new meta layout: a seeded
  single-bit-flip sweep over a v5 attention blob detects 100% of
  payload flips, a corrupt family tag indicts the ``meta`` stream, and
  salvage semantics are unchanged.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro import codec
from repro.codec import families
from repro.codec import format as codec_format
from repro.codec import runtime as codec_runtime
from repro.core import container as container_format
from repro.core.container import ContainerFormatError, ContainerReader, \
    ContainerWriter
from repro.core.pipeline import PipelineConfig
from repro.data import s3d
from repro.serve import DecodeService
from repro.testing.faults import FaultInjector, blob_regions

BOUND = 1e-2


@pytest.fixture(scope="module")
def small_cfg():
    return s3d.S3DConfig(n_species=4, n_time=16, height=20, width=16, seed=7)


@pytest.fixture(scope="module")
def small_data(small_cfg):
    return s3d.generate(small_cfg)["species"]


@pytest.fixture(scope="module")
def conv_codec(small_data):
    cfg = PipelineConfig(ae_steps=8, corr_steps=4, conv_channels=(8, 16),
                         seed=0)
    return codec.GBATCCodec(cfg).fit(small_data)


@pytest.fixture(scope="module")
def attn_codec(small_data):
    cfg = PipelineConfig(family="attention", arch=(16, 2, 1, 32),
                         ae_steps=40, corr_steps=4, seed=0)
    return codec.GBATCCodec(cfg).fit(small_data)


@pytest.fixture(scope="module")
def conv_report(conv_codec):
    return conv_codec.compress_report(target_nrmse=BOUND)


@pytest.fixture(scope="module")
def attn_report(attn_codec):
    return attn_codec.compress_report(target_nrmse=BOUND)


@pytest.fixture(scope="module")
def conv_blob(conv_report):
    return conv_report[0]


@pytest.fixture(scope="module")
def attn_blob(attn_report):
    return attn_report[0]


def _resign_v5(blob: bytes, mutate) -> bytes:
    """Re-emit a v5 container with ``mutate(name, payload)`` applied and
    the integrity stream recomputed — so structural wire checks are
    reached instead of (correctly) tripping a digest first."""
    r = ContainerReader(blob)
    assert r.version == container_format.FORMAT_VERSION_FAMILY
    w = ContainerWriter(version=r.version)
    for name in r.names:
        if name == "integrity":
            continue
        payload = mutate(name, r[name])
        w.add(name, payload if payload is not None else r[name])
    streams = list(w._streams)
    integ = codec_format.pack_integrity_stream(streams)
    header = container_format.pack_header(
        r.version,
        [(n, len(p)) for n, p in streams] + [("integrity", len(integ))],
    )
    w.add("integrity", codec_format.finalize_integrity_stream(integ, header))
    return w.to_bytes()


class TestConvV5Equivalence:
    """The refactor gate: conv through the registry is the old codec."""

    def test_v5_decode_bitwise_equals_v4(self, conv_report):
        blob5, rep = conv_report
        blob4 = codec.encode(rep.artifact, version=4)
        assert ContainerReader(blob5).version == 5
        assert codec.decompress(blob5).tobytes() \
            == codec.decompress(blob4).tobytes()

    def test_legacy_versions_decode_through_all_entry_points(
        self, conv_report
    ):
        blob5, rep = conv_report
        full = codec.decompress(blob5)
        with DecodeService() as svc:
            for version in (1, 2, 3, 4):
                b = codec.encode(rep.artifact, version=version)
                assert codec.decompress(b).tobytes() == full.tobytes()
                pd = codec.PartialDecoder(b)
                assert pd.decode(species=[1]).tobytes() \
                    == full[[1]].tobytes()
                svc.register(f"v{version}", b)
                assert svc.decode(f"v{version}").tobytes() == full.tobytes()


class TestAttentionFamily:
    """The seam proven: a second family through the unchanged engine."""

    def test_blob_is_v5_and_tagged_attention(self, attn_blob):
        r = ContainerReader(attn_blob)
        assert r.version == 5
        assert r["meta"][:1] == bytes([families.ATTENTION.tag])
        assert codec.verify_blob(attn_blob) == 5

    def test_meets_per_species_bound(self, attn_blob, small_data):
        out = codec.decompress(attn_blob)
        rng = small_data.max(axis=(1, 2, 3)) - small_data.min(axis=(1, 2, 3))
        err = np.sqrt(
            ((out - small_data) ** 2).mean(axis=(1, 2, 3))
        ) / rng
        assert (err <= BOUND + 1e-12).all()

    def test_selective_decodes_bitwise_match_full(self, attn_blob):
        full = codec.decompress(attn_blob)
        pd = codec.PartialDecoder(attn_blob)
        assert pd.decode(species=[2]).tobytes() == full[[2]].tobytes()
        assert pd.decode(time_range=(4, 12)).tobytes() \
            == full[:, 4:12].tobytes()
        assert pd.decode(species=[0, 3], time_range=(0, 8)).tobytes() \
            == full[[0, 3]][:, 0:8].tobytes()
        assert codec.decompress(attn_blob, species=[1]).tobytes() \
            == full[[1]].tobytes()

    def test_decode_service_round_trip(self, attn_blob, conv_blob):
        with DecodeService() as svc:
            svc.register("attn", attn_blob)
            svc.register("conv", conv_blob)
            full_a = codec.decompress(attn_blob)
            full_c = codec.decompress(conv_blob)
            assert svc.decode("attn").tobytes() == full_a.tobytes()
            assert svc.decode("conv").tobytes() == full_c.tobytes()
            assert svc.decode("attn", species=[1],
                              time_range=(4, 8)).tobytes() \
                == full_a[[1]][:, 4:8].tobytes()

    def test_legacy_versions_refuse_attention(self, attn_report):
        _, rep = attn_report
        for version in (1, 2, 3, 4):
            with pytest.raises(ValueError, match="predates encoder"):
                codec.encode(rep.artifact, version=version)

    def test_file_round_trip(self, attn_blob, tmp_path):
        p = tmp_path / "attn.gbtc"
        codec.write(p, attn_blob)
        assert codec.read(p) == attn_blob


class TestWireStrictness:
    def test_unknown_family_tag_raises_with_coordinates(self, conv_blob):
        bad = _resign_v5(
            conv_blob,
            lambda n, p: bytes([99]) + p[1:] if n == "meta" else None,
        )
        for entry in (codec.decompress, codec.PartialDecoder):
            with pytest.raises(ContainerFormatError,
                               match="unknown encoder family tag 99") as ei:
                entry(bad)
            assert ei.value.stream == "meta"
            assert ei.value.offset == 0

    def test_family_param_stream_mismatch_raises(
        self, conv_blob, attn_blob
    ):
        """An attention meta over a conv decoder stream (a mis-spliced
        write) must fail as provable decoder-stream corruption, never
        decode through the wrong parameter tree."""
        conv_dec = ContainerReader(conv_blob)["decoder"]
        bad = _resign_v5(
            attn_blob,
            lambda n, p: conv_dec if n == "decoder" else None,
        )
        with pytest.raises(ContainerFormatError) as ei:
            codec.decompress(bad)
        assert ei.value.stream == "decoder"

    def test_retagged_meta_fails_arch_validation(self, conv_blob):
        """Flipping a conv blob's tag to attention must be rejected at
        the meta parse: conv arch words cannot configure attention."""
        bad = _resign_v5(
            conv_blob,
            lambda n, p: bytes([families.ATTENTION.tag]) + p[1:]
            if n == "meta" else None,
        )
        with pytest.raises(ContainerFormatError,
                           match="bad attention arch") as ei:
            codec.decompress(bad)
        assert ei.value.stream == "meta"


class TestRuntimeIsolation:
    def test_runtime_keys_never_alias_across_families(self):
        from repro.core import blocking

        geom = blocking.BlockGeometry(bt=4, ph=4, pw=4)
        arch = (16, 2, 1, 32)
        mk = lambda fam: families.StructuralConfig(  # noqa: E731
            family=fam, geometry=geom, latent=8, arch=arch,
            use_correction=True, param_dtype_bytes=2,
        )
        k_conv = codec_runtime._runtime_key(mk("conv"), 4, True)
        k_attn = codec_runtime._runtime_key(mk("attention"), 4, True)
        assert k_conv != k_attn
        assert k_conv[0] == "conv" and k_attn[0] == "attention"
        assert k_conv[1:] == k_attn[1:]  # identical but for the family

    def test_cached_runtimes_are_distinct_objects(
        self, conv_blob, attn_blob
    ):
        head_c = codec_runtime._cached_head(conv_blob)
        head_a = codec_runtime._cached_head(attn_blob)
        assert head_c.runtime is not head_a.runtime
        assert head_c.runtime.family.name == "conv"
        assert head_a.runtime.family.name == "attention"
        assert type(head_c.runtime.model) is not type(head_a.runtime.model)

    def test_head_cache_never_aliases_blobs(self, conv_blob, attn_blob):
        assert codec_runtime._cached_head(conv_blob) \
            is not codec_runtime._cached_head(attn_blob)


#: the seed's LM stack: a leaf that no codec path may load
_LM_MODULES = (
    "repro.configs",
    "repro.models.registry",
    "repro.models.transformer",
    "repro.models.whisper",
    "repro.models.rglru",
    "repro.models.rwkv6",
    "repro.serve.serve_loop",
    "repro.data.tokens",
)

_IMPORT_PROBE = """
import sys
from repro.codec import families
from repro.core.pipeline import PipelineConfig

fam = families.get("attention")
fam.build_model(families.structural(PipelineConfig(family="attention")), 4)
assert "repro.models.block_attention" in sys.modules
print("\\n".join(sorted(sys.modules)))
"""


class TestImportIsolation:
    def test_attention_family_loads_no_lm_module(self):
        """Building the attention family in a fresh process loads the
        encoder and its layers, and nothing of the seed's LM stack."""
        src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (src, env.get("PYTHONPATH")) if p)
        out = subprocess.run([sys.executable, "-c", _IMPORT_PROBE],
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert out.returncode == 0, out.stderr
        loaded = set(out.stdout.split())
        leaked = sorted(m for m in loaded for lm in _LM_MODULES
                        if m == lm or m.startswith(lm + "."))
        assert not leaked, f"the attention family loaded {leaked}"


class TestAttentionFaultSweep:
    """The integrity contract holds over the new meta layout."""

    @pytest.fixture(scope="class")
    def regions(self, attn_blob):
        return blob_regions(attn_blob)

    def test_regions_include_family_tag(self, attn_blob, regions):
        labels = [r.label for r in regions]
        assert "meta:family" in labels
        fam = next(r for r in regions if r.label == "meta:family")
        r = ContainerReader(attn_blob)
        lo, _ = r.stream_extent("meta")
        assert (fam.lo, fam.hi, fam.stream) == (lo, lo + 1, "meta")

    def test_all_single_bit_flips_detected(self, attn_blob, regions):
        inj = FaultInjector(seed=909)
        flips = 0
        for reg in regions:
            for _ in range(25):
                bad, _ = inj.flip_bit(attn_blob, reg)
                with pytest.raises(ContainerFormatError):
                    codec.verify_blob(bad)
                flips += 1
        assert flips >= 400

    def test_family_tag_flip_indicts_meta(self, attn_blob, regions):
        inj = FaultInjector(seed=910)
        fam = next(r for r in regions if r.label == "meta:family")
        for _ in range(8):
            bad, _ = inj.flip_bit(attn_blob, fam)
            with pytest.raises(ContainerFormatError) as ei:
                codec.decompress(bad)
            assert ei.value.stream == "meta"

    def test_salvage_semantics_unchanged(self, attn_blob, regions):
        clean = codec.decompress(attn_blob)
        field, rep = codec.decompress(attn_blob, on_error="salvage")
        assert rep.ok and rep.integrity and rep.version == 5
        assert field.tobytes() == clean.tobytes()
        inj = FaultInjector(seed=911)
        s1 = next(r for r in regions if r.label == "guarantee:s1:coeff")
        bad, _ = inj.flip_bit(attn_blob, s1)
        field, rep = codec.decompress(bad, on_error="salvage")
        assert rep.quarantined == [1]
        assert np.isnan(field[1]).all()
        for i in (0, 2, 3):
            assert rep.species[i].status == "verified"
            assert field[i].tobytes() == clean[i].tobytes()
