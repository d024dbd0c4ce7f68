"""The block-attention family against its plain reference on the CPU.

``encode_ref`` and ``decode_ref`` (``repro.models.block_attention_ref``)
write the family's equations out in float32; the family's ``encode``,
``decode`` and ``__call__`` must give the same numbers on seeded random
weights, at a toy arch and at the benchmark configuration's arch words,
and a container's own decoder and latents must decode through the public
``codec.decompress`` to what ``decode_ref`` gives for them.

Tolerances: on the CPU both sides compute in float32 (XLA:CPU does not
round matmul operands), so they differ only by the order of float32 sums
(XLA fuses and reassociates; the reference does not). At the config's
widths a value passes through about ten products of up to 512 terms
each, and one rounding of a sum of n terms is at most n ulp, so the
differences stay below 1e-5 of the values' scale, which is 1 to 4 here
(the largest seen is 2.5e-6); ``RTOL``/``ATOL`` allow 2e-5.
"""

import jax
import numpy as np
import pytest

from repro import codec
from repro.core import blocking
from repro.core.pipeline import PipelineConfig
from repro.data import s3d
from repro.models import block_attention as ba
from repro.models.block_attention_ref import decode_ref, encode_ref

RTOL = ATOL = 2e-5
S, GEOM = 3, (4, 5, 4)
# a toy arch, and the s3d_attention configuration's arch words
ARCHS = {"toy": (16, 2, 1, 32), "config": (128, 2, 4, 512)}


def _model(arch):
    dm, nh, depth, mlp = arch
    return ba.BlockAttentionAE(ba.BlockAttentionConfig(
        n_species=S, block=GEOM, latent=36, d_model=dm, n_heads=nh,
        depth=depth, mlp_hidden=mlp))


@pytest.fixture(scope="module", params=sorted(ARCHS))
def case(request):
    arch = ARCHS[request.param]
    model = _model(arch)
    params = model.init(jax.random.PRNGKey(7))
    x = np.random.default_rng(7).random((6, S) + GEOM, dtype=np.float32)
    return model, params, x, arch[1]


def test_encode_matches_encode_ref(case):
    model, params, x, heads = case
    np.testing.assert_allclose(np.asarray(model.encode(params, x)),
                               np.asarray(encode_ref(params, x, heads)),
                               rtol=RTOL, atol=ATOL)


def test_decode_matches_decode_ref(case):
    model, params, x, heads = case
    z = np.random.default_rng(8).normal(size=(6, 36)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(model.decode(params, z)),
                               np.asarray(decode_ref(params, z, heads,
                                                     (S,) + GEOM)),
                               rtol=RTOL, atol=ATOL)


def test_call_matches_decode_ref_of_encode_ref(case):
    model, params, x, heads = case
    want = decode_ref(params, encode_ref(params, x, heads), heads,
                      (S,) + GEOM)
    np.testing.assert_allclose(np.asarray(model(params, x)),
                               np.asarray(want), rtol=RTOL, atol=ATOL)


def test_container_decodes_to_decode_ref():
    """A tiny attention fit compressed at a bound its network already
    meets: the container stores no guarantee coefficient, so the public
    decode is the network's output alone, denormalised."""
    data = s3d.generate(s3d.S3DConfig(n_species=S, n_time=4, height=20,
                                      width=16, seed=5))["species"]
    cfg = PipelineConfig(family="attention", arch=ARCHS["toy"], ae_steps=30,
                         batch_size=8, seed=0, use_correction=False)
    blob = codec.GBATCCodec(cfg).fit(data).compress(target_nrmse=10.0)
    art = codec.decode_artifact(blob)
    assert all(g.coeff_q.size == 0 for g in art.species_guarantees)
    lat = (art.latent_q.astype(np.float64) * art.latent_bin).astype(
        np.float32)
    blocks = np.asarray(decode_ref(art.ae_params, lat, ARCHS["toy"][1],
                                   (S,) + GEOM))
    geom = blocking.BlockGeometry(*GEOM)
    normed = blocking.from_blocks(blocks, data.shape, geom)
    want = (normed * art.norm_range[:, None, None, None]
            + art.norm_min[:, None, None, None])
    got = codec.decompress(blob)
    scale = art.norm_range[:, None, None, None]
    np.testing.assert_allclose(got / scale, want / scale, rtol=RTOL,
                               atol=ATOL)
