"""Stage spans of the encode path (``repro.tracing``) on the CPU.

A tiny fit and compress recorded by the profiler: every stage of the
encode path appears as a ``gbatc.*`` span inside one of the two entry
spans, the entry spans carry what JAX spent compiling, and the container
is bitwise the one written with the profiler off.
"""

from __future__ import annotations

import glob

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core.container import ContainerReader
from repro.core.pipeline import GBATCCodec, PipelineConfig
from repro.data import s3d

STAGES = {
    "gbatc.fit": {
        "gbatc.fit.blocks", "gbatc.train.ae", "gbatc.fit.latents",
        "gbatc.fit.latents.encode", "gbatc.train.correction",
        "gbatc.decode.fused",
    },
    "gbatc.compress": {
        "gbatc.compress.latents", "gbatc.compress.latents.decode",
        "gbatc.decode.fused",
        "gbatc.guarantee.prepare", "gbatc.guarantee.prepare.residual",
        "gbatc.guarantee.prepare.pca", "gbatc.guarantee.prepare.project",
        "gbatc.guarantee.prepare.order", "gbatc.guarantee.prepare.stage",
        "gbatc.guarantee.select", "gbatc.guarantee.artifacts",
        "gbatc.container.encode", "gbatc.container.encode.latent",
        "gbatc.container.encode.guarantee", "gbatc.compress.report",
    },
}


def _spans(trace_dir) -> list[tuple[str, float, float, dict]]:
    """(name, start, end, stats) of every ``gbatc.*`` host event."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


CFG = PipelineConfig(ae_steps=20, corr_steps=10, conv_channels=(4, 8),
                     latent=6, batch_size=16)


def _job(data) -> bytes:
    cfg = CFG
    blob, _ = GBATCCodec(cfg).fit(data).compress_report(target_nrmse=1e-3)
    return blob


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    """One job with the profiler off, then the same job traced."""
    data = s3d.generate(s3d.S3DConfig(n_species=4, n_time=8, height=20,
                                      width=20, seed=3))["species"]
    untraced = _job(data)
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        traced = _job(data)
    finally:
        jax.profiler.stop_trace()
    return untraced, traced, _spans(trace_dir)


def test_container_bytes_same_with_the_profiler_on_and_off(jobs):
    untraced, traced, _ = jobs
    assert traced == untraced


def test_every_stage_nests_in_its_entry_span(jobs):
    spans = jobs[2]
    for entry, stages in STAGES.items():
        (lo, hi) = [(a, b) for n, a, b, _ in spans if n == entry][0]
        inside = {n for n, a, b, _ in spans if lo <= a and b <= hi}
        assert stages <= inside, stages - inside


def test_entry_spans_carry_the_jit_cost(jobs):
    """A fresh codec re-traces its per-instance programs: the fit's entry
    span carries what JAX spent, and its innermost span that compiled is
    a stage, not the entry span."""
    spans = jobs[2]
    fit = [st for n, _, _, st in spans if n == "gbatc.fit"][0]
    assert fit["jit_s"] > 0 and fit["compiles"] >= 1
    compiled = {n for n, _, _, st in spans if st.get("compiles", 0) > 0}
    assert compiled - {"gbatc.fit", "gbatc.compress"}


def test_guarantee_packing_counts_its_codebooks(jobs):
    """The guarantee stream's span counts the species it packed and the
    coefficient streams whose codebook was counted in linear time."""
    (stats,) = [st for n, _, _, st in jobs[2]
                if n == "gbatc.container.encode.guarantee"]
    assert stats["species"] == 4
    assert 1 <= stats["dense_codebooks"] <= 4


@pytest.mark.parametrize("profiler", ["on", "off"])
@pytest.mark.parametrize("backend", ["host", "jit"])
def test_guarantee_select_counts_what_it_fetched(backend, profiler,
                                                 monkeypatch, tmp_path):
    """While the profiler records, the select stage counts the bytes it
    brought back from the device: the host cut's fp32 correction, or the
    select program's (S, NB) cut and no (S, NB, D) tensor. With the
    profiler off the stage counts nothing."""
    import contextlib

    from repro.core import gae

    handles = []
    real = tracing.span

    @contextlib.contextmanager
    def spy(name, **meta):
        with real(name, **meta) as handle:
            if name == "guarantee.select":
                handles.append(handle)
            yield handle

    monkeypatch.setattr(tracing, "span", spy)
    rng = np.random.default_rng(5)
    s, nb, d = 2, 96, 80
    x = rng.normal(size=(s, nb, d)).astype(np.float32)
    x_rec = x + 0.1 * rng.normal(size=x.shape).astype(np.float32)
    engine = gae.GuaranteeEngine(interpret=True, select_backend=backend)
    prep = engine.prepare(x, x_rec)
    if profiler == "on":
        jax.profiler.start_trace(str(tmp_path))
    try:
        engine.select(prep, 0.5)
    finally:
        if profiler == "on":
            jax.profiler.stop_trace()
    (handle,) = handles
    if profiler == "off":
        assert not handle.on and "fetch_bytes" not in handle.stats
        return
    want = s * nb * d * 4 if backend == "host" else s * nb * (4 + 8)
    assert handle.stats["fetch_bytes"] == want
    (stats,) = [st for n, _, _, st in _spans(tmp_path)
                if n == "gbatc.guarantee.select"]
    assert stats["fetch_bytes"] == want


def test_trainer_and_container_spans_carry_their_sizes(jobs):
    """The AE trainer's span counts its steps and batch; the container's
    span its bytes and, of them, the decoder stream's."""
    untraced, _, spans = jobs
    (train,) = [st for n, _, _, st in spans if n == "gbatc.train.ae"]
    assert (train["steps"], train["batch"]) == (CFG.ae_steps,
                                                 CFG.batch_size)
    (cont,) = [st for n, _, _, st in spans if n == "gbatc.container.encode"]
    assert cont["bytes"] == len(untraced)
    assert cont["decoder_bytes"] == len(ContainerReader(untraced)["decoder"])


def test_span_records_nothing_with_the_profiler_off():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = tracing._totals.snapshot()
    kept = tracing.stages()
    with tracing.span("probe") as sp:
        jax.jit(lambda x: x * 3)(np.arange(4.0)).block_until_ready()
        sp.count(rounds=1)
    assert not sp.on
    assert tracing._totals.snapshot() == before
    assert tracing.stages() == kept


def test_count_attaches_stats(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("probe", kind="test") as sp:
            sp.count(rounds=2)
    finally:
        jax.profiler.stop_trace()
    (probe,) = [st for n, _, _, st in _spans(tmp_path)
                if n == "gbatc.probe"]
    assert probe == {"kind": "test", "rounds": 2}


def test_kept_stages_carry_the_same_stats(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("outer"):
            with tracing.span("inner", kind="test") as sp:
                sp.count(rounds=2)
    finally:
        jax.profiler.stop_trace()
    inner, outer = tracing.stages()[-2:]
    assert (inner.name, outer.name) == ("gbatc.inner", "gbatc.outer")
    assert inner.stats == {"kind": "test", "rounds": 2}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
