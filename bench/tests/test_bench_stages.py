"""The program's stages on the trace's clock (``bench/stages.py``) and the
readers built on them, on the CPU: hand-made traces and records, a real
profiler session, and the tiny encode cell traced."""

from __future__ import annotations

import glob
import time
from types import SimpleNamespace

import jax
import pytest

from bench import harness, stages, tracefile
from bench.tests import tiny
from bench.tests.test_bench_units import _hand_trace
from repro import tracing

# the program's clock runs 1000 ns ahead of the hand trace's
OFF = 1000


def _rec(name, start, end, **stats):
    return tracing.Stage(name, OFF + start, OFF + end, stats)


def _record():
    """Two jobs' stages as the program keeps them: a stale fit from
    before the window, then one fit (1-49) and one compress (51-99)
    inside the driver's spans, with the stages inside them."""
    return [
        _rec("gbatc.train.ae", -498, -470),
        _rec("gbatc.fit", -499, -460),
        _rec("gbatc.train.ae", 2, 45),
        _rec("gbatc.train.correction", 45, 48),
        _rec("gbatc.fit", 1, 49, jit_s=0.5, compiles=2),
        _rec("gbatc.guarantee.prepare", 52, 80),
        _rec("gbatc.guarantee.hold_bound", 82, 90, rounds=2, blocks_over=7),
        _rec("gbatc.container.encode", 90, 99),
        _rec("gbatc.compress", 51, 99, jit_s=0.125, cache_loads=1),
    ]


@pytest.fixture
def record(monkeypatch):
    monkeypatch.setattr(stages, "_record", _record)


def _ctx(trace=None, units=2):
    return SimpleNamespace(trace=_hand_trace() if trace is None else trace,
                           units=units)


def test_spans_on_the_trace_clock_inside_the_window(record):
    ctx = _ctx()
    found = {s.name: s for s in stages.spans(ctx)}
    # the stale fit lands before the window and is left out
    assert len(found) == 7
    assert (found["gbatc.train.ae"].start_ns,
            found["gbatc.train.ae"].end_ns) == (2, 45)
    assert found["gbatc.guarantee.hold_bound"].stats == {
        "rounds": 2, "blocks_over": 7}
    # added to the trace's spans once, however often read
    stages.spans(ctx)
    names = [s.name for s in ctx.trace.spans]
    assert names.count("gbatc.train.ae") == 1
    assert "gbatc.compress" in names


def test_nothing_read_without_the_program_record(monkeypatch):
    monkeypatch.setattr(stages, "_record", lambda: None)
    ctx = _ctx()
    assert stages.spans(ctx) is None
    assert [s.name for s in ctx.trace.spans] == [
        "bench.window", "bench.fit", "bench.compress"]
    # a record with no entry span matches nothing
    monkeypatch.setattr(stages, "_record",
                        lambda: [_rec("gbatc.train.ae", 2, 45)])
    assert stages.spans(_ctx()) is None


def test_uncovered_idle_leaves_out_the_entry_spans(record):
    ctx = _ctx()
    found = stages.spans(ctx)
    # idle 0-10, 40-60, 70-95; stages cover 2-48, 52-80, 82-99:
    # 0-2, 48-52 and 80-82 are idle and in no stage
    assert stages.uncovered_idle_ns(ctx.trace, found) == 8
    # with the entry spans, 1-49 and 51-99: only 0-1 and 49-51
    assert stages.uncovered_idle_ns(ctx.trace, found, exclude=()) == 3
    # with no program span, all idle time is uncovered
    assert stages.uncovered_idle_ns(_hand_trace(), []) == 55


def test_gaps_labelled_by_the_innermost_program_stage(record):
    ctx = _ctx()
    stages.spans(ctx)
    gaps = {round(g[1] * 1e9): g[0] for g in tracefile.idle_gaps(ctx.trace)}
    # 70-95 (midpoint 82.5) in hold_bound, 0-10 in the AE trainer
    assert gaps[25] == "gbatc.guarantee.hold_bound"
    assert gaps[10] == "gbatc.train.ae"


@pytest.mark.parametrize("metric, want", [
    ("ae_train_s", 43e-9 / 2),
    ("corr_train_s", 3e-9 / 2),
    ("guarantee_prepare_s", 28e-9 / 2),
    ("guarantee_hold_s", 8e-9 / 2),
    ("hold_bound_rounds", 1.0),
    ("container_encode_s", 9e-9 / 2),
    ("jit_s", (0.5 + 0.125) / 2),
    ("idle_unattributed.encode", 100.0 * 8 / 55),
])
def test_reader(record, monkeypatch, metric, want):
    """Each reader's per-job value over a window of two jobs, and
    nothing to read without a trace or without the program's record."""
    read = harness.load_module(tiny.BENCH / "layers" / f"{metric}.py").read
    assert read(_ctx()) == pytest.approx(want)
    assert read(SimpleNamespace(trace=None, units=0)) is None
    monkeypatch.setattr(stages, "_record", lambda: None)
    assert read(_ctx()) is None


def test_recorded_stages_match_the_profilers_clock(tmp_path):
    """Aligned through the driver's span around the entry span, each kept
    stage lands where the profiler put the same span."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(0.01)
            with jax.profiler.TraceAnnotation("bench.fit"):
                with tracing.span("fit"):
                    time.sleep(0.01)
                    with tracing.span("train.ae") as sp:
                        time.sleep(0.02)
                        sp.count(rounds=3)
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    ctx = SimpleNamespace(trace=tracefile.load(tmp_path), units=1)
    found = {s.name: s for s in stages.spans(ctx)}
    assert set(found) == {"gbatc.fit", "gbatc.train.ae"}
    assert found["gbatc.train.ae"].stats == {"rounds": 3}
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    traced = {e.name: e for plane in
              jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(tracing.PREFIX)}
    for name, s in found.items():
        assert s.start_ns == pytest.approx(traced[name].start_ns, abs=5e6)
        assert s.dur_ns == pytest.approx(traced[name].duration_ns, abs=5e6)


def test_encode_traced_line_reads_program_stages(tmp_path):
    """The tiny encode cell's traced line carries the stage metrics; the
    CPU projects in fp64 (no ``_hold_bound``) and has no device idle to
    split."""
    spec = tiny.make(tmp_path / "tinybench")
    out = harness.run(tiny.ROOT, "tiny.encode", 2**31 + 99, 0.5, True,
                      t_start=time.perf_counter(), require_tpu=False,
                      persist=False, bench_dir=spec.parent, spec=spec)
    assert out["correct"] is True
    assert {"ae_train_s", "corr_train_s", "guarantee_prepare_s",
            "container_encode_s", "jit_s"} <= set(out["metrics"])
    assert not {"guarantee_hold_s", "hold_bound_rounds",
                "idle_unattributed.encode"} & set(out["metrics"])
    assert out["metrics"]["jit_s"]["unit"] == "s"
