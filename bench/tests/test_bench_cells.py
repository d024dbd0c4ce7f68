"""Whole runs of tiny cells on the CPU: the result line's schema, a cell
found by its files alone, and the control that the check has to fail.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of each run is the run the benchmark makes on the chip, at a few
species on a 20x20 grid.
"""

from __future__ import annotations

import json
import time

import pytest

from bench import harness
from bench.tests import tiny

SEED = 2**31 + 99  # more than 32 signed bits, as the benchmark's are


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tinybench"))


def run_cell(spec, cell, *, trace=False, seconds=0.5, control=False):
    return harness.run(tiny.ROOT, cell, SEED, seconds, trace,
                       t_start=time.perf_counter(), require_tpu=False,
                       persist=False, bench_dir=spec.parent, spec=spec,
                       control=control)


def _schema(out, cell, metric_names, trace):
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    units = {m["name"]: m["unit"] for m in metric_names}
    assert set(out["metrics"]) <= set(units)
    for name, m in out["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert isinstance(m["value"], float)
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert dev["window_s"] > 0 and "busy_s" in dev
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "breakdown" not in out
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)  # one JSON line


def test_decode_line(spec, capsys):
    cell = harness.Cell(spec, "tiny.decode", spec.parent)
    out = run_cell(spec, "tiny.decode")
    _schema(out, cell, cell.end_to_end(), trace=False)
    assert set(out["metrics"]) == {"decode_throughput", "setup_s"}
    # the numbers compared are the last lines on standard error
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("check nrmse:")
    assert err[-1].startswith("check block:")


def test_encode_traced_line(spec):
    cell = harness.Cell(spec, "tiny.encode", spec.parent)
    out = run_cell(spec, "tiny.encode", trace=True)
    _schema(out, cell, cell.per_layer(), trace=True)
    # host spans read on the CPU; device metrics have no device to read
    assert {"fit_s", "compress_s", "encode_mfu"} <= set(out["metrics"])
    assert "idle_share.encode" not in out["metrics"]


def test_query_line(spec):
    cell = harness.Cell(spec, "tiny.query", spec.parent)
    out = run_cell(spec, "tiny.query", seconds=1.0)
    _schema(out, cell, cell.end_to_end(), trace=False)
    assert set(out["metrics"]) == {"query_p95_ms", "setup_s"}
    assert out["checks"]["failed_queries"] == {"value": 0.0, "limit": 0.0}


def test_query_traced_line(spec):
    """A metric added with its reader file is read in the traced run."""
    cell = harness.Cell(spec, "tiny.query", spec.parent)
    out = run_cell(spec, "tiny.query", trace=True, seconds=1.0)
    _schema(out, cell, cell.per_layer(), trace=True)
    assert 0 < out["metrics"]["dispatches_per_query"]["value"] <= 1


@pytest.mark.parametrize("cell", ["tiny.encode", "tiny.decode", "tiny.query"])
def test_control_fails_where_the_program_passes(spec, cell):
    """The answers carried in bfloat16, put in the program's place, break
    the block bound that the program's own answers meet: the run comes
    out not correct."""
    out = run_cell(spec, cell, control=True, seconds=1.0)
    assert out["correct"] is False
    limit = tiny.tiny_config()["limits"]["block"]
    assert out["checks"]["block"]["value"] > limit
