"""Runs with the timed path broken underneath: ``correct`` comes out false.

Each case plants one fault of ``bench/faults.py`` in the program for the
length of one tiny run on the CPU (the harness's look for a chip
skipped) and drives the rest of the run as the benchmark does. The
faults are those these cells can have: a trainer step that returns its
state unchanged, part of the batch left out, and an answer altered
where it is produced. One chip has no exchange between chips. A trainer
that leaves half of each batch out is read on the chip
(``control.py --fault trainer_half_batch``) but is not a case here: its
networks come within a few percent of sound ones and its container
meets the guarantee, so no number of the check can tell it from a sound
run.
"""

from __future__ import annotations

import json

import pytest

from bench import faults
from bench.tests import tiny
from bench.tests.test_bench_cells import run_cell


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    path = tiny.make(tmp_path_factory.mktemp("tinyfaults"))
    q = path.parent / "traffic" / "tiny_query.json"
    # a query left out is counted failed once this wait is over
    q.write_text(json.dumps(dict(json.loads(q.read_text()), drain_s=2)))
    return path


# test id: (tiny cell, fault of bench/faults.py)
FAULTS = {
    "encode-trainer-frozen": ("tiny.encode", "trainer_frozen"),
    "encode-half-batch": ("tiny.encode", "blocks_uncorrected"),
    "encode-answer-altered": ("tiny.encode", "latent_altered"),
    "decode-half-batch": ("tiny.decode", "rows_undecoded"),
    "decode-answer-altered": ("tiny.decode", "field_altered"),
    "query-half-batch": ("tiny.query", "tick_half_dropped"),
    "query-answer-altered": ("tiny.query", "answer_altered"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_correct_false(spec, fault, monkeypatch):
    cell, name = FAULTS[fault]
    kind, plant = faults.FAULTS[name]
    assert cell == f"tiny.{kind}"
    plant(monkeypatch, tiny.tiny_config())
    out = run_cell(spec, cell, seconds=1.0)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
