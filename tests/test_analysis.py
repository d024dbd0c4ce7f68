"""Tests for :mod:`repro.analysis` — the invariant checker itself.

Covers: each lint rule fires exactly on its planted fixture violation
and nowhere else; inline suppression and baseline round-trips; the
wire-schema conformance pass (clean on the live layout, loud under
mutation); the jaxpr-audit regression pins (fused decode: zero host
callbacks, zero d2h transfers; x64 guard); the CLI contract (exit 0 on
the repo, nonzero on a fixture violation); and closure tests keeping the
reference-pairing rule satisfied for ``_runtime_reference`` and
``huffman_decode_payload_ref``.
"""

import json
import os
import struct
import unittest

import numpy as np

from repro.analysis import __main__ as cli
from repro.analysis import jaxpr_audit, wire_schema
from repro.analysis.findings import (
    Finding,
    apply_baseline,
    load_baseline,
    save_baseline,
    scan_suppressions,
)
from repro.analysis.lint import lint_tree

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE_TREE = os.path.join(HERE, "fixtures", "lint", "tree")
FIXTURE_CORPUS = os.path.join(HERE, "fixtures", "lint", "testcorpus")


def _fixture_result():
    return lint_tree(FIXTURE_TREE, FIXTURE_CORPUS)


# ---------------------------------------------------------------------------
class TestFixtureRules(unittest.TestCase):
    """Each rule fires exactly at its planted violation, nowhere else."""

    @classmethod
    def setUpClass(cls):
        cls.result = _fixture_result()
        cls.by_rule = {}
        for f in cls.result.findings:
            cls.by_rule.setdefault(f.rule, []).append((f.path, f.line))

    def test_decode_purity_fires_exactly_at_plants(self):
        self.assertEqual(sorted(self.by_rule["decode-purity"]), [
            ("codec/decode.py", 5),   # ambient default_config import
            ("codec/decode.py", 9),   # os.getenv on the decode path
            ("codec/encode.py", 3),   # core.pipeline module import
            ("serve/decode_service.py", 5),  # ambient import in serve/
            ("serve/decode_service.py", 9),  # env read in serve/
        ])

    def test_wire_centralization_fires_exactly_at_plants(self):
        self.assertEqual(sorted(self.by_rule["wire-centralization"]), [
            ("core/bad_wire.py", 5),  # magic-shaped literal
            ("core/bad_wire.py", 9),  # struct.pack
        ])

    def test_typed_errors_fires_exactly_at_plants(self):
        self.assertEqual(sorted(self.by_rule["typed-errors"]), [
            ("codec/runtime.py", 11),  # CFE without stream=/offset=/unit=
            ("codec/runtime.py", 13),  # untyped raise in a parse scope
            ("core/bad_except.py", 7),   # broad swallow
            ("core/bad_except.py", 14),  # bare except
        ])

    def test_determinism_fires_exactly_at_plants(self):
        self.assertEqual(sorted(self.by_rule["determinism"]), [
            ("core/bad_random.py", 3),   # import random
            ("core/bad_random.py", 10),  # np.random.rand
            ("core/bad_random.py", 11),  # unseeded default_rng()
            ("core/bad_random.py", 16),  # time.time in core/
        ])

    def test_reference_pairing_fires_only_on_orphan(self):
        self.assertEqual(self.by_rule["reference-pairing"],
                         [("core/suppressed.py", 8)])

    def test_no_rule_fires_on_clean_module(self):
        paths = {f.path for f in self.result.findings}
        self.assertNotIn("clean.py", paths)

    def test_no_findings_beyond_the_plants(self):
        self.assertEqual(len(self.result.findings), 16)

    def test_inline_suppression_lands_in_suppressed(self):
        supp = [(f.rule, f.path) for f in self.result.suppressed]
        self.assertIn(("wire-centralization", "core/suppressed.py"), supp)
        # and suppressed findings never appear as findings
        self.assertNotIn(
            ("wire-centralization", "core/suppressed.py"),
            [(f.rule, f.path) for f in self.result.findings],
        )


# ---------------------------------------------------------------------------
class TestSuppressions(unittest.TestCase):
    def test_line_tag_scopes_to_its_line_and_rule(self):
        s = scan_suppressions(
            "x = 1\ny = pack()  # repro: allow[wire-centralization]\n"
        )
        self.assertTrue(s.allows("wire-centralization", 2))
        self.assertFalse(s.allows("wire-centralization", 1))
        self.assertFalse(s.allows("typed-errors", 2))

    def test_comma_list_and_file_tag(self):
        s = scan_suppressions(
            "# repro: allow-file[determinism]\n"
            "z = 3  # repro: allow[typed-errors,decode-purity]\n"
        )
        self.assertTrue(s.allows("determinism", 999))
        self.assertTrue(s.allows("typed-errors", 2))
        self.assertTrue(s.allows("decode-purity", 2))
        self.assertFalse(s.allows("typed-errors", 1))


# ---------------------------------------------------------------------------
class TestBaseline(unittest.TestCase):
    def test_round_trip_matches_ignoring_line_numbers(self):
        f1 = Finding("typed-errors", "a.py", 10, "bare except")
        f2 = Finding("determinism", "b.py", 20, "import random")
        path = os.path.join(HERE, "fixtures", "lint", "_tmp_baseline.json")
        try:
            save_baseline(path, [f1])
            records = load_baseline(path)
            moved = Finding("typed-errors", "a.py", 99, "bare except")
            new, baselined, stale = apply_baseline([moved, f2], records)
            self.assertEqual(new, [f2])
            self.assertEqual(baselined, [moved])
            self.assertEqual(stale, [])
        finally:
            os.unlink(path)

    def test_stale_entries_surface_without_failing(self):
        records = [{"rule": "typed-errors", "path": "gone.py",
                    "detail": "bare except"}]
        new, baselined, stale = apply_baseline([], records)
        self.assertEqual((new, baselined), ([], []))
        self.assertEqual(stale, records)

    def test_missing_baseline_is_empty(self):
        self.assertEqual(load_baseline("/nonexistent/baseline.json"), [])


# ---------------------------------------------------------------------------
class TestWireSchema(unittest.TestCase):
    def test_conformance_clean_on_live_layout(self):
        self.assertEqual(wire_schema.check_conformance(), [])

    def test_conformance_covers_all_five_versions(self):
        self.assertEqual(wire_schema.VERSIONS, (1, 2, 3, 4, 5))
        from repro.core import container as container_format
        self.assertEqual(tuple(container_format.SUPPORTED_VERSIONS),
                         wire_schema.VERSIONS)

    def test_stream_sets_per_version(self):
        v1 = wire_schema.expected_stream_set(1, 3, True)
        self.assertEqual(v1, frozenset({
            "meta", "latent", "decoder", "correction",
            "guarantee0", "guarantee1", "guarantee2",
        }))
        v4 = wire_schema.expected_stream_set(4, 3, False)
        self.assertEqual(v4, frozenset({
            "meta", "latent", "decoder", "guarantee", "integrity",
        }))
        # v5 keeps v4's stream set (the family tag rides inside meta)
        self.assertEqual(wire_schema.expected_stream_set(5, 3, False), v4)
        with self.assertRaises(ValueError):
            wire_schema.expected_stream_set(6, 1, False)

    def test_mutated_live_magic_is_caught(self):
        from repro.core import container as container_format
        orig = container_format.MAGIC
        container_format.MAGIC = b"GBTX"
        try:
            findings = wire_schema.check_conformance()
        finally:
            container_format.MAGIC = orig
        self.assertTrue(any("outer magic" in f.detail for f in findings))

    def test_mutated_record_layout_is_caught(self):
        from repro.codec import format as wire
        orig = wire._GDIR_REC
        wire._GDIR_REC = struct.Struct("<ddIIQQ")  # one field dropped
        try:
            findings = wire_schema.check_conformance()
        finally:
            wire._GDIR_REC = orig
        self.assertTrue(any("gdir_rec" in f.detail for f in findings))

    def test_region_kind_renders_fault_harness_labels(self):
        RK = wire_schema.RegionKind
        self.assertEqual(RK.HEADER.label(), "header")
        self.assertEqual(RK.STREAM.label(name="meta"), "stream:meta")
        self.assertEqual(RK.LATENT_SHARD.label(unit=3), "latent:shard3")
        self.assertEqual(
            RK.GUARANTEE_SPECIES_PART.label(unit=2, part="coeff"),
            "guarantee:s2:coeff",
        )
        self.assertEqual(wire_schema.GUARANTEE_PARTS,
                         ("coeff", "index", "basis"))


# ---------------------------------------------------------------------------
class TestJaxprAuditRegressions(unittest.TestCase):
    """Satellite pins: fused decode is callback- and transfer-free, and
    the audit runs (and leaves) the default-f32 world."""

    def test_x64_guard_before_and_after(self):
        import jax
        self.assertFalse(jax.config.jax_enable_x64)
        report = jaxpr_audit.AuditReport()
        for spec in jaxpr_audit._program_specs():
            if spec.name.startswith("fused_decode"):
                jaxpr_audit._audit_program(spec, report)
        self.assertFalse(jax.config.jax_enable_x64)

    def test_fused_decode_zero_callbacks_zero_d2h(self):
        report = jaxpr_audit.AuditReport()
        audited = []
        for spec in jaxpr_audit._program_specs():
            if spec.name.startswith("fused_decode"):
                jaxpr_audit._audit_program(spec, report)
                audited.append(spec.name)
        self.assertEqual(sorted(audited),
                         ["fused_decode", "fused_decode_attention",
                          "fused_decode_corrected"])
        self.assertEqual(report.findings, [])
        for name in audited:
            stats = report.programs[name]
            self.assertEqual(stats.callbacks, {})
            self.assertEqual(stats.transfers, 0)
            self.assertEqual(stats.f64_eqns, 0)

    def test_walker_sees_planted_callback_and_f64(self):
        import jax

        def noisy(x):
            jax.debug.callback(lambda v: None, x[0])
            return x * 2

        stats = jaxpr_audit.ProgramStats()
        closed = jax.make_jaxpr(noisy)(np.zeros(4, np.float32))
        jaxpr_audit._walk_jaxpr(closed.jaxpr, stats)
        self.assertEqual(stats.callbacks.get("debug_callback"), 1)


# ---------------------------------------------------------------------------
class TestCLI(unittest.TestCase):
    def test_repo_is_clean(self):
        # the acceptance gate: zero non-baselined findings on the repo
        # from all three tiers (lint, wire schema and the jaxpr audit)
        self.assertEqual(cli.main([]), 0)

    def test_fixture_violations_exit_nonzero(self):
        self.assertEqual(
            cli.main(["--no-audit", "--root", FIXTURE_TREE,
                      "--tests", FIXTURE_CORPUS]), 1)

    def test_write_baseline_then_clean(self):
        path = os.path.join(HERE, "fixtures", "lint", "_tmp_fix_base.json")
        try:
            self.assertEqual(
                cli.main(["--no-audit", "--root", FIXTURE_TREE,
                          "--tests", FIXTURE_CORPUS,
                          "--baseline", path, "--write-baseline"]), 0)
            self.assertEqual(
                cli.main(["--no-audit", "--root", FIXTURE_TREE,
                          "--tests", FIXTURE_CORPUS,
                          "--baseline", path]), 0)
        finally:
            os.unlink(path)

    def test_json_report(self):
        path = os.path.join(HERE, "fixtures", "lint", "_tmp_report.json")
        try:
            cli.main(["--no-audit", "--root", FIXTURE_TREE,
                      "--tests", FIXTURE_CORPUS, "--json", path])
            with open(path, encoding="utf-8") as fh:
                payload = json.load(fh)
            self.assertEqual(payload["rule_counts"]["determinism"], 4)
            self.assertEqual(len(payload["new"]), 16)
            self.assertIn("lint_wall_clock_s", payload)
        finally:
            os.unlink(path)


# ---------------------------------------------------------------------------
class TestReferencePairingClosures(unittest.TestCase):
    """Parity tests that also close the reference-pairing rule over the
    two previously untested reference twins."""

    def test_huffman_decode_payload_ref_parity(self):
        from repro.core import entropy

        rng = np.random.default_rng(0)
        values = rng.integers(-7, 7, size=257).astype(np.int64)
        symbols, lengths = entropy.huffman_codebook(values)
        payload = entropy.huffman_payload(values, symbols, lengths)
        fast = entropy.huffman_decode_payload(
            payload, len(values), symbols, lengths
        )
        ref = entropy.huffman_decode_payload_ref(
            payload, len(values), symbols, lengths
        )
        np.testing.assert_array_equal(fast, ref)
        np.testing.assert_array_equal(fast, values)

    def test_runtime_reference_builds_the_xla_twin(self):
        from repro.codec.runtime import _runtime, _runtime_reference
        from repro.core.blocking import BlockGeometry
        from repro.core.pipeline import PipelineConfig

        cfg = PipelineConfig(
            geometry=BlockGeometry(bt=2, ph=4, pw=4), latent=8,
            conv_channels=(4,), use_correction=False,
        )
        rt_ref = _runtime_reference(cfg, 2, False)
        self.assertEqual(rt_ref.model.cfg.conv_impl, "xla")
        # cached: same structural signature -> same runtime object
        self.assertIs(rt_ref, _runtime_reference(cfg, 2, False))
        # the fused/staged twin keeps a distinct conv impl
        self.assertEqual(_runtime(cfg, 2, False).model.cfg.conv_impl, "2d")


if __name__ == "__main__":
    unittest.main()
