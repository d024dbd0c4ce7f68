"""A benchmark directory of tiny cells, for running the harness on the CPU.

:func:`make` copies the real ``BENCHMARK.json`` and the benchmark's
configurations, mixes, drivers and layer readers into a directory, then
adds cells by adding files and entries alone: a configuration at a few
species on a 20x20 grid with 100 training steps, one cell per mix on
it, and the decode and query cells' metrics, one with a reader of its
own. Nothing of
the harness is edited to find them.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
MIXES = {"encode": "encode_jobs", "decode": "decode_cold",
         "query": "query_open"}


def tiny_config(use_correction: bool = True) -> dict:
    cfg = json.loads((BENCH / "configs" / "s3d_gbatc.json").read_text())
    cfg["name"] = "tiny"
    cfg["data"].update(n_species=4, height=20, width=20, n_series=8,
                       first_frame=2)
    cfg["pipeline"].update(use_correction=use_correction, ae_steps=100,
                           corr_steps=4, batch_size=16, conv_channels=[4, 8],
                           latent=6)
    cfg["correction_batch"] = 64
    # trained 100 steps the tiny AE reads 0.188-0.194 on its own, left
    # at its initialisation 0.349-0.364 (seeds 1-3 on the CPU)
    cfg["limits"]["net_nrmse"] = 0.27
    return cfg


def make(dest: Path, *, use_correction: bool = True,
         query_rate: float = 40.0) -> Path:
    """Write the tiny benchmark under ``dest``; returns its spec path."""
    dest = Path(dest)
    for sub in ("configs", "traffic", "drivers", "layers"):
        shutil.copytree(BENCH / sub, dest / sub)
    (dest / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config(use_correction)))
    q = json.loads((BENCH / "traffic" / "query_open.json").read_text())
    q.update(rate_per_s=query_rate, check_sample=1000, drain_s=20)
    (dest / "traffic" / "tiny_query.json").write_text(json.dumps(q))

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {"tiny.encode": "encode_jobs", "tiny.decode": "decode_cold",
             "tiny.query": "tiny_query"}
    for name, mix in cells.items():
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": mix, "chips": 1,
                                  "why": "tiny CPU cell"})
    # the decode and query cells' metrics, added by entries and reader
    # files alone
    spec["end_to_end"].append({
        "name": "decode_throughput", "unit": "MB/s", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": ["tiny.decode"]})
    spec["end_to_end"].append({
        "name": "query_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": ["tiny.query"]})
    spec["per_layer"].append({
        "name": "dispatches_per_query", "unit": "dispatches",
        "better": "lower", "source": "program_counter",
        "layer": "service scheduler", "moves": "query_p95_ms",
        "workloads": ["tiny.query"]})
    (dest / "layers" / "dispatches_per_query.py").write_text(
        "def read(ctx):\n"
        "    done = ctx.counters.get('completed', 0)\n"
        "    return ctx.counters.get('dispatches', 0) / done if done "
        "else None\n")
    kind = {"gbatc.encode": "tiny.encode", "gba.encode": "tiny.encode"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted(set(m["workloads"]) | {
                kind[w] for w in m["workloads"] if w in kind})
    path = dest / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path
