#!/usr/bin/env python3
"""Find the query rate the decode service sustains: one sweep on the chip.

    python bench/sweep.py --workload gbatc.query --seed 5 --seconds 30 --rates 1 2 4 8

From the root of a checkout, on a TPU. Does the cell's set-up once, then
runs the cell's open-loop window at each rate in turn (the mix's other
parameters unchanged) and prints one JSON line per rate: queries sent and
answered, latency percentiles, and the backlog, the queries still
unanswered when the window closed. A rate the service sustains ends its
window with a backlog of a few queries; above it the backlog grows with
the window. The cell's rate is fixed in its mix file at about 0.8 of the
highest rate sustained; the benchmark's runs never sweep.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np

    from bench import harness, surrogate
    from repro import compile_cache

    if jax.default_backend() != "tpu":
        print("bench: JAX found no TPU; nothing was run", file=sys.stderr)
        return 1
    compile_cache.configure(ROOT)
    cell = harness.Cell(ROOT / "BENCHMARK.json", args.workload)
    ctx = harness.Ctx(cell, args.seed, args.seconds)
    ctx.field = surrogate.field_for(cell.config["data"], args.seed,
                                    ctx.shapes.block)
    drv = harness.load_module(cell.driver_path)
    state = drv.setup(ctx)
    s, t = ctx.field.shape[:2]
    print(json.dumps({"setup_s": time.perf_counter() - T_START}),
          flush=True)
    for i, rate in enumerate(args.rates):
        ctx.traffic = dict(cell.traffic, rate_per_s=rate)
        state["plan"] = drv.schedule(ctx.traffic, s, t, args.seconds,
                                     args.seed + i)
        state["sample"] = set()
        out = drv.window(ctx, state)
        lat = np.asarray(state["lat_ms"])
        half = len(lat) // 2
        print(json.dumps({
            "rate_per_s": rate, "sent": out["attempted"],
            "answered": len(lat), "failed": out["failed"],
            "backlog_at_close": state["backlog_at_close"],
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
            "first_half_p50_ms": (float(np.median(lat[:half]))
                                  if half else None),
            "second_half_p50_ms": (float(np.median(lat[half:]))
                                   if half else None),
            "late_max_ms": max(state["late"]) * 1e3 if state["late"] else 0,
            "counters": ctx.counters,
        }), flush=True)
    state["svc"].stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
