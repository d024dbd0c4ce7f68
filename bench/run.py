#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload gbatc.encode --seed 7 --seconds 51 --trace 0

From the root of a checkout. The cell is a workload of ``BENCHMARK.json``.
The run makes its field from ``--seed``, does the cell's set-up, measures
for ``--seconds`` (a window of whole jobs, decodes or open-loop queries),
then checks what the window produced against the codec's guarantee. The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, each with its unit),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit, which are also the last lines written to
standard error.

Off a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside this directory, it exits 1 and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# import the benchmark as the package ``bench``, never its files bare
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
