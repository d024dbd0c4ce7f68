#!/usr/bin/env python3
"""Read the numbers the check compares, on the chip at a cell's own size:
of sound runs, of the control, or of a fault planted in the program.

    python bench/control.py --workload gbatc.encode --seconds 1 \\
        --seeds 11 12 13 [--fault bf16|<name in bench/faults.py>] [--no-warm]

From the root of a checkout, on a TPU. For each seed it makes one run of
the cell as ``run.py`` does, with a short window at the cell's own load,
and prints one JSON line: whether it came out correct, and each number
compared with its limit. ``--fault bf16`` puts the control in the
program's place (the window's answers carried in bfloat16, the precision
below the fp32 the configurations state); a fault's name plants that
fault for the whole process. ``--no-warm`` skips set-up's warm-up: these
readings time nothing. The benchmark's runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
# a fault is planted in the program before the run imports it
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    from bench import faults, harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=["none", "bf16", *faults.FAULTS],
                    default="none")
    ap.add_argument("--no-warm", action="store_true")
    args = ap.parse_args(argv)

    import pytest

    cell = harness.Cell(ROOT / "BENCHMARK.json", args.workload)
    with pytest.MonkeyPatch.context() as mp:
        if args.fault in faults.FAULTS:
            kind, plant = faults.FAULTS[args.fault]
            if kind != cell.traffic["driver"]:
                print(f"bench: {args.fault} is a fault of {kind} cells",
                      file=sys.stderr)
                return 1
            plant(mp, cell.config)
        for seed in args.seeds:
            try:
                out = harness.run(ROOT, args.workload, seed, args.seconds,
                                  False, t_start=time.perf_counter(),
                                  control=args.fault == "bf16",
                                  warm=not args.no_warm)
            except harness.Refused as e:
                print(f"bench: {e}", file=sys.stderr)
                return 1
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": args.fault,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
