"""The control of the check that decides ``correct``: the plain
reference put in the served path's place, with one guarantee of the
configuration broken, has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 21,22,23 --seconds 5

The configurations state an exact count of cell pairs within L1 ``eps``
*inclusive*. The control counts pairs strictly closer than ``eps`` (the
step a kernel that compared with ``<`` would take). For each seed, one
process runs the cell as a benchmark run does (set-up, a short window
on the chips), then replaces every answer of the window with the
control's before the check, and prints the compared numbers beside
their limits. It exits non-zero if any seed's control came out
correct. The benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def strict_count(cells, eps: int) -> int:
    """Unordered pairs strictly closer than ``eps``: the inclusive
    reference at ``eps - 1`` (coordinates are integers)."""
    from bench import reference
    return reference.pair_count(cells, eps - 1)


def main(argv=None) -> int:
    """Run the control on each seed; non-zero if any came out correct."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    passed = 0
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  t_start, answer=strict_count)
        t_start = time.perf_counter()
        passed += result["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": result["correct"],
                          "attempted": result["attempted"],
                          "checks": result["checks"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
