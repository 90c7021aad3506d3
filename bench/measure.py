"""Run one cell several times, each run its own process, and report the
spread of every metric.

    python3 bench/measure.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--trace 0|1] [--sets 2] [--out runs.jsonl]

Each set runs every seed once, in the order given, through
``bench/run.py``; this process never touches JAX, so each run holds the
chips alone. Every run's result line (with its wall time and exit code)
is appended to ``--out``. At the end, for each set and metric, the
median and the spread (the distance between the first and the third
quartile of ``statistics.quantiles(values, n=4)``, as a share of the
median) are printed, and each seed's compared numbers.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    """One run of ``bench/run.py``: its result line (``None`` when it
    printed none), exit code, wall time and the end of its stderr."""
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"workload": workload, "seed": seed, "trace": trace,
            "rc": p.returncode, "wall_s": wall, "result": result,
            "stderr": p.stderr[-3000:]}


def spread(values) -> float:
    """Interquartile distance over the median (0 where both are 0)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    if med:
        return (q3 - q1) / med
    return 0.0 if q3 == q1 else float("inf")


def main(argv=None) -> int:
    """Run the sets and print the spreads; non-zero if any run failed."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bad = 0
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            run = one_run(args.workload, seed, args.seconds, args.trace)
            run["set"] = k
            runs.append(run)
            res = run["result"]
            ok = run["rc"] == 0 and res is not None and res["correct"]
            bad += not ok
            checks = res["checks"] if res else None
            print(f"set {k} seed {seed}: rc {run['rc']}, wall "
                  f"{run['wall_s']:.1f} s, correct "
                  f"{res['correct'] if res else None}, checks {checks}, "
                  + (", ".join(f"{n} {m['value']:.6g}" for n, m in
                               res["metrics"].items()) if res else
                     run["stderr"][-800:]), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(run) + "\n")
        sets.append(runs)
    for k, runs in enumerate(sets):
        done = [r["result"] for r in runs if r["result"]]
        if len(done) < 2:
            continue
        for name in done[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in done
                    if name in r["metrics"]]
            if len(vals) >= 2:
                print(f"set {k} {name}: median {statistics.median(vals):.6g}"
                      f", spread {spread(vals):.4f} over {len(vals)} runs")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
