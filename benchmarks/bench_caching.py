"""Figure 5: query execution time vs cache budget for the registered
caching policies, across PTF-1 (hdf5), PTF-2 (fits), GEO (csv).

CLI knobs (the perf-trajectory harness):

    python -m benchmarks.bench_caching --policy cost,chunk_lru \
        --batch-size 4 --reuse on --out BENCH_caching.json
    python -m benchmarks.bench_caching --sweep --out BENCH_caching.json

``--policy`` selects any registered policy combos (default: the paper's
three), ``--sweep`` replaces the policy list with the FULL valid
(granularity x eviction x placement) cross product from the registries
and records the per-workload winner under the JSON's ``sweep`` key,
``--batch-size`` routes admission through the coordinator's batched
planning path, ``--reuse on`` enables the semantic cache-reuse rewrite,
and ``--out`` writes a JSON summary — including the resolved policy
spec and the reuse stats of every run — so successive PRs can diff the
trajectory.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, Optional, Sequence

from benchmarks.common import (build_geo, build_ptf, cell_anchors,
                               dataset_bytes, make_cluster, timed)
from repro.core.cluster import workload_summary
from repro.core.workload import geo_workload, ptf1_workload, ptf2_workload

POLICIES = ("file_lru", "chunk_lru", "cost")
# Budget fractions spanning the paper's regime: the smallest is near the
# workload's chunk working set (eviction pressure on chunk caches, thrash
# for whole-file caching); the largest lets chunk caches converge while
# file-granularity caching still cannot hold the touched files (§4.2.1).
BUDGET_FRACTIONS = (0.05, 0.10, 0.20)
# Join radii matched to the synthetic data's cell spacing so cross-chunk
# pairs exist (the paper joins arcsecond-scale matches on dense real data).
PTF_EPS, GEO_EPS = 300, 500


def sweep_policy_names() -> Sequence[str]:
    """The full valid (granularity x eviction x placement) cross product,
    as registered combo names. Triples already registered keep their
    canonical name (``cost``, ``chunk_lru``, ...); the rest are
    registered on the fly as ``{granularity}_{eviction}_{placement}``."""
    from repro.core.policies import (EVICTION_REGISTRY, GRANULARITIES,
                                     PLACEMENT_REGISTRY, POLICY_REGISTRY,
                                     PolicySpec, register_policy)
    names = []
    for gran in GRANULARITIES:
        for ev in EVICTION_REGISTRY:
            for pl in PLACEMENT_REGISTRY:
                spec = PolicySpec(f"{gran}_{ev}_{pl}", gran, ev, pl)
                try:
                    spec.validate()
                except ValueError:
                    continue            # e.g. file granularity needs an
                    # online-capable eviction policy
                existing = next(
                    (s.name for s in POLICY_REGISTRY.values()
                     if (s.granularity, s.eviction, s.placement)
                     == (gran, ev, pl)), None)
                names.append(existing or register_policy(spec).name)
    return tuple(names)


def sweep_winners(results: Dict) -> Dict:
    """Per-workload winners over a sweep: the combo minimizing total
    modeled time summed across budget fractions, plus the per-budget
    winner (ties break lexicographically for determinism)."""
    totals: Dict[str, Dict[str, float]] = {}
    by_budget: Dict[str, Dict[str, Dict[str, float]]] = {}
    specs: Dict[str, Dict] = {}
    for (wl, frac, policy), payload in sorted(results.items()):
        t = payload["summary"]["total_time_s"]
        totals.setdefault(wl, {})
        totals[wl][policy] = totals[wl].get(policy, 0.0) + t
        by_budget.setdefault(wl, {}).setdefault(str(frac), {})[policy] = t
        specs[policy] = payload["policy_spec"]
    out: Dict = {}
    for wl in sorted(totals):
        best = min(sorted(totals[wl]), key=lambda p: totals[wl][p])
        out[wl] = {
            "policy": best,
            "policy_spec": specs[best],
            "total_time_s": totals[wl][best],
            "by_budget": {
                frac: min(sorted(t), key=lambda p: t[p])
                for frac, t in sorted(by_budget[wl].items())},
        }
    return out


def _workloads():
    ptf1_cat, ptf1_rd = build_ptf("hdf5", seed=21)
    ptf2_cat, ptf2_rd = build_ptf("fits", seed=22)
    geo_cat, geo_rd = build_geo("csv", seed=11)
    a1 = cell_anchors(ptf1_cat, ptf1_rd, seed=1)
    a2 = cell_anchors(ptf2_cat, ptf2_rd, seed=2)
    return {
        "ptf1_hdf5": (ptf1_cat, ptf1_rd,
                      ptf1_workload(ptf1_cat.domain, n_queries=10,
                                    eps=PTF_EPS, anchors=a1)),
        "ptf2_fits": (ptf2_cat, ptf2_rd,
                      ptf2_workload(ptf2_cat.domain, n_queries=10,
                                    eps=PTF_EPS, anchors=a2)),
        "geo_csv": (geo_cat, geo_rd,
                    geo_workload(geo_cat.domain, eps=GEO_EPS)),
    }


def run(print_rows: bool = True, policies: Sequence[str] = POLICIES,
        budget_fractions: Sequence[float] = BUDGET_FRACTIONS,
        batch_size: Optional[int] = None, reuse: str = "off"):
    results = {}
    for wl_name, (catalog, reader, queries) in _workloads().items():
        total = dataset_bytes(catalog)
        for frac in budget_fractions:
            for policy in policies:
                cluster = make_cluster(catalog, reader, policy,
                                       int(total * frac), reuse=reuse)
                executed, us = timed(cluster.run_workload, queries,
                                     batch_size=batch_size)
                summ = workload_summary(executed)
                per_query = [e.time_total_s for e in executed]
                spec = cluster.coordinator.spec
                key = (wl_name, frac, policy)
                results[key] = {
                    "summary": summ, "per_query": per_query,
                    "policy_spec": {"granularity": spec.granularity,
                                    "eviction": spec.eviction,
                                    "placement": spec.placement}}
                if print_rows:
                    print(f"fig5/{wl_name}/b{frac}/{policy},{us:.0f},"
                          f"{summ['total_time_s']:.3f}")
    # Headline derived metric: cost vs baselines at the smallest budget.
    f = budget_fractions[0]
    for wl_name in ("ptf1_hdf5", "ptf2_fits", "geo_csv"):
        if (wl_name, f, "cost") not in results:
            continue
        cost = results[(wl_name, f, "cost")]["summary"]["total_time_s"]
        for base in ("file_lru", "chunk_lru"):
            if (wl_name, f, base) not in results:
                continue
            b = results[(wl_name, f, base)]["summary"]["total_time_s"]
            if print_rows:
                print(f"fig5/{wl_name}/speedup_vs_{base},0,"
                      f"{b / max(cost, 1e-9):.2f}")
    return results


def to_json_summary(results: Dict, policies: Sequence[str],
                    batch_size: Optional[int],
                    reuse: str = "off", sweep: bool = False) -> Dict:
    """Serialize run() results: per (workload, policy, budget fraction)
    the modeled times, scan volume, the resolved policy spec, and the
    semantic-reuse counters of that run (the ``reuse`` knob is recorded
    once, at the top level). With ``sweep=True`` the per-workload winning
    combos are recorded under the ``sweep`` key."""
    out: Dict = {"benchmark": "bench_caching", "policies": list(policies),
                 "batch_size": batch_size, "reuse": reuse, "workloads": {}}
    if sweep:
        out["sweep"] = {"policies": list(policies),
                        "winners": sweep_winners(results)}
    for (wl, frac, policy), payload in results.items():
        wl_entry = out["workloads"].setdefault(wl, {})
        pol_entry = wl_entry.setdefault(policy, {})
        pol_entry[str(frac)] = {
            **{k: payload["summary"][k]
               for k in ("total_time_s", "scan_time_s", "net_time_s",
                         "compute_time_s", "opt_time_s", "bytes_scanned",
                         "files_scanned", "reuse_hits", "reuse_bytes_served",
                         "residual_bytes_scanned", "reuse_scan_skips")},
            # Join-kernel block-pair counters (0.0 when joins are not
            # executed, the bench_caching default).
            **{k: payload["summary"].get(k, 0.0)
               for k in ("block_pairs_total", "block_pairs_evaluated")},
            "policy_spec": payload["policy_spec"],
        }
    return out


def main(argv: Optional[Sequence[str]] = None) -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--policy", default=",".join(POLICIES),
                    help="comma-separated registered policy combos "
                         "(e.g. cost,chunk_lru,chunk_lfu)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the full valid (granularity x eviction x "
                         "placement) registry cross product and record "
                         "per-workload winners (overrides --policy)")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="admit queries through process_batch in groups "
                         "of N (default: per-query admission)")
    ap.add_argument("--reuse", default="off", choices=("off", "on"),
                    help="semantic cache reuse: serve covered sub-regions "
                         "from resident chunks (default: off, seed parity)")
    ap.add_argument("--budget-frac", default=None,
                    help="comma-separated budget fractions "
                         f"(default: {BUDGET_FRACTIONS})")
    ap.add_argument("--out", default="BENCH_caching.json",
                    help="JSON summary path ('' disables)")
    args = ap.parse_args(argv)
    policies = (sweep_policy_names() if args.sweep
                else tuple(p for p in args.policy.split(",") if p))
    fracs = (tuple(float(f) for f in args.budget_frac.split(","))
             if args.budget_frac else BUDGET_FRACTIONS)
    results = run(policies=policies, budget_fractions=fracs,
                  batch_size=args.batch_size, reuse=args.reuse)
    if args.sweep:
        for wl, win in sweep_winners(results).items():
            print(f"sweep/{wl}/winner,0,{win['policy']}")
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(to_json_summary(results, policies, args.batch_size,
                                      args.reuse, sweep=args.sweep),
                      fh, indent=2, sort_keys=True)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
