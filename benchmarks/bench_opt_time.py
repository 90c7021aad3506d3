"""Figure 7: optimization time (query-driven chunking vs eviction+placement
plans) per query on the GEO workload — the coordinator's own cost, measured
for real (these algorithms execute, they are not simulated).

The ``best_split`` rows isolate the split-choice step inside chunking
(``RefineStats.split_eval_s`` wall-clock over ``split_candidates``
candidate faces): the part the vectorized ``EvolvingRTree._best_split``
accelerates, so planner-side speedups are visible in the trajectory."""
from __future__ import annotations

from benchmarks.common import build_geo, dataset_bytes, make_cluster
from repro.core.workload import geo_workload


def run(print_rows: bool = True):
    catalog, reader = build_geo("csv", seed=13)
    cluster = make_cluster(catalog, reader, "cost",
                           dataset_bytes(catalog) // 8)
    rows = []
    split_s = 0.0
    split_cands = 0
    for i, q in enumerate(geo_workload(catalog.domain), 1):
        ex = cluster.run_query(q)
        rep = ex.report
        rows.append((rep.opt_time_chunking_s, rep.opt_time_evict_place_s))
        split_s += rep.refine_stats.split_eval_s
        split_cands += rep.refine_stats.split_candidates
        if print_rows:
            print(f"fig7/q{i}/chunking,{rep.opt_time_chunking_s*1e6:.0f},"
                  f"{rep.refine_stats.splits}")
            print(f"fig7/q{i}/best_split,"
                  f"{rep.refine_stats.split_eval_s*1e6:.0f},"
                  f"{rep.refine_stats.split_candidates}")
            print(f"fig7/q{i}/evict_place,"
                  f"{rep.opt_time_evict_place_s*1e6:.0f},"
                  f"{rep.cached_chunks_after}")
    total_opt = sum(a + b for a, b in rows)
    if print_rows:
        print(f"fig7/total_best_split_s,{split_s*1e6:.0f},{split_cands}")
        print(f"fig7/total_opt_s,0,{total_opt:.4f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
