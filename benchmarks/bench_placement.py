"""Figure 8: reduction in (modeled) similarity-join communication time with
cost-based cache placement (dynamic, Alg. 3) vs origin-pinned caching
(static), per workload."""
from __future__ import annotations

from benchmarks.common import (build_geo, build_ptf, cell_anchors,
                               dataset_bytes, make_cluster)
from repro.core.cluster import workload_summary
from repro.core.workload import geo_workload, ptf1_workload, ptf2_workload


def run(print_rows: bool = True):
    setups = {}
    c1, r1 = build_ptf("hdf5", seed=41)
    setups["ptf1"] = (c1, r1, ptf1_workload(c1.domain, n_queries=10,
                                            eps=300,
                                            anchors=cell_anchors(c1, r1)))
    c2, r2 = build_ptf("fits", seed=42)
    setups["ptf2"] = (c2, r2, ptf2_workload(c2.domain, n_queries=10,
                                            eps=300,
                                            anchors=cell_anchors(c2, r2)))
    c3, r3 = build_geo("csv", seed=43)
    setups["geo"] = (c3, r3, geo_workload(c3.domain, eps=500))
    out = {}
    for name, (catalog, reader, queries) in setups.items():
        budget = dataset_bytes(catalog) // 16
        nets = {}
        for mode in ("static", "dynamic"):
            cluster = make_cluster(catalog, reader, "cost", budget,
                                   placement=mode)
            executed = cluster.run_workload(queries)
            nets[mode] = workload_summary(executed)["net_time_s"]
            if print_rows:
                print(f"fig8/{name}/{mode},0,{nets[mode]:.4f}")
        ratio = nets["static"] / max(nets["dynamic"], 1e-9)
        out[name] = ratio
        if print_rows:
            print(f"fig8/{name}/static_over_dynamic,0,{ratio:.2f}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
