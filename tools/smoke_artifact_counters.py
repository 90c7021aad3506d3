#!/usr/bin/env python
"""CI smoke gate for the join-artifact cache (ISSUE 5 satellite).

Runs a repeat-query clustered workload through the default
(``prune="block"``, pallas) cluster and fails unless the warm pass

  * reports ``artifact_hits > 0`` — catches a silent cache bypass where
    the counters are wired but the executors stopped consulting the
    cache (every query would quietly repay the host-prep cost);
  * returns per-query match counts identical to the cold pass — catches
    a stale-artifact path where a hit serves wrong derived data.

Usage (the CI tier-1 job runs exactly this):

    PYTHONPATH=src python tools/smoke_artifact_counters.py
"""
from __future__ import annotations

import sys
import tempfile


def main() -> int:
    """Run the smoke workload; returns a process exit code."""
    from repro.arrayio.catalog import FileReader, build_catalog
    from repro.arrayio.generator import make_geo_files
    from repro.core.cluster import RawArrayCluster, workload_summary
    from repro.core.workload import geo_workload

    files = make_geo_files(n_files=3, n_seeds=120, clones_per_seed=20,
                           seed=5)
    catalog, data = build_catalog(files,
                                  tempfile.mkdtemp(prefix="smoke_art_"),
                                  "csv", n_nodes=4)
    # Budget covers the dataset: repeats must be answered warm.
    budget = sum(f.n_cells * f.cell_bytes for f in catalog.files)
    cluster = RawArrayCluster(catalog, FileReader(catalog, data), 4,
                              budget // 4, policy="cost", min_cells=512,
                              join_backend="pallas")
    queries = geo_workload(catalog.domain, eps=300, range_frac=0.4)
    cold = cluster.run_workload(queries)
    warm = cluster.run_workload(queries)
    cold_m = [e.matches for e in cold]
    warm_m = [e.matches for e in warm]
    summ = workload_summary(warm)
    print(f"cold matches: {cold_m}")
    print(f"warm matches: {warm_m}")
    print(f"warm artifact_hits={summ.get('artifact_hits')} "
          f"artifact_misses={summ.get('artifact_misses')} "
          f"prep_s={summ.get('prep_s', 0.0):.4f} "
          f"dispatch_s={summ.get('dispatch_s', 0.0):.4f}")
    if summ.get("artifact_hits", 0) <= 0:
        print("FAIL: warm pass reported no artifact hits — the join-"
              "artifact cache is being bypassed", file=sys.stderr)
        return 1
    if warm_m != cold_m or sum(m or 0 for m in cold_m) <= 0:
        print("FAIL: warm match counts differ from cold (stale artifact "
              "served?)", file=sys.stderr)
        return 1
    print("OK: artifact cache hit on the warm pass with bit-identical "
          "match counts")
    return 0


if __name__ == "__main__":
    sys.exit(main())
