"""Figure 6: improvement over file_lru across a 100-query PTF stress
workload with a generous cache budget (favoring LRU, as in the paper) —
plus two executed-join sections:

  * the execution-backend comparison (``run_backends``): the same
    workload run under the simulated cost model and under the jax
    device-mesh backend, across the dense / block-sparse / auto join
    grids, reporting REAL (measured, not modeled) transfer and join
    wall-clock per backend;
  * the cross-query sharing scenario (``run_mqo``): a Zipf-skewed
    repeat workload run MQO-on/off x result-cache-on/off on both
    backends, recording the task-dedup and result-serving counters;
  * the failover scenario (``run_failover``): a skewed workload with
    the hottest node killed mid-run, replication off/on on both
    backends, recording post-kill tail latency and the
    replica-vs-raw recovery split;
  * the chaos scenario (``run_chaos``): the same replicated workload
    under seeded fault storms at increasing rates on both backends,
    recording completed/degraded fractions, latency inflation vs the
    fault-free baseline, and the reroute-vs-raw-fallback recovery
    split.

The sections emit structured row dicts and merge them into
``BENCH_caching.json`` (under the ``backends`` / ``mqo`` /
``failover`` / ``chaos`` keys, preserving whatever ``bench_caching``
wrote) so successive PRs can diff the perf trajectory.

Run the backend sections with virtual devices to exercise real
cross-device transfers on a CPU-only host:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.bench_scalability --n-queries 30 --seed 33
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.common import (N_NODES, build_ptf, cell_anchors,
                               dataset_bytes, make_cluster, timed)
from repro.core.cluster import RawArrayCluster, workload_summary
from repro.core.workload import ptf_stress_workload, zipf_workload


def run(n_queries: int = 100, print_rows: bool = True):
    """Fig. 6: per-policy modeled improvement over the file_lru baseline."""
    catalog, reader = build_ptf("hdf5", n_files=16, cells=2500, seed=31)
    queries = ptf_stress_workload(catalog.domain, n_queries=n_queries,
                                  eps=300,
                                  anchors=cell_anchors(catalog, reader))
    budget = dataset_bytes(catalog) // 8          # generous: favors LRU
    times = {}
    for policy in ("file_lru", "chunk_lru", "cost"):
        cluster = make_cluster(catalog, reader, policy, budget)
        executed, us = timed(cluster.run_workload, queries)
        times[policy] = [e.time_total_s for e in executed]
        if print_rows:
            print(f"fig6/{policy},{us:.0f},"
                  f"{workload_summary(executed)['total_time_s']:.3f}")
    base = np.asarray(times["file_lru"])
    for policy in ("chunk_lru", "cost"):
        imp = base / np.maximum(np.asarray(times[policy]), 1e-9)
        if print_rows:
            print(f"fig6/median_improvement_{policy},0,"
                  f"{float(np.median(imp)):.2f}")
    return times


def run_backends(n_queries: int = 30, print_rows: bool = True,
                 seed: int = 33) -> List[Dict]:
    """Backend comparison: identical plans executed by the simulated and
    jax_mesh backends, each under the dense, block-sparse, and
    adaptive-auto join grids. Returns one structured row dict per
    configuration carrying the modeled net/compute times, the block-pair
    pruning counters (``block_pairs_evaluated/total``), and for the mesh
    backend the MEASURED transfer + join kernel wall-clock and measured
    shipped device bytes; rows also print in the scaffold CSV shape."""
    from repro.backend import JaxMeshBackend
    catalog, reader = build_ptf("hdf5", n_files=12, cells=1500, seed=seed)
    queries = ptf_stress_workload(catalog.domain, n_queries=n_queries,
                                  eps=300,
                                  anchors=cell_anchors(catalog, reader))
    budget = dataset_bytes(catalog) // 8
    rows: List[Dict] = []
    matches = {}
    for backend in ("simulated", "jax_mesh"):
        for prune in ("dense", "block", "auto"):
            label = f"{backend}_{prune}"
            cluster = RawArrayCluster(
                catalog, reader, N_NODES, budget // N_NODES, policy="cost",
                min_cells=48, execute_joins=True, backend=backend,
                join_backend="pallas", prune=prune)
            executed, us = timed(cluster.run_workload, queries)
            summ = workload_summary(executed)
            mesh_ran = isinstance(cluster.backend, JaxMeshBackend)
            matches[label] = sum(e.matches or 0 for e in executed)
            row = {
                "backend": backend, "prune": prune, "seed": seed,
                "n_queries": n_queries, "bench_us": us,
                "modeled_net_s": summ["net_time_s"],
                "modeled_compute_s": summ["compute_time_s"],
                "block_pairs_total": summ.get("block_pairs_total", 0.0),
                "block_pairs_evaluated": summ.get("block_pairs_evaluated",
                                                  0.0),
                "matches": matches[label],
            }
            # make_backend degrades jax_mesh -> simulated when jax is
            # absent; only emit measured rows when the mesh actually ran.
            if mesh_ran:
                row.update({
                    "measured_net_s": summ["measured_net_s"],
                    "dispatch_s": summ["dispatch_s"],
                    "measured_ship_bytes": summ["measured_ship_bytes"],
                    "committed_bytes_moved":
                        cluster.backend.device_stats["committed_bytes_moved"],
                })
            rows.append(row)
            if print_rows:
                print(f"backend/{label}/modeled_net_s,{us:.0f},"
                      f"{summ['net_time_s']:.4f}")
                print(f"backend/{label}/modeled_compute_s,0,"
                      f"{summ['compute_time_s']:.4f}")
                print(f"backend/{label}/block_pairs,0,"
                      f"{summ.get('block_pairs_evaluated', 0):.0f}/"
                      f"{summ.get('block_pairs_total', 0):.0f}")
                if mesh_ran:
                    print(f"backend/{label}/measured_net_s,0,"
                          f"{summ['measured_net_s']:.4f}")
                    print(f"backend/{label}/dispatch_s,0,"
                          f"{summ['dispatch_s']:.4f}")
                    print(f"backend/{label}/measured_ship_bytes,0,"
                          f"{summ['measured_ship_bytes']:.0f}")
                    stats = cluster.backend.device_stats
                    print(f"backend/{label}/committed_bytes_moved,0,"
                          f"{stats['committed_bytes_moved']:.0f}")
    if print_rows:
        parity = len(set(matches.values())) == 1
        print(f"backend/match_parity,0,{int(parity)}")
    return rows


def run_mqo(n_queries: int = 60, n_templates: int = 12,
            batch_size: int = 8, print_rows: bool = True,
            seed: int = 41) -> List[Dict]:
    """Cross-query sharing scenario: a seeded Zipf(s=1.1) repeat workload
    executed MQO-on/off x result-cache-on/off on both backends. Each row
    records the dedup counters (``mqo_tasks_total/executed/shared_hits``),
    the result-tier counters (hits/misses + ``planner_invocations``), and
    the match total — identical across every configuration by
    construction (the parity row asserts it)."""
    from repro.backend import JaxMeshBackend  # noqa: F401 (mesh probe)
    catalog, reader = build_ptf("hdf5", n_files=12, cells=1500, seed=35)
    queries = zipf_workload(catalog.domain, n_queries=n_queries,
                            n_templates=n_templates, s=1.1, eps=300,
                            seed=seed,
                            anchors=cell_anchors(catalog, reader))
    budget = dataset_bytes(catalog) // 8
    rows: List[Dict] = []
    matches = {}
    for backend in ("simulated", "jax_mesh"):
        for mqo in ("off", "on"):
            for rc in ("off", "on"):
                label = f"{backend}_mqo_{mqo}_rc_{rc}"
                cluster = RawArrayCluster(
                    catalog, reader, N_NODES, budget // N_NODES,
                    policy="cost", min_cells=48, execute_joins=True,
                    backend=backend, join_backend="pallas", prune="auto",
                    mqo=mqo, result_cache=rc)
                executed, us = timed(cluster.run_workload, queries,
                                     batch_size=batch_size)
                summ = workload_summary(executed)
                coord = cluster.coordinator
                matches[label] = sum(e.matches or 0 for e in executed)
                rows.append({
                    "backend": backend, "mqo": mqo, "result_cache": rc,
                    "seed": seed, "n_queries": n_queries,
                    "n_templates": n_templates, "batch_size": batch_size,
                    "bench_us": us, "matches": matches[label],
                    "mqo_tasks_total": summ.get("mqo_tasks_total", 0.0),
                    "mqo_tasks_executed": summ.get("mqo_tasks_executed",
                                                   0.0),
                    "mqo_shared_hits": summ.get("mqo_shared_hits", 0.0),
                    "result_cache_hits":
                        coord.stats["result_cache_hits"],
                    "result_cache_misses":
                        coord.stats["result_cache_misses"],
                    "planner_invocations": coord.planner_invocations,
                    "compute_time_s": summ["compute_time_s"],
                    "dispatch_s": summ.get("dispatch_s", 0.0),
                })
                if print_rows:
                    print(f"mqo/{label}/tasks,{us:.0f},"
                          f"{summ.get('mqo_tasks_executed', 0):.0f}/"
                          f"{summ.get('mqo_tasks_total', 0):.0f}")
                    print(f"mqo/{label}/result_cache_hits,0,"
                          f"{coord.stats['result_cache_hits']}")
                    print(f"mqo/{label}/planner_invocations,0,"
                          f"{coord.planner_invocations}")
    if print_rows:
        parity = len(set(matches.values())) == 1
        print(f"mqo/match_parity,0,{int(parity)}")
    return rows


def run_failover(n_queries: int = 48, n_templates: int = 6,
                 batch_size: int = 6, print_rows: bool = True,
                 seed: int = 57) -> List[Dict]:
    """Failover scenario: a skewed Zipf(s=1.5) workload run
    replication-off/on on both backends; halfway through, the hottest
    node (most cached bytes) is killed. Each row records the post-kill
    tail latency (p95 of the modeled per-query time after the failure —
    the hot-node recovery penalty the paper's single-copy cache pays),
    the recovery source split (``recovery_bytes_from_replica`` vs
    ``recovery_bytes_from_raw``), the recovery wall-clock, and the match
    total — identical across every configuration and to an unfailed
    reference by construction (the parity row asserts it)."""
    catalog, reader = build_ptf("hdf5", n_files=12, cells=1500, seed=35)
    queries = zipf_workload(catalog.domain, n_queries=n_queries,
                            n_templates=n_templates, s=1.5, eps=300,
                            seed=seed,
                            anchors=cell_anchors(catalog, reader))
    # 1/4 (vs the other sections' 1/8): enough leftover headroom that
    # the hot tier can actually afford secondaries, while staying far
    # from fitting two full copies of the dataset.
    budget = dataset_bytes(catalog) // 4
    half = (len(queries) // (2 * batch_size)) * batch_size

    def build(backend: str, replication: str) -> RawArrayCluster:
        return RawArrayCluster(
            catalog, reader, N_NODES, budget // N_NODES, policy="cost",
            min_cells=48, execute_joins=True, backend=backend,
            join_backend="pallas", prune="auto", replication=replication,
            replica_k=2, replication_threshold=2.0)

    rows: List[Dict] = []
    matches = {}
    for backend in ("simulated", "jax_mesh"):
        ref = build(backend, "off").run_workload(queries,
                                                 batch_size=batch_size)
        matches[f"{backend}_ref"] = sum(e.matches or 0 for e in ref)
        for replication in ("off", "hot"):
            label = f"{backend}_repl_{replication}"
            cluster = build(backend, replication)
            executed, us = timed(cluster.run_workload, queries[:half],
                                 batch_size=batch_size)
            cache = cluster.coordinator.cache
            chunk_bytes, _ = cluster.coordinator.chunks.size_tables()
            by_node = cache.bytes_by_node(chunk_bytes)
            victim = max(by_node, key=lambda n: (by_node[n], -n))
            event = cluster.fail_node(victim)
            tail = cluster.run_workload(queries[half:],
                                        batch_size=batch_size)
            executed += tail
            summ = workload_summary(executed)
            matches[label] = sum(e.matches or 0 for e in executed)
            post_kill = sorted(e.time_total_s for e in tail)
            p95 = post_kill[min(len(post_kill) - 1,
                                int(0.95 * len(post_kill)))]
            rows.append({
                "backend": backend, "replication": replication,
                "seed": seed, "n_queries": n_queries,
                "n_templates": n_templates, "batch_size": batch_size,
                "bench_us": us, "matches": matches[label],
                "killed_node": victim,
                "failover_readmits": summ.get("failover_readmits", 0.0),
                "recovery_bytes_from_replica":
                    summ.get("recovery_bytes_from_replica", 0.0),
                "recovery_bytes_from_raw":
                    summ.get("recovery_bytes_from_raw", 0.0),
                "recovery_s": float(event["recovery_s"]),
                "replica_hits": summ.get("replica_hits", 0.0),
                "replicas_dropped": summ.get("replicas_dropped", 0.0),
                "post_kill_p95_s": p95,
                "post_kill_total_s": sum(post_kill),
            })
            if print_rows:
                print(f"failover/{label}/readmits,{us:.0f},"
                      f"{summ.get('failover_readmits', 0):.0f}")
                print(f"failover/{label}/recovery_bytes,0,"
                      f"{summ.get('recovery_bytes_from_replica', 0):.0f}/"
                      f"{summ.get('recovery_bytes_from_raw', 0):.0f}")
                print(f"failover/{label}/recovery_s,0,"
                      f"{event['recovery_s']:.5f}")
                print(f"failover/{label}/post_kill_p95_s,0,{p95:.4f}")
    if print_rows:
        parity = len(set(matches.values())) == 1
        print(f"failover/match_parity,0,{int(parity)}")
    return rows


def run_chaos(n_queries: int = 36, n_templates: int = 4,
              batch_size: int = 4, print_rows: bool = True,
              seed: int = 73,
              rates: Sequence[float] = (0.0, 0.05, 0.15)) -> List[Dict]:
    """Chaos scenario (ISSUE 10): a broad-field Zipf workload on a
    replicated cluster, swept across seeded fault-storm rates on both
    backends. ``rate == 0`` is the fault-free baseline row; each faulted
    row records the completed/degraded query fractions, the wall-clock
    and p95 modeled-latency inflation over the baseline, the
    recovery-source split (``transfer_reroutes`` — retries re-sourced
    from a surviving replica — vs ``raw_fallbacks`` — transfers that
    exhausted every replica and re-scanned raw files), the checksum
    catches, and the audit-violation count (zero by construction). The
    parity flag asserts every *completed* query's match count is
    bit-identical to the baseline — degraded-mode serving never leaks
    into completed answers."""
    from repro.faults import FaultInjector
    catalog, reader = build_ptf("hdf5", n_files=12, cells=1500, seed=35)
    # field_frac=0.5: query boxes span files on several nodes, so join
    # plans carry live transfer routes — the storm's ship.transfer
    # faults then exercise the re-route → raw-fallback ladder.
    queries = zipf_workload(catalog.domain, n_queries=n_queries,
                            n_templates=n_templates, s=1.5, eps=300,
                            field_frac=0.5, seed=seed)
    budget = dataset_bytes(catalog) // 4

    def build(backend: str, faults) -> RawArrayCluster:
        return RawArrayCluster(
            catalog, reader, N_NODES, budget // N_NODES, policy="cost",
            min_cells=48, execute_joins=True, backend=backend,
            join_backend="pallas", prune="auto", replication="hot",
            replica_k=2, replication_threshold=2.0, faults=faults)

    def p95(values: List[float]) -> float:
        xs = sorted(values)
        return xs[min(len(xs) - 1, int(0.95 * len(xs)))]

    rows: List[Dict] = []
    for backend in ("simulated", "jax_mesh"):
        # Warmup: the first run per backend pays one-time JIT/page-cache
        # costs that would otherwise inflate the fault-free baseline and
        # make the faulted rows look *faster* than rate 0.
        build(backend, "off").run_workload(queries, batch_size=batch_size)
        base_us = base_p95 = None
        ref_matches: List = []
        for rate in rates:
            faults = (FaultInjector.storm(rate, seed=seed)
                      if rate > 0 else "off")
            cluster = build(backend, faults)
            executed, us = timed(cluster.run_workload, queries,
                                 batch_size=batch_size)
            summ = workload_summary(executed)
            lat_p95 = p95([e.time_total_s for e in executed])
            if rate == 0:
                base_us, base_p95 = us, lat_p95
                ref_matches = [e.matches for e in executed]
            degraded = int(summ.get("degraded_queries", 0))
            completed_parity = all(
                e.matches == m
                for e, m in zip(executed, ref_matches)
                if e.degraded is None)
            label = f"{backend}_rate_{rate:g}"
            rows.append({
                "backend": backend, "fault_rate": rate, "seed": seed,
                "n_queries": n_queries, "n_templates": n_templates,
                "batch_size": batch_size, "bench_us": us,
                "completed_frac": (len(executed) - degraded)
                                  / len(executed),
                "degraded_frac": degraded / len(executed),
                "wall_inflation": us / base_us if base_us else 1.0,
                "p95_total_s": lat_p95,
                "p95_inflation": lat_p95 / base_p95 if base_p95 else 1.0,
                "faults_injected": summ.get("faults_injected", 0.0),
                "retries": summ.get("retries", 0.0),
                "transfer_reroutes": summ.get("transfer_reroutes", 0.0),
                "raw_fallbacks": summ.get("raw_fallbacks", 0.0),
                "checksum_mismatch": summ.get("checksum_mismatch", 0.0),
                "audit_violations": summ.get("audit_violations", 0.0),
                "completed_match_parity": completed_parity,
            })
            if print_rows:
                print(f"chaos/{label}/completed_frac,{us:.0f},"
                      f"{rows[-1]['completed_frac']:.3f}")
                print(f"chaos/{label}/injected_retries,0,"
                      f"{rows[-1]['faults_injected']:.0f}/"
                      f"{rows[-1]['retries']:.0f}")
                print(f"chaos/{label}/recovery_split,0,"
                      f"{rows[-1]['transfer_reroutes']:.0f}/"
                      f"{rows[-1]['raw_fallbacks']:.0f}")
                print(f"chaos/{label}/wall_inflation,0,"
                      f"{rows[-1]['wall_inflation']:.3f}")
                print(f"chaos/{label}/violations_parity,0,"
                      f"{rows[-1]['audit_violations']:.0f}/"
                      f"{int(completed_parity)}")
    return rows


#: Telemetry-only recording hooks outside ``src/repro/obs/`` whose
#: self-time counts as instrumentation cost in ``run_observability``.
_TELEMETRY_FUNCS = frozenset({
    "record_executed", "register_summary_counters", "_record",
    "_record_cache_health", "_mirror_device_stats"})


def run_observability(n_queries: int = 60, n_templates: int = 12,
                      batch_size: int = 8, repeats: int = 5,
                      print_rows: bool = True, seed: int = 41) -> List[Dict]:
    """Telemetry overhead scenario (ISSUE 8): the ``run_mqo`` mixed
    workload (reuse + MQO + result cache + hot replication, so every
    instrumented path fires) run ``telemetry="off"`` vs ``"on"`` on the
    simulated backend. The acceptance number, ``overhead_frac`` (<3%),
    is the *attributed* instrumentation share of a profiled
    telemetry-on run: the summed self-time of every function in
    ``src/repro/obs/`` plus the recording hooks (``record_executed``,
    cache-health/device-stat mirrors), over total run time — a
    deterministic measurement that cProfile's per-call cost biases
    *upward*, i.e. conservative. Differencing two wall-clocks cannot
    resolve a sub-1% effect on a shared machine (run-to-run jitter is
    an order of magnitude larger than the instrumentation), so the raw
    on-vs-off min-of-``repeats`` delta is recorded as the informational
    ``wall_delta_frac`` only. The row also carries the span volume and
    the off/on counter parity flag (every non-timing summary value must
    be bit-identical across modes)."""
    import cProfile
    import gc
    import pstats
    catalog, reader = build_ptf("hdf5", n_files=12, cells=1500, seed=35)
    queries = zipf_workload(catalog.domain, n_queries=n_queries,
                            n_templates=n_templates, s=1.1, eps=300,
                            seed=seed,
                            anchors=cell_anchors(catalog, reader))
    budget = dataset_bytes(catalog) // 8

    def once(telemetry: str, profile: bool = False):
        cluster = RawArrayCluster(
            catalog, reader, N_NODES, budget // N_NODES, policy="cost",
            min_cells=48, execute_joins=True, backend="simulated",
            join_backend="pallas", prune="auto", reuse="on", mqo="on",
            result_cache="on", replication="hot", telemetry=telemetry)
        # GC pauses (not the instrumentation) dominate run-to-run jitter
        # on this Python-geometry-heavy workload: collect up front and
        # keep the collector out of the timed region in both modes.
        gc.collect()
        gc.disable()
        try:
            prof = None
            if profile:
                prof = cProfile.Profile()
                prof.enable()
            executed, us = timed(cluster.run_workload, queries,
                                 batch_size=batch_size)
            if prof is not None:
                prof.disable()
        finally:
            gc.enable()
        return cluster, workload_summary(executed), us, prof

    best: Dict[str, float] = {}
    summaries: Dict[str, Dict] = {}
    spans = 0
    once("off"), once("on")           # warmup: JIT/page-cache/allocator
    # Interleave the repeats, alternating which mode goes first each
    # round (whichever runs second inherits a warmer allocator); keep
    # the minimum, the least-noise wall-clock estimate.
    for r in range(repeats):
        order = ("off", "on") if r % 2 == 0 else ("on", "off")
        for mode in order:
            cluster, summ, us, _ = once(mode)
            best[mode] = min(best.get(mode, float("inf")), us)
            summaries[mode] = summ
            if mode == "on":
                spans = len(cluster.telemetry.tracer.spans)
    wall_delta = (best["on"] - best["off"]) / best["off"]

    _, _, _, prof = once("on", profile=True)
    st = pstats.Stats(prof)
    telemetry_s = sum(
        tt for (fname, _lineno, func), (_cc, _nc, tt, _ct, _callers)
        in st.stats.items()
        if "/repro/obs/" in fname.replace("\\", "/")
        or func in _TELEMETRY_FUNCS)
    overhead = telemetry_s / st.total_tt if st.total_tt else 0.0

    parity = all(summaries["off"][k] == summaries["on"][k]
                 for k in summaries["off"] if not k.endswith("_s"))
    row = {
        "backend": "simulated", "seed": seed, "n_queries": n_queries,
        "n_templates": n_templates, "batch_size": batch_size,
        "repeats": repeats, "off_us": best["off"], "on_us": best["on"],
        "wall_delta_frac": wall_delta,
        "telemetry_self_us": telemetry_s * 1e6,
        "overhead_frac": overhead, "spans": spans,
        "counter_parity": parity, "pass_under_3pct": overhead < 0.03,
    }
    if print_rows:
        print(f"observability/simulated/off_us,{best['off']:.0f},0")
        print(f"observability/simulated/on_us,{best['on']:.0f},0")
        print(f"observability/simulated/wall_delta_pct,0,"
              f"{100.0 * wall_delta:.3f}")
        print(f"observability/simulated/overhead_pct,0,"
              f"{100.0 * overhead:.4f}")
        print(f"observability/simulated/spans,0,{spans}")
        print(f"observability/counter_parity,0,{int(parity)}")
    return [row]


def merge_json(path: str, backends_rows: Optional[List[Dict]] = None,
               mqo_rows: Optional[List[Dict]] = None,
               failover_rows: Optional[List[Dict]] = None,
               observability_rows: Optional[List[Dict]] = None,
               chaos_rows: Optional[List[Dict]] = None) -> None:
    """Read-modify-write ``BENCH_caching.json``: replace only the
    ``backends`` / ``mqo`` / ``failover`` / ``observability`` /
    ``chaos`` keys, preserving everything ``bench_caching`` (or a
    previous run) recorded."""
    data: Dict = {}
    if os.path.exists(path):
        with open(path) as fh:
            data = json.load(fh)
    if backends_rows is not None:
        data["backends"] = backends_rows
    if mqo_rows is not None:
        data["mqo"] = mqo_rows
    if failover_rows is not None:
        data["failover"] = failover_rows
    if observability_rows is not None:
        data["observability"] = observability_rows
    if chaos_rows is not None:
        data["chaos"] = chaos_rows
    with open(path, "w") as fh:
        json.dump(data, fh, indent=2, sort_keys=True)
    print(f"wrote {path}")


def main(argv: Optional[Sequence[str]] = None) -> None:
    """CLI: Fig. 6 + both executed-join sections, JSON-merged."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-queries", type=int, default=30,
                    help="workload length of the backend/MQO sections "
                         "(Fig. 6 keeps its 100-query stress workload)")
    ap.add_argument("--seed", type=int, default=33,
                    help="dataset/workload seed of the backend and MQO "
                         "sections")
    ap.add_argument("--skip-fig6", action="store_true",
                    help="run only the executed-join sections")
    ap.add_argument("--trace", action="store_true",
                    help="also measure telemetry on-vs-off overhead "
                         "(merged under the 'observability' key)")
    ap.add_argument("--out", default="BENCH_caching.json",
                    help="JSON path to merge backend/mqo rows into "
                         "('' disables)")
    args = ap.parse_args(argv)
    if not args.skip_fig6:
        run()
    backends_rows = run_backends(n_queries=args.n_queries, seed=args.seed)
    mqo_rows = run_mqo(n_queries=max(args.n_queries * 2, 20),
                       seed=args.seed + 8)
    failover_rows = run_failover(n_queries=max(args.n_queries, 24),
                                 seed=args.seed + 24)
    chaos_rows = run_chaos(n_queries=max(args.n_queries, 24),
                           seed=args.seed + 40)
    observability_rows = (run_observability(n_queries=max(args.n_queries, 24),
                                            seed=args.seed + 8)
                          if args.trace else None)
    if args.out:
        merge_json(args.out, backends_rows, mqo_rows, failover_rows,
                   observability_rows, chaos_rows)


if __name__ == "__main__":
    main()
