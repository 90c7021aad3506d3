"""Kernel micro-benchmarks (interpret mode on CPU — wall time is a
correctness-path cost, not TPU perf; the derived column reports the
work done: cell-pairs, attention FLOPs, pages touched, block pairs).

The simjoin section records the kernel perf trajectory: dense vs
block-sparse (eps-pruned, ``PrefetchScalarGridSpec``) simjoin on
clustered inputs, plus the clustered GEO workload executed end-to-end
under prune=dense/block/bitmap/auto on both execution backends —
match-count parity, the ``block_pairs_evaluated / block_pairs_total``
pruning counters, the cell-exact bitmap stage's
``block_pairs_bitmap_killed``/``bitmap_build_s``, and
(``run_artifact_amortization``) cold-vs-warm rows for the
join-artifact cache: hit rates, the prep/dispatch wall-clock split, and
the warm prep speedup on a repeated workload.
``run(out_json=...)`` (the module main writes ``BENCH_kernels.json``)
serializes all of it so successive PRs can diff kernel performance.
"""
from __future__ import annotations

import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.simjoin import ops as sj_ops


def _time(fn, *args, n=3, **kwargs):
    fn(*args, **kwargs)                        # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def clustered_coords(rng, n: int, d: int = 3, n_clusters: int = 12,
                     domain: int = 100_000, spread: int = 40) -> np.ndarray:
    """Clustered integer coordinates (the geo/ptf regime: dense knots in
    a huge domain) — the distribution block pruning exploits."""
    centers = rng.integers(0, domain, (n_clusters, d))
    pick = rng.integers(0, n_clusters, n)
    return (centers[pick] + rng.integers(-spread, spread + 1,
                                         (n, d))).astype(np.int32)


def run_simjoin_pruning(print_rows: bool = True, n: int = 4096,
                        eps: int = 64):
    """Dense vs block-sparse simjoin self-join on clustered coords:
    timings, block-pair counters, match parity, and the jit trace tally
    (repeat dispatches must not retrace)."""
    rng = np.random.default_rng(7)
    a = clustered_coords(rng, n)
    aj = jnp.asarray(a)
    dense_us = _time(sj_ops.count_similar_pairs, aj, aj, eps, True)
    matches_dense = int(sj_ops.count_similar_pairs(aj, aj, eps, True))
    matches_pruned, total, evaluated = sj_ops.count_similar_pairs_pruned_np(
        a, a, eps, True)
    pruned_us = _time(sj_ops.count_similar_pairs_pruned_np, a, a, eps, True)
    trace_before = dict(sj_ops.TRACE_COUNTS)
    for _ in range(3):                         # repeat dispatch: no retrace
        sj_ops.count_similar_pairs_pruned_np(a, a, eps, True)
    retraced = dict(sj_ops.TRACE_COUNTS) != trace_before
    out = {
        "n": n, "eps": eps, "dense_us": dense_us, "pruned_us": pruned_us,
        "matches_dense": matches_dense, "matches_pruned": matches_pruned,
        "match_parity": matches_dense == matches_pruned,
        "block_pairs_total": total, "block_pairs_evaluated": evaluated,
        "evaluated_fraction": evaluated / max(total, 1),
        "retraced_on_repeat": retraced,
    }
    if print_rows:
        print(f"kernel/simjoin_dense_clustered_{n}x3,{dense_us:.0f},{total}")
        print(f"kernel/simjoin_pruned_clustered_{n}x3,{pruned_us:.0f},"
              f"{evaluated}")
        print(f"kernel/simjoin_pruned_fraction,0,"
              f"{out['evaluated_fraction']:.3f}")
    return out


def _geo_dataset():
    """The join-heavy clustered GEO dataset shared by the workload
    benches: fewer but denser files, chunks kept multi-block
    (``min_cells=8192``) — the regime where per-pair block pruning has
    room to act on top of the planner's chunk-level eps-box pruning (at
    bench_caching's CI scale most chunk pairs are a single 128-block,
    which nothing can prune further)."""
    import tempfile
    from benchmarks.common import N_NODES
    from repro.arrayio.catalog import FileReader, build_catalog
    from repro.arrayio.generator import make_geo_files
    from repro.core.workload import geo_workload
    files = make_geo_files(n_files=4, n_seeds=300, clones_per_seed=40,
                           seed=11)
    catalog, data = build_catalog(files, tempfile.mkdtemp(prefix="bk_geo_"),
                                  "csv", n_nodes=N_NODES)
    reader = FileReader(catalog, data)
    queries = geo_workload(catalog.domain, eps=500, range_frac=0.5)
    return catalog, reader, queries, N_NODES


def _geo_cluster(catalog, reader, n_nodes, backend, prune, budget_frac=8):
    from repro.core.cluster import RawArrayCluster
    budget = (sum(f.n_cells * f.cell_bytes for f in catalog.files)
              // budget_frac)
    return RawArrayCluster(
        catalog, reader, n_nodes, budget // n_nodes, policy="cost",
        min_cells=8192, execute_joins=True, backend=backend,
        join_backend="pallas", prune=prune)


def run_geo_workload_pruning(print_rows: bool = True):
    """The clustered GEO workload executed end-to-end (joins for real)
    under prune=dense/block/bitmap/auto on both the simulated backend
    and the jax device mesh: identical match counts, the per-run
    block-pair counters (including the cell-exact bitmap stage's
    killed-pair counter and build wall-clock), and the host-side
    prep/dispatch split from ``workload_summary`` — the numbers the
    ``prune="auto"`` default is judged by (auto must not do more grid
    work than the best of dense, block, and bitmap)."""
    from repro.core.cluster import workload_summary
    catalog, reader, queries, n_nodes = _geo_dataset()
    out = {}
    for backend, prune in (("simulated", "dense"), ("simulated", "block"),
                           ("simulated", "bitmap"), ("simulated", "auto"),
                           ("jax_mesh", "dense"), ("jax_mesh", "block"),
                           ("jax_mesh", "bitmap"), ("jax_mesh", "auto")):
        cluster = _geo_cluster(catalog, reader, n_nodes, backend, prune)
        t0 = time.perf_counter()
        executed = cluster.run_workload(queries)
        wall_us = (time.perf_counter() - t0) * 1e6
        summ = workload_summary(executed)
        label = f"{backend}_{prune}"
        out[label] = {
            "matches": int(sum(e.matches or 0 for e in executed)),
            "wall_us": wall_us,
            "block_pairs_total": summ.get("block_pairs_total", 0.0),
            "block_pairs_evaluated": summ.get("block_pairs_evaluated", 0.0),
            "prep_s": summ.get("prep_s", 0.0),
            "dispatch_s": summ.get("dispatch_s", 0.0),
        }
        if "block_pairs_bitmap_killed" in summ:
            out[label]["block_pairs_bitmap_killed"] = \
                summ["block_pairs_bitmap_killed"]
            out[label]["bitmap_build_s"] = summ.get("bitmap_build_s", 0.0)
        if print_rows:
            print(f"geo_join/{label},{wall_us:.0f},"
                  f"{out[label]['matches']}")
            print(f"geo_join/{label}/block_pairs,0,"
                  f"{out[label]['block_pairs_evaluated']:.0f}/"
                  f"{out[label]['block_pairs_total']:.0f}")
    base = out["simulated_dense"]["matches"]
    parity = all(v["matches"] == base for v in out.values()
                 if isinstance(v, dict))
    frac = (out["simulated_block"]["block_pairs_evaluated"]
            / max(out["simulated_block"]["block_pairs_total"], 1.0))
    bitmap_frac = (out["simulated_bitmap"]["block_pairs_evaluated"]
                   / max(out["simulated_bitmap"]["block_pairs_total"], 1.0))
    # The adaptive default's acceptance, compared in like units:
    # auto <= dense holds in the evaluated counter directly (a dense-
    # routed task counts its full grid, a block-routed one its live
    # pairs <= grid). Against prune=block the evaluated counters are
    # NOT commensurate — block under-reports its *padded* kernel cost
    # (the kernel sweeps padded_pair_len rows) while auto's dense-routed
    # tasks count their exact grid, which the routing rule only takes
    # when grid <= that pad — so auto <= block holds in padded units by
    # construction; the ratio below is informational, not a gate.
    auto_work = out["simulated_auto"]["block_pairs_evaluated"]
    dense_work = out["simulated_dense"]["block_pairs_evaluated"]
    block_work = out["simulated_block"]["block_pairs_evaluated"]
    bitmap_work = out["simulated_bitmap"]["block_pairs_evaluated"]
    if print_rows:
        print(f"geo_join/match_parity,0,{int(parity)}")
        print(f"geo_join/pruned_fraction,0,{frac:.3f}")
        print(f"geo_join/bitmap_pruned_fraction,0,{bitmap_frac:.3f}")
        print(f"geo_join/auto_work_vs_dense_vs_block_vs_bitmap,0,"
              f"{auto_work:.0f}/{dense_work:.0f}/{block_work:.0f}/"
              f"{bitmap_work:.0f}")
    out["match_parity"] = parity
    out["pruned_fraction"] = frac
    out["bitmap_pruned_fraction"] = bitmap_frac
    out["auto_work_le_dense"] = bool(auto_work <= dense_work)
    out["bitmap_work_le_block"] = bool(bitmap_work <= block_work)
    out["auto_vs_block_evaluated_ratio"] = auto_work / max(block_work, 1.0)
    return out


def run_artifact_amortization(print_rows: bool = True):
    """Cold-vs-warm artifact-cache rows (the ISSUE-5 amortization
    evidence): the clustered GEO workload repeated against a long-lived
    cluster whose cache holds the working set. The cold pass pays the
    full host prep (sort/boxes/pad/pair lists, all artifact misses); the
    warm pass replays the identical queries and must show hits, a
    collapsed per-query ``prep_s``, and bit-identical match counts — on
    the mesh backend additionally re-dispatching pinned device batches
    instead of re-staging them."""
    from repro.core.cluster import workload_summary
    catalog, reader, queries, n_nodes = _geo_dataset()
    out = {}
    for backend in ("simulated", "jax_mesh"):
        cluster = _geo_cluster(catalog, reader, n_nodes, backend, "auto",
                               budget_frac=1)     # working set resident
        passes = {}
        for tag in ("cold", "warm"):
            t0 = time.perf_counter()
            executed = cluster.run_workload(queries)
            wall_us = (time.perf_counter() - t0) * 1e6
            summ = workload_summary(executed)
            hits = summ.get("artifact_hits", 0.0)
            misses = summ.get("artifact_misses", 0.0)
            passes[tag] = {
                "matches": int(sum(e.matches or 0 for e in executed)),
                "wall_us": wall_us,
                "prep_s": summ.get("prep_s", 0.0),
                "dispatch_s": summ.get("dispatch_s", 0.0),
                "artifact_hits": hits,
                "artifact_misses": misses,
                "hit_rate": hits / max(hits + misses, 1.0),
            }
            if print_rows:
                print(f"geo_artifacts/{backend}_{tag},{wall_us:.0f},"
                      f"prep_us={passes[tag]['prep_s'] * 1e6:.0f}")
                print(f"geo_artifacts/{backend}_{tag}/hit_rate,0,"
                      f"{passes[tag]['hit_rate']:.3f}")
        passes["match_parity"] = (passes["warm"]["matches"]
                                  == passes["cold"]["matches"])
        passes["prep_speedup"] = (passes["cold"]["prep_s"]
                                  / max(passes["warm"]["prep_s"], 1e-9))
        if isinstance(getattr(cluster.backend, "device_stats", None), dict):
            passes["pinned_batch_hits"] = \
                cluster.backend.device_stats.get("pinned_batch_hits", 0.0)
        if print_rows:
            print(f"geo_artifacts/{backend}/prep_speedup,0,"
                  f"{passes['prep_speedup']:.1f}x")
        out[backend] = passes
    return out


def run(print_rows: bool = True, out_json: Optional[str] = None):
    """All kernel rows; ``out_json`` additionally writes the JSON perf
    trajectory (``BENCH_kernels.json`` from the module main)."""
    rng = np.random.default_rng(0)
    rows = []
    a = jnp.asarray(rng.integers(0, 1000, (512, 3)), jnp.int32)
    us = _time(sj_ops.count_similar_pairs, a, a, 2, True)
    rows.append(("kernel/simjoin_512x512x3", us, 512 * 512))

    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
    us = _time(flash_ops.flash_attention, q, k, k, causal=True)
    rows.append(("kernel/flash_256_gqa2", us,
                 2 * 256 * 256 * 4 * 64 * 2))

    kp = jnp.asarray(rng.normal(size=(64, 16, 4, 64)), jnp.bfloat16)
    qd = jnp.asarray(rng.normal(size=(4, 8, 64)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(64)[:4 * 4].reshape(4, 4), jnp.int32)
    lens = jnp.full((4,), 64, jnp.int32)
    us = _time(paged_decode_attention, qd, kp, kp, table, lens)
    rows.append(("kernel/paged_decode_4x4pages", us, 4 * 4 * 16))

    if print_rows:
        for name, us, derived in rows:
            print(f"{name},{us:.0f},{derived}")
    pruning = run_simjoin_pruning(print_rows=print_rows)
    geo = run_geo_workload_pruning(print_rows=print_rows)
    artifacts = run_artifact_amortization(print_rows=print_rows)
    if out_json:
        payload = {
            "benchmark": "bench_kernels",
            "platform": jax.default_backend(),
            "rows": [{"name": n_, "us_per_call": u, "derived": d}
                     for n_, u, d in rows],
            "simjoin_pruning": pruning,
            "geo_workload_pruning": geo,
            "artifact_amortization": artifacts,
        }
        with open(out_json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        if print_rows:
            print(f"wrote {out_json}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(out_json="BENCH_kernels.json")
