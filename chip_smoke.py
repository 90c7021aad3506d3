#!/usr/bin/env python3
"""Chip smoke test: the served path, end to end, on a TPU.

Generates GEO-like (2-D, ``fits``) and PTF-like (3-D, ``hdf5``) raw-file
datasets from ``--seed`` into a temporary directory and runs each
paper workload through ``RawArrayCluster(backend="jax_mesh",
policy="cost")`` with 8 nodes: coordinator -> mesh backend -> compiled
Pallas simjoin kernel. It checks that the kernel is compiled (not
interpreted), that no query degraded, that a warm repeat compiles
nothing new, that cached chunks sit on their node's device, and that
the match counts of the densest queries equal a brute-force numpy count
over the same cells.

  python chip_smoke.py             # one chip: GEO, then PTF
  python chip_smoke.py --chips 4   # GEO only, 8 nodes over four chips

Without a TPU it exits non-zero before any phase runs. The last line of
standard output is ``{"ok": true, "device": {...}}``; earlier lines
report the scale, the interpret flag, the compile count, wall times,
``dispatch_s``, the kernel launches and the mesh backend's
``device_stats``. Wall
times include compilation on a cold run; they are not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_NODES = 8
#: Queries per phase whose match counts are checked against numpy.
N_CHECKED = 2
#: Rows per brute-force slab of the reference count.
SLAB = 2048
#: Threads counting reference slabs (each holds a few hundred MB).
REF_THREADS = min(8, os.cpu_count() or 1)

# Scale, cut from the paper's (§4.1) to what one run's time limit holds:
# the host-side prune prep and the per-bucket kernel compiles grow with
# the chunk pairs per query. Shapes, eps and policy are the paper's.
GEO = dict(n_files=16, n_seeds=125_000, clones_per_seed=40)
GEO_EPS = 500
PTF = dict(n_files=32, cells_per_file_mean=32_000)
PTF_EPS = 300


def fail(msg: str) -> None:
    """Report a failed check on stderr and exit non-zero."""
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices; exits non-zero without them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: jax.devices()[0].platform is "
             f"{devices[0].platform!r}")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, jax has "
             f"{len(devices)}")
    return devices[:chips]


def brute_force_count(cells: np.ndarray, eps: int) -> int:
    """Unordered cell pairs within L1 ``eps``, by numpy brute force and
    independent of the planner: cells sorted on one coordinate are cut
    into slabs, and each slab is compared with every later cell whose
    coordinate is within ``eps`` of the slab's last one (no pair farther
    apart in one coordinate can match). The coordinate is the one that
    leaves the fewest cells to compare."""
    from repro.backend.executors import count_similar_pairs_np
    starts = np.arange(0, len(cells), SLAB)
    last = np.minimum(starts + SLAB, len(cells)) - 1
    best = None
    for k in range(cells.shape[1]):
        pts = cells[np.argsort(cells[:, k], kind="stable")]
        ends = np.searchsorted(pts[:, k], pts[last, k] + eps, side="right")
        if best is None or (ends - starts).sum() < (best[1] - starts).sum():
            best = (pts, ends)
    pts, ends = best

    def slab(i0: int, j1: int) -> int:
        # Both sides start at i0, so the local i < j mask of the
        # self-join is the global one.
        return count_similar_pairs_np(pts[i0:i0 + SLAB], pts[i0:j1], eps,
                                      same=True)
    # numpy drops the GIL inside its array loops, so slabs overlap.
    with ThreadPoolExecutor(REF_THREADS) as pool:
        return sum(pool.map(slab, starts, ends))


def cells_in_box(files, box) -> np.ndarray:
    """Every generated cell inside ``box`` (bounds inclusive)."""
    lo, hi = np.asarray(box.lo), np.asarray(box.hi)
    parts = [f.coords[np.all((f.coords >= lo) & (f.coords <= hi), axis=1)]
             for f in files]
    return np.concatenate(parts)


def run_phase(name: str, files, fmt: str, queries_for, eps: int, devices,
              root: str, warm: bool, chips: int) -> None:
    """Write ``files`` as ``fmt`` raw files, run the workload through the
    served path, and check it; prints one line per measurement."""
    from repro.arrayio import FileReader, build_catalog
    from repro.core import RawArrayCluster
    from repro.kernels.simjoin import ops

    t0 = time.perf_counter()
    catalog, _ = build_catalog(files, f"{root}/{name}", fmt, N_NODES,
                               in_memory=False)
    raw_bytes = sum(f.n_cells * f.cell_bytes for f in catalog.files)
    file_bytes = sum(f.file_bytes for f in catalog.files)
    print(f"{name}: wrote {len(catalog.files)} {fmt} files, "
          f"{sum(f.n_cells for f in catalog.files)} cells, "
          f"{file_bytes} B on disk, {raw_bytes} B raw, "
          f"in {time.perf_counter() - t0:.3f} s")
    queries = queries_for(catalog.domain, eps)
    cluster = RawArrayCluster(
        catalog, FileReader(catalog), N_NODES, raw_bytes // 4 // N_NODES,
        policy="cost", backend="jax_mesh", devices=devices)
    interpret = cluster.executor.interpret
    print(f"{name}: {N_NODES} nodes over {len(devices)} "
          f"{devices[0].device_kind} device(s), cache budget a quarter "
          f"of the raw bytes, interpret={interpret}")
    if interpret:
        fail(f"{name}: the simjoin kernel would run in interpret mode")

    traced = sum(ops.TRACE_COUNTS.values())
    t0 = time.perf_counter()
    cold = cluster.run_workload(queries)
    cold_s = time.perf_counter() - t0
    compiles = sum(ops.TRACE_COUNTS.values()) - traced
    check_executed(name, cold)
    print(f"{name}: cold pass {len(queries)} queries in {cold_s:.3f} s, "
          f"{compiles} compiles ({dict(ops.TRACE_COUNTS)}), "
          f"prep_s={sum_of(cold, 'prep_s'):.3f}, "
          f"dispatch_s={sum_of(cold, 'dispatch_s'):.3f}, "
          f"dispatch_launches={sum_of(cold, 'dispatch_launches')}, "
          f"measured_ship_bytes={sum_of(cold, 'measured_ship_bytes')}, "
          f"matches={[e.matches for e in cold]}")
    if warm:
        traced = sum(ops.TRACE_COUNTS.values())
        t0 = time.perf_counter()
        again = cluster.run_workload(queries)
        warm_s = time.perf_counter() - t0
        new = sum(ops.TRACE_COUNTS.values()) - traced
        check_executed(name, again)
        print(f"{name}: warm repeat in {warm_s:.3f} s, {new} new compiles, "
              f"prep_s={sum_of(again, 'prep_s'):.3f}, "
              f"dispatch_s={sum_of(again, 'dispatch_s'):.3f}, "
              f"dispatch_launches={sum_of(again, 'dispatch_launches')}")
        if new:
            fail(f"{name}: the warm repeat compiled {new} kernels")
        if [e.matches for e in again] != [e.matches for e in cold]:
            fail(f"{name}: the warm repeat changed match counts")
    print(f"{name}: device_stats={cluster.backend.device_stats}")
    check_placement(name, cluster, cold, chips)

    t0 = time.perf_counter()
    # The densest distinct query boxes (the workloads revisit boxes).
    first = {}
    for i, q in enumerate(queries):
        first.setdefault(q.box, i)
    densest = sorted(first.values(),
                     key=lambda i: -cold[i].report.queried_cells)
    for i in sorted(densest[:N_CHECKED]):
        q = queries[i]
        cells = cells_in_box(files, q.box)
        want = brute_force_count(cells, q.eps)
        print(f"{name}: query {i} ({len(cells)} cells) matches "
              f"{cold[i].matches}, numpy reference {want}")
        if cold[i].matches != want:
            fail(f"{name}: query {i} counted {cold[i].matches} matches, "
                 f"the numpy reference {want}")
    print(f"{name}: reference check in {time.perf_counter() - t0:.3f} s")


def sum_of(executed, field: str):
    """Sum of one measured ExecutedQuery field over a pass."""
    return sum(getattr(e, field) or 0 for e in executed)


def check_executed(name: str, executed) -> None:
    """Every query completed: a match count, nothing degraded."""
    for i, e in enumerate(executed):
        if e.matches is None:
            fail(f"{name}: query {i} returned no match count")
        if e.degraded is not None:
            fail(f"{name}: query {i} degraded: {e.degraded}")


def check_placement(name: str, cluster, executed, chips: int) -> None:
    """Every cached chunk's primary buffer sits on its node's device.
    Across several chips, the buffers must span more than one device,
    join batches must run on every chip, and ship decisions must move
    real bytes between devices. (The cost policy co-locates each query's
    chunks on one node, so the primaries of a GEO pass sit on as many
    devices as the nodes it picked, not necessarily on all of them.)"""
    backend = cluster.backend
    primary = cluster.coordinator.cache.primary_map()
    for cid, node in primary.items():
        if backend.buffer_device(cid) != backend.device_for_node(node):
            fail(f"{name}: chunk {cid} is not on node {node}'s device")
    held = {backend.buffer_device(cid) for cid in primary}
    ran = {backend.device_for_node(node) for e in executed
           if e.report.join_plan is not None
           for node in e.report.join_plan.pair_node.values()}
    print(f"{name}: {len(primary)} cached chunks on nodes "
          f"{sorted(set(primary.values()))}, {len(held)} distinct "
          f"device(s); join batches ran on {len(ran)} device(s)")
    if chips > 1:
        if len(held) < 2:
            fail(f"{name}: every primary buffer is on one device")
        if len(ran) != chips:
            fail(f"{name}: join batches ran on {len(ran)} devices, "
                 f"not {chips}")
        if backend.device_stats["ship_bytes_measured"] <= 0:
            fail(f"{name}: no bytes shipped between devices")


def main(argv=None) -> int:
    """Run the phases for ``--chips`` and print the result line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated datasets")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the GEO phase only, 8 nodes over four chips")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    import jax
    from repro.arrayio import make_geo_files, make_ptf_files
    from repro.compile_cache import enable_compile_cache
    from repro.core.workload import geo_workload, ptf2_workload

    t_start = time.perf_counter()
    print(f"compile cache: {enable_compile_cache()}")
    print(f"scale: GEO {GEO}, eps {GEO_EPS}, cut from 256 files and "
          f"250,000 seeds (about 10.2M cells; the paper has 8,000 files); "
          f"PTF {PTF}, eps {PTF_EPS}, cut from a few million cells (the "
          f"paper has 262 GB of nightly files)")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        t0 = time.perf_counter()
        geo = make_geo_files(**GEO, seed=args.seed)
        print(f"geo: generated in {time.perf_counter() - t0:.3f} s")
        run_phase("geo", geo, "fits",
                  lambda dom, eps: geo_workload(dom, eps=eps), GEO_EPS,
                  devices, root, warm=args.chips == 1, chips=args.chips)
        del geo
        if args.chips == 1:
            t0 = time.perf_counter()
            ptf = make_ptf_files(**PTF, seed=args.seed)
            print(f"ptf: generated in {time.perf_counter() - t0:.3f} s")
            run_phase("ptf", ptf, "hdf5",
                      lambda dom, eps: ptf2_workload(dom, eps=eps),
                      PTF_EPS, devices, root, warm=True, chips=1)
    print(f"total wall {time.perf_counter() - t_start:.3f} s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
