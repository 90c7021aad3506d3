"""One run of one benchmark cell: set-up, a closed-loop window, the
check against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric
lives in files of its own and is found by name:

- ``bench/configs/<config>.json``: the deployment (data sizes, format,
  eps, nodes, cache budget) and the ``generator`` of its data;
- ``bench/datasets/<generator>.py``: ``generate(config, seed)`` returns
  the raw files' cells and the anchors the traffic may centre on;
- ``bench/traffic/<mix>.json``: the mix's parameters and the
  ``generator`` that reads them;
- ``bench/generators/<generator>.py``: ``build(mix, config, seed,
  anchors)`` returns the warm-up boxes and the stream as a list of
  passes over the mix (a mix without passes gives one box a pass);
- ``bench/metrics/<metric>.py``: ``read(run)`` returns the metric's
  value from the run's records, counters and trace, or ``None`` when
  the run holds nothing to read.

The system under test is ``RawArrayCluster(backend="jax_mesh",
policy="cost")`` driven through ``run_query`` by one client that sends
each query when the previous answer has returned.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from bench import reference, tracing

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: Regular expression naming the simjoin kernels' device events.
KERNEL = r"simjoin"


class BenchError(RuntimeError):
    """A run that cannot be measured: no result is printed."""


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    cell: dict
    config: dict
    setup_s: float
    window_s: float
    records: List[dict]
    compiles: int
    peak: dict
    trace: Optional[dict] = None


def load_cell(name: str) -> tuple:
    """``(benchmark, cell, config, mix)`` of the workload ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = json.loads((ROOT / files[cell["config"]]).read_text())
    mix = json.loads((BENCH / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, mix


def load_module(directory: str, name: str):
    """``bench/<directory>/<name>.py`` as a module."""
    path = BENCH / directory / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_devices(chips: int, platform: str = "tpu") -> list:
    """The first ``chips`` devices; fails without that many of
    ``platform``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchError(f"no {platform.upper()} found: jax.devices()[0] "
                         f"is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, jax has "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (a fixed path: it is part of the cache's key), handed to the program
    through ``JAX_COMPILATION_CACHE_DIR``."""
    path = ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    from repro.compile_cache import enable_compile_cache as enable
    return enable()


def build_cluster(config: dict, files, devices, workdir: str,
                  telemetry: str):
    """Write the files in the configuration's format through the
    program's catalog and stand the cluster up over them."""
    from repro.arrayio import FileReader, build_catalog
    from repro.arrayio.generator import GeneratedFile
    from repro.core import RawArrayCluster
    from repro.core.geometry import Box
    generated = [GeneratedFile(c, a, Box(tuple(map(int, c.min(axis=0))),
                                         tuple(map(int, c.max(axis=0)))))
                 for c, a in files]
    catalog, _ = build_catalog(generated, workdir, config["format"],
                               config["n_nodes"], in_memory=False)
    raw = sum(f.n_cells * f.cell_bytes for f in catalog.files)
    budget = int(raw * config["budget_frac"]) // config["n_nodes"]
    cluster = RawArrayCluster(
        catalog, FileReader(catalog), config["n_nodes"], budget,
        policy=config["policy"], backend="jax_mesh", devices=devices,
        prune=config["prune"],
        telemetry=telemetry)
    return cluster


def queries_of(boxes, eps: int) -> list:
    """Program queries for ``(lo, hi)`` boxes."""
    from repro.core.coordinator import SimilarityJoinQuery
    from repro.core.geometry import Box
    return [SimilarityJoinQuery(box=Box(lo, hi), eps=eps) for lo, hi in boxes]


def record(box, latency_s: float, e) -> dict:
    """One served query as the metric readers see it."""
    r = e.report
    return {
        "box": box, "latency_s": latency_s, "matches": e.matches,
        "failed": e.matches is None or e.degraded is not None,
        "queried_cells": r.queried_cells,
        "opt_s": r.opt_time_chunking_s + r.opt_time_evict_place_s,
        "prep_s": e.prep_s, "dispatch_s": e.dispatch_s,
        "net_s": e.measured_net_s, "ship_bytes": e.measured_ship_bytes,
        "artifact_hits": e.artifact_hits,
        "artifact_misses": e.artifact_misses,
        "files_scanned": len(r.files_scanned), "evicted": r.evicted_items,
    }


def serve(cluster, passes, eps: int, seconds: float) -> tuple:
    """The closed loop: each query is sent when the previous answer has
    returned, pass after pass, until the end of the first pass that ends
    after ``seconds``, so that each box of a pass weighs the same in the
    window. Returns the records and the window's length."""
    out = []
    start = time.perf_counter()
    deadline = start + seconds
    for boxes in passes:
        for q, box in zip(queries_of(boxes, eps), boxes):
            t0 = time.perf_counter()
            e = cluster.run_query(q)
            t1 = time.perf_counter()
            out.append(record(box, t1 - t0, e))
        if t1 >= deadline:
            return out, t1 - start
    raise BenchError(f"the stream ran out after {len(out)} queries; "
                     f"raise the mix's cycles")


def warm(cluster, queries) -> None:
    """Serve the warm-up queries; each has to complete. One line each on
    standard error: cells, seconds, host prep, dispatch, artifact
    misses."""
    for k, q in enumerate(queries):
        t0 = time.perf_counter()
        e = cluster.run_query(q)
        if e.matches is None or e.degraded is not None:
            raise BenchError(f"warm-up query {q.box} failed: {e.degraded}")
        print(f"warm-up {k}: {e.report.queried_cells} cells "
              f"{time.perf_counter() - t0:.3f} s, prep {e.prep_s or 0:.3f}, "
              f"dispatch {e.dispatch_s or 0:.3f}, misses "
              f"{e.artifact_misses or 0}", file=sys.stderr, flush=True)


def span_totals(cluster, since: float) -> Dict[str, float]:
    """Seconds per ``obs`` span name, over the closed spans that began
    after the host clock read ``since``, largest first."""
    out: Dict[str, float] = {}
    for sp in cluster.telemetry.tracer.spans:
        if sp.end is not None and sp.start >= since:
            out[sp.name] = out.get(sp.name, 0.0) + sp.end - sp.start
    return {k: round(v, 3) for k, v in sorted(out.items(),
                                              key=lambda kv: -kv[1])}


def peak_memory(devices) -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def check(records: Sequence[dict], files, eps: int) -> Dict[str, dict]:
    """Every answer of the window against the plain reference over the
    generated cells of its box: the numbers compared, each with its
    limit. An answer that never came (a failed query) is a wrong
    answer."""
    want = {box: reference.pair_count(reference.cells_in_box(files, box),
                                      eps)
            for box in {r["box"] for r in records}}
    gaps = [abs(r["matches"] - want[r["box"]]) for r in records
            if not r["failed"]]
    wrong = sum(1 for g in gaps if g) + len(records) - len(gaps)
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "largest_gap": {"value": max(gaps, default=0), "limit": 0}}


def is_correct(checks: Dict[str, dict]) -> bool:
    """Correct when no number compared passes its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def load_reader(name: str) -> Callable[[Run], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    return load_module("metrics", name).read


def metrics(bench: dict, cell: dict, run: Run, traced: bool) -> dict:
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced), each read by its own reader; a reader that finds nothing
    leaves its metric out."""
    out = {}
    for m in bench["per_layer" if traced else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks_of(kind: str) -> dict:
    """The published peaks of ``kind``; a device missing from the table
    is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float, platform: str = "tpu",
             config_override: Optional[dict] = None,
             answer: Optional[Callable[[np.ndarray, int], int]] = None
             ) -> dict:
    """One run of ``workload``; returns the result line's object.

    ``platform`` is the device platform required and
    ``config_override`` changes the configuration's sizes (the tests run
    on the CPU at a small size). ``answer``
    puts a count in the served path's place once the window has closed
    (the control: the window's answers are replaced by ``answer`` over
    the generated cells of each box before the check)."""
    bench, cell, config, mix = load_cell(workload)
    config = {**config, **(config_override or {})}
    log = sys.stderr
    devices = require_devices(cell["chips"], platform)
    import jax
    from repro.kernels.simjoin import ops
    peak = peaks_of(devices[0].device_kind) if platform == "tpu" else {}
    dev = devices[0]
    cache = enable_compile_cache() if platform == "tpu" else "off"
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache}", file=log)
    t0 = time.perf_counter()
    files, anchors = load_module("datasets",
                                 config["generator"]).generate(config, seed)
    t_gen = time.perf_counter() - t0
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        t0 = time.perf_counter()
        cluster = build_cluster(config, files, devices,
                                os.path.join(tmp, "data"),
                                "on" if traced else "off")
        t_catalog = time.perf_counter() - t0
        if platform == "tpu" and cluster.executor.interpret:
            raise BenchError("the simjoin kernel would run in interpret "
                             "mode")
        warm_boxes, stream = load_module(
            "generators", mix["generator"]).build(mix, config, seed, anchors)
        eps = int(config["eps"])
        t0 = time.perf_counter()
        warm(cluster, queries_of(warm_boxes, eps))
        t_warm = time.perf_counter() - t0
        compiles0 = sum(ops.TRACE_COUNTS.values())
        setup_s = time.perf_counter() - t_start
        print(f"setup {setup_s:.3f} s: process start and imports "
              f"{setup_s - t_gen - t_catalog - t_warm:.3f}, data "
              f"{t_gen:.3f}, raw files and cluster {t_catalog:.3f}, warm-up "
              f"{t_warm:.3f} ({len(warm_boxes)} queries, {compiles0} "
              f"kernel traces)", file=log)
        trace = None
        if traced:
            print(f"warm-up spans: {span_totals(cluster, t_start)}",
                  file=log)
            tdir = os.path.join(tmp, "trace")
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                enter = time.perf_counter()
                records, window_s = serve(cluster, stream, eps, seconds)
            jax.profiler.stop_trace()
            print(f"window spans: {span_totals(cluster, enter)}", file=log)
            planes = tracing.flatten(tdir)
            start_ns, _ = tracing.window_ns(planes)
            spans = tracing.spans_on_profile_clock(
                cluster.telemetry.tracer.spans, enter, start_ns)
            trace = tracing.reduce(planes, KERNEL, spans,
                                   devices=len(devices))
        else:
            records, window_s = serve(cluster, stream, eps, seconds)
        compiles = sum(ops.TRACE_COUNTS.values()) - compiles0
        mem = peak_memory(devices)
        del cluster
    lat = sorted(r["latency_s"] for r in records)
    each = " ".join(f"{r['queried_cells']}:{r['latency_s'] * 1e3:.1f}"
                    for r in records)
    print(f"window {window_s:.3f} s, {len(records)} queries, latency "
          f"min/median/max {lat[0]:.4f}/{lat[len(lat) // 2]:.4f}/"
          f"{lat[-1]:.4f} s, queries (cells:ms) {each}, prep {sum(r['prep_s'] or 0 for r in records):.3f}"
          f" s, dispatch {sum(r['dispatch_s'] or 0 for r in records):.3f} s, "
          f"artifact misses {sum(r['artifact_misses'] or 0 for r in records)}"
          f", files scanned {sum(r['files_scanned'] for r in records)}, "
          f"evictions {sum(r['evicted'] for r in records)}, {compiles} "
          f"kernel traces", file=log)
    if answer is not None:
        put = {box: answer(reference.cells_in_box(files, box), eps)
               for box in {r["box"] for r in records}}
        for r in records:
            r["matches"] = put[r["box"]]
    checks = check(records, files, eps)
    run = Run(cell=cell, config=config, setup_s=setup_s, window_s=window_s,
              records=records, compiles=compiles, peak=peak, trace=trace)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": is_correct(checks), "attempted": len(records),
              "failed": sum(1 for r in records if r["failed"]),
              "metrics": metrics(bench, cell, run, traced),
              "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    return result
