"""The readers of the program's spans and per-query counters, on a real
run of a small ``jax_mesh`` cluster (CPU, interpret mode), and on a
program that keeps no recording to read."""
import json
import tempfile

import pytest

from bench import harness, program_spans

COUNTERS = {"dispatch.launches": ("dispatch_launches", 1.0),
            "dispatch.put_mb": ("dispatch_put_bytes", 1e-6),
            "device.sync_mb": ("device_sync_bytes", 1e-6),
            "scan.read_mb": ("scan_read_bytes", 1e-6)}
SPANS = {"dispatch.lookup_ms": "dispatch.lookup",
         "dispatch.put_ms": "dispatch.put",
         "dispatch.launch_ms": "dispatch.launch",
         "dispatch.wait_ms": "dispatch.wait",
         "dispatch.readback_ms": "dispatch.readback",
         "device.sync_ms": "device.sync", "scan.read_ms": "scan.read"}


def new_metrics():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["per_layer"]}
    assert set(COUNTERS) | set(SPANS) <= names
    return sorted(set(COUNTERS) | set(SPANS))


@pytest.fixture(scope="module")
def served():
    """A warm-up of two queries, then a window of four (the last of
    them a repeat, which reads no raw file)."""
    from repro.arrayio import FileReader, build_catalog
    from repro.arrayio.generator import make_ptf_files
    from repro.core import RawArrayCluster
    from repro.core.workload import ptf2_workload
    files = make_ptf_files(n_files=8, cells_per_file_mean=900, seed=21)
    catalog, _ = build_catalog(files, tempfile.mkdtemp(prefix="sreaders_"),
                               "hdf5", n_nodes=4, in_memory=False)
    cluster = RawArrayCluster(catalog, FileReader(catalog), 4, 6_000,
                              min_cells=64, backend="jax_mesh",
                              prune="block", telemetry="on")
    qs = ptf2_workload(catalog.domain, n_queries=4, eps=300)
    for q in qs[:2]:
        cluster.run_query(q)
    enter = cluster.telemetry.tracer.clock.now()
    window = [cluster.run_query(q) for q in qs[2:] + qs[:2]]
    records = [harness.record((q.box.lo, q.box.hi), 0.1, e)
               for q, e in zip(qs[2:] + qs[:2], window)]
    run = harness.Run(cell={"name": "ptf.ptf2"}, config={}, setup_s=1.0,
                      window_s=1.0, records=records, compiles=0, peak={})
    spans = [s for s in cluster.telemetry.tracer.spans if s.start >= enter]
    return run, window, spans


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_counter_readers(served, name):
    run, window, _ = served
    key, scale = COUNTERS[name]
    want = sum(getattr(e, key) for e in window) / len(window) * scale
    assert harness.load_reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_readers(served, name):
    run, _, spans = served
    want = sum(s.duration_s for s in spans
               if s.name == SPANS[name]) / len(run.records) * 1e3
    assert harness.load_reader(name)(run) == pytest.approx(want)


def test_window_is_the_last_queries(served):
    run, window, spans = served
    assert program_spans.window(run) == [s for s in spans
                                         if s.end is not None]
    assert harness.load_reader("dispatch.launches")(run) > 0
    assert window[-2].scan_read_bytes == window[-1].scan_read_bytes == 0


def test_readers_without_the_program_facility(served, monkeypatch):
    """A program that keeps no recording (an older one) leaves every
    new metric out instead of failing."""
    import repro.obs.telemetry
    run, _, _ = served
    monkeypatch.delattr(repro.obs.telemetry, "last_recording")
    for name in new_metrics():
        assert harness.load_reader(name)(run) is None, name
