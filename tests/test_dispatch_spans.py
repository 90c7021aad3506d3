"""Spans and counters inside the mesh backend's dispatch, the device
residency sync and the raw reads.

A small ``jax_mesh`` cluster over raw files read from disk runs a few
queries with telemetry on. Each query's dispatch is split into
``dispatch.lookup`` / ``dispatch.put`` / ``dispatch.launch`` /
``dispatch.wait`` / ``dispatch.readback`` spans; the policy round's device reconcile is a
``device.sync`` span, task gathering a ``gather`` span and each
first-touch file read a ``scan.read`` span. The per-query counters
(``dispatch_launches``, ``dispatch_put_bytes``, ``device_sync_bytes``,
``scan_read_bytes``) are the same with telemetry off.
"""
import glob
import os
import tempfile

import pytest

jax = pytest.importorskip("jax")

from repro.arrayio.catalog import FileReader, build_catalog
from repro.arrayio.generator import make_ptf_files
from repro.core.cluster import QUERY_COUNTERS, RawArrayCluster
from repro.core.workload import ptf2_workload
from repro.obs import NULL_TRACER, Tracer, last_recording

N_NODES = 4
NODE_BUDGET = 6_000
SUBSPANS = ("dispatch.lookup", "dispatch.put", "dispatch.launch",
            "dispatch.wait", "dispatch.readback")


@pytest.fixture(scope="module")
def catalog():
    files = make_ptf_files(n_files=8, cells_per_file_mean=900, seed=21)
    cat, _ = build_catalog(files, tempfile.mkdtemp(prefix="dspans_"),
                           "hdf5", n_nodes=N_NODES, in_memory=False)
    return cat


def queries_of(catalog):
    # PTF-2's four alternating boxes, twice: the second pass repeats
    # the first four queries over files already read.
    qs = ptf2_workload(catalog.domain, n_queries=4, eps=300)
    return qs + qs


def make(catalog, telemetry):
    return RawArrayCluster(catalog, FileReader(catalog), N_NODES,
                           NODE_BUDGET, policy="cost", min_cells=64,
                           backend="jax_mesh", prune="block",
                           telemetry=telemetry)


@pytest.fixture(scope="module")
def traced(catalog):
    cl = make(catalog, "on")
    executed = [cl.run_query(q) for q in queries_of(catalog)]
    return cl, executed


def query_spans(tracer):
    """Each root ``query`` span with every span under it."""
    by_id = {s.span_id: s for s in tracer.spans}

    def root(s):
        while s.parent_id is not None:
            s = by_id[s.parent_id]
        return s

    out = {}
    for s in tracer.spans:
        out.setdefault(root(s).span_id, []).append(s)
    return [(by_id[k], v) for k, v in sorted(out.items())
            if by_id[k].name == "query"]


def test_dispatch_subspans_nest_under_dispatch(traced):
    cl, executed = traced
    spans = cl.telemetry.tracer.spans
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.name in SUBSPANS:
            assert by_id[s.parent_id].name == "dispatch", s.name
    names = {s.name for s in spans}
    assert set(SUBSPANS) <= names
    assert sum(e.dispatch_launches for e in executed) > 0


def test_launch_spans_count_dispatch_launches(traced):
    cl, executed = traced
    groups = query_spans(cl.telemetry.tracer)
    assert len(groups) == len(executed)
    for (_, spans), e in zip(groups, executed):
        per = {n: sum(1 for s in spans if s.name == n)
               for n in ("dispatch.lookup", "dispatch.launch",
                         "dispatch.wait", "dispatch.readback")}
        assert set(per.values()) == {e.dispatch_launches}
        puts = sum(1 for s in spans if s.name == "dispatch.put")
        assert (puts == 0) == (e.dispatch_put_bytes == 0)


def test_put_bytes_count_pinned_misses(catalog, traced):
    cl, executed = traced
    puts = sum(1 for s in cl.telemetry.tracer.spans
               if s.name == "dispatch.put")
    assert puts == cl.backend.device_stats["pinned_batch_misses"] > 0
    assert sum(e.dispatch_put_bytes for e in executed) > 0
    # The same query at once again re-dispatches its pinned batches:
    # kernel launches, but nothing placed.
    q = next(q for q, e in zip(queries_of(catalog), executed)
             if e.dispatch_launches)
    fresh = make(catalog, "off")
    first, again = fresh.run_query(q), fresh.run_query(q)
    assert first.dispatch_put_bytes > 0
    assert again.dispatch_launches == first.dispatch_launches > 0
    assert again.dispatch_put_bytes == 0


def test_sync_and_gather_nest_under_query(traced):
    cl, executed = traced
    by_id = {s.span_id: s for s in cl.telemetry.tracer.spans}
    for name in ("device.sync", "gather"):
        found = [s for s in by_id.values() if s.name == name]
        assert len(found) == len(executed), name
        assert all(by_id[s.parent_id].name == "query" for s in found)


def test_device_sync_bytes_sum_to_reconcile_totals(traced):
    cl, executed = traced
    d = cl.backend.device_stats
    assert sum(e.device_sync_bytes for e in executed) == (
        d["committed_bytes_materialized"] + d["committed_bytes_moved"]
        + d["replica_bytes_copied"])
    assert d["committed_bytes_materialized"] > 0


def test_scan_read_fires_on_first_touch_only(catalog, traced):
    cl, executed = traced
    groups = query_spans(cl.telemetry.tracer)
    seen = set()
    for (_, spans), e in zip(groups, executed):
        reads = [s for s in spans if s.name == "scan.read"]
        files = [s.args["file"] for s in reads]
        assert not seen & set(files)          # never the same file twice
        seen.update(files)
        assert e.scan_read_bytes == sum(
            catalog.by_id(f).file_bytes for f in files)
    assert seen and sum(e.scan_read_bytes for e in executed[:4]) > 0
    assert all(e.scan_read_bytes == 0 for e in executed[4:])
    assert cl.reader.bytes_read == sum(catalog.by_id(f).file_bytes
                                       for f in seen)


def test_query_span_carries_index_and_counters(traced):
    cl, executed = traced
    groups = query_spans(cl.telemetry.tracer)
    for (q, _), e in zip(groups, executed):
        assert q.args["index"] == e.report.query_index
        for key in QUERY_COUNTERS:
            assert q.args[key] == getattr(e, key)


def test_telemetry_off_records_nothing_and_counts_the_same(catalog,
                                                          traced):
    _, on = traced
    cl = make(catalog, "off")
    off = [cl.run_query(q) for q in queries_of(catalog)]
    assert cl.telemetry.tracer is NULL_TRACER and not NULL_TRACER.spans
    assert [e.matches for e in off] == [e.matches for e in on]
    for key in QUERY_COUNTERS:
        assert [getattr(e, key) for e in off] == \
            [getattr(e, key) for e in on], key


def test_tracer_mirror_enters_and_exits_with_spans():
    calls = []

    class Mirror:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            calls.append(("enter", self.name))

        def __exit__(self, *exc):
            calls.append(("exit", self.name))

    tr = Tracer()
    tr.mirror = Mirror
    outer = tr.begin("dispatch")
    with tr.span("dispatch.launch"):
        pass
    tr.begin("dispatch.wait")
    tr.end(outer)                 # closes the open child first
    assert calls == [("enter", "dispatch"), ("enter", "dispatch.launch"),
                     ("exit", "dispatch.launch"), ("enter", "dispatch.wait"),
                     ("exit", "dispatch.wait"), ("exit", "dispatch")]


def test_spans_mirror_into_the_profiler_trace(catalog, tmp_path):
    cl = make(catalog, "on")
    assert last_recording() is cl.telemetry
    jax.profiler.start_trace(str(tmp_path))
    try:
        for q in queries_of(catalog)[:2]:
            cl.run_query(q)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name] = host.get(ev.name, 0) + 1
    spans = {}
    for s in cl.telemetry.tracer.spans:
        spans[s.name] = spans.get(s.name, 0) + 1
    for name, n in spans.items():
        assert host.get(name) == n, name
