"""Spans and counters inside the mesh backend's dispatch, the device
residency sync and the raw reads.

A small ``jax_mesh`` cluster over raw files read from disk runs a few
queries with telemetry on. Each query's dispatch is split into one
``dispatch.lookup`` and one ``dispatch.launch`` span per batch, and one
``dispatch.put`` (misses only), ``dispatch.wait`` and
``dispatch.readback`` span per query; the policy round's device
reconcile is a ``device.sync`` span, task gathering a ``gather`` span
and each first-touch file read a ``scan.read`` span. The per-query
counters (``dispatch_launches``, ``dispatch_pinned_hits``,
``dispatch_put_bytes``,
``dispatch_host_syncs``, ``device_sync_bytes``, ``scan_read_bytes``,
``dispatch_devices``, ``dispatch_max_device_launches``,
``measured_ship_bytes``, ``ship_transfers``) are the same with
telemetry off; ``dispatch_backend_compiles`` counts the executables
the process built. On four devices each cross-device ship route is a
``ship.transfer`` span and each staged payload a ``ship.stage`` span.
"""
import glob
import os
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.arrayio.catalog import FileReader, build_catalog
from repro.arrayio.generator import make_ptf_files
from repro.backend.jax_mesh import (PINNED_BYTES_UNREPORTED,
                                    JaxMeshBackend, pinned_budget)
from repro.core.cluster import (QUERY_COUNTERS, RawArrayCluster,
                                count_similar_pairs_np)
from repro.core.coordinator import SimilarityJoinQuery
from repro.core.geometry import points_in_box
from repro.core.workload import ptf2_workload
from repro.obs import NULL_TRACER, Tracer, last_recording

N_NODES = 4
NODE_BUDGET = 6_000
SUBSPANS = ("dispatch.lookup", "dispatch.put", "dispatch.launch",
            "dispatch.wait", "dispatch.readback")


@pytest.fixture(scope="module")
def files():
    return make_ptf_files(n_files=8, cells_per_file_mean=900, seed=21)


@pytest.fixture(scope="module")
def catalog(files):
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


def traced_run(catalog):
    cl = make(catalog, "on")
    executed = [cl.run_query(q) for q in queries_of(catalog)]
    return cl, executed


@pytest.fixture(scope="module")
def traced(catalog):
    return traced_run(catalog)


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
    assert any(e.dispatch_launches > 1 for e in executed)
    for (_, spans), e in zip(groups, executed):
        per = {n: sum(1 for s in spans if s.name == n) for n in SUBSPANS}
        # Per batch: a lookup and a launch. Per query: one put when
        # anything was placed, one wait and one readback when anything
        # was launched.
        assert per["dispatch.lookup"] == per["dispatch.launch"] \
            == e.dispatch_launches
        assert per["dispatch.put"] == int(e.dispatch_put_bytes > 0)
        assert per["dispatch.wait"] == per["dispatch.readback"] \
            == int(e.dispatch_launches > 0)


def test_put_bytes_count_pinned_misses(catalog, traced):
    cl, executed = traced
    puts = sum(1 for s in cl.telemetry.tracer.spans
               if s.name == "dispatch.put")
    assert puts == sum(1 for e in executed if e.dispatch_put_bytes) > 0
    assert cl.backend.device_stats["pinned_batch_misses"] > puts
    # The same query at once again re-dispatches its pinned batches:
    # kernel launches, but nothing placed.
    q = next(q for q, e in zip(queries_of(catalog), executed)
             if e.dispatch_launches)
    fresh = make(catalog, "off")
    first, again = fresh.run_query(q), fresh.run_query(q)
    assert first.dispatch_put_bytes > 0
    assert again.dispatch_launches == first.dispatch_launches > 0
    assert again.dispatch_put_bytes == 0


@pytest.mark.parametrize("telemetry", ["on", "off"])
def test_repeat_query_hits_every_pinned_batch(catalog, traced, telemetry):
    # A fresh cluster has nothing pinned; the same query at once again
    # finds every batch among the stacks its first run pinned.
    _, executed = traced
    q = next(q for q, e in zip(queries_of(catalog), executed)
             if e.dispatch_launches)
    cl = make(catalog, telemetry)
    first, again = cl.run_query(q), cl.run_query(q)
    assert first.dispatch_pinned_hits == 0 < first.dispatch_launches
    assert again.dispatch_pinned_hits == again.dispatch_launches \
        == first.dispatch_launches
    if telemetry == "on":
        spans = [s for s, _ in query_spans(cl.telemetry.tracer)]
        assert [s.args["dispatch_pinned_hits"] for s in spans] == \
            [0, again.dispatch_launches]


def test_host_syncs_once_per_query_with_launches(catalog, traced):
    cl, on = traced
    quiet = make(catalog, "off")
    off = [quiet.run_query(q) for q in queries_of(catalog)]
    for executed in (on, off):
        assert {e.dispatch_host_syncs for e in executed} == {0, 1}
        for e in executed:
            assert e.dispatch_host_syncs == int(e.dispatch_launches > 0)
    for (q, _), e in zip(query_spans(cl.telemetry.tracer), on):
        assert q.args["dispatch_host_syncs"] == e.dispatch_host_syncs


def test_pinned_cap_below_one_query_keeps_counts_exact(files, catalog,
                                                       traced):
    # With a budget of one byte, pinning a query's misses evicts its own
    # batches from the cache while their kernels are still pending; the
    # counts must not change.
    _, default = traced
    cl = make(catalog, "off")
    cl.backend._pinned_budget = 1
    capped = [cl.run_query(q) for q in queries_of(catalog)]
    assert max(e.dispatch_launches for e in capped) > 1
    assert cl.backend.device_stats["pinned_batches_freed"] > 0
    assert cl.backend._pinned == {}
    assert all(e.dispatch_pinned_hits == 0 for e in capped)
    assert [e.matches for e in capped] == [e.matches for e in default]
    coords = np.unique(np.concatenate([f.coords for f in files]), axis=0)
    for q, e in zip(queries_of(catalog), capped):
        pts = coords[points_in_box(coords, q.box)]
        assert e.matches == count_similar_pairs_np(pts, pts, q.eps,
                                                   same=True)


def spy_stacks(cl):
    """Count the batches the executor stacks, with ``[total]``: every
    batch handed to ``stack`` must still be unstacked."""
    total = [0]
    orig = cl.backend.executor.stack

    def stack(batches, stats):
        assert all(b.arrays is None for b in batches)
        total[0] += len(batches)
        return orig(batches, stats)
    cl.backend.executor.stack = stack
    return total


def run_counting_stacks(cl, queries):
    """Run ``queries``; each query's executed record with the number of
    batches it stacked."""
    total = spy_stacks(cl)
    out = []
    for q in queries:
        before = total[0]
        e = cl.run_query(q)
        out.append((e, total[0] - before))
    return out


@pytest.mark.parametrize("telemetry", ["on", "off"])
def test_stacks_only_batches_no_pinned_stack_serves(catalog, telemetry):
    cl = make(catalog, telemetry)
    qs = queries_of(catalog)
    runs = run_counting_stacks(cl, qs)
    for e, stacked in runs:
        assert stacked == e.dispatch_launches - e.dispatch_pinned_hits
    assert sum(e.dispatch_pinned_hits for e, _ in runs) > 0
    assert sum(stacked for _, stacked in runs) > 0
    if telemetry == "on":
        groups = query_spans(cl.telemetry.tracer)
        assert len(groups) == len(runs)
        for (_, spans), (_, stacked) in zip(groups, runs):
            assert sum(s.args["batches"] for s in spans
                       if s.name == "prep.stack") == stacked
    # On a fresh cluster the same query at once again finds every batch
    # pinned: launches, and not one host stack built.
    q = next(q for q, (e, _) in zip(qs, runs) if e.dispatch_launches)
    (first, n_first), (again, n_again) = run_counting_stacks(
        make(catalog, telemetry), [q, q])
    assert n_first == first.dispatch_launches > 0
    assert again.dispatch_launches == first.dispatch_launches
    assert n_again == 0


def test_stack_spans_lie_in_prep_not_dispatch(traced):
    cl, executed = traced
    by_id = {s.span_id: s for s in cl.telemetry.tracer.spans}

    def dur(spans, name):
        return sum(s.end - s.start for s in spans if s.name == name)

    groups = query_spans(cl.telemetry.tracer)
    assert any(s.name == "prep.stack" for _, spans in groups for s in spans)
    for (_, spans), e in zip(groups, executed):
        puts = [s for s in spans if s.name == "dispatch.put"]
        for s in spans:
            if s.name != "prep.stack":
                continue
            p = by_id[s.parent_id]
            assert p.name == "dispatch"
            assert all(s.end <= put.start for put in puts)
        if e.prep_s is None:
            continue
        stack = dur(spans, "prep.stack")
        assert e.prep_s >= dur(spans, "prep") + stack - 1e-9
        assert e.dispatch_s <= dur(spans, "dispatch") - stack + 1e-9
        assert e.dispatch_s >= sum(dur(spans, n) for n in SUBSPANS) - 1e-9


@pytest.mark.parametrize("budget", [None, 1])
@pytest.mark.parametrize("prune", ["block", "dense"])
def test_counts_exact_when_only_misses_are_stacked(files, catalog, prune,
                                                   budget):
    # A budget of one byte pins nothing a later batch can find, so every
    # batch is stacked; the default budget serves repeats from pinned
    # stacks. Both count what the numpy reference counts.
    cl = RawArrayCluster(catalog, FileReader(catalog), N_NODES,
                         NODE_BUDGET, policy="cost", min_cells=64,
                         backend="jax_mesh", prune=prune, telemetry="off")
    if budget is not None:
        cl.backend._pinned_budget = budget
    qs = queries_of(catalog)
    runs = run_counting_stacks(cl, qs)
    hits = sum(e.dispatch_pinned_hits for e, _ in runs)
    assert (hits == 0) == (budget is not None)
    coords = np.unique(np.concatenate([f.coords for f in files]), axis=0)
    for q, (e, stacked) in zip(qs, runs):
        assert stacked == e.dispatch_launches - e.dispatch_pinned_hits
        pts = coords[points_in_box(coords, q.box)]
        assert e.matches == count_similar_pairs_np(pts, pts, q.eps,
                                                   same=True)


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
    # Executables built depend on what the process compiled before (the
    # traced run already built this workload's), not on telemetry.
    for key in set(QUERY_COUNTERS) - {"dispatch_backend_compiles"}:
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


def test_backend_compiles_count_a_forced_recompile(catalog):
    """A query whose kernels are all built reads 0; the same box at a
    new eps (a static argument of the kernel) builds its kernels again,
    and a repeat of it reads 0."""
    cl = make(catalog, "off")
    qs = queries_of(catalog)
    last = [cl.run_query(q) for q in qs + qs][-4:]
    q = qs[max(range(4), key=lambda k: last[k].dispatch_launches)]
    again = cl.run_query(q)
    assert again.dispatch_launches > 0
    assert again.dispatch_backend_compiles == 0
    odd = SimilarityJoinQuery(box=q.box, eps=q.eps + 17)
    first = cl.run_query(odd)
    assert first.dispatch_launches > 0
    assert first.dispatch_backend_compiles >= 1
    assert cl.run_query(odd).dispatch_backend_compiles == 0


def test_ship_spans_and_device_counters_on_four_devices(
        catalog, rerun_on_cpu_devices):
    """With a device per node, every cross-device ship route is one
    ``ship.transfer`` span under ``ship`` and counted in
    ``ship_transfers``; launches spread over devices, each query's
    busiest device holds at least its share of them."""
    if len(jax.devices()) < N_NODES:
        rerun_on_cpu_devices(
            f"{__file__}::test_ship_spans_and_device_counters_on_four_devices",
            N_NODES)
        return
    cl, executed = traced_run(catalog)
    by_id = {s.span_id: s for s in cl.telemetry.tracer.spans}
    for (q, spans), e in zip(query_spans(cl.telemetry.tracer), executed):
        names = [s.name for s in spans]
        assert names.count("ship.transfer") == e.ship_transfers
        assert names.count("ship.stage") <= e.ship_transfers
        for s in spans:
            if s.name in ("ship.transfer", "ship.stage"):
                assert by_id[s.parent_id].name == "ship"
        assert (e.measured_ship_bytes > 0) == (e.ship_transfers > 0)
        assert e.dispatch_devices <= N_NODES
        assert (e.dispatch_max_device_launches * max(e.dispatch_devices, 1)
                >= e.dispatch_launches >= e.dispatch_max_device_launches)
    assert sum(e.ship_transfers for e in executed) \
        == cl.backend.device_stats["ship_transfers"] > 0
    assert max(e.dispatch_devices for e in executed) > 1


@pytest.mark.parametrize("limits, budget", [
    ((16 << 30, 16 << 30), 4 << 30),
    ((16 << 30, 8 << 30), 2 << 30),
    ((None, None), PINNED_BYTES_UNREPORTED)])
def test_pinned_budget_is_a_quarter_of_device_memory(limits, budget):
    devices = [SimpleNamespace(memory_stats=lambda b=b: (
        None if b is None else {"bytes_limit": b})) for b in limits]
    assert pinned_budget(devices) == budget


def test_pinned_budget_evicts_lru_bytes_per_device(rerun_on_cpu_devices):
    """More than 256 small pinned batches all stay under the derived
    budget. Shrunk below what every device holds, the budget evicts a
    device's least recently used batches, by bytes, when that device
    pins, and no other device's; the chunk index is pruned alongside,
    and dropping every chunk brings each device's count back to 0."""
    if len(jax.devices()) < N_NODES:
        rerun_on_cpu_devices(
            f"{__file__}::test_pinned_budget_evicts_lru_bytes_per_device",
            N_NODES)
        return
    devs = jax.devices()[:N_NODES]
    backend = JaxMeshBackend(N_NODES, devices=devs)
    assert backend._pinned_budget == PINNED_BYTES_UNREPORTED
    size = {}

    def pin(i):
        dev = devs[i % N_NODES]
        key = (dev, "simjoin", 300, (((i, 0), (1000 + i % 7, 0)),))
        arrays = (jax.device_put(np.zeros(16 * (1 + i % 5), np.int32),
                                 dev),)
        backend._pin(key, arrays)
        size[key] = arrays[0].nbytes
        return key

    keys = [pin(i) for i in range(300)]
    assert list(backend._pinned) == keys
    for d in devs:
        assert backend._pinned_bytes[d] == sum(size[k] for k in keys
                                               if k[0] == d)
    # A hit on device 0's oldest batch makes it recent; then the budget
    # drops below every device's bytes and device 0 pins once more.
    backend._pin(keys[0], backend._pinned[keys[0]])
    lru = keys[1:] + keys[:1]
    backend._pinned_budget = min(backend._pinned_bytes.values()) // 3
    new = pin(300)
    want = [k for k in lru + [new] if k[0] == devs[0]]
    held = sum(size[k] for k in want)
    while held > backend._pinned_budget:
        held -= size[want.pop(0)]
    assert [k for k in backend._pinned if k[0] == devs[0]] == want
    assert keys[0] in want and keys[N_NODES] not in want
    assert backend._pinned_bytes[devs[0]] == held
    for d in devs[1:]:
        assert [k for k in backend._pinned if k[0] == d] == \
            [k for k in lru if k[0] == d]
        assert backend._pinned_bytes[d] > backend._pinned_budget
    assert backend.device_stats["pinned_batches_freed"] == \
        len(keys) + 1 - len(backend._pinned)
    index = {}
    for k in backend._pinned:
        for ka, kb in k[3]:
            index.setdefault(ka[0], set()).add(k)
            index.setdefault(kb[0], set()).add(k)
    assert backend._pinned_by_chunk == index
    for cid in list(index):
        backend.on_drop(cid)
    assert backend._pinned == {} and backend._pinned_by_chunk == {}
    assert set(backend._pinned_bytes.values()) == {0}
