"""Hot-chunk replication + simulated node failure handling.

The contract under test: ``replication="off"`` (the default) is
bit-for-bit the single-copy pipeline on both backends; with
``replication="hot"`` match counts never change, secondaries are shed
strictly before sole copies when budget tightens, the join planner
routes deterministically to the least-loaded replica, and a
``fail_node`` crash-restart re-admits lost chunks (cheap from surviving
replicas, raw-file fallback otherwise) while every listener-driven tier
— device buffers, join artifacts, result-cache version stamps — forgets
the dead copies. Also holds the ISSUE-7 accessor discipline: nothing
outside ``cache_state.py`` touches the raw ``locations`` dict.
"""
import re
from pathlib import Path

import pytest

from repro.arrayio.catalog import FileReader, build_catalog
from repro.arrayio.generator import make_ptf_files
from repro.backend.base import workload_summary
from repro.core.cache_state import CacheState
from repro.core.chunk import ChunkMeta
from repro.core.cluster import RawArrayCluster
from repro.core.geometry import Box
from repro.core.join_planner import plan_join
from repro.core.policies import (REPLICATION_MODES, HotChunkReplication,
                                 ReplicationContext, build_replication)
from repro.core.workload import zipf_workload

N_NODES = 4


@pytest.fixture(scope="module")
def ptf(tmp_path_factory):
    root = tmp_path_factory.mktemp("ptf_repl")
    files = make_ptf_files(n_files=8, cells_per_file_mean=700, seed=11)
    catalog, data = build_catalog(files, str(root), "fits", n_nodes=N_NODES)
    return catalog, data


def make_cluster(ptf, budget=400_000, **kw):
    catalog, data = ptf
    return RawArrayCluster(catalog, FileReader(catalog, data), N_NODES,
                           budget, policy="cost", min_cells=64, **kw)


def skewed(catalog, n_queries=18, seed=3):
    return zipf_workload(catalog.domain, n_queries=n_queries, n_templates=3,
                         s=1.5, eps=1, field_frac=0.25, seed=seed)


def hottest_node(cluster):
    """The node holding the most cached bytes (the failover victim)."""
    chunk_bytes, _ = cluster.coordinator.chunks.size_tables()
    by_node = cluster.coordinator.cache.bytes_by_node(chunk_bytes)
    return max(by_node, key=lambda n: (by_node[n], -n))


# ------------------------------------------------------- knob validation

def test_knob_validation(ptf):
    assert REPLICATION_MODES == ("off", "hot")
    with pytest.raises(ValueError):
        build_replication("mirror")
    with pytest.raises(ValueError):
        HotChunkReplication(k=0)
    with pytest.raises(ValueError):
        make_cluster(ptf, replication="all")
    cl = make_cluster(ptf)
    assert cl.coordinator.replication == "off"   # off by default
    with pytest.raises(ValueError):
        cl.coordinator.fail_node(N_NODES)


# ------------------------------------------------- off = seed parity

@pytest.mark.parametrize("backend", ["simulated", "jax_mesh"])
def test_replication_off_is_single_copy_seed_path(ptf, backend):
    """Default and explicit ``replication="off"`` produce identical
    workloads, keep every replica tuple at length one, and leave every
    replication/failover observable absent (None fields, no summary
    keys) — the single-copy path of the seed."""
    if backend == "jax_mesh":
        pytest.importorskip("jax")
    queries = skewed(ptf[0], n_queries=12)
    default = make_cluster(ptf, backend=backend)
    explicit = make_cluster(ptf, backend=backend, replication="off")
    ed = default.run_workload(queries, batch_size=3)
    ee = explicit.run_workload(queries, batch_size=3)
    assert [e.matches for e in ed] == [e.matches for e in ee]

    def modeled(executed):
        # opt_time_s is real measured policy-round wall-clock (and
        # total_time_s includes it): strip the nondeterministic timings,
        # compare every planned/counted observable exactly.
        s = workload_summary(executed)
        return {k: v for k, v in s.items()
                if k not in ("total_time_s", "opt_time_s", "prep_s",
                             "dispatch_s", "bitmap_build_s",
                             "measured_net_s", "recovery_s")}
    assert modeled(ed) == modeled(ee)
    summary = workload_summary(ee)
    assert "replica_hits" not in summary
    assert "failover_readmits" not in summary
    assert all(e.replica_hits is None and e.failover_readmits is None
               for e in ee)
    cache = explicit.coordinator.cache
    assert cache.location_items()
    assert all(len(reps) == 1 for _, reps in cache.location_items())


@pytest.mark.parametrize("backend", ["simulated", "jax_mesh"])
def test_hot_replication_same_matches_and_forms_replicas(ptf, backend):
    """Replication never changes a match count; under a skewed repeat
    workload with slack budget, hot chunks actually gain secondaries and
    the summary grows the replica counter group."""
    if backend == "jax_mesh":
        pytest.importorskip("jax")
    queries = skewed(ptf[0])
    off = make_cluster(ptf, backend=backend)
    hot = make_cluster(ptf, backend=backend, replication="hot",
                       replica_k=2, replication_threshold=2.0)
    eo = off.run_workload(queries, batch_size=3)
    eh = hot.run_workload(queries, batch_size=3)
    assert [e.matches for e in eo] == [e.matches for e in eh]
    cache = hot.coordinator.cache
    assert any(len(reps) > 1 for _, reps in cache.location_items())
    assert all(len(reps) <= 2 for _, reps in cache.location_items())
    summary = workload_summary(eh)
    assert "replica_hits" in summary and "replicas_dropped" in summary
    assert hot.coordinator.stats["replica_hits"] >= 0


# ------------------------------------------------ planner replica routing

def _cm(cid, lo, hi, n_cells=100, nbytes=1000):
    return ChunkMeta(cid, 0, Box(lo, hi), n_cells, nbytes)


def test_plan_join_replica_routing_is_deterministic_and_served_in_place():
    chunks = [_cm(1, (0, 0), (4, 4)), _cm(2, (3, 3), (9, 9))]
    locs = {1: (0, 1), 2: (1,)}
    p1 = plan_join(chunks, locs, eps=1, n_nodes=N_NODES)
    p2 = plan_join(chunks, locs, eps=1, n_nodes=N_NODES)
    assert p1.pair_node == p2.pair_node
    assert p1.transfer_routes == p2.transfer_routes
    # Chunk 1's secondary at node 1 serves the cross pair in place: the
    # whole plan runs without shipping a byte.
    assert p1.transfers == []
    assert p1.replica_hits > 0


def test_plan_join_single_copy_forms_are_bit_identical():
    """A bare node id and its one-tuple plan identically (the compat
    guarantee the off-parity rows rely on), with zero replica hits."""
    chunks = [_cm(1, (0, 0), (4, 4)), _cm(2, (3, 3), (9, 9))]
    a = plan_join(chunks, {1: 0, 2: 1}, eps=1, n_nodes=N_NODES)
    b = plan_join(chunks, {1: (0,), 2: (1,)}, eps=1, n_nodes=N_NODES)
    assert a.pair_node == b.pair_node
    assert a.transfer_routes == b.transfer_routes
    assert a.bytes_in == b.bytes_in and a.bytes_out == b.bytes_out
    assert a.replica_hits == b.replica_hits == 0


# -------------------------------------------- replica-aware eviction

def test_budget_squeeze_sheds_secondaries_before_sole_copies():
    """The structural ordering: when leftover budget disappears, the
    policy sheds secondaries (counted) while residency — every sole
    copy — is untouched."""
    state = CacheState(n_nodes=2, node_budget_bytes=1000,
                       budget_scope="node")
    chunk_bytes = {1: 300, 2: 300, 3: 600}
    state.cached = {1, 2}
    state.set_replicas(1, 0)
    state.set_replicas(2, 1)
    pol = HotChunkReplication(k=2, threshold=1.0)
    shed = pol.replicate(ReplicationContext(
        state=state, chunk_bytes=chunk_bytes, freq={1: 5.0},
        home_of=lambda c: 0))
    assert shed == 0
    assert state.replicas_of(1) == (0, 1)      # hot chunk gained a copy
    # Next round: placement admitted sole-copy chunk 3 at node 1 and (as
    # every round does) wiped locations back to single-valued. The
    # leftover budget no longer fits chunk 1's secondary -> it is shed;
    # no resident chunk is dropped.
    state.cached = {1, 2, 3}
    state.assign_locations({1: 0, 2: 1, 3: 1})
    shed = pol.replicate(ReplicationContext(
        state=state, chunk_bytes=chunk_bytes, freq={1: 0.5},
        home_of=lambda c: 0))
    assert shed == 1
    assert state.replicas_of(1) == (0,)
    assert state.cached == {1, 2, 3}


def test_replicas_never_push_a_node_over_budget(ptf):
    """Per-node budgets hold with every replica charged at its holder."""
    budget = 60_000
    cl = make_cluster(ptf, budget=budget, budget_scope="node",
                      replication="hot", replication_threshold=1.5)
    cl.run_workload(skewed(ptf[0]), batch_size=3)
    chunk_bytes, _ = cl.coordinator.chunks.size_tables()
    for node, used in cl.coordinator.cache.bytes_by_node(
            chunk_bytes).items():
        assert used <= budget, f"node {node} over budget"


# ------------------------------------------------ kill -> re-admit

@pytest.mark.parametrize("backend", ["simulated", "jax_mesh"])
def test_kill_node_readmits_and_preserves_matches(ptf, backend):
    """Crash-restart of the hottest node mid-workload: lost chunks are
    re-admitted (from replicas or raw files), the recovery counters land
    on the next executed query, and every match count is identical to an
    unfailed reference run."""
    if backend == "jax_mesh":
        pytest.importorskip("jax")
    queries = skewed(ptf[0])
    kw = dict(backend=backend, replication="hot", replica_k=2,
              replication_threshold=2.0)
    reference = [e.matches
                 for e in make_cluster(ptf, **kw).run_workload(
                     queries, batch_size=3)]
    cl = make_cluster(ptf, **kw)
    half = len(queries) // 2
    before = cl.run_workload(queries[:half], batch_size=3)
    victim = hottest_node(cl)
    event = cl.fail_node(victim)
    assert cl.coordinator.stats["node_failures"] == 1
    assert event["failover_readmits"] > 0
    assert (event["recovery_bytes_from_replica"]
            + event["recovery_bytes_from_raw"]) > 0
    after = cl.run_workload(queries[half:], batch_size=3)
    assert [e.matches for e in before + after] == reference
    # The event's counters ride exactly once into the executed stream.
    summary = workload_summary(before + after)
    assert summary["failover_readmits"] == event["failover_readmits"]
    assert summary["recovery_bytes_from_replica"] == \
        event["recovery_bytes_from_replica"]
    assert summary["recovery_bytes_from_raw"] == \
        event["recovery_bytes_from_raw"]


def test_kill_without_replication_recovers_from_raw(ptf):
    """``fail_node`` works under ``replication="off"`` too: every lost
    chunk is a sole copy, so recovery is raw-file re-scan only."""
    cl = make_cluster(ptf)
    cl.run_workload(skewed(ptf[0], n_queries=6), batch_size=3)
    event = cl.fail_node(hottest_node(cl))
    assert event["recovery_bytes_from_replica"] == 0
    assert event["failover_readmits"] > 0
    assert event["recovery_bytes_from_raw"] > 0
    # Post-recovery residency is still single-copy and consistent.
    cache = cl.coordinator.cache
    assert all(len(reps) == 1 for _, reps in cache.location_items())


def test_kill_during_warm_artifact_and_result_cache_workload(ptf):
    """A failure under warm host tiers: the result tier's version stamp
    bumps (no pre-failure hit survives), the artifact cache keeps no
    entry for a non-resident chunk, and the re-planned repeat query
    still produces the identical match count."""
    cl = make_cluster(ptf, join_backend="pallas", result_cache="on",
                      replication="hot", replication_threshold=1.5)
    q = skewed(ptf[0], n_queries=1)[0]
    first = cl.run_query(q)
    warm = cl.run_query(q)
    assert warm.report.result_cache_hit
    assert warm.matches == first.matches
    rc = cl.coordinator.result_cache
    v_before = rc.version
    event = cl.fail_node(hottest_node(cl))
    assert event["failover_readmits"] > 0
    assert rc.version > v_before           # stamp bumped: hits are dead
    assert cl.backend.artifacts is not None
    assert cl.backend.artifacts.chunk_ids() <= cl.coordinator.cache.cached
    again = cl.run_query(q)
    assert not again.report.result_cache_hit
    assert again.matches == first.matches


def test_mesh_replica_buffers_track_replica_sets(ptf):
    """On the mesh backend every cached chunk holds one committed buffer
    per replica, each on its holder's device — before and after a node
    failure."""
    pytest.importorskip("jax")
    cl = make_cluster(ptf, backend="jax_mesh", replication="hot",
                      replication_threshold=1.5)

    def check():
        backend, cache = cl.backend, cl.coordinator.cache
        chunks = cl.coordinator.chunks
        seen_multi = 0
        for cid, reps in cache.location_items():
            if cid not in cache.cached or chunks.meta_of(cid) is None:
                continue
            devs = backend.replica_devices(cid)
            assert set(devs) == set(reps)
            seen_multi += len(reps) > 1
            for node, dev in devs.items():
                assert dev == backend.device_for_node(node)
        return seen_multi

    cl.run_workload(skewed(ptf[0]), batch_size=3)
    assert check() > 0                     # replication actually engaged
    cl.fail_node(hottest_node(cl))
    check()


# --------------------------------------- accessor-discipline regression

FORBIDDEN = re.compile(r"(?:state|cache)\.locations")


def test_no_raw_location_access_outside_cache_state():
    """ISSUE-7 satellite: every location read/write in ``src/repro``
    goes through the ``CacheState`` accessor surface. Any ``*state.
    locations`` / ``*cache.locations`` expression outside
    ``cache_state.py`` — code or docstring — fails this test, so a
    future caller cannot silently hold a single-valued view of a
    multi-valued entry."""
    src = Path(__file__).resolve().parent.parent / "src" / "repro"
    offenders = []
    for path in sorted(src.rglob("*.py")):
        if path.name == "cache_state.py":
            continue
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            if FORBIDDEN.search(line):
                offenders.append(
                    f"{path.relative_to(src)}:{lineno}: {line.strip()}")
    assert not offenders, (
        "raw CacheState.locations access outside the accessor surface "
        "(use node_of/replicas_of/set_replicas/...):\n"
        + "\n".join(offenders))
