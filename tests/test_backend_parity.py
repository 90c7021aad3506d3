"""Backend parity: the jax device-mesh backend executes the *same plans*
as the simulated backend — identical match counts and identical planned
ship/scan byte totals on the seed workloads (including with semantic
reuse on) — while committing every cached chunk as a device buffer on the
node its ``CacheState.locations`` entry names.

The suite runs at any device count (with one device the node axis wraps
and transfers collapse to the same device). The test that needs one
device per node reruns itself under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` when its process
has fewer, so cross-device placement and real transfers are exercised
by every run of the suite.
"""
import tempfile

import pytest

jax = pytest.importorskip("jax")

from repro.arrayio.catalog import FileReader, build_catalog
from repro.arrayio.generator import make_ptf_files
from repro.backend import JaxMeshBackend, SimulatedBackend, make_backend
from repro.core.cluster import RawArrayCluster, workload_summary
from repro.core.workload import ptf1_workload, ptf2_workload

N_NODES = 4
NODE_BUDGET = 6_000


@pytest.fixture(scope="module")
def dataset():
    files = make_ptf_files(n_files=10, cells_per_file_mean=900, seed=21)
    catalog, data = build_catalog(files, tempfile.mkdtemp(prefix="bparity_"),
                                  "fits", n_nodes=N_NODES)
    return catalog, data


def fixed_workload(catalog):
    return (ptf1_workload(catalog.domain, n_queries=4, eps=300, seed=7)
            + ptf2_workload(catalog.domain, n_queries=4, eps=300))


def make(dataset, backend, policy="cost", reuse="off", budget=NODE_BUDGET):
    catalog, data = dataset
    return RawArrayCluster(catalog, FileReader(catalog, data), N_NODES,
                           budget, policy=policy, min_cells=64,
                           backend=backend, reuse=reuse)


def planned_bytes(executed):
    """(total planned ship bytes, total planned scan bytes) of a run."""
    ship = sum(sum(e.report.join_plan.bytes_in.values())
               for e in executed if e.report.join_plan is not None)
    scan = sum(sum(e.report.scan_bytes_by_node.values()) for e in executed)
    return ship, scan


@pytest.mark.parametrize("policy", ["cost", "chunk_lru", "file_lru"])
def test_match_and_planned_byte_parity(dataset, policy):
    catalog, _ = dataset
    queries = fixed_workload(catalog)
    runs = {b: make(dataset, b, policy=policy).run_workload(queries)
            for b in ("simulated", "jax_mesh")}
    assert ([e.matches for e in runs["jax_mesh"]]
            == [e.matches for e in runs["simulated"]])
    assert planned_bytes(runs["jax_mesh"]) == planned_bytes(runs["simulated"])
    assert sum(e.matches for e in runs["simulated"]) > 0


def test_parity_with_semantic_reuse(dataset):
    catalog, _ = dataset
    # Repeat the workload so the second pass is served from cache.
    queries = fixed_workload(catalog) + fixed_workload(catalog)
    runs = {b: make(dataset, b, reuse="on",
                    budget=10 * NODE_BUDGET).run_workload(queries)
            for b in ("simulated", "jax_mesh")}
    assert ([e.matches for e in runs["jax_mesh"]]
            == [e.matches for e in runs["simulated"]])
    assert planned_bytes(runs["jax_mesh"]) == planned_bytes(runs["simulated"])
    assert workload_summary(runs["jax_mesh"])["reuse_hits"] > 0


def test_committed_buffers_track_locations(dataset):
    """Every cached chunk's committed buffer lives on the device matching
    its CacheState.locations node, and eviction frees buffers (the
    buffer table equals the resident set)."""
    catalog, _ = dataset
    cluster = make(dataset, "jax_mesh")
    cluster.run_workload(fixed_workload(catalog))
    backend = cluster.backend
    cache = cluster.coordinator.cache
    assert isinstance(backend, JaxMeshBackend)
    assert set(backend.committed_chunks()) == cache.cached
    assert len(cache.cached) > 0
    for cid, node in cache.primary_map().items():
        assert backend.buffer_device(cid) == backend.device_for_node(node), \
            f"chunk {cid} not on node {node}'s device"


def test_distinct_devices_and_real_transfers(dataset, rerun_on_cpu_devices):
    """With one device per node, chunks at different nodes occupy
    *different* physical devices and ship decisions move real bytes."""
    if len(jax.devices()) < N_NODES:
        rerun_on_cpu_devices(
            f"{__file__}::test_distinct_devices_and_real_transfers", N_NODES)
        return
    catalog, _ = dataset
    cluster = make(dataset, "jax_mesh")
    executed = cluster.run_workload(fixed_workload(catalog))
    backend = cluster.backend
    cache = cluster.coordinator.cache
    nodes_used = set(cache.primary_map().values())
    devices_used = {backend.buffer_device(cid)
                    for cid in cache.primary_map()}
    assert len(devices_used) == len(nodes_used) > 1
    assert backend.device_stats["ship_bytes_measured"] > 0
    assert sum(e.measured_ship_bytes for e in executed) \
        == backend.device_stats["ship_bytes_measured"]


def test_measured_fields_by_backend(dataset):
    catalog, _ = dataset
    queries = fixed_workload(catalog)[:3]
    sim = make(dataset, "simulated").run_workload(queries)
    mesh = make(dataset, "jax_mesh").run_workload(queries)
    assert all(e.measured_net_s is None for e in sim)
    assert all(e.backend == "simulated" for e in sim)
    assert all(e.measured_net_s is not None and e.measured_net_s >= 0
               for e in mesh)
    assert all(e.dispatch_s is not None and e.dispatch_launches is not None
               for e in mesh)
    assert all(e.dispatch_launches is None and e.device_sync_bytes is None
               for e in sim)
    assert all(e.backend == "jax_mesh" for e in mesh)
    summ = workload_summary(mesh)
    assert "measured_net_s" in summ and "measured_ship_bytes" in summ
    assert "measured_net_s" not in workload_summary(sim)


def test_compiled_mode_parity(dataset):
    """The mesh backend's interpret flag follows the platform of the
    devices it holds — interpret mode on CPU devices only, compiled on
    the chip — and its Pallas dispatch returns the same match counts as
    the simulated backend's numpy reference either way."""
    catalog, _ = dataset
    queries = fixed_workload(catalog)
    sim = make(dataset, "simulated").run_workload(queries)
    cluster = make(dataset, "jax_mesh")
    mesh = cluster.run_workload(queries)
    platform = cluster.backend.devices[0].platform
    assert cluster.executor.interpret == (platform == "cpu")
    assert [e.matches for e in mesh] == [e.matches for e in sim]


def test_backend_factory_validation():
    with pytest.raises(ValueError, match="unknown backend"):
        make_backend("warp_drive", 4)
    with pytest.raises(ValueError, match="Pallas simjoin kernel"):
        make_backend("jax_mesh", 4, join_fn=lambda a, b, e, s: 0)
    assert isinstance(make_backend("simulated", 4), SimulatedBackend)
    assert isinstance(make_backend("jax_mesh", 4), JaxMeshBackend)
