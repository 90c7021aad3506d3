"""Join-artifact caching and the prune routes: warm results must be
bit-identical to cold across evict -> re-admit -> split, artifacts must
be invalidated on every residency event (``on_drop``/``on_split``/
``reconcile`` — no stale-artifact path survives), and the served
``prune="block"`` route must count exactly what the ``"dense"``
reference and the numpy executor count on random and clustered
workloads under both execution backends."""
import tempfile

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.backend.artifacts import (ChunkView, JoinArtifactCache,  # noqa: E402
                                     task_coords)
from repro.backend.executors import (NumpyJoinExecutor,  # noqa: E402
                                     PallasJoinExecutor,
                                     count_similar_pairs_np,
                                     make_join_executor)
from repro.backend.jax_mesh import JaxMeshBackend  # noqa: E402
from repro.core.geometry import Box  # noqa: E402


def clustered_coords(rng, n, d=3, n_clusters=6, domain=50_000, spread=30):
    centers = rng.integers(0, domain, (n_clusters, d))
    pick = rng.integers(0, n_clusters, n)
    return (centers[pick] + rng.integers(-spread, spread + 1,
                                         (n, d))).astype(np.int32)


# ----------------------------------------------------- cache unit tests

def test_view_keying_canonicalizes_coverage():
    cache = JoinArtifactCache()
    coords = np.zeros((4, 2), np.int32)
    chunk = Box((10, 10), (20, 20))
    # Full coverage from two different query boxes -> ONE artifact key.
    v1 = cache.view(7, chunk, Box((0, 0), (100, 100)), coords)
    v2 = cache.view(7, chunk, Box((5, 5), (50, 50)), coords)
    assert v1.key == v2.key == (7, ())
    # Partial coverage keys by the intersected box.
    v3 = cache.view(7, chunk, Box((0, 0), (15, 15)), coords)
    assert v3.key == (7, ((10, 10), (15, 15)))
    assert v3.key != v1.key
    # Unknown geometry / disjoint boxes degrade to uncacheable views.
    assert cache.view(7, None, Box((0, 0), (1, 1)), coords).key is None
    assert cache.view(7, chunk, Box((0, 0), (5, 5)), coords).key is None
    assert task_coords(v1) is coords
    assert task_coords(coords) is coords


def test_getters_memoize_and_count():
    cache = JoinArtifactCache()
    coords = np.arange(12, dtype=np.int32).reshape(6, 2)
    v = cache.view(1, Box((0, 0), (11, 11)), Box((0, 0), (99, 99)), coords)
    calls = []
    got1 = cache.sorted_coords(v, lambda: calls.append("s") or coords[::-1])
    got2 = cache.sorted_coords(v, lambda: calls.append("s") or coords[::-1])
    assert got1 is got2 and calls == ["s"]
    assert cache.misses == 1 and cache.hits == 1
    pad = np.ones((2, 128), np.int32)
    assert cache.padded(v, 5, lambda: pad) is pad
    assert cache.padded(v, 5, lambda: 0 / 0) is pad       # memoized
    assert cache.padded(v, -5, lambda: -pad) is not pad   # per join side
    pairs = (np.ones((2, 3), np.int32), 4)
    w = cache.view(2, Box((50, 50), (60, 60)), Box((0, 0), (99, 99)),
                   coords)
    assert cache.block_pairs(v, w, 128, 3, False, lambda: pairs) is pairs
    assert cache.block_pairs(v, w, 128, 3, False, lambda: 0 / 0) is pairs
    # Different eps is a different artifact.
    pairs9 = (np.zeros((1, 3), np.int32), 4)
    assert cache.block_pairs(v, w, 128, 9, False, lambda: pairs9) is pairs9
    # Uncacheable side -> computed every time, no counters.
    raw = np.zeros((3, 2), np.int32)
    h, m = cache.hits, cache.misses
    assert cache.block_pairs(v, raw, 128, 3, False, lambda: pairs) is pairs
    assert (cache.hits, cache.misses) == (h, m)


def test_invalidation_on_drop_split_reconcile():
    class FakeState:
        cached = {1}

    cache = JoinArtifactCache()
    q = Box((0, 0), (99, 99))
    coords = np.zeros((2, 2), np.int32)
    v1 = cache.view(1, Box((0, 0), (9, 9)), q, coords)
    v2 = cache.view(2, Box((10, 10), (19, 19)), q, coords)
    cache.sorted_coords(v1, lambda: coords)
    cache.sorted_coords(v2, lambda: coords)
    cache.block_pairs(v1, v2, 128, 3, False,
                      lambda: (np.ones((1, 3), np.int32), 1))
    assert cache.chunk_ids() == {1, 2}
    # on_drop removes the chunk's entries AND pair lists it fed.
    cache.on_drop(2)
    assert cache.chunk_ids() == {1}
    assert not cache.has_chunk(2)
    # on_split retires the parent id the same way.
    cache.on_split(1, leaves=[])
    assert cache.chunk_ids() == set()
    assert len(cache) == 0
    # reconcile prunes everything not resident.
    v1 = cache.view(1, Box((0, 0), (9, 9)), q, coords)
    v3 = cache.view(3, Box((30, 30), (39, 39)), q, coords)
    cache.sorted_coords(v1, lambda: coords)
    cache.sorted_coords(v3, lambda: coords)
    cache.reconcile(FakeState())
    assert cache.chunk_ids() == {1}
    assert cache.invalidations > 0


def test_subset_cap_evicts_least_recently_used():
    cache = JoinArtifactCache(max_subsets_per_chunk=2)
    chunk = Box((0, 0), (99, 99))
    coords = np.zeros((2, 2), np.int32)
    views = [cache.view(1, chunk, Box((0, 0), (k, k)), coords)
             for k in (10, 20, 30)]
    for v in views:
        cache.sorted_coords(v, lambda: coords)
    assert len(cache) == 2
    # Oldest subset recomputes (miss), newest still hits.
    h = cache.hits
    cache.sorted_coords(views[-1], lambda: 0 / 0)
    assert cache.hits == h + 1
    m = cache.misses
    cache.sorted_coords(views[0], lambda: coords)
    assert cache.misses == m + 1
    # LRU, not FIFO: a hit refreshes the subset's position, so a hot
    # subset survives a newer one-off insertion.
    cache.sorted_coords(views[-1], lambda: 0 / 0)      # touch 30: hot
    cache.sorted_coords(cache.view(1, chunk, Box((0, 0), (40, 40)),
                                   coords), lambda: coords)
    cache.sorted_coords(views[-1], lambda: 0 / 0)      # 30 still cached


# ------------------------------------------------- executor-level parity

def make_tasks(rng, k=8, maker=clustered_coords):
    tasks = []
    for i in range(k):
        a = maker(rng, int(rng.integers(1, 700)))
        b = maker(rng, int(rng.integers(1, 700)))
        tasks.append((i % 3, a, b, False))
        tasks.append((i % 3, a, a, True))
    tasks.append((0, np.zeros((0, 3), np.int32), a, False))
    return tasks


def uniform_coords(rng, n, d=3, hi=400):
    return rng.integers(0, hi, size=(n, d)).astype(np.int32)


@pytest.mark.parametrize("maker", [clustered_coords, uniform_coords])
def test_auto_parity_and_counters(maker):
    """The block route counts exactly what the dense reference and the
    numpy executor count, shares the dense grid's denominator, and
    evaluates no more block pairs than that grid holds."""
    rng = np.random.default_rng(11)
    tasks = make_tasks(rng, maker=maker)
    eps = 40
    dense = PallasJoinExecutor(prune="dense")
    block = PallasJoinExecutor(prune="block")
    ref = NumpyJoinExecutor(count_similar_pairs_np)
    cd = dense.count_pairs(tasks, eps)
    cb = block.count_pairs(tasks, eps)
    cn = ref.count_pairs(tasks, eps)
    assert cd == cb == cn
    assert sum(cb) > 0
    t = dense.last_stats["block_pairs_total"]
    assert dense.last_stats["block_pairs_evaluated"] == t
    assert block.last_stats["block_pairs_total"] == t
    assert block.last_stats["block_pairs_evaluated"] <= t
    for ex in (dense, block):
        assert ex.last_stats["prep_s"] >= 0
        assert ex.last_stats["dispatch_s"] >= 0


def test_executor_artifact_reuse_with_views():
    """Repeated dispatch over the same ChunkViews hits the artifact
    cache; counts are bit-identical to the cold pass and to raw-array
    (uncached) tasks."""
    rng = np.random.default_rng(9)
    a = clustered_coords(rng, 900)
    b = clustered_coords(rng, 500)
    q = Box((0, 0, 0), tuple([60_000] * 3))
    for mode in ("dense", "block"):
        ex = PallasJoinExecutor(prune=mode)
        va = ex.artifacts.view(1, Box((0, 0, 0), (50_100, 50_100, 50_100)),
                               q, a)
        vb = ex.artifacts.view(2, Box((0, 0, 0), (50_100, 50_100, 50_100)),
                               q, b)
        tasks = [(0, va, vb, False), (1, va, va, True)]
        raw = [(0, a, b, False), (1, a, a, True)]
        cold = ex.count_pairs(tasks, 35)
        assert ex.last_stats["artifact_misses"] > 0, mode
        warm = ex.count_pairs(tasks, 35)
        assert warm == cold == PallasJoinExecutor(
            prune=mode).count_pairs(raw, 35), mode
        assert ex.last_stats["artifact_hits"] > 0, mode
        assert ex.last_stats["artifact_misses"] == 0, mode


def reference_rows(mode, a, b, same, eps):
    """Task rows built straight from the kernel helpers, independent of
    the executor: both sides padded (sorted first on the block grid),
    plus the packed live pair list there."""
    from repro.kernels.simjoin import ops, prune
    from repro.kernels.simjoin.simjoin import BLOCK, SENTINEL
    if mode == "dense":
        return (ops.pad_cm_np(a, SENTINEL), ops.pad_cm_np(b, -SENTINEL))
    a_s = prune.spatial_sort(a)
    b_s = a_s if same else prune.spatial_sort(b)
    pairs, _ = prune.build_block_pairs(a_s, b_s, BLOCK, eps, same)
    return (ops.pad_cm_np(a_s, SENTINEL), ops.pad_cm_np(b_s, -SENTINEL),
            ops.pack_pairs_np(pairs, prune.padded_pair_len(len(pairs))))


@pytest.mark.parametrize("same", [False, True])
@pytest.mark.parametrize("mode", ["dense", "block"])
def test_stacks_equal_stack_then_pad(mode, same):
    """Grouping builds no arrays; stacking on demand writes bytes equal
    to ``np.stack`` of the rows followed by the inert ``np.full`` pad,
    for a full group and a partial one."""
    from repro.kernels.simjoin import ops
    from repro.kernels.simjoin.simjoin import SENTINEL
    rng = np.random.default_rng(23)
    eps = 40
    sides = [(uniform_coords(rng, int(rng.integers(40, 128))),
              uniform_coords(rng, int(rng.integers(40, 128))))
             for _ in range(ops.GROUP + 3)]
    tasks = [(0, a, a if same else b, same) for a, b in sides]
    ex = PallasJoinExecutor(prune=mode)
    batches, stats = ex.iter_batches(tasks, eps)
    assert sorted(len(b.idxs) for b in batches) == [3, ops.GROUP]
    assert all(b.arrays is None for b in batches)
    ex.stack(batches, stats)
    for batch in batches:
        rows = [reference_rows(mode, *tasks[i][1:], eps)
                for i in batch.idxs]
        pad = ops.GROUP - len(batch.idxs)
        for x, parts, fill in zip(batch.arrays, zip(*rows),
                                  (SENTINEL, -SENTINEL, 0)):
            ref = np.stack(parts)
            want = np.concatenate(
                [ref, np.full((pad,) + ref.shape[1:], fill, ref.dtype)])
            assert x.dtype == want.dtype and x.shape == want.shape
            assert x.tobytes() == want.tobytes()
        assert len(batch.arrays) == (2 if mode == "dense" else 3)


@pytest.mark.parametrize("mode, cold, warm", [
    # dense: every lookup happens at stack time — padded a/b of the
    # cross task (each computing its sorted side), then the self-join's
    # padded a (hit) and padded b (computed from the sorted hit).
    ("dense", (2, 5), (4, 0)),
    # block: grouping looks up sorted sides and pair lists (1 hit, 4
    # misses), stacking the padded sides (4 hits, 3 misses).
    ("block", (5, 7), (9, 0))])
def test_count_pairs_counts_stack_time_artifact_lookups(mode, cold, warm):
    """``count_pairs`` stacks every batch and reports the artifact
    hits/misses of all its lookups, stack time included: exactly the
    cache's own counters' growth, and the deltas that stacking every
    batch up front gave."""
    rng = np.random.default_rng(9)
    a = clustered_coords(rng, 900)
    b = clustered_coords(rng, 500)
    q = Box((0, 0, 0), tuple([60_000] * 3))
    cb = Box((0, 0, 0), (50_100, 50_100, 50_100))
    ex = PallasJoinExecutor(prune=mode)
    va, vb = ex.artifacts.view(1, cb, q, a), ex.artifacts.view(2, cb, q, b)
    tasks = [(0, va, vb, False), (1, va, va, True)]
    want = NumpyJoinExecutor(count_similar_pairs_np).count_pairs(tasks, 35)
    for expect in (cold, warm):
        h0, m0 = ex.artifacts.hits, ex.artifacts.misses
        assert ex.count_pairs(tasks, 35) == want
        got = (ex.last_stats["artifact_hits"],
               ex.last_stats["artifact_misses"])
        assert got == (ex.artifacts.hits - h0, ex.artifacts.misses - m0)
        assert got == expect


def test_bitmap_eps0_and_duplicate_parity():
    """The eps=0 edge: duplicated cells are the only matches, and the
    box prune's distance bound is exact there — they must count
    identically under both prune routes and the numpy reference."""
    rng = np.random.default_rng(17)
    base = clustered_coords(rng, 600)
    dup = np.repeat(base[:40], 10, axis=0)        # duplicates: matches
    tasks = [(0, base, base, True), (1, dup, dup, True),
             (0, base, dup, False)]
    for eps in (0, 1):
        want = NumpyJoinExecutor(count_similar_pairs_np).count_pairs(
            tasks, eps)
        assert sum(want) > 0
        for mode in ("dense", "block"):
            got = PallasJoinExecutor(prune=mode).count_pairs(tasks, eps)
            assert got == want, (mode, eps)


def test_auto_default_is_accepted_by_every_executor():
    """``"block"`` is the default everywhere: the numpy executor (no
    grid, the same pairs either way) accepts it, and the pallas
    executor serves it (stale modes are rejected in
    test_simjoin_pruning)."""
    assert isinstance(make_join_executor("numpy", count_similar_pairs_np),
                      NumpyJoinExecutor)
    assert isinstance(make_join_executor(
        "numpy", count_similar_pairs_np, prune="block"), NumpyJoinExecutor)
    assert PallasJoinExecutor().prune == "block"
    assert make_join_executor("pallas", count_similar_pairs_np).prune \
        == "block"


# ------------------------------------------------- cluster-level parity

@pytest.fixture(scope="module")
def dataset():
    from repro.arrayio.catalog import build_catalog
    from repro.arrayio.generator import make_geo_files
    files = make_geo_files(n_files=3, n_seeds=150, clones_per_seed=25,
                           seed=13)
    catalog, data = build_catalog(files, tempfile.mkdtemp(prefix="bart_"),
                                  "csv", n_nodes=4)
    return catalog, data


def make_cluster(dataset, backend="simulated", prune="block",
                 budget_frac=8, min_cells=512):
    from repro.arrayio.catalog import FileReader
    from repro.core.cluster import RawArrayCluster
    catalog, data = dataset
    total = sum(f.n_cells * f.cell_bytes for f in catalog.files)
    return RawArrayCluster(catalog, FileReader(catalog, data), 4,
                           max(total // budget_frac, 4_000) // 4,
                           policy="cost", min_cells=min_cells,
                           backend=backend, join_backend="pallas",
                           prune=prune)


def workload(catalog, eps=400):
    from repro.core.workload import geo_workload
    return geo_workload(catalog.domain, eps=eps, range_frac=0.45)


@pytest.mark.parametrize("backend", ["simulated", "jax_mesh"])
def test_prune_mode_parity_both_backends(dataset, backend):
    """Match counts bit-identical across prune=dense|block on each
    backend."""
    catalog, _ = dataset
    queries = workload(catalog)
    runs = {p: [e.matches for e in
                make_cluster(dataset, backend, p).run_workload(queries)]
            for p in ("dense", "block")}
    assert runs["dense"] == runs["block"]
    assert sum(m or 0 for m in runs["dense"]) > 0


def test_warm_equals_cold_with_hits(dataset):
    """A repeated workload over an all-resident cache: pass 2 answers
    from memoized artifacts (hits > 0, zero misses on the pallas prep)
    with bit-identical per-query matches."""
    from repro.core.cluster import workload_summary
    catalog, _ = dataset
    queries = workload(catalog)
    cluster = make_cluster(dataset, budget_frac=1)   # everything fits
    cold = cluster.run_workload(queries)
    warm = cluster.run_workload(queries)
    assert [e.matches for e in warm] == [e.matches for e in cold]
    cold_s, warm_s = workload_summary(cold), workload_summary(warm)
    assert warm_s["artifact_hits"] > 0
    assert warm_s["artifact_misses"] == 0
    assert cold_s["artifact_misses"] > 0
    for e in warm:
        if e.report.join_plan is not None:
            assert e.prep_s is not None and e.dispatch_s is not None


@pytest.mark.parametrize("backend", ["simulated", "jax_mesh"])
def test_warm_bit_identical_across_evict_readmit_split(dataset, backend):
    """The acceptance sequence: evict -> re-admit -> split, every step
    answered identically by a long-lived (warm) prune="block" cluster on
    each backend, a fresh dense cluster, and the numpy reference — no
    stale-artifact (or, on the mesh, stale pinned-stack) path."""
    from repro.arrayio.catalog import FileReader
    from repro.core.cluster import RawArrayCluster
    from repro.core.coordinator import SimilarityJoinQuery
    catalog, data = dataset
    q_main = workload(catalog)[:2]
    # A sub-box query forces R-tree refinement (splits) on re-touch.
    d = catalog.domain
    mid = tuple((l + h) // 2 for l, h in zip(d.lo, d.hi))
    q_sub = SimilarityJoinQuery(box=Box(d.lo, mid), eps=400)
    seq = q_main + q_main + [q_sub] + q_main     # repeat / split / repeat
    warm = make_cluster(dataset, backend, prune="block",
                        budget_frac=16,             # tight: forces evicts
                        min_cells=256)
    got = [e.matches for e in warm.run_workload(seq)]
    dense = make_cluster(dataset, prune="dense", budget_frac=16,
                         min_cells=256)
    want = [e.matches for e in dense.run_workload(seq)]
    np_cluster = RawArrayCluster(
        catalog, FileReader(catalog, data), 4,
        warm.coordinator.cache.node_budget, policy="cost", min_cells=256,
        join_backend="numpy")
    ref = [e.matches for e in np_cluster.run_workload(seq)]
    assert got == want == ref
    assert sum(m or 0 for m in got) > 0
    assert warm.backend.artifacts.invalidations > 0   # evict/split fired


def test_artifacts_never_outlive_residency(dataset):
    """After a reconcile, every chunk with live artifacts is resident —
    the invalidation guarantee of the CacheState listener hooks."""
    catalog, _ = dataset
    cluster = make_cluster(dataset, budget_frac=16, min_cells=256)
    cluster.run_workload(workload(catalog))
    cache = cluster.coordinator.cache
    art = cluster.backend.artifacts
    assert art is cluster.backend.executor.artifacts
    assert art in cache.listeners
    cache.sync_devices()                        # post-round reconcile
    assert art.chunk_ids() <= cache.cached
    assert len(art.chunk_ids()) > 0


def test_mesh_pins_padded_batches_across_queries(dataset):
    """The mesh backend device_puts a resident chunk set's stacked batch
    once: the repeat pass re-dispatches pinned device buffers
    (pinned_batch_hits > 0) with identical matches, and pinned entries
    never outlive residency."""
    catalog, _ = dataset
    cluster = make_cluster(dataset, backend="jax_mesh", budget_frac=1)
    queries = workload(catalog)
    cold = [e.matches for e in cluster.run_workload(queries)]
    backend = cluster.backend
    assert isinstance(backend, JaxMeshBackend)
    assert backend.device_stats["pinned_batch_misses"] > 0
    warm = [e.matches for e in cluster.run_workload(queries)]
    assert warm == cold
    assert backend.device_stats["pinned_batch_hits"] > 0
    cluster.coordinator.cache.sync_devices()
    assert set(backend._pinned_by_chunk) <= cluster.coordinator.cache.cached
    # Device memory is budgeted in bytes per device: shrinking the
    # budget to one byte and re-running evicts every entry (with the
    # chunk index pruned alongside), while match counts stay identical.
    backend._pinned_budget = 1
    again = [e.matches for e in cluster.run_workload(queries)]
    assert again == cold
    assert backend._pinned == {} and backend._pinned_by_chunk == {}
    assert set(backend._pinned_bytes.values()) == {0}
    assert backend.device_stats["pinned_batches_freed"] > 0


def test_workload_summary_amortization_counters(dataset):
    """workload_summary aggregates the prep/dispatch split and artifact
    counters on the pallas path and omits them on the numpy path."""
    from repro.arrayio.catalog import FileReader
    from repro.core.cluster import RawArrayCluster, workload_summary
    catalog, data = dataset
    queries = workload(catalog)[:3]
    summ = workload_summary(make_cluster(dataset).run_workload(queries))
    for key in ("prep_s", "dispatch_s", "artifact_hits",
                "artifact_misses"):
        assert key in summ, key
    np_run = RawArrayCluster(catalog, FileReader(catalog, data), 4, 8_000,
                             policy="cost", min_cells=512,
                             join_backend="numpy").run_workload(queries)
    assert "prep_s" not in workload_summary(np_run)
