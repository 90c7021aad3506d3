"""Block-sparse simjoin parity and effectiveness: the pruned
(``PrefetchScalarGridSpec``) kernel path must count exactly what the
dense grid counts — on random and clustered coordinates, across eps=0,
self-join dedup, and sentinel-padding edges — while evaluating a
fraction of the block pairs on clustered inputs, without retracing
across repeated same-shape dispatches, on both execution backends
(the CI ``tier1-mesh`` job reruns this file under 4 virtual devices)."""
import tempfile

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.backend.executors import (NumpyJoinExecutor,  # noqa: E402
                                     PallasJoinExecutor,
                                     count_similar_pairs_np,
                                     make_join_executor)
from repro.kernels.simjoin import ops, prune  # noqa: E402
from repro.kernels.simjoin.ref import count_pairs_ref  # noqa: E402
from repro.kernels.simjoin.simjoin import BLOCK  # noqa: E402


def uniform_coords(rng, n, d, hi=500):
    return rng.integers(0, hi, size=(n, d)).astype(np.int32)


def clustered_coords(rng, n, d, n_clusters=6, domain=50_000, spread=30):
    centers = rng.integers(0, domain, (n_clusters, d))
    pick = rng.integers(0, n_clusters, n)
    return (centers[pick] + rng.integers(-spread, spread + 1,
                                         (n, d))).astype(np.int32)


# ------------------------------------------------------- kernel parity

@pytest.mark.parametrize("n,m", [(1, 1), (7, 13), (128, 128), (130, 255),
                                 (300, 41), (1024, 77)])
@pytest.mark.parametrize("maker", [uniform_coords, clustered_coords])
def test_pruned_cross_join_matches_ref(n, m, maker):
    rng = np.random.default_rng(n * 1000 + m)
    a = maker(rng, n, 3)
    b = maker(rng, m, 3)
    for eps in (0, 1, 3, 50):
        got, total, evaluated = ops.count_similar_pairs_pruned_np(
            a, b, eps, False)
        want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(b), eps,
                                   False))
        assert got == want, (n, m, eps, maker.__name__)
        assert evaluated <= total


@pytest.mark.parametrize("n", [1, 5, 129, 384, 1000])
@pytest.mark.parametrize("maker", [uniform_coords, clustered_coords])
def test_pruned_self_join_matches_ref(n, maker):
    rng = np.random.default_rng(n)
    a = maker(rng, n, 3)
    for eps in (0, 1, 2):
        got, _, _ = ops.count_similar_pairs_pruned_np(a, a, eps, True)
        want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(a), eps,
                                   True))
        assert got == want, (n, eps, maker.__name__)


@pytest.mark.parametrize("n", [BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK,
                               2 * BLOCK + 7])
def test_sentinel_padding_edges(n):
    """Sizes straddling the BLOCK boundary: sentinel-padded tail cells
    must not join, with boxes built from real cells only."""
    rng = np.random.default_rng(n)
    a = clustered_coords(rng, n, 2)
    b = clustered_coords(rng, n + 3, 2)
    for same in (False, True):
        bb = a if same else b
        got, _, _ = ops.count_similar_pairs_pruned_np(a, bb, 5, same)
        want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(bb), 5,
                                   same))
        assert got == want


def test_duplicate_coords_self_join_dedup():
    """eps=0 self-join over duplicated cells: each unordered duplicate
    pair counts once, across the sorted order and block boundaries."""
    base = np.array([[10, 10], [10, 10], [10, 10], [99, 1]], np.int32)
    a = np.repeat(base, 80, axis=0)          # 320 cells, 3 blocks
    got, _, _ = ops.count_similar_pairs_pruned_np(a, a, 0, True)
    want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(a), 0, True))
    assert got == want


def test_pruning_skips_blocks_on_clustered():
    rng = np.random.default_rng(0)
    a = clustered_coords(rng, 4096, 3, n_clusters=12, domain=100_000)
    _, total, evaluated = ops.count_similar_pairs_pruned_np(a, a, 64, True)
    assert total == (4096 // BLOCK) ** 2
    assert evaluated <= total // 2, (evaluated, total)


def test_prune_helpers():
    rng = np.random.default_rng(3)
    a = clustered_coords(rng, 300, 3)
    s = prune.spatial_sort(a)
    assert sorted(map(tuple, s)) == sorted(map(tuple, a))  # permutation
    lo, hi = prune.block_bounds(s, BLOCK)
    assert lo.shape == hi.shape == (3, 3)
    assert (lo <= hi).all()
    assert prune.padded_pair_len(1) == 8
    assert prune.padded_pair_len(9) == 16
    padded = prune.pad_pairs(np.ones((3, 3), np.int32), 8)
    assert padded.shape == (8, 3) and (padded[3:] == 0).all()


def test_pad_pairs_oversize_raises_value_error():
    """An oversize pair list is a ValueError with the shapes in the
    message — not a bare assert, which vanishes under ``python -O`` and
    would let silent truncation drop matches."""
    with pytest.raises(ValueError, match=r"\(9, 3\).*8"):
        prune.pad_pairs(np.ones((9, 3), np.int32), 8)


def test_spatial_sort_lexicographic_tiebreak():
    """Equal primary-key runs are ordered lexicographically over the
    remaining dimensions, so duplicate-key cells land in adjacent
    (tighter) blocks; the output stays a permutation of the input."""
    rng = np.random.default_rng(8)
    # dim 0 has the largest span but only 3 distinct values: long
    # equal-key runs exercise the tie-break.
    a = np.stack([rng.choice([0, 5_000, 10_000], 500),
                  rng.integers(0, 40, 500),
                  rng.integers(0, 40, 500)], axis=1).astype(np.int32)
    s = prune.spatial_sort(a)
    assert sorted(map(tuple, s)) == sorted(map(tuple, a))
    # Full ordering: primary key, then the remaining dims in ascending
    # dimension order.
    keyed = [(int(r[0]), int(r[1]), int(r[2])) for r in s]
    assert keyed == sorted(keyed)


# ----------------------------------------------------- executor parity

def make_tasks(rng, k=8):
    tasks = []
    for i in range(k):
        a = clustered_coords(rng, int(rng.integers(1, 700)), 3)
        b = clustered_coords(rng, int(rng.integers(1, 700)), 3)
        tasks.append((i % 3, a, b, False))
        tasks.append((i % 3, a, a, True))
    tasks.append((0, np.zeros((0, 3), np.int32), a, False))
    return tasks


def test_executor_parity_dense_block_numpy():
    rng = np.random.default_rng(1)
    tasks = make_tasks(rng)
    eps = 40
    dense = PallasJoinExecutor(prune="dense")
    block = PallasJoinExecutor(prune="block")
    ref = NumpyJoinExecutor(count_similar_pairs_np)
    cd = dense.count_pairs(tasks, eps)
    cb = block.count_pairs(tasks, eps)
    cn = ref.count_pairs(tasks, eps)
    assert cd == cb == cn
    assert sum(cd) > 0
    assert dense.last_stats["block_pairs_evaluated"] == \
        dense.last_stats["block_pairs_total"]
    assert block.last_stats["block_pairs_total"] == \
        dense.last_stats["block_pairs_total"]
    assert block.last_stats["block_pairs_evaluated"] <= \
        block.last_stats["block_pairs_total"]
    assert ref.last_stats is None


def test_no_retrace_across_repeated_same_shape_queries():
    """Repeated same-shape dispatches must hit the memoized jitted
    callables without re-tracing (ops.TRACE_COUNTS bumps at trace time
    only) — the recompile guard of the batched executor."""
    rng = np.random.default_rng(2)
    tasks = make_tasks(rng, k=4)
    for prune_mode in ("dense", "block"):
        ex = PallasJoinExecutor(prune=prune_mode)
        first = ex.count_pairs(tasks, 25)       # traces once per bucket
        before = dict(ops.TRACE_COUNTS)
        for _ in range(3):
            assert ex.count_pairs(tasks, 25) == first
        assert dict(ops.TRACE_COUNTS) == before, prune_mode
        assert len(ex._fn_cache) > 0


@pytest.mark.parametrize("mode", ["bitmap", "auto", "sparse"])
@pytest.mark.parametrize("join_backend", ["numpy", "pallas"])
def test_make_join_executor_prune_validation(join_backend, mode):
    """A prune mode outside ("dense", "block") — the retired bitmap and
    auto modes included — fails loudly, naming the known modes, at the
    executor factory and at the front door; it never falls back."""
    from repro.core.cluster import RawArrayCluster
    known = r"known: \('dense', 'block'\)"
    with pytest.raises(ValueError, match=known):
        make_join_executor(join_backend, count_similar_pairs_np, prune=mode)
    with pytest.raises(ValueError, match=known):
        RawArrayCluster(None, None, 4, 1_000, join_backend=join_backend,
                        prune=mode)
    if join_backend == "pallas":
        with pytest.raises(ValueError, match="unknown prune mode"):
            PallasJoinExecutor(prune=mode)


# ------------------------------------------------------ backend parity

@pytest.fixture(scope="module")
def dataset():
    from repro.arrayio.catalog import build_catalog
    from repro.arrayio.generator import make_ptf_files
    files = make_ptf_files(n_files=10, cells_per_file_mean=900, seed=21)
    catalog, data = build_catalog(files,
                                  tempfile.mkdtemp(prefix="bprune_"),
                                  "fits", n_nodes=4)
    return catalog, data


def run_cluster(dataset, backend, prune, queries):
    from repro.arrayio.catalog import FileReader
    from repro.core.cluster import RawArrayCluster
    catalog, data = dataset
    cluster = RawArrayCluster(catalog, FileReader(catalog, data), 4,
                              8_000, policy="cost", min_cells=64,
                              backend=backend, join_backend="pallas",
                              prune=prune)
    return cluster.run_workload(queries)


def test_backend_parity_pruned(dataset):
    """Identical per-query match counts under prune=dense/block on the
    simulated backend and prune=block on the device mesh, with the
    block-pair counters populated on every ExecutedQuery."""
    from repro.core.workload import ptf1_workload, ptf2_workload
    catalog, _ = dataset
    queries = (ptf1_workload(catalog.domain, n_queries=4, eps=300, seed=7)
               + ptf2_workload(catalog.domain, n_queries=4, eps=300))
    runs = {
        ("simulated", "dense"): run_cluster(dataset, "simulated", "dense",
                                            queries),
        ("simulated", "block"): run_cluster(dataset, "simulated", "block",
                                            queries),
        ("jax_mesh", "block"): run_cluster(dataset, "jax_mesh", "block",
                                           queries),
    }
    base = [e.matches for e in runs[("simulated", "dense")]]
    assert sum(m or 0 for m in base) > 0
    for key, executed in runs.items():
        assert [e.matches for e in executed] == base, key
        joined = [e for e in executed if e.report.join_plan is not None]
        assert all(e.block_pairs_total is not None for e in joined), key
        assert all((e.block_pairs_evaluated or 0)
                   <= (e.block_pairs_total or 0) for e in joined), key
    blocked = runs[("simulated", "block")]
    dense = runs[("simulated", "dense")]
    assert (sum(e.block_pairs_total or 0 for e in blocked)
            == sum(e.block_pairs_total or 0 for e in dense))


def test_workload_summary_block_counters(dataset):
    from repro.backend import workload_summary
    from repro.core.workload import ptf2_workload
    catalog, _ = dataset
    queries = ptf2_workload(catalog.domain, n_queries=4, eps=300)
    summ = workload_summary(run_cluster(dataset, "simulated", "block",
                                        queries))
    assert "block_pairs_total" in summ
    assert summ["block_pairs_evaluated"] <= summ["block_pairs_total"]
    # The numpy executor path reports no block counters at all.
    from repro.arrayio.catalog import FileReader
    from repro.core.cluster import RawArrayCluster
    catalog, data = dataset
    np_run = RawArrayCluster(catalog, FileReader(catalog, data), 4, 8_000,
                             policy="cost", min_cells=64,
                             join_backend="numpy").run_workload(queries)
    assert "block_pairs_total" not in workload_summary(np_run)
