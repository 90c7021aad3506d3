"""Property-based tests (hypothesis), consolidated from the per-module
suites so the rest of the suite collects when the ``hypothesis`` dev extra
is not installed (``pip install -e .[dev]`` provides it)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.backend.artifacts import JoinArtifactCache  # noqa: E402
from repro.core.cache_state import CacheState  # noqa: E402
from repro.core.eviction import Triple, cost_based_eviction  # noqa: E402
from repro.core.geometry import (Box, bounding_box, box_subtract,  # noqa: E402
                                 expand, points_in_box, residual_boxes)
from repro.core.policies import (HotChunkReplication,  # noqa: E402
                                 ReplicationContext)
from repro.core.result_cache import ResultCache  # noqa: E402
from repro.core.rtree import EvolvingRTree  # noqa: E402


# ------------------------------------------------------------- eviction

def T(l, f, chunks):
    return Triple(l, f, frozenset(chunks))


@given(st.integers(0, 10_000), st.integers(50, 2000))
@settings(max_examples=40, deadline=None)
def test_budget_never_exceeded_property(seed, budget):
    import random
    rnd = random.Random(seed)
    chunk_bytes = {i: rnd.randint(10, 200) for i in range(30)}
    file_bytes = {i: rnd.randint(500, 5000) for i in range(6)}
    history = []
    for l in range(1, 12):
        f = rnd.randrange(6)
        cs = rnd.sample(range(30), rnd.randint(1, 5))
        history.append(T(l, f, cs))
    current = [T(12, 0, rnd.sample(range(30), 3))]
    res = cost_based_eviction(history, current, budget,
                              chunk_bytes, file_bytes)
    used = sum(chunk_bytes[c] for c in res.cached_chunks)
    current_bytes = sum(chunk_bytes[c] for c in
                        set().union(*[t.chunk_ids for t in current]))
    # Current query may overflow on its own; beyond that, budget holds.
    assert used <= max(budget, current_bytes)
    for t in res.state:
        assert t.chunk_ids <= res.cached_chunks


# ---------------------------------------------------------- replication

def _random_state_ops(rnd, state, n_nodes, n_ops=60):
    """Drive a CacheState through a random admit/drop/split-like/fail
    sequence using only the accessor surface; yields after every op."""
    for _ in range(n_ops):
        op = rnd.randrange(5)
        cid = rnd.randint(1, 24)
        if op == 0:                      # admit with a random replica set
            state.cached.add(cid)
            ks = rnd.randint(1, n_nodes)
            state.set_replicas(
                cid, tuple(rnd.randrange(n_nodes) for _ in range(ks)))
        elif op == 1:                    # admit single-copy
            state.cached.add(cid)
            state.ensure_location(cid, rnd.randrange(n_nodes))
        elif op == 2:                    # full drop
            state.drop(cid)
        elif op == 3:                    # one copy dies
            state.drop_replica(cid, rnd.randrange(n_nodes))
        else:                            # node failure: every copy there
            node = rnd.randrange(n_nodes)
            for c, reps in state.location_items():
                if node in reps:
                    state.drop_replica(c, node)
        yield


@given(st.integers(0, 2**31 - 1), st.integers(2, 6))
@settings(max_examples=40, deadline=None)
def test_replica_sets_wellformed_and_bytes_account_per_replica(seed,
                                                               n_nodes):
    """After ANY accessor-driven op sequence: every stored replica set
    is a non-empty de-duplicated tuple whose head is the primary, every
    cached chunk stays located, and per-node byte accounting equals the
    sum of per-replica charges (= ``cached_bytes``)."""
    import random
    rnd = random.Random(seed)
    state = CacheState(n_nodes=n_nodes, node_budget_bytes=10_000)
    chunk_bytes = {cid: rnd.randint(1, 500) for cid in range(1, 25)}
    for _ in _random_state_ops(rnd, state, n_nodes):
        for c, reps in state.location_items():
            assert reps, "empty replica tuple stored"
            assert len(set(reps)) == len(reps), "duplicate replica"
            assert state.node_of(c) == reps[0]
            assert all(0 <= n < n_nodes for n in reps)
        assert all(state.replicas_of(c) for c in state.cached)
        per_node = state.bytes_by_node(chunk_bytes)
        assert sum(per_node.values()) == sum(
            chunk_bytes[c] * len(state.replicas_of(c))
            for c in state.cached)
        assert sum(per_node.values()) == state.cached_bytes(chunk_bytes)


@given(st.integers(0, 2**31 - 1), st.integers(1, 3), st.integers(2, 5),
       st.sampled_from(["global", "node"]))
@settings(max_examples=40, deadline=None)
def test_hot_replication_never_touches_residency_or_primaries(
        seed, k, n_nodes, scope):
    """A replication round only ADDS copies within leftover budget: the
    resident set and every primary are bit-identical afterwards, no
    chunk exceeds ``k`` copies, budget-charged bytes never grow past the
    scope's limit, and an immediate second round is an exact no-op."""
    import random
    rnd = random.Random(seed)
    budget = rnd.randint(500, 3000)
    state = CacheState(n_nodes=n_nodes, node_budget_bytes=budget,
                       budget_scope=scope)
    chunk_bytes = {}
    for cid in range(1, rnd.randint(3, 15)):
        chunk_bytes[cid] = rnd.randint(1, budget)
        state.cached.add(cid)
        state.set_replicas(cid, rnd.randrange(n_nodes))
    freq = {cid: rnd.uniform(0.0, 6.0) for cid in chunk_bytes}
    pol = HotChunkReplication(k=k, threshold=3.0)
    before_primary = before_cached = None
    for round_no in range(2):
        before_primary = state.primary_map()
        before_cached = set(state.cached)
        before_used = state.bytes_by_node(chunk_bytes)
        shed = pol.replicate(ReplicationContext(
            state=state, chunk_bytes=chunk_bytes, freq=freq,
            home_of=lambda c: 0))
        assert shed >= 0
        assert state.primary_map() == before_primary
        assert state.cached == before_cached
        after_used = state.bytes_by_node(chunk_bytes)
        if scope == "node":
            for n in range(n_nodes):
                assert after_used.get(n, 0) <= max(budget,
                                                   before_used.get(n, 0))
        else:
            assert sum(after_used.values()) <= max(
                state.total_budget, sum(before_used.values()))
        for c in state.cached:
            reps = state.replicas_of(c)
            assert 1 <= len(reps) <= max(k, len(set(reps)))
            assert len(set(reps)) == len(reps)
        if round_no == 1:                # idempotent re-run
            assert shed == 0
            assert after_used == before_used


@given(st.integers(0, 2**31 - 1), st.integers(2, 5))
@settings(max_examples=30, deadline=None)
def test_drop_split_fail_never_orphans_artifact_or_result_entries(
        seed, n_nodes):
    """The listener contract under churn: after any drop/split-like/fail
    sequence plus the post-round ``sync_devices`` reconcile, the
    artifact cache holds no entry for a non-resident chunk and the
    result tier serves no entry stored before a residency change."""
    import random
    rnd = random.Random(seed)
    state = CacheState(n_nodes=n_nodes, node_budget_bytes=10_000)
    artifacts = JoinArtifactCache()
    results = ResultCache()
    state.add_listener(artifacts)
    state.add_listener(results)
    key = ResultCache.key_of(Box((0,), (9,)), 1)
    coords = np.zeros((3, 2), dtype=np.int64)
    box = Box((0, 0), (9, 9))
    prev = (frozenset(state.cached), state.location_snapshot())
    for _ in _random_state_ops(rnd, state, n_nodes, n_ops=40):
        for cid in state.cached:         # warm artifacts for residents
            artifacts.sorted_coords(artifacts.view(cid, box, box, coords),
                                    lambda: coords)
        results.store(key, 1)            # stored against current state
        state.sync_devices()             # reconcile every listener
        assert artifacts.chunk_ids() <= state.cached, "orphaned artifact"
        now = (frozenset(state.cached), state.location_snapshot())
        if now != prev:                  # ANY residency/replica change
            assert results.lookup(key) is None, \
                "result entry survived a residency change"
        prev = now
    state.sync_devices()
    assert artifacts.chunk_ids() <= state.cached


# ------------------------------------------------------------- geometry

coords_strategy = st.lists(
    st.tuples(st.integers(0, 50), st.integers(0, 50), st.integers(0, 50)),
    min_size=1, max_size=200)


@given(coords_strategy)
@settings(max_examples=50, deadline=None)
def test_bounding_box_is_tight_and_contains_all(pts):
    arr = np.array(pts, dtype=np.int64)
    bb = bounding_box(arr)
    assert points_in_box(arr, bb).all()
    lo, hi = bb.as_arrays()
    assert (arr.min(axis=0) == lo).all() and (arr.max(axis=0) == hi).all()


@given(coords_strategy, st.integers(0, 5))
@settings(max_examples=30, deadline=None)
def test_expand_contains_all_l1_neighbors(pts, eps):
    arr = np.array(pts, dtype=np.int64)
    bb = bounding_box(arr)
    grown = expand(bb, eps)
    # Any point at L1 distance <= eps from a member is inside the expansion.
    shifted = arr.copy()
    shifted[:, 0] += eps
    assert points_in_box(shifted, grown).all()


box_strategy = st.builds(
    lambda lo, side: Box(tuple(lo), tuple(l + s for l, s in zip(lo, side))),
    st.tuples(st.integers(0, 40), st.integers(0, 40), st.integers(0, 40)),
    st.tuples(st.integers(0, 30), st.integers(0, 30), st.integers(0, 30)))


@given(box_strategy, box_strategy)
@settings(max_examples=60, deadline=None)
def test_box_subtract_partitions_exactly(a, b):
    """The residual pieces of a \\ b are disjoint, inside a, outside b,
    and conserve volume — the semantic-reuse decomposition invariant."""
    pieces = box_subtract(a, b)
    inter = a.intersection(b)
    assert len(pieces) <= 2 * a.ndim
    assert sum(p.volume() for p in pieces) == \
        a.volume() - (inter.volume() if inter else 0)
    for i, p in enumerate(pieces):
        assert a.contains_box(p)
        assert not p.overlaps(b)
        for q in pieces[i + 1:]:
            assert not p.overlaps(q)


@given(box_strategy, st.lists(box_strategy, max_size=4))
@settings(max_examples=40, deadline=None)
def test_residual_boxes_cover_exactly_the_uncovered_cells(q, covers):
    """Every integer cell of the query is either inside some cover or in
    exactly one residual box."""
    residual = residual_boxes(q, covers)
    rng = np.random.default_rng(0)
    pts = np.stack([rng.integers(lo, hi + 1, size=64)
                    for lo, hi in zip(q.lo, q.hi)], axis=1)
    for p in pts:
        covered = any(c.contains_point(p) for c in covers)
        in_residual = sum(r.contains_point(p) for r in residual)
        assert in_residual == (0 if covered else 1)


# ---------------------------------------------------------------- rtree

def make_tree(coords, min_cells=5):
    counter = iter(range(1, 1_000_000))
    return EvolvingRTree(0, np.asarray(coords, dtype=np.int64), 12,
                         min_cells, lambda: next(counter))


@given(st.integers(0, 2**31 - 1), st.integers(2, 40))
@settings(max_examples=25, deadline=None)
def test_invariants_under_random_workload(seed, min_cells):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 400))
    coords = rng.integers(0, 80, size=(n, 2))
    t = make_tree(coords, min_cells=min_cells)
    for _ in range(8):
        lo = rng.integers(0, 70, size=2)
        hi = lo + rng.integers(1, 25, size=2)
        q = Box(tuple(int(x) for x in lo), tuple(int(x) for x in hi))
        got = t.refine(q)
        t.validate()
        # Leaves returned are exactly those holding >= 1 queried cell.
        expect = set()
        for c in t.leaves():
            if points_in_box(t.coords[c.cell_idx], q).any():
                expect.add(c.chunk_id)
        assert {c.chunk_id for c in got} == expect


# -------------------------------------------------------- simjoin kernel

@given(st.lists(st.tuples(st.integers(0, 300), st.integers(0, 300)),
                min_size=1, max_size=60),
       st.lists(st.tuples(st.integers(0, 300), st.integers(0, 300)),
                min_size=1, max_size=60),
       st.integers(1, 16))
@settings(max_examples=50, deadline=None)
def test_min_l1_box_dist_lower_bounds_cell_distance(pts_a, pts_b, block):
    """Soundness of the block prune: the minimal L1 distance between two
    blocks' bounding boxes never exceeds the L1 distance of ANY cell
    pair drawn from the two blocks — so dropping block pairs with box
    distance > eps cannot drop a matching cell pair. Blocks are taken
    over the *spatially sorted* order (longest-dimension key with
    lexicographic tie-break), exactly as the executor builds them.
    (Pure numpy: the prune module never imports jax.)"""
    from repro.kernels.simjoin.prune import (block_bounds, min_l1_box_dist,
                                             spatial_sort)
    a = spatial_sort(np.asarray(pts_a, dtype=np.int64))
    b = spatial_sort(np.asarray(pts_b, dtype=np.int64))
    lo_a, hi_a = block_bounds(a, block)
    lo_b, hi_b = block_bounds(b, block)
    dmat = min_l1_box_dist(lo_a, hi_a, lo_b, hi_b)
    for i in range(a.shape[0]):
        for j in range(b.shape[0]):
            cell_dist = int(np.abs(a[i] - b[j]).sum())
            assert dmat[i // block, j // block] <= cell_dist


@given(st.lists(st.tuples(st.integers(0, 50), st.integers(0, 6),
                          st.integers(0, 6)), min_size=2, max_size=80))
@settings(max_examples=50, deadline=None)
def test_spatial_sort_permutation_and_tiebreak(pts):
    """``spatial_sort`` is a permutation whose order is the primary
    (longest-span) dimension with a full lexicographic tie-break over
    the remaining dimensions — equal-key runs can never interleave."""
    from repro.kernels.simjoin.prune import spatial_sort
    a = np.asarray(pts, dtype=np.int64)
    s = spatial_sort(a)
    assert sorted(map(tuple, s)) == sorted(map(tuple, a))
    spans = a.max(axis=0) - a.min(axis=0)
    dim = int(np.argmax(spans))
    rest = [k for k in range(a.shape[1]) if k != dim]
    keys = [tuple(int(r[k]) for k in [dim] + rest) for r in s]
    assert keys == sorted(keys)


@given(st.integers(0, 2**31 - 1), st.integers(1, 300), st.integers(0, 6))
@settings(max_examples=25, deadline=None)
def test_pruned_simjoin_property_random(seed, n, eps):
    """Pruned-vs-oracle parity over random self-joins (block-boundary
    sizes and eps=0 included by generation)."""
    pytest.importorskip("jax")
    from repro.kernels.simjoin import ops
    from repro.kernels.simjoin.ref import count_pairs_ref
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 40, size=(n, 2)).astype(np.int32)
    got, total, evaluated = ops.count_similar_pairs_pruned_np(a, a, eps,
                                                              True)
    want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(a), eps, True))
    assert got == want
    assert evaluated <= total


@given(st.integers(0, 2**31 - 1), st.integers(1, 80), st.integers(1, 80),
       st.integers(0, 4))
@settings(max_examples=20, deadline=None)
def test_simjoin_property_random(seed, n, m, eps):
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.simjoin import ops
    from repro.kernels.simjoin.ref import count_pairs_ref
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 12, size=(n, 2)).astype(np.int32)
    b = rng.integers(0, 12, size=(m, 2)).astype(np.int32)
    got = int(ops.count_similar_pairs(jnp.asarray(a), jnp.asarray(b),
                                      eps, False))
    want = int(count_pairs_ref(jnp.asarray(a), jnp.asarray(b), eps, False))
    assert got == want


# ------------------------------------------------------ telemetry (obs)

@given(st.lists(st.floats(min_value=-1e9, max_value=1e9,
                          allow_nan=False, allow_infinity=False),
                max_size=200),
       st.lists(st.floats(min_value=0, max_value=1e6,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=12, unique=True))
@settings(max_examples=60, deadline=None)
def test_histogram_bucket_counts_sum_to_observation_count(values, bounds):
    from repro.obs import Histogram
    h = Histogram("prop", bounds=tuple(sorted(bounds)))
    for v in values:
        h.observe(v)
    assert sum(h.bucket_counts) == h.count == len(values)
    assert len(h.bucket_counts) == len(h.bounds) + 1
    # every observation landed in exactly the first bucket whose upper
    # bound admits it
    recomputed = [0] * (len(h.bounds) + 1)
    for v in values:
        for i, b in enumerate(h.bounds):
            if v <= b:
                recomputed[i] += 1
                break
        else:
            recomputed[-1] += 1
    assert recomputed == h.bucket_counts
