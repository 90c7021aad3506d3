"""The benchmark's copy of the plain reference against the program's
numpy pair count, on small inputs."""
import numpy as np
import pytest

from bench import reference
from repro.backend.executors import count_similar_pairs_np


@pytest.mark.parametrize("d,n,hi,eps", [(2, 300, 2000, 0), (2, 900, 3000, 120),
                                        (3, 700, 800, 60), (3, 2500, 5000, 300),
                                        (2, 3000, 300, 7), (3, 1500, 90, 2)])
def test_pair_count_matches_program_numpy(d, n, hi, eps):
    rng = np.random.default_rng(n + d)
    cells = rng.integers(1, hi, (n, d))
    cells = np.concatenate([cells, cells[:7]])        # duplicate cells
    want = count_similar_pairs_np(cells, cells, eps, same=True)
    assert reference.pair_count(cells, eps) == want


def test_pair_count_across_chunks(monkeypatch):
    # Dense buckets and a small chunk, so bucket pairs span chunks.
    monkeypatch.setattr(reference, "CHUNK", 1000)
    rng = np.random.default_rng(5)
    cells = rng.integers(1, 60, (3000, 2))
    want = count_similar_pairs_np(cells, cells, 3, same=True)
    assert reference.pair_count(cells, 3) == want


def test_pair_count_at_bucket_edges():
    # Pairs exactly eps apart across a bucket edge, in every direction.
    eps = 4
    base = np.array([[0, 0], [5, 0], [0, 5], [5, 5], [10, 0], [3, 2],
                     [2, 3], [-5, 4], [4, -5], [9, 9]]) + 100
    want = count_similar_pairs_np(base, base, eps, same=True)
    assert reference.pair_count(base, eps) == want > 0


def test_cells_in_box_is_closed():
    c = np.array([[1, 1], [5, 5], [5, 6], [6, 5], [3, 4]])
    got = reference.cells_in_box([(c[:3], None), (c[3:], None)],
                                 ((1, 1), (5, 5)))
    assert sorted(map(tuple, got)) == [(1, 1), (3, 4), (5, 5)]
