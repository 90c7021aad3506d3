"""The plain reference: unordered cell pairs within L1 ``eps`` in a box.

It reads the generated cells, never the program's cache, chunks or
artifacts, and imports nothing of the program. The cells inside the
query box (bounds inclusive) are put in a grid of buckets of side
``eps + 1``: two cells within L1 ``eps`` differ by at most ``eps`` in
every coordinate, so they lie in the same bucket or in neighbouring
ones. Every candidate pair of such buckets is then compared cell by cell
with numpy.
"""
from __future__ import annotations

import itertools
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence, Tuple

import numpy as np

#: Candidate cell pairs compared at once by one thread.
CHUNK = 1 << 21


def _chunk(pts: np.ndarray, sa: np.ndarray, na: np.ndarray,
           sb: np.ndarray, nb: np.ndarray, eps: int, same: bool) -> int:
    """Pairs within ``eps`` between bucket ``k`` of each side, for every
    ``k``: rows ``sa[k]:sa[k]+na[k]`` against rows ``sb[k]:sb[k]+nb[k]``
    of ``pts``; with ``same`` the two buckets are one and only rows
    ``i < j`` count."""
    sizes = na * nb
    k = np.repeat(np.arange(len(sizes)), sizes)
    r = np.arange(int(sizes.sum()), dtype=np.int64) - np.repeat(
        np.cumsum(sizes) - sizes, sizes)
    q, m = np.divmod(r, nb[k])
    i, j = sa[k] + q, sb[k] + m
    hit = np.abs(pts[i] - pts[j]).sum(axis=1) <= eps
    if same:
        hit &= i < j
    return int(hit.sum())


def _between(pts: np.ndarray, sa: np.ndarray, na: np.ndarray,
             sb: np.ndarray, nb: np.ndarray, eps: int, same: bool,
             pool: ThreadPoolExecutor) -> int:
    """:func:`_chunk` over all bucket pairs, cut into runs of whole
    bucket pairs of about :data:`CHUNK` candidates each; numpy drops the
    GIL inside its array loops, so the runs overlap on the threads."""
    ends = np.cumsum(na * nb)
    cuts = np.searchsorted(ends, np.arange(CHUNK, int(ends[-1]) if len(ends)
                                           else 0, CHUNK), side="right")
    bounds = np.unique(np.concatenate([[0], cuts, [len(ends)]]))
    runs = [(lo, hi) for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]
    return sum(pool.map(lambda lh: _chunk(
        pts, sa[lh[0]:lh[1]], na[lh[0]:lh[1]], sb[lh[0]:lh[1]],
        nb[lh[0]:lh[1]], eps, same), runs))


def pair_count(cells: np.ndarray, eps: int, threads: int = 8) -> int:
    """Unordered pairs of distinct rows of ``cells`` within L1 ``eps``."""
    if len(cells) < 2:
        return 0
    pts = np.asarray(cells, dtype=np.int64)
    # Bucket coordinates, shifted so that a neighbour of any bucket has
    # non-negative coordinates too.
    b = (pts - pts.min(axis=0)) // (int(eps) + 1) + 1
    dims = b.max(axis=0) + 2
    key = np.ravel_multi_index(b.T, dims)
    order = np.argsort(key, kind="stable")
    pts, key = pts[order], key[order]
    keys, starts, counts = np.unique(key, return_index=True,
                                     return_counts=True)
    coords = np.stack(np.unravel_index(keys, dims), axis=1)
    pool = ThreadPoolExecutor(threads)
    total = _between(pts, starts, counts, starts, counts, eps, True, pool)
    d = pts.shape[1]
    for off in itertools.product((-1, 0, 1), repeat=d):
        # Each unordered pair of neighbouring buckets once: the offsets
        # whose first non-zero step is +1.
        nz = [o for o in off if o]
        if not nz or nz[0] < 0:
            continue
        want = np.ravel_multi_index((coords + np.array(off)).T, dims)
        at = np.searchsorted(keys, want)
        at = np.minimum(at, len(keys) - 1)
        ok = keys[at] == want
        total += _between(pts, starts[ok], counts[ok], starts[at[ok]],
                          counts[at[ok]], eps, False, pool)
    pool.shutdown()
    return total


def cells_in_box(files: Sequence[Tuple[np.ndarray, np.ndarray]],
                 box) -> np.ndarray:
    """Every generated cell inside ``box = (lo, hi)``, bounds inclusive."""
    lo, hi = np.asarray(box[0]), np.asarray(box[1])
    return np.concatenate([c[np.all((c >= lo) & (c <= hi), axis=1)]
                           for c, _ in files])
