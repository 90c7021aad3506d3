"""Host-side block pruning for the block-sparse simjoin kernel.

Pure-numpy preprocessing that turns a coordinate set into the inputs the
``simjoin_pruned_count`` kernel consumes:

  1. ``spatial_sort`` orders cells along the longest dimension of their
     bounding box (lexicographic tie-break over the remaining
     dimensions) so consecutive 128-wide kernel blocks are spatially
     coherent (tight per-block boxes);
  2. ``block_bounds`` computes those per-block bounding boxes (real
     cells only — sentinel padding never enters a box);
  3. ``build_block_pairs`` keeps only the block pairs whose minimal L1
     box distance is ``<= eps`` — a sound prune because the minimal box
     distance lower-bounds the distance of every cell pair inside the
     two blocks (property-tested in ``test_hypothesis_properties``);
  4. ``pad_pairs``/``padded_pair_len`` pad surviving pair lists to a
     power-of-two bucket length so shape-bucketed batch dispatch does
     not retrace per distinct pair count.

The count is invariant under the reordering: the join is a sum over
unordered cell pairs, and self-join dedup compares *positions in the
sorted order*, which still counts each unordered pair exactly once.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def spatial_sort(coords: np.ndarray) -> np.ndarray:
    """Order (n, d) integer cell coordinates along the longest dimension
    of their bounding box, breaking ties lexicographically over the
    remaining dimensions (in ascending dimension order; stable), so
    equal-key runs stay spatially compact and per-block boxes come out
    tighter. A 0/1-cell set is returned unchanged; the pair count is
    invariant under any reordering (see the module docstring)."""
    if coords.shape[0] <= 1:
        return coords
    spans = coords.max(axis=0) - coords.min(axis=0)
    dim = int(np.argmax(spans))
    rest = [k for k in range(coords.shape[1]) if k != dim]
    # np.lexsort sorts by its LAST key first: primary = the longest
    # dimension, then the remaining dimensions most-significant first.
    keys = tuple(coords[:, k] for k in reversed(rest)) + (coords[:, dim],)
    return coords[np.lexsort(keys)]


def block_bounds(coords: np.ndarray, block: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Tight per-block bounding boxes of (n, d) coords split into
    ``block``-sized runs (last run possibly partial): (lo, hi) int64
    arrays of shape (ceil(n/block), d). Boxes come from real cells only,
    so downstream sentinel padding cannot loosen them."""
    if coords.shape[0] == 0:
        return (np.zeros((0, coords.shape[1]), np.int64),
                np.zeros((0, coords.shape[1]), np.int64))
    idx = np.arange(0, coords.shape[0], block)
    c = coords.astype(np.int64, copy=False)
    return (np.minimum.reduceat(c, idx, axis=0),
            np.maximum.reduceat(c, idx, axis=0))


def min_l1_box_dist(lo_a: np.ndarray, hi_a: np.ndarray,
                    lo_b: np.ndarray, hi_b: np.ndarray) -> np.ndarray:
    """(A, B) matrix of minimal L1 distances between two box sets given
    as (A, d)/(B, d) lo/hi corners: per dimension the gap between the
    closed intervals (zero when they overlap), summed over dimensions.
    Lower-bounds the L1 distance of any cell pair drawn from the two
    boxes — the soundness condition of the block prune."""
    gap = (np.maximum(lo_a[:, None, :] - hi_b[None, :, :], 0)
           + np.maximum(lo_b[None, :, :] - hi_a[:, None, :], 0))
    return gap.sum(axis=-1)


def build_block_pairs(a_sorted: np.ndarray, b_sorted: np.ndarray,
                      block: int, eps: int, same: bool
                      ) -> Tuple[np.ndarray, int]:
    """The live block-pair list for two spatially sorted coordinate
    sets: rows ``(block_i, block_j, 1)`` (int32) for every block pair
    whose minimal L1 box distance is ``<= eps``. Self-join mode keeps
    only ``i <= j`` pairs — every cell pair of an ``i > j`` block pair
    is eliminated by the kernel's ``i < j`` dedup mask anyway.

    Returns ``(pairs, dense_total)`` where ``dense_total`` is the number
    of block pairs the dense kernel would evaluate (the denominator of
    the ``block_pairs_evaluated / block_pairs_total`` counters)."""
    lo_a, hi_a = block_bounds(a_sorted, block)
    lo_b, hi_b = block_bounds(b_sorted, block)
    keep = min_l1_box_dist(lo_a, hi_a, lo_b, hi_b) <= eps
    if same:
        bi = np.arange(keep.shape[0])
        keep &= bi[:, None] <= bi[None, :]
    pi, pj = np.nonzero(keep)
    pairs = np.stack([pi, pj, np.ones_like(pi)], axis=1).astype(np.int32)
    return pairs, int(keep.size)


def padded_pair_len(n_pairs: int) -> int:
    """Bucket granularity for pair lists: the next power of two (at
    least 8), so batched dispatch sees a handful of pair-list shapes
    instead of one per distinct live-pair count."""
    n = max(int(n_pairs), 1)
    return max(8, 1 << (n - 1).bit_length())


def pad_pairs(pairs: np.ndarray, to_len: int) -> np.ndarray:
    """Pad a (P, 3) pair list to ``to_len`` rows with invalid
    ``(0, 0, 0)`` entries — the kernel multiplies their counts away.
    An oversize pair list raises ``ValueError`` (a real error, not an
    ``assert``: silent truncation here would drop matches, and asserts
    vanish under ``python -O``)."""
    if pairs.shape[0] == to_len:
        return pairs
    if pairs.shape[0] > to_len:
        raise ValueError(
            f"pair list of shape {pairs.shape} does not fit the padded "
            f"length {to_len}; pad_pairs only grows pair lists")
    out = np.zeros((to_len, 3), np.int32)
    out[:pairs.shape[0]] = pairs
    return out
