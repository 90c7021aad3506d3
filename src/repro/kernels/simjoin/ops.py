"""jit'd wrappers around the simjoin Pallas kernels: padding, sentinel
injection, block-count reduction, numpy-friendly entry points usable as
``RawArrayCluster.join_fn``, and the pruned (block-sparse) variants fed
by the host-side ``repro.kernels.simjoin.prune`` preprocessing.

``TRACE_COUNTS`` tallies how often each jitted entry point is *traced*
(the counter bumps run at trace time only): repeated same-shape
dispatches must not grow it — the no-recompile guarantee
``tests/test_simjoin_pruning.py`` asserts."""
from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.simjoin.simjoin import (BLOCK, PAIR_CAP, SENTINEL,
                                           simjoin_dense_count,
                                           simjoin_pruned_count)

# Entry-point name -> times jax traced it (bumped at trace time only).
TRACE_COUNTS: "collections.Counter[str]" = collections.Counter()

#: Stack depth of every batched dispatch. Executors cut a shape bucket
#: into groups of this many chunk pairs and pad the last group, so how
#: many pairs share a bucket (which varies with node placement from one
#: pass to the next) never shapes a compile.
GROUP = 8


def _pad_cm(x: jax.Array, sentinel: int) -> jax.Array:
    """(N, d) -> coordinate-major (d, N_padded) with sentinel fill."""
    n, d = x.shape
    npad = (-n) % BLOCK
    xt = jnp.transpose(x.astype(jnp.int32))
    if npad or n == 0:
        pad_n = npad if n else BLOCK
        xt = jnp.pad(xt, ((0, 0), (0, pad_n)), constant_values=sentinel)
    return xt


@functools.partial(jax.jit, static_argnames=("eps", "same", "interpret"))
def count_similar_pairs(a: jax.Array, b: jax.Array, eps: int, same: bool,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Unordered L1-neighbor pair count between coordinate sets (see
    ref.count_pairs_ref)."""
    TRACE_COUNTS["count_similar_pairs"] += 1
    at = _pad_cm(a, SENTINEL)
    bt = _pad_cm(b, -SENTINEL)
    return simjoin_dense_count(at, bt, eps, same, interpret=interpret)


def count_similar_pairs_np(a: np.ndarray, b: np.ndarray, eps: int,
                           same: bool) -> int:
    """Drop-in ``join_fn`` for repro.core.cluster.RawArrayCluster."""
    if a.shape[0] == 0 or b.shape[0] == 0:
        return 0
    return int(count_similar_pairs(jnp.asarray(a, jnp.int32),
                                   jnp.asarray(b, jnp.int32), int(eps),
                                   bool(same)))


def pad_cm_np(x: np.ndarray, sentinel: int) -> np.ndarray:
    """Host-side version of ``_pad_cm``: (N, d) int coords -> coordinate-
    major (d, N_padded) int32 with sentinel fill, N_padded a positive
    multiple of BLOCK. Used to stack shape-bucketed pair batches before a
    single device transfer."""
    n, d = x.shape
    pad_n = (-n) % BLOCK if n else BLOCK
    xt = np.ascontiguousarray(x.astype(np.int32, copy=False).T)
    if pad_n:
        xt = np.pad(xt, ((0, 0), (0, pad_n)), constant_values=sentinel)
    return xt


def pack_pairs_np(pairs: np.ndarray, to_len: int) -> np.ndarray:
    """Host-side SMEM layout of a (P, 3) block-pair list for the pruned
    kernel: padded with invalid ``(0, 0, 0)`` rows to ``to_len`` (a
    power of two, ``prune.padded_pair_len``) and split into
    ``(to_len // L, 3L)`` int32, one flat row per kernel call of
    ``L = min(to_len, PAIR_CAP)`` pairs."""
    from repro.kernels.simjoin.prune import pad_pairs
    rows = min(int(to_len), PAIR_CAP)
    if to_len % rows:
        raise ValueError(f"padded pair length {to_len} is not a multiple "
                         f"of the per-call length {rows}")
    padded = pad_pairs(np.asarray(pairs, np.int32), int(to_len))
    return np.ascontiguousarray(padded.reshape(to_len // rows, 3 * rows))


def _count_first(count_one, n: jax.Array, stacks: Tuple[jax.Array, ...]
                 ) -> jax.Array:
    """(k,) int32: ``count_one`` of the first ``n`` elements of the
    stacked operands, in a loop whose trip count is traced (so ``n``
    never shapes a compile), and 0 for the padding after them."""
    k = stacks[0].shape[0]

    def body(i, out):
        return out.at[i].set(count_one(*(s[i] for s in stacks)))
    return jax.lax.fori_loop(0, n, body, jnp.zeros((k,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("eps", "same", "interpret"))
def count_similar_pairs_batch(a_stack: jax.Array, b_stack: jax.Array,
                              n: jax.Array, eps: int, same: bool,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Batched pair counting: ``a_stack``/``b_stack`` are (k, d, Na) /
    (k, d, Nb) coordinate-major stacks (pre-padded to BLOCK multiples with
    sentinels, e.g. via :func:`pad_cm_np`) whose first ``n`` elements
    are real. Returns (k,) int32 match counts — one dispatch per group
    of :data:`GROUP` chunk pairs instead of one per pair. Each element
    runs the unbatched grid, so the self-join ``program_id`` masking is
    unchanged."""
    TRACE_COUNTS["batch"] += 1
    return _count_first(
        lambda a, b: simjoin_dense_count(a, b, eps, same,
                                         interpret=interpret),
        n, (a_stack, b_stack))


@functools.partial(jax.jit, static_argnames=("eps", "same", "interpret"))
def count_similar_pairs_pruned(a_cm: jax.Array, b_cm: jax.Array,
                               pairs: jax.Array, eps: int, same: bool,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Block-sparse pair counting for ONE coordinate-set pair:
    ``a_cm``/``b_cm`` are (d, N) coordinate-major sets already spatially
    sorted and sentinel-padded on host (``prune.spatial_sort`` +
    :func:`pad_cm_np`), ``pairs`` the live block-pair list from
    ``prune.build_block_pairs`` in the :func:`pack_pairs_np` layout.
    Returns the scalar int32 match count."""
    TRACE_COUNTS["pruned"] += 1
    return simjoin_pruned_count(a_cm, b_cm, pairs, eps, same,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "same", "interpret"))
def count_similar_pairs_pruned_batch(a_stack: jax.Array, b_stack: jax.Array,
                                     pairs_stack: jax.Array, n: jax.Array,
                                     eps: int, same: bool,
                                     interpret: Optional[bool] = None
                                     ) -> jax.Array:
    """Batched block-sparse pair counting: (k, d, Na) / (k, d, Nb)
    coordinate-major stacks plus a (k, m, 3L) pair-list stack (every
    element's live pairs padded to the bucket's length with
    ``valid == 0`` rows and laid out by :func:`pack_pairs_np`), of which
    the first ``n`` elements are real. Returns (k,) int32 match
    counts."""
    TRACE_COUNTS["pruned_batch"] += 1
    return _count_first(
        lambda a, b, pr: simjoin_pruned_count(a, b, pr, eps, same,
                                              interpret=interpret),
        n, (a_stack, b_stack, pairs_stack))


def count_similar_pairs_pruned_np(a: np.ndarray, b: np.ndarray, eps: int,
                                  same: bool,
                                  interpret: Optional[bool] = None
                                  ) -> Tuple[int, int, int]:
    """Full host pipeline for one pair — sort, prune, pad, dispatch —
    returning ``(match_count, block_pairs_total, block_pairs_evaluated)``
    where *total* is the dense kernel's grid size and *evaluated* the
    live pairs actually dispatched. Used by benchmarks and parity tests;
    the batched executor path lives in ``repro.backend.executors``."""
    from repro.kernels.simjoin import prune
    if a.shape[0] == 0 or b.shape[0] == 0:
        return 0, 0, 0
    a_s = prune.spatial_sort(np.asarray(a))
    b_s = a_s if same else prune.spatial_sort(np.asarray(b))
    pairs, total = prune.build_block_pairs(a_s, b_s, BLOCK, int(eps),
                                           bool(same))
    if pairs.shape[0] == 0:
        return 0, total, 0
    at = pad_cm_np(a_s, SENTINEL)
    bt = pad_cm_np(b_s, -SENTINEL)
    packed = pack_pairs_np(pairs, prune.padded_pair_len(pairs.shape[0]))
    got = count_similar_pairs_pruned(jnp.asarray(at), jnp.asarray(bt),
                                     jnp.asarray(packed), int(eps),
                                     bool(same), interpret=interpret)
    return int(got), total, int(pairs.shape[0])
