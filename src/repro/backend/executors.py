"""Join executors: per-node grouped chunk-pair work -> match counts
(extracted from ``repro.core.cluster``).

  * ``"numpy"``  — the reference executor: one blocked numpy evaluation
    per chunk pair (``join_fn`` override preserved).
  * ``"pallas"`` — the batched executor: each node's chunk-pair work is
    grouped, coordinate sets are padded to the kernel's 128-wide BLOCK,
    and shape-bucketed pair batches are dispatched to the
    ``kernels/simjoin`` Pallas kernel (compiled on the chip; interpret
    mode on CPU devices only, per ``simjoin.interpret_mode``). Its
    ``prune`` knob selects the grid:

      - ``"block"`` (default) — coordinates spatially sorted, per-block
        bounding boxes pruned against ``eps`` on host, only live block
        pairs scalar-prefetched into the kernel
        (``kernels.simjoin.prune``);
      - ``"dense"`` — every block pair evaluated (the parity reference).

Host-side prep (sort, boxes, padding, pair lists) is memoized in a
:class:`repro.backend.artifacts.JoinArtifactCache` when tasks carry
:class:`~repro.backend.artifacts.ChunkView` handles (attached by the
backends, invalidated with cache residency); bare ndarray tasks prep
uncached, preserving the seed behavior for direct callers. Prep runs in
two steps: grouping (:meth:`PallasJoinExecutor.iter_batches`) decides
every batch without building its arrays, and stacking
(:meth:`PallasJoinExecutor.stack`) builds the arrays of only the
batches that will be placed on a device.

Every pallas dispatch records ``last_stats``: ``block_pairs_total`` (the
dense grid size) and ``block_pairs_evaluated`` (block pairs actually
dispatched), plus ``prep_s``/``dispatch_s`` wall-clock and the query's
``artifact_hits``/``artifact_misses`` — the backends surface all of them
per query on ``ExecutedQuery``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.backend.artifacts import ChunkView, JoinArtifactCache, task_coords
from repro.obs.trace import NULL_TRACER

JOIN_BACKENDS = ("numpy", "pallas")
PRUNE_MODES = ("dense", "block")

# One unit of join work: (node, a side, b side, self-join?). Each side is
# a (n, d) coordinate array or a ChunkView wrapping one (see
# repro.backend.artifacts.task_coords).
JoinTask = Tuple[int, np.ndarray, np.ndarray, bool]


@dataclasses.dataclass
class PreparedBatch:
    """One group of a shape bucket's tasks, decided for dispatch but
    stacked only on demand.

    ``idxs`` are the group's tasks (at most ``ops.GROUP``); ``fn_key``
    identifies the jitted entry point + static shape bucket (the
    executor memoizes the bound callable per ``fn_key`` + eps); ``node``
    is the executing node, ``None`` for node-agnostic bucketing.
    ``rows(i)`` gives task ``i``'s kernel inputs: its padded a side, b
    side and, on the block-sparse grid, its packed pair list.

    ``arrays`` stays ``None`` until :meth:`PallasJoinExecutor.stack`
    writes those rows into ``(a_stack, b_stack)`` (dense) or
    ``(a_stack, b_stack, pairs_stack)`` (block-sparse), each
    ``ops.GROUP`` deep: the tasks ``idxs``, then inert padding. The mesh
    backend stacks only the batches it does not find among its pinned
    device stacks, and places those onto ``node``'s device."""

    node: Optional[int]
    same: bool
    idxs: List[int]
    fn_key: tuple
    rows: Callable[[int], Tuple[np.ndarray, ...]]
    arrays: Optional[Tuple[np.ndarray, ...]] = None


def count_similar_pairs_np(a: np.ndarray, b: np.ndarray, eps: int,
                           same: bool, block: int = 4096) -> int:
    """Unordered (x != y) L1-neighbor pairs between cell coordinate sets.
    Blocked to bound memory; numpy reference executor."""
    if a.shape[0] == 0 or b.shape[0] == 0:
        return 0
    total = 0
    for i0 in range(0, a.shape[0], block):
        ai = a[i0:i0 + block]
        for j0 in range(0, b.shape[0], block):
            bj = b[j0:j0 + block]
            dist = np.abs(ai[:, None, :].astype(np.int64)
                          - bj[None, :, :].astype(np.int64)).sum(axis=2)
            hit = dist <= eps
            if same:
                # Count each unordered pair once; drop identical cells.
                ii = i0 + np.arange(ai.shape[0])[:, None]
                jj = j0 + np.arange(bj.shape[0])[None, :]
                hit &= ii < jj
            total += int(hit.sum())
    return total


def bucket_by_shape(tasks: Sequence[JoinTask], block: int,
                    by_node: bool = False) -> Dict[tuple, List[int]]:
    """Group non-empty tasks into batched-dispatch buckets keyed by
    self-join mode and BLOCK-padded coordinate-set shapes (plus the
    executing node when ``by_node`` — the mesh backend pins each bucket
    to its node's device). Returns key -> task indices."""
    buckets: Dict[tuple, List[int]] = {}
    for i, (node, a, b, same) in enumerate(tasks):
        ca, cb = task_coords(a), task_coords(b)
        if ca.shape[0] == 0 or cb.shape[0] == 0:
            continue
        na = -(-ca.shape[0] // block) * block
        nb = -(-cb.shape[0] // block) * block
        key = (node, same, na, nb) if by_node else (same, na, nb)
        buckets.setdefault(key, []).append(i)
    return buckets


class NumpyJoinExecutor:
    """Reference executor: evaluate each pair independently."""

    # The fault points a join round through this executor crosses, in
    # order (the backends arm them — with retry — before dispatching;
    # see ``SimulatedBackend._arm_join_points``). The numpy reference
    # has no host-prep stage to fail, only the dispatch itself.
    fault_points = ("dispatch.kernel",)

    def __init__(self, join_fn: Callable[..., int]):
        self.join_fn = join_fn
        # Block-pair counters are a kernel-path concept; the numpy
        # reference has none (ExecutedQuery fields stay None).
        self.last_stats: Optional[Dict[str, int]] = None
        # Backends swap in a live tracer at bind time (telemetry on).
        self.tracer = NULL_TRACER

    def count_pairs(self, tasks: Sequence[JoinTask], eps: int) -> List[int]:
        """Per-task match counts via the (overridable) numpy predicate
        (ChunkView task sides are unwrapped to the raw arrays the
        predicate expects)."""
        with self.tracer.span("dispatch", tasks=len(tasks)):
            return [self.join_fn(task_coords(a), task_coords(b), eps, same)
                    for _, a, b, same in tasks]


class PallasJoinExecutor:
    """Batched executor over the ``kernels/simjoin`` Pallas kernels.

    Each node's chunk-pair tasks are padded to BLOCK and bucketed by
    padded shape and self-join mode; each bucket is dispatched as
    stacked kernel calls of ``ops.GROUP`` tasks — turning a
    pair-at-a-time python loop into a handful of jit'd launches per
    query, with a stack depth that never varies (so a bucket's size
    never forces a compile). Buckets span nodes because the
    simulated backend executes every node's work on this one device; the
    mesh backend (``repro.backend.jax_mesh``) keys buckets by node and
    pins each bucket to that node's device.

    ``prune`` selects the grid: ``"block"`` (default, block-sparse: per
    task the coordinates are spatially sorted, live block pairs
    computed on host, and the pair list — padded to a power-of-two
    bucket length so pair-count jitter does not retrace —
    scalar-prefetched into the kernel) or ``"dense"`` (full grid — the
    parity reference).

    Host-side prep is memoized in :attr:`artifacts` (a
    :class:`~repro.backend.artifacts.JoinArtifactCache`) for tasks whose
    sides are :class:`~repro.backend.artifacts.ChunkView` handles — the
    backends attach them so repeated queries over resident chunks skip
    sort/box/pad/pair-list work entirely; ``last_stats`` records the
    per-query ``prep_s``/``dispatch_s`` split and artifact hit/miss
    deltas alongside the block-pair counters.

    The jitted batch callable for every ``(kernel, same, shapes, eps)``
    bucket key is memoized in ``_fn_cache``: repeated same-shape queries
    dispatch through the SAME bound callable, so jax's jit cache is hit
    without re-binding statics (``ops.TRACE_COUNTS`` proves no retrace).
    """

    # A join round through this executor has two failure-prone stages:
    # the host-side batch build and the kernel dispatch. The backends
    # arm these fault points (with retry) before the round — re-arming
    # without re-running is a faithful redo since both are pure.
    fault_points = ("prep.build", "dispatch.kernel")

    def __init__(self, prune: str = "block",
                 artifacts: Optional[JoinArtifactCache] = None,
                 devices: Optional[Sequence[Any]] = None):
        check_prune(prune)
        # Imported lazily so the numpy backend never pulls in jax.
        from repro.kernels.simjoin import ops, prune as prune_mod, simjoin
        self._ops = ops
        self._prune = prune_mod
        self._block = simjoin.BLOCK
        self._sentinel = simjoin.SENTINEL
        # Interpret mode follows the platform of the devices this
        # executor dispatches to (jax's default devices when None).
        self.interpret = simjoin.interpret_mode(devices)
        self.prune = prune
        self.artifacts = (artifacts if artifacts is not None
                          else JoinArtifactCache())
        self._fn_cache: Dict[tuple, Callable] = {}
        self.last_stats: Optional[Dict[str, int]] = None
        # Backends swap in a live tracer at bind time (telemetry on);
        # prep/prep.stack/dispatch spans bracket the host-side grouping,
        # the stacking and the kernel-dispatch loop respectively.
        self.tracer = NULL_TRACER

    # ------------------------------------------------ artifact-aware prep

    def _sorted_side(self, x) -> np.ndarray:
        """Spatially sorted coordinates of one task side (artifact-cached
        for ChunkViews, computed in place for raw arrays)."""
        if isinstance(x, ChunkView) and x.key is not None:
            return self.artifacts.sorted_coords(
                x, lambda: self._prune.spatial_sort(x.coords))
        return self._prune.spatial_sort(task_coords(x))

    def _padded_side(self, x, sentinel: int,
                     sorted_arr: Optional[np.ndarray] = None) -> np.ndarray:
        """Sentinel-padded coordinate-major form of one task side.
        ChunkViews cache the padded *sorted* artifact (shared across
        dense and block dispatch — the count is invariant under the
        reordering); raw arrays pad ``sorted_arr`` when the caller
        pre-sorted them (block path) and the original order otherwise
        (dense path, the seed behavior)."""
        if isinstance(x, ChunkView) and x.key is not None:
            return self.artifacts.padded(
                x, sentinel,
                lambda: self._ops.pad_cm_np(self._sorted_side(x), sentinel))
        base = sorted_arr if sorted_arr is not None else task_coords(x)
        return self._ops.pad_cm_np(base, sentinel)

    def _pair_list(self, xa, xb, a_s: np.ndarray, b_s: np.ndarray,
                   eps: int, same: bool) -> Tuple[np.ndarray, int]:
        """The task's ``(pairs, dense_total)`` block-pair list
        (artifact-cached per chunk pair + eps when both sides are
        cacheable views)."""
        return self.artifacts.block_pairs(
            xa, xb, self._block, int(eps), bool(same),
            lambda: self._prune.build_block_pairs(
                a_s, b_s, self._block, int(eps), bool(same)))

    # ------------------------------------------------- batch preparation

    def iter_batches(self, tasks: Sequence[JoinTask], eps: int,
                     by_node: bool = False
                     ) -> Tuple[List[PreparedBatch], Dict[str, int]]:
        """Group the tasks into batches (dense or block grid, per the
        ``prune`` knob) without stacking them: per task the artifact
        lookups, bucket key and block-pair counters, then each bucket
        cut into batches of at most ``ops.GROUP`` tasks whose
        ``arrays`` are still ``None`` (see :meth:`stack`). Returns
        ``(batches, stats)`` where stats carries the query's
        ``block_pairs_total`` / ``_evaluated``, the host-side ``prep_s``
        wall-clock of the grouping, and its artifact-cache hit/miss
        deltas; :meth:`stack` adds its own to the last three."""
        t0 = time.perf_counter()
        h0, m0 = self.artifacts.hits, self.artifacts.misses
        with self.tracer.span("prep", tasks=len(tasks)):
            if self.prune == "dense":
                batches, stats = self._batches_dense(tasks, by_node)
            else:
                batches, stats = self._batches_block(tasks, eps, by_node)
        stats["prep_s"] = time.perf_counter() - t0
        stats["artifact_hits"] = self.artifacts.hits - h0
        stats["artifact_misses"] = self.artifacts.misses - m0
        return batches, stats

    def stack(self, batches: Sequence[PreparedBatch],
              stats: Dict[str, Any]) -> float:
        """Build the host arrays of ``batches`` in a ``prep.stack``
        span: each operand is written row by row into one preallocated
        ``ops.GROUP``-deep array, and the rows after the batch's tasks
        are filled with inert values (sentinel coordinates, an
        all-invalid pair list) that the kernel loop never reaches.
        Adds the wall-clock and the artifact hits/misses of the padded
        sides it looks up to ``stats``' ``prep_s``, ``artifact_hits``
        and ``artifact_misses``; returns the seconds spent."""
        if not batches:
            return 0.0
        t0 = time.perf_counter()
        h0, m0 = self.artifacts.hits, self.artifacts.misses
        fills = (self._sentinel, -self._sentinel, 0)
        depth = self._ops.GROUP
        with self.tracer.span("prep.stack", batches=len(batches)):
            for batch in batches:
                out: Tuple[np.ndarray, ...] = ()
                for k, i in enumerate(batch.idxs):
                    rows = batch.rows(i)
                    if not out:
                        out = tuple(np.empty((depth,) + r.shape, r.dtype)
                                    for r in rows)
                    for x, r in zip(out, rows):
                        x[k] = r
                for x, fill in zip(out, fills):
                    x[len(batch.idxs):] = fill
                batch.arrays = out
        spent = time.perf_counter() - t0
        stats["prep_s"] += spent
        stats["artifact_hits"] += self.artifacts.hits - h0
        stats["artifact_misses"] += self.artifacts.misses - m0
        return spent

    def _rows_dense(self, tasks: Sequence[JoinTask], i: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Task ``i``'s dense-grid inputs: both coordinate sets padded to
        BLOCK (±sentinel fill), coordinate-major (d, N)."""
        _, a, b, _ = tasks[i]
        return (self._padded_side(a, self._sentinel),
                self._padded_side(b, -self._sentinel))

    def _rows_block(self, tasks: Sequence[JoinTask],
                    prepped: Dict[int, tuple], plen: int, i: int
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Task ``i``'s block-sparse inputs: its sorted, sentinel-padded
        sides and its pair list laid out for ``plen`` rows
        (``prepped[i]`` is the task's ``(sorted a, sorted b, live
        pairs)``)."""
        _, a, b, _ = tasks[i]
        a_s, b_s, pairs = prepped[i]
        return (self._padded_side(a, self._sentinel, sorted_arr=a_s),
                self._padded_side(b, -self._sentinel, sorted_arr=b_s),
                self._ops.pack_pairs_np(pairs, plen))

    def _grouped(self, node: Optional[int], same: bool, idxs: Sequence[int],
                 fn_key: tuple, rows: Callable[[int], tuple]
                 ) -> List[PreparedBatch]:
        """One bucket's batches: its tasks cut into groups of
        ``ops.GROUP``, unstacked."""
        depth = self._ops.GROUP
        return [PreparedBatch(node=node, same=same,
                              idxs=list(idxs[s:s + depth]), fn_key=fn_key,
                              rows=rows)
                for s in range(0, len(idxs), depth)]

    def _batches_dense(self, tasks: Sequence[JoinTask], by_node: bool
                       ) -> Tuple[List[PreparedBatch], Dict[str, int]]:
        """Dense grid: every block pair of every bucketed task runs."""
        batches: List[PreparedBatch] = []
        total = 0
        for key, idxs in bucket_by_shape(tasks, self._block,
                                         by_node=by_node).items():
            node = key[0] if by_node else None
            same, na, nb = key[-3:]
            total += (na // self._block) * (nb // self._block) * len(idxs)
            batches += self._grouped(
                node, same, idxs, ("dense", same, na, nb),
                functools.partial(self._rows_dense, tasks))
        return batches, {"block_pairs_total": total,
                         "block_pairs_evaluated": total}

    def _batches_block(self, tasks: Sequence[JoinTask], eps: int,
                       by_node: bool
                       ) -> Tuple[List[PreparedBatch], Dict[str, int]]:
        """Block-sparse grid: per task, sort both sides, build the live
        block-pair list, and bucket the task by its padded shapes and
        pair-list length; tasks with no surviving block pair skip
        dispatch (their count is provably zero)."""
        total = evaluated = 0
        prepped: Dict[int, tuple] = {}
        buckets: Dict[tuple, List[int]] = {}
        for i, (node, a, b, same) in enumerate(tasks):
            ca, cb = task_coords(a), task_coords(b)
            if ca.shape[0] == 0 or cb.shape[0] == 0:
                continue
            a_s = self._sorted_side(a)
            b_s = a_s if same else self._sorted_side(b)
            pairs, dense_total = self._pair_list(a, b, a_s, b_s, eps, same)
            total += dense_total
            if pairs.shape[0] == 0:
                continue
            evaluated += pairs.shape[0]
            prepped[i] = (a_s, b_s, pairs)
            na = -(-ca.shape[0] // self._block) * self._block
            nb = -(-cb.shape[0] // self._block) * self._block
            plen = self._prune.padded_pair_len(pairs.shape[0])
            key = ((node,) if by_node else ()) + (same, na, nb, plen)
            buckets.setdefault(key, []).append(i)
        batches: List[PreparedBatch] = []
        for key, idxs in buckets.items():
            node = key[0] if by_node else None
            same, na, nb, plen = key[-4:]
            batches += self._grouped(
                node, same, idxs, ("block", same, na, nb, plen),
                functools.partial(self._rows_block, tasks, prepped, plen))
        return batches, {"block_pairs_total": total,
                         "block_pairs_evaluated": evaluated}

    # ---------------------------------------------------------- dispatch

    def dispatch(self, batch: PreparedBatch, eps: int,
                 arrays: Optional[tuple] = None):
        """Run one prepared batch through its memoized jitted entry;
        returns the ``(ops.GROUP,)`` match-count device array, whose
        first ``len(batch.idxs)`` entries are the batch's tasks.
        ``arrays`` are device-placed copies of the batch's stacks (the
        mesh backend's, on the executing node's device); without them
        the batch must have been stacked (:meth:`stack`)."""
        key = batch.fn_key + (int(eps), self.interpret)
        fn = self._fn_cache.get(key)
        if fn is None:
            base = (self._ops.count_similar_pairs_batch
                    if batch.fn_key[0] == "dense"
                    else self._ops.count_similar_pairs_pruned_batch)
            fn = functools.partial(base, eps=int(eps), same=batch.same,
                                   interpret=self.interpret)
            self._fn_cache[key] = fn
        return fn(*(arrays if arrays is not None else batch.arrays),
                  len(batch.idxs))

    def count_pairs(self, tasks: Sequence[JoinTask], eps: int) -> List[int]:
        """Per-task match counts via bucketed batched kernel dispatch
        (every batch is stacked: there is no pinned device cache here);
        records the query's block-pair counters, prep/dispatch split,
        and artifact hit/miss deltas in ``last_stats``."""
        counts = [0] * len(tasks)
        batches, stats = self.iter_batches(tasks, eps)
        self.stack(batches, stats)
        t0 = time.perf_counter()
        with self.tracer.span("dispatch", batches=len(batches)):
            for batch in batches:
                got = np.asarray(self.dispatch(batch, eps))
                for i, c in zip(batch.idxs, got):
                    counts[i] = int(c)
        stats["dispatch_s"] = time.perf_counter() - t0
        self.last_stats = stats
        return counts


def check_prune(prune: str) -> None:
    """Reject a ``prune`` mode outside :data:`PRUNE_MODES`, naming the
    known ones (a stale mode must fail loudly, never fall back)."""
    if prune not in PRUNE_MODES:
        raise ValueError(f"unknown prune mode {prune!r}; "
                         f"known: {PRUNE_MODES}")


def make_join_executor(backend: str, join_fn: Callable[..., int],
                       prune: str = "block",
                       artifacts: Optional[JoinArtifactCache] = None,
                       devices: Optional[Sequence[Any]] = None):
    """Build a join executor for ``backend``. ``prune`` selects the
    pallas grid (``"block"`` block-sparse, the default, or ``"dense"``
    full grid); the numpy executor has no grid and counts the same
    pairs either way, so it accepts both. ``devices`` are the devices
    the pallas executor dispatches to (jax's default devices when
    ``None``); their platform decides interpret mode."""
    if backend == "numpy":
        check_prune(prune)
        return NumpyJoinExecutor(join_fn)
    if backend == "pallas":
        return PallasJoinExecutor(prune=prune, artifacts=artifacts,
                                  devices=devices)
    raise ValueError(f"unknown join backend {backend!r}; "
                     f"known: {JOIN_BACKENDS}")
