"""Device-mesh backend: real jax transfers and compiled Pallas joins.

Maps the paper's *nodes* onto jax devices over a one-axis
``jax.sharding.Mesh`` (axis name ``"node"``). Three things become real
that the simulated backend only models:

  * **Committed cache buffers** — every chunk in ``CacheState.cached``
    is materialized as a device-resident jax array pinned (via
    ``jax.device_put``) to the device of each holder node in its
    ``CacheState`` replica set (one buffer per replica copy; single-copy
    under ``replication="off"``). Buffers move/free in lockstep with admit, evict, and
    split-remap through the :class:`~repro.backend.base.
    DeviceBindingListener` hooks (the same life-cycle points the
    CoverageIndex syncs on).
  * **Ship decisions** — each ``plan_join`` transfer route (chunk, src,
    dest) is replayed as an actual cross-device ``jax.device_put`` with
    measured bytes, wall-clock and count (``measured_net_s`` /
    ``measured_ship_bytes`` / ``ship_transfers``); with telemetry on,
    each transfer is a ``ship.transfer`` span and each host-to-device
    staging of a just-scanned chunk a ``ship.stage`` span.
  * **Join compute** — each node's chunk-pair batch is shape-bucketed
    and dispatched to the ``kernels/simjoin`` Pallas kernel on that
    node's device: compiled (``interpret=False``) on the chip, interpret
    mode only when the mesh's devices are CPU devices
    (``simjoin.interpret_mode``). Only the batches missing from the
    pinned device stacks are stacked on host (a ``prep.stack`` span,
    counted in ``prep_s``); a query's misses are placed in one
    transfer per device, every kernel is enqueued, and all counts are
    read back after one wait. The dispatch less that stacking is timed
    as ``dispatch_s``; with telemetry on, each batch's ``dispatch.lookup``
    and ``dispatch.launch`` are spans, and so are the query's one
    ``dispatch.put`` (misses only), ``dispatch.wait`` and
    ``dispatch.readback``. Every query counts its launches, the batches
    it found among the pinned device stacks, placed bytes and host syncs
    (``dispatch_launches`` / ``dispatch_pinned_hits`` /
    ``dispatch_put_bytes`` / ``dispatch_host_syncs``), the devices its
    launches went to and the launches on the busiest one
    (``dispatch_devices`` / ``dispatch_max_device_launches``), and the
    executables the process built since the previous query
    (``dispatch_backend_compiles``).

With fewer devices than nodes the node axis wraps (node ``k`` lives on
device ``k % n_devices``): 8 nodes on one chip share its device. On
CPU-only hosts, run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` so jax exposes N
virtual CPU devices and CI exercises real cross-device placement.

Modeled ``time_*_s`` fields are still reported (computed from the same
plans) so the two backends remain directly comparable; the measured
fields are additive, never substitutes.
"""
from __future__ import annotations

import collections
import itertools
import time
import warnings
from typing import (TYPE_CHECKING, Any, Callable, Dict, List, Optional,
                    Sequence, Tuple)

import numpy as np

if TYPE_CHECKING:
    from repro.core.cache_state import CacheState
    from repro.core.chunk import ChunkMeta
    from repro.core.coordinator import (CacheCoordinator, QueryReport,
                                        SimilarityJoinQuery)
from repro.backend.artifacts import ChunkView
from repro.backend.base import BACKENDS, ExecutedQuery
from repro.backend.cost_model import CostModel
from repro.backend.simulated import SimulatedBackend
from repro.faults.errors import RetryExhaustedError

#: JAX's event around building one executable: an XLA compile, or a
#: load from the persistent compilation cache.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_built = [0]
_listening: List[bool] = []


def _on_duration_event(event: str, duration_secs: float, **kwargs) -> None:
    """Count :data:`BACKEND_COMPILE_EVENT` (a ``jax.monitoring``
    duration listener)."""
    if event == BACKEND_COMPILE_EVENT:
        _built[0] += 1


def backend_compiles() -> int:
    """Executables this process has built since the first call, which
    registers the one listener that counts them. Compile caches are the
    process's, so the count is too; a jit-cache hit builds nothing, and
    a kernel shape dispatched to a device it never ran on builds one."""
    if not _listening:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _listening.append(True)
    return _built[0]


#: Where a device reports no memory limit (a CPU device), each device's
#: pinned-batch budget: about a quarter of one v5e chip's 16 GB.
PINNED_BYTES_UNREPORTED = 4 << 30


def pinned_budget(devices: Sequence[Any]) -> int:
    """Bytes of pinned dispatch batches each device may hold: a quarter
    of the smallest ``bytes_limit`` the devices report, or
    :data:`PINNED_BYTES_UNREPORTED` where none reports one."""
    limits = [(d.memory_stats() or {}).get("bytes_limit")
              for d in devices]
    limits = [int(b) for b in limits if b]
    return min(limits) // 4 if limits else PINNED_BYTES_UNREPORTED


def _nbytes(arrays: tuple) -> int:
    return sum(int(x.nbytes) for x in arrays)


class JaxMeshBackend(SimulatedBackend):
    """Execution over a one-axis device mesh: nodes -> jax devices."""

    name = "jax_mesh"

    def __init__(self, n_nodes: int, cost_model: Optional[CostModel] = None,
                 devices: Optional[Sequence[Any]] = None,
                 execute_joins: bool = True, prune: str = "block",
                 mqo: str = "off"):
        import jax
        from jax.sharding import Mesh
        self.devices = tuple(devices if devices is not None
                             else jax.devices())
        if not self.devices:
            raise ValueError("jax reports no devices")
        # The mesh backend always joins through the Pallas kernel on the
        # mesh's devices (their platform decides interpret mode); the
        # simulated parent's executor field holds the dispatch cache and
        # the prune preprocessing shared with the per-node path here.
        super().__init__(n_nodes, cost_model=cost_model,
                         join_backend="pallas", execute_joins=execute_joins,
                         prune=prune, mqo=mqo, devices=self.devices)
        if len(self.devices) < n_nodes:
            hint = (f" Set XLA_FLAGS=--xla_force_host_platform_device_count"
                    f"={n_nodes} for one CPU device per node."
                    if self.devices[0].platform == "cpu" else "")
            warnings.warn(
                f"jax_mesh: {n_nodes} nodes over {len(self.devices)} "
                f"devices — the node axis wraps (node k -> device "
                f"k % {len(self.devices)}).{hint}", RuntimeWarning,
                stacklevel=2)
        self.mesh = Mesh(np.array(self.devices), ("node",))
        backend_compiles()
        # Committed cache buffers, one per replica copy: chunk id ->
        # {holder node -> device array}. ``_buffer_node`` tracks the
        # PRIMARY holder (the CacheState ``primary_map`` view the parity
        # assertions compare against); under ``replication="off"`` every
        # inner dict has exactly one entry and the behavior reduces to
        # the seed's single-buffer-per-chunk map.
        self._buffers: Dict[int, Dict[int, Any]] = {}
        self._buffer_node: Dict[int, int] = {}
        # Pinned dispatch batches: the stacked, device-placed kernel
        # inputs of a prepared batch, keyed by (device, fn_key, eps, the
        # ordered artifact keys of the batch's tasks). A repeat query
        # over resident chunks re-dispatches the SAME device buffers
        # instead of re-device_put-ting identical host stacks; entries
        # are invalidated with the chunks they were stacked from, and —
        # because they live in device memory — each device's entries
        # are evicted least recently used first (insertion order of the
        # dict, refreshed on hit) while their bytes exceed
        # ``_pinned_budget`` (see :func:`pinned_budget`).
        self._pinned: Dict[tuple, tuple] = {}
        self._pinned_by_chunk: Dict[int, set] = {}
        self._pinned_bytes: Dict[Any, int] = {}
        self._pinned_budget = pinned_budget(self.devices)
        # Cumulative device-side counters (bench_scalability surfaces them).
        self.device_stats: Dict[str, float] = {
            "committed_bytes_materialized": 0.0,
            "committed_bytes_moved": 0.0,
            "committed_buffers_freed": 0.0,
            "ship_bytes_measured": 0.0,
            "ship_transfers": 0.0,
            "pinned_batch_hits": 0.0,
            "pinned_batch_misses": 0.0,
            "pinned_batches_freed": 0.0,
            # Replication/failover device counters: bytes copied
            # device-to-device to fill a secondary replica buffer, and
            # committed buffers lost to a simulated node crash (kept
            # separate from ``committed_buffers_freed`` so policy-driven
            # frees stay comparable across replication on/off runs).
            "replica_bytes_copied": 0.0,
            "failover_buffers_dropped": 0.0,
        }

    # --------------------------------------------------------- device math

    def device_for_node(self, node: int) -> Any:
        """The mesh device hosting a paper node (wraps when the mesh is
        smaller than the node count). The mesh's device array is the
        single source of truth for the node -> device map."""
        devs = self.mesh.devices
        return devs[node % devs.size]

    def buffer_device(self, chunk_id: int) -> Optional[Any]:
        """The device holding a chunk's PRIMARY committed buffer, or
        ``None`` when the chunk has no committed buffer at all."""
        per_node = self._buffers.get(chunk_id)
        if not per_node:
            return None
        node = self._buffer_node.get(chunk_id)
        buf = per_node.get(node) if node is not None else None
        if buf is None:
            buf = next(iter(per_node.values()))
        (dev,) = buf.devices()
        return dev

    def replica_devices(self, chunk_id: int) -> Dict[int, Any]:
        """Every committed buffer of a chunk: holder node -> device (one
        entry per replica copy; empty when nothing is committed)."""
        out: Dict[int, Any] = {}
        for node, buf in self._buffers.get(chunk_id, {}).items():
            (dev,) = buf.devices()
            out[node] = dev
        return out

    def committed_chunks(self) -> Dict[int, int]:
        """Snapshot of committed buffers: chunk id -> node."""
        return dict(self._buffer_node)

    # ------------------------------------------------------------- binding

    def bind(self, coordinator: "CacheCoordinator") -> None:
        """Attach to the coordinator and register the device-binding
        hooks on its ``CacheState`` so buffers track residency."""
        super().bind(coordinator)
        coordinator.cache.add_listener(self)
        self._io_seen = self._io_totals()

    # ------------------------- DeviceBindingListener (cache life-cycle) --

    def _free_pinned(self, key: tuple) -> None:
        """Free one pinned batch: take its bytes off its device's count
        and its key out of every chunk's key set."""
        arrays = self._pinned.pop(key)
        self._pinned_bytes[key[0]] -= _nbytes(arrays)
        self.device_stats["pinned_batches_freed"] += 1
        for ka, kb in key[3]:
            for cid in (ka[0], kb[0]):
                refs = self._pinned_by_chunk.get(cid)
                if refs is not None:
                    refs.discard(key)
                    if not refs:
                        del self._pinned_by_chunk[cid]

    def _drop_pinned(self, chunk_id: int) -> None:
        """Free every pinned dispatch batch stacked from a chunk (and
        unindex it from the partner chunks' key sets)."""
        for key in self._pinned_by_chunk.pop(chunk_id, ()):
            if key in self._pinned:
                self._free_pinned(key)

    def on_drop(self, chunk_id: int) -> None:
        """Eviction/placement dropped a chunk: free the device buffer of
        EVERY replica copy and every pinned dispatch batch it
        participated in."""
        per_node = self._buffers.pop(chunk_id, None)
        if per_node:
            self.device_stats["committed_buffers_freed"] += len(per_node)
        self._buffer_node.pop(chunk_id, None)
        self._drop_pinned(chunk_id)

    def on_split(self, parent_id: int, leaves: List["ChunkMeta"]) -> None:
        """A cached chunk split: retire the parent's buffers (every
        replica copy) and pinned batches. The children inherit its
        residency/replica set in ``CacheState`` and materialize on the
        inherited nodes' devices at the next reconcile."""
        per_node = self._buffers.pop(parent_id, None)
        if per_node:
            self.device_stats["committed_buffers_freed"] += len(per_node)
        self._buffer_node.pop(parent_id, None)
        self._drop_pinned(parent_id)

    def reconcile(self, state: "CacheState") -> None:
        """Post-round sync (the device twin of ``sync_coverage``): free
        buffers of chunks no longer resident, materialize buffers for
        newly resident chunks and replica copies, move single-copy
        buffers whose location changed, and free buffers of replicas
        that left the set — so each cached chunk holds exactly one
        committed buffer per node in ``CacheState.replicas_of``."""
        import jax
        import jax.numpy as jnp
        if self.coordinator is None:
            raise RuntimeError("backend not bound — call bind() first")
        chunks = self.coordinator.chunks
        for cid in list(self._buffers):
            if cid not in state.cached:
                self.on_drop(cid)
        # Pinned batches may reference just-scanned chunks that were
        # never admitted (no committed buffer): prune those too, the
        # same never-outlives-residency rule the artifact cache applies.
        for cid in list(self._pinned_by_chunk):
            if cid not in state.cached:
                self._drop_pinned(cid)
        for cid in state.cached:
            want = state.replicas_of(cid)
            if not want:
                # Not yet located (e.g. origin placement before first
                # touch): the chunk lives at its home node.
                if cid not in chunks.chunk_file:
                    continue
                want = (chunks.home_node(cid),)
            have = self._buffers.get(cid, {})
            if len(want) == 1 and len(have) == 1 and want[0] not in have:
                # Single-copy relocation — the seed path: MOVE the one
                # buffer with one device_put, counting neither a free nor
                # a materialization, so replication-off device stats stay
                # bit-identical to the single-valued implementation.
                ((old_node, buf),) = have.items()
                moved = jax.device_put(buf, self.device_for_node(want[0]))
                moved.block_until_ready()
                self._buffers[cid] = {want[0]: moved}
                self._buffer_node[cid] = want[0]
                # Count only relocations that cross physical devices: a
                # node change that wraps onto the same device (mesh
                # smaller than the node count) moves no bytes — the same
                # exclusion _ship applies to transfer routes.
                if (self.device_for_node(old_node)
                        != self.device_for_node(want[0])):
                    self.device_stats["committed_bytes_moved"] += buf.nbytes
                continue
            for node in want:
                if node in have:
                    continue
                src = next(iter(have.values()), None)
                if src is None:
                    meta = chunks.meta_of(cid)
                    if meta is None:   # retired id; re-enters next round
                        break
                    coords = chunks.chunk_coords(cid, meta.file_id)
                    buf = jax.device_put(jnp.asarray(coords, jnp.int32),
                                         self.device_for_node(node))
                    buf.block_until_ready()
                    self.device_stats["committed_bytes_materialized"] += \
                        buf.nbytes
                else:
                    # Replica fill: a real device-to-device copy from an
                    # existing holder — the cheap restore path a failover
                    # re-admission from a surviving replica rides on.
                    buf = jax.device_put(src, self.device_for_node(node))
                    buf.block_until_ready()
                    (src_dev,) = src.devices()
                    if src_dev != self.device_for_node(node):
                        self.device_stats["replica_bytes_copied"] += \
                            buf.nbytes
                have = self._buffers.setdefault(cid, {})
                have[node] = buf
            for node in [n for n in have if n not in want]:
                del have[node]
                self.device_stats["committed_buffers_freed"] += 1
            if not have:
                self._buffers.pop(cid, None)
                self._buffer_node.pop(cid, None)
            else:
                self._buffer_node[cid] = (want[0] if want[0] in have
                                          else next(iter(have)))

    # ------------------------------------------- simulated node failure

    def fail_node(self, node: int) -> Dict[str, float]:
        """Crash-restart one node on the mesh: free every committed
        replica buffer it held (and the pinned dispatch batches staged
        on its device), then run the coordinator's recovery. The
        reconcile the recovery triggers re-materializes the node's lost
        buffers for real — device-to-device from a surviving replica
        (``replica_bytes_copied``) or from host coordinates after a raw
        re-scan (``committed_bytes_materialized``) — so the device
        counters reflect the actual restore traffic."""
        if self.coordinator is None:
            raise RuntimeError("backend not bound — call bind() first")
        for cid in list(self._buffers):
            per_node = self._buffers[cid]
            if node not in per_node:
                continue
            per_node.pop(node)
            self.device_stats["failover_buffers_dropped"] += 1
            self._drop_pinned(cid)
            if not per_node:
                del self._buffers[cid]
                self._buffer_node.pop(cid, None)
            elif self._buffer_node.get(cid) == node:
                self._buffer_node[cid] = next(iter(per_node))
        dev = self.device_for_node(node)
        for key in [k for k in self._pinned if k[0] == dev]:
            self._free_pinned(key)
        return self.coordinator.fail_node(node)

    # ----------------------------------------------------------- execution

    def _mirror_device_stats(self) -> None:
        """Refresh the ``device.*`` registry gauges from the cumulative
        :attr:`device_stats` counters (telemetry-on callers only)."""
        reg = self.telemetry.registry
        for k, v in self.device_stats.items():
            reg.gauge(f"device.{k}").set(v)

    def _ship(self, report: "QueryReport",
              coords_of: Callable[[int], np.ndarray],
              skip: Optional[set] = None
              ) -> Tuple[float, int]:
        """Replay the join plan's ship decisions as real cross-device
        transfers; returns (measured seconds, measured bytes). Routes
        whose src and dest land on the same physical device (mesh wrap)
        move no bytes and are excluded from the byte count, as are
        routes for ``skip`` chunks (transfers already declared degraded
        by the fault guard — no source can produce their payload).
        Wrapped in a ``ship`` span when telemetry is on."""
        import jax
        import jax.numpy as jnp
        if report.join_plan is None:
            return 0.0, 0
        with self.telemetry.tracer.span(
                "ship", routes=len(report.join_plan.transfer_routes)):
            total_s, total_b = self._ship_routes(report, coords_of,
                                                 skip=skip)
        if self.telemetry.enabled:
            self._mirror_device_stats()
        return total_s, total_b

    def _ship_routes(self, report: "QueryReport",
                     coords_of: Callable[[int], np.ndarray],
                     skip: Optional[set] = None
                     ) -> Tuple[float, int]:
        """The transfer-replay loop behind :meth:`_ship`. Each
        cross-device route is one waited ``jax.device_put`` of the
        chunk's payload to the destination device (a ``ship.transfer``
        span); the returned seconds (``measured_net_s``) are those
        transfers and their waits, and nothing else. A payload with no
        committed buffer at the source (a just-scanned chunk, or a
        sliced extent under semantic reuse) is first staged onto the
        source device from the host and waited on: that staging is the
        ``ship.stage`` span, outside the returned seconds."""
        import jax
        import jax.numpy as jnp
        tracer = self.telemetry.tracer
        total_s, total_b = 0.0, 0
        n_transfers = 0
        staged: Dict[int, Any] = {}
        reuse_on = self.coordinator.reuse == "on"
        for cid, src, dst in report.join_plan.transfer_routes:
            if skip and cid in skip:
                continue
            src_dev = self.device_for_node(src)
            dst_dev = self.device_for_node(dst)
            if src_dev == dst_dev:
                continue
            payload = staged.get(cid)
            if payload is None:
                # Without reuse slicing the shipped payload is the whole
                # chunk — exactly the committed buffer when it is already
                # pinned at the source node; stage a fresh copy only when
                # no such buffer exists (just-scanned chunk) or the plan
                # ships a sliced extent.
                if not reuse_on and src in self._buffers.get(cid, {}):
                    payload = self._buffers[cid][src]
                else:
                    with tracer.span("ship.stage"):
                        payload = jax.device_put(
                            jnp.asarray(coords_of(cid), jnp.int32), src_dev)
                        payload.block_until_ready()
                staged[cid] = payload
            with tracer.span("ship.transfer"):
                t0 = time.perf_counter()
                shipped = jax.device_put(payload, dst_dev)
                shipped.block_until_ready()
                total_s += time.perf_counter() - t0
            total_b += int(payload.nbytes)
            n_transfers += 1
        self.device_stats["ship_bytes_measured"] += total_b
        self.device_stats["ship_transfers"] += n_transfers
        return total_s, total_b

    def _pinned_key(self, batch, tasks, eps: int, dev) -> Optional[tuple]:
        """The pinned-batch cache key of one prepared batch: the target
        device, the jitted entry's ``fn_key``, eps, and the ORDERED
        artifact keys of the batch's tasks — content-addressed through
        chunk identity, so identical stacks across queries collide.
        ``None`` (uncacheable) when any task side lacks an artifact key."""
        keys = []
        for i in batch.idxs:
            _, a, b, _ = tasks[i]
            ka = a.key if isinstance(a, ChunkView) else None
            kb = b.key if isinstance(b, ChunkView) else None
            if ka is None or kb is None:
                return None
            keys.append((ka, kb))
        return (dev, batch.fn_key, int(eps), tuple(keys))

    def _pin(self, ckey: tuple, arrays: tuple) -> None:
        """Make ``arrays`` the newest pinned entry under ``ckey`` (moved
        to the end of the LRU order if already there), index it under
        its chunks, and evict the device's least recently used entries
        while its pinned bytes exceed the budget."""
        dev = ckey[0]
        old = self._pinned.pop(ckey, ())
        self._pinned[ckey] = arrays
        self._pinned_bytes[dev] = (self._pinned_bytes.get(dev, 0)
                                   + _nbytes(arrays) - _nbytes(old))
        for ka, kb in ckey[3]:
            self._pinned_by_chunk.setdefault(ka[0], set()).add(ckey)
            self._pinned_by_chunk.setdefault(kb[0], set()).add(ckey)
        while self._pinned_bytes[dev] > self._pinned_budget:
            self._free_pinned(next(k for k in self._pinned if k[0] == dev))

    def _count_tasks(self, tasks, eps: int
                     ) -> Tuple[List[int], Dict[str, int]]:
        """Shape-bucketed per-node Pallas dispatch with one host sync per
        query. Every bucket's batch (dense or block-sparse per the
        executor's ``prune`` knob) runs on its node's device, in three
        phases: look each grouped, still unstacked batch up among the
        pinned device stacks (placed ONCE per resident chunk set, keyed
        per (device, batch content), invalidated with their chunks'
        residency), stack only the misses (``prep.stack``; a hit builds
        no host arrays) and place them with one unwaited
        ``jax.device_put`` per device; enqueue every kernel (each is
        ordered after its inputs' transfer); then wait once and read
        every count back in one ``jax.device_get``. Returns the per-task
        match counts and the query's counters: the executor's prep stats
        (``prep_s`` includes the stacking) plus ``dispatch_s``
        (wall-clock of the three phases less the stacking),
        ``dispatch_launches``, ``dispatch_pinned_hits`` (batches served
        from pinned stacks), ``dispatch_put_bytes``,
        ``dispatch_host_syncs``, ``dispatch_devices`` and
        ``dispatch_max_device_launches``."""
        import jax
        counts = [0] * len(tasks)
        batches, stats = self.executor.iter_batches(tasks, eps,
                                                    by_node=True)
        tracer = self.telemetry.tracer
        dispatch_span = tracer.begin("dispatch", batches=len(batches))
        t0_all = time.perf_counter()
        # Every batch's device arrays, held here until all launches are
        # issued: pinning this query's misses can evict its own earlier
        # entries from ``_pinned`` (more bytes than the budget).
        placed: List[Optional[tuple]] = []
        keys: List[Optional[tuple]] = []
        misses: Dict[Any, List[int]] = {}
        hits = 0
        for j, batch in enumerate(batches):
            with tracer.span("dispatch.lookup"):
                dev = self.device_for_node(batch.node)
                ckey = self._pinned_key(batch, tasks, eps, dev)
                arrays = (self._pinned.get(ckey) if ckey is not None
                          else None)
                if arrays is not None:
                    hits += 1
                    self.device_stats["pinned_batch_hits"] += 1
                    self._pin(ckey, arrays)  # LRU refresh
                else:
                    misses.setdefault(dev, []).append(j)
                    if ckey is not None:
                        self.device_stats["pinned_batch_misses"] += 1
                placed.append(arrays)
                keys.append(ckey)
        put_bytes = 0
        stack_s = 0.0
        if misses:
            # Host prep, inside the dispatch span but out of dispatch_s.
            stack_s = self.executor.stack(
                [b for b, arrays in zip(batches, placed) if arrays is None],
                stats)
            with tracer.span("dispatch.put"):
                for dev, idxs in misses.items():
                    flat = jax.device_put(
                        [x for j in idxs for x in batches[j].arrays], dev)
                    put_bytes += sum(x.nbytes for x in flat)
                    it = iter(flat)
                    for j in idxs:
                        placed[j] = tuple(itertools.islice(
                            it, len(batches[j].arrays)))
                # Re-pin every cacheable batch in batch order, so the
                # LRU order a query leaves is that of a one-batch-at-a-
                # time loop.
                for ckey, arrays in zip(keys, placed):
                    if ckey is not None:
                        self._pin(ckey, arrays)
        results = []
        for batch, arrays in zip(batches, placed):
            with tracer.span("dispatch.launch"):
                results.append(self.executor.dispatch(batch, eps,
                                                      arrays=arrays))
        if results:
            with tracer.span("dispatch.wait"):
                jax.block_until_ready(results)
            with tracer.span("dispatch.readback"):
                for batch, got in zip(batches, jax.device_get(results)):
                    for i, c in zip(batch.idxs, got):
                        counts[i] = int(c)
        stats["dispatch_s"] = time.perf_counter() - t0_all - stack_s
        stats["dispatch_launches"] = len(batches)
        stats["dispatch_pinned_hits"] = hits
        stats["dispatch_put_bytes"] = put_bytes
        stats["dispatch_host_syncs"] = int(bool(results))
        per_device = collections.Counter(
            self.device_for_node(b.node) for b in batches)
        stats["dispatch_devices"] = len(per_device)
        stats["dispatch_max_device_launches"] = max(per_device.values(),
                                                    default=0)
        tracer.end(dispatch_span)
        if self.telemetry.enabled:
            self._mirror_device_stats()
        return counts, stats

    def _io_totals(self) -> Dict[str, float]:
        """Cumulative counts behind :meth:`_io_fields`: bytes the device
        reconcile materialized, moved or copied; raw bytes the
        coordinator's reader read from disk; cross-device ship
        transfers; executables the process built."""
        d = self.device_stats
        return {
            "device_sync_bytes": (d["committed_bytes_materialized"]
                                  + d["committed_bytes_moved"]
                                  + d["replica_bytes_copied"]),
            "scan_read_bytes": getattr(self.coordinator.reader,
                                       "bytes_read", 0),
            "ship_transfers": d["ship_transfers"],
            "dispatch_backend_compiles": backend_compiles()}

    def _io_fields(self) -> Dict[str, int]:
        """``device_sync_bytes``, ``scan_read_bytes``, ``ship_transfers``
        and ``dispatch_backend_compiles`` of the query being built: the
        growth of :meth:`_io_totals` since the previous one (a batch's
        policy round and scans land on its first query)."""
        now = self._io_totals()
        out = {k: int(v - self._io_seen[k]) for k, v in now.items()}
        self._io_seen = now
        return out

    def _measured_ship(self, query: "SimilarityJoinQuery",
                       report: "QueryReport",
                       coords_cache: Dict[int, np.ndarray],
                       skip: Optional[set] = None
                       ) -> Tuple[Optional[float], Optional[int]]:
        """Batch-execution seam: replay this query's ship decisions as
        real cross-device transfers (shipping stays per-query under MQO
        — only kernel work is deduplicated across the batch). ``skip``
        chunks degraded by the fault guard are not replayed."""
        cm = {c.chunk_id: c for c in report.queried_chunks}

        def coords_of(cid: int) -> np.ndarray:
            if self.coordinator.reuse == "on":
                if cid not in coords_cache:
                    coords_cache[cid] = self._queried_coords(
                        cid, cm[cid].file_id, query.box)
                return coords_cache[cid]
            return self.coordinator.chunks.chunk_coords(
                cid, cm[cid].file_id)

        return self._ship(report, coords_of, skip=skip)

    def execute(self, query: "SimilarityJoinQuery",
                report: "QueryReport") -> ExecutedQuery:
        """Execute one planned query on the mesh: modeled phase times
        from the shared cost model, plus measured transfer and join
        wall-clock/bytes from the real device work."""
        if self.coordinator is None:
            raise RuntimeError("backend not bound — call bind() first")
        if report.result_cache_hit:
            return self._cached_result(report)
        time_scan = self.modeled_scan_time(report)
        time_net = self.modeled_net_time(report)
        drop, ship_ops = self._guard_transfers(query, report)
        with self.telemetry.tracer.span("gather"):
            tasks, work_by_node, coords_cache, _ = self.gather_join_tasks(
                query, report, exclude=drop)
        # Ship what the plan ships: the sliced extent under semantic
        # reuse, the whole chunk otherwise (a shipped chunk becomes a
        # full replica the placement round may keep). The host-level
        # fault guard runs first, so only routes with a producible
        # payload replay as real device transfers.
        measured_net, measured_bytes = self._measured_ship(
            query, report, coords_cache, skip=drop)
        matches: Optional[int] = None
        stats: Dict[str, int] = {}
        join_ops: List[str] = []
        if report.join_plan is not None and self.execute_joins:
            try:
                counts, stats = self._guarded_count(tasks, query.eps)
                matches = sum(counts)
            except RetryExhaustedError as e:
                join_ops.append(e.op)
                matches = 0
                stats = {}
        time_compute = (max(work_by_node.values(), default=0)
                        / self.cost.cell_pairs_per_sec)
        t_opt = report.opt_time_chunking_s + report.opt_time_evict_place_s
        degraded = self._assemble_degraded(query, report, drop, ship_ops,
                                           join_ops, matches)
        return self._record(ExecutedQuery(
            report=report, time_scan_s=time_scan, time_net_s=time_net,
            time_compute_s=time_compute, time_opt_s=t_opt, matches=matches,
            backend=self.name,
            measured_net_s=measured_net,
            measured_ship_bytes=measured_bytes,
            block_pairs_total=stats.get("block_pairs_total"),
            block_pairs_evaluated=stats.get("block_pairs_evaluated"),
            prep_s=stats.get("prep_s"),
            dispatch_s=stats.get("dispatch_s"),
            artifact_hits=stats.get("artifact_hits"),
            artifact_misses=stats.get("artifact_misses"),
            dispatch_launches=stats.get("dispatch_launches", 0),
            dispatch_pinned_hits=stats.get("dispatch_pinned_hits", 0),
            dispatch_put_bytes=stats.get("dispatch_put_bytes", 0),
            dispatch_host_syncs=stats.get("dispatch_host_syncs", 0),
            dispatch_devices=stats.get("dispatch_devices", 0),
            dispatch_max_device_launches=stats.get(
                "dispatch_max_device_launches", 0),
            **self._io_fields(),
            **self._resilience_fields(report),
            **self._fault_fields(degraded)))


def make_backend(backend: str, n_nodes: int,
                 cost_model: Optional[CostModel] = None,
                 join_fn: Optional[Callable[..., int]] = None,
                 join_backend: str = "numpy", execute_joins: bool = True,
                 devices: Optional[Sequence[Any]] = None,
                 prune: str = "block", mqo: str = "off") -> SimulatedBackend:
    """Build an execution backend by name. ``prune`` selects the Pallas
    join grid (``"block"``-sparse, the default, or ``"dense"``) and
    applies to any backend that joins through the Pallas kernel;
    ``mqo`` toggles cross-batch task dedup in ``execute_batch``
    (off = seed parity). ``devices`` are the mesh's
    devices for ``jax_mesh`` (all of jax's devices when ``None``); the
    simulated backend runs on jax's default devices."""
    if backend == "simulated":
        return SimulatedBackend(n_nodes, cost_model=cost_model,
                                join_fn=join_fn, join_backend=join_backend,
                                execute_joins=execute_joins, prune=prune,
                                mqo=mqo)
    if backend == "jax_mesh":
        if join_fn is not None:
            raise ValueError(
                "join_fn overrides the numpy executor's predicate; the "
                "jax_mesh backend always runs the Pallas simjoin kernel "
                "— pass one or the other")
        return JaxMeshBackend(n_nodes, cost_model=cost_model,
                              devices=devices, execute_joins=execute_joins,
                              prune=prune, mqo=mqo)
    raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")
