"""Shared-nothing cluster façade over the pluggable execution backends.

The *algorithms* (chunking, planning, eviction, placement) and the *join
compute* always run for real; how disk, network, and device placement are
carried out is the backend's job (``repro.backend``):

  * ``backend="simulated"`` — the §4.1 calibrated cost model (the seed
    behavior, extracted into :class:`repro.backend.SimulatedBackend`):
    the container is one box, wall-clock is modeled as

        t(query) = max_n scan_n + max_n net_n + max_n compute_n + t_opt

    with scan_n = scanned_bytes/disk_bw + decoded_cells/decode_rate(fmt),
    net_n = max(bytes_in, bytes_out)/net_bw (full-duplex switch), and
    compute_n = assigned cell-pair work / pair_rate.
  * ``backend="jax_mesh"`` — real execution over a jax device mesh
    (:class:`repro.backend.JaxMeshBackend`): cached chunks become
    device-resident buffers pinned to the nodes of their ``CacheState``
    replica set, ship decisions become measured cross-device transfers, and
    each node's simjoin batch dispatches to the Pallas kernel on that
    node's device (compiled on the chip, interpret mode on CPU devices).

Join execution backends for the simulated path (``join_backend``):

  * ``"numpy"``  — the reference executor: one blocked numpy evaluation
    per chunk pair (``join_fn`` override preserved).
  * ``"pallas"`` — the batched executor: BLOCK-padded, shape-bucketed
    pair batches dispatched to the ``kernels/simjoin`` Pallas kernel
    (compiled on the chip; interpret mode on CPU devices only). Its
    ``prune`` knob selects the grid: ``"block"`` (default — spatially
    sorted coordinates, host-pruned block pairs scalar-prefetched into
    the kernel) or ``"dense"`` (every block pair evaluated, the parity
    reference); any other mode raises ``ValueError``. Match counts are
    identical across both; the work done is reported per query as
    ``ExecutedQuery.block_pairs_evaluated / block_pairs_total``.
    Host-side prep (sort/boxes/padding/pair lists) is memoized per
    resident chunk in a ``JoinArtifactCache`` invalidated with cache
    residency; the per-query ``prep_s``/``dispatch_s`` split and
    ``artifact_hits``/``artifact_misses`` land on ``ExecutedQuery``.

This module re-exports the cost model, executors, ``ExecutedQuery``, and
``workload_summary`` from ``repro.backend`` so seed-era imports keep
working.
"""
from __future__ import annotations

from typing import (TYPE_CHECKING, Any, Callable, List, Optional, Sequence)

if TYPE_CHECKING:  # duck-typed at runtime to avoid a package cycle
    from repro.arrayio.catalog import Catalog, FileReader
from repro.backend import (BACKENDS, CostModel, ExecutedQuery, JOIN_BACKENDS,
                           JoinTask, NumpyJoinExecutor, PallasJoinExecutor,
                           count_similar_pairs_np, make_backend,
                           make_join_executor, workload_summary)
from repro.core.coordinator import CacheCoordinator, SimilarityJoinQuery
from repro.obs.telemetry import Telemetry

__all__ = ["BACKENDS", "CostModel", "ExecutedQuery", "JOIN_BACKENDS",
           "JoinTask", "NumpyJoinExecutor", "PallasJoinExecutor",
           "RawArrayCluster", "count_similar_pairs_np", "make_backend",
           "make_join_executor", "workload_summary"]

#: ``ExecutedQuery`` counters copied onto each ``query`` span's args.
QUERY_COUNTERS = ("dispatch_launches", "dispatch_put_bytes",
                  "dispatch_host_syncs", "device_sync_bytes",
                  "scan_read_bytes", "dispatch_devices",
                  "dispatch_max_device_launches", "measured_ship_bytes",
                  "ship_transfers", "dispatch_backend_compiles",
                  "dispatch_pinned_hits")


class RawArrayCluster:
    """N worker nodes + coordinator, wired to the caching stack and an
    execution backend (simulated cost model or real jax device mesh)."""

    def __init__(self, catalog: "Catalog", reader: "FileReader", n_nodes: int,
                 node_budget_bytes: int, policy: str = "cost",
                 placement_mode: str = "dynamic", min_cells: int = 256,
                 cost_model: Optional[CostModel] = None,
                 join_fn: Optional[Callable[..., int]] = None,
                 execute_joins: bool = True,
                 join_backend: str = "numpy",
                 budget_scope: str = "global",
                 reuse: str = "off",
                 backend: str = "simulated",
                 devices: Optional[Sequence[Any]] = None,
                 prune: str = "block",
                 mqo: str = "off",
                 result_cache: str = "off",
                 result_cache_capacity: int = 256,
                 result_cache_ttl_s: Optional[float] = None,
                 replication: str = "off",
                 replica_k: int = 2,
                 replication_threshold: float = 3.0,
                 telemetry: "str | Telemetry | None" = "off",
                 faults: Any = "off",
                 retry: Any = None,
                 audit: str = "auto"):
        if join_fn is not None and join_backend != "numpy":
            raise ValueError(
                "join_fn overrides the join predicate of the numpy "
                "executor; the pallas backend always runs the L1 simjoin "
                "kernel — pass one or the other")
        self.catalog = catalog
        self.reader = reader
        self.n_nodes = n_nodes
        self.backend = make_backend(
            backend, n_nodes, cost_model=cost_model, join_fn=join_fn,
            join_backend=join_backend, execute_joins=execute_joins,
            devices=devices, prune=prune, mqo=mqo)
        self.coordinator = CacheCoordinator(
            catalog, reader, n_nodes, node_budget_bytes, policy=policy,
            placement_mode=placement_mode, min_cells=min_cells,
            budget_scope=budget_scope, reuse=reuse,
            result_cache=result_cache,
            result_cache_capacity=result_cache_capacity,
            result_cache_ttl_s=result_cache_ttl_s,
            replication=replication, replica_k=replica_k,
            replication_threshold=replication_threshold,
            telemetry=telemetry, faults=faults, retry=retry, audit=audit)
        self.backend.bind(self.coordinator)
        if self.telemetry.enabled:
            # Mirror every span into a captured jax profiler trace as a
            # host annotation of the same name (one clock with the device).
            from jax.profiler import TraceAnnotation
            self.telemetry.tracer.mirror = TraceAnnotation

    @property
    def telemetry(self) -> Telemetry:
        """The shared telemetry bundle (``"off"`` default = the no-op
        tracer/registry; pass ``telemetry="on"`` or a ``Telemetry``
        instance to record spans and metrics)."""
        return self.coordinator.telemetry

    def export_trace(self, path: str) -> str:
        """Write the recorded spans as Chrome trace-event JSON to
        ``path`` (Perfetto/``chrome://tracing``-loadable); returns
        ``path``. An off-mode cluster writes an empty—but well-formed—
        trace."""
        return self.telemetry.tracer.export(path)

    def summary(self, executed: Sequence[ExecutedQuery]):
        """``workload_summary`` over ``executed``, also surfacing any
        replication/failover events still pending in the coordinator's
        event channel (e.g. a ``fail_node`` after the last query)."""
        return workload_summary(executed, coordinator=self.coordinator)

    # -------------------------------------------------- failure injection

    def fail_node(self, node: int):
        """Simulate a crash-restart of one worker node (see
        ``SimulatedBackend.fail_node``): its cached copies are lost,
        device buffers freed, and the coordinator re-admits what it can
        from surviving replicas or raw files. Returns the recovery
        event's counters."""
        return self.backend.fail_node(node)

    # ------------------------------------------------ backend-state views

    @property
    def cost(self) -> CostModel:
        """The backend's calibrated cost model (seed-API view)."""
        return self.backend.cost

    @property
    def join_fn(self) -> Callable[..., int]:
        """The numpy executor's join predicate (seed-API view)."""
        return self.backend.join_fn

    @property
    def executor(self):
        """The backend's join executor (seed-API view)."""
        return self.backend.executor

    @property
    def execute_joins(self) -> bool:
        """Whether join compute actually runs (seed-API view)."""
        return self.backend.execute_joins

    # ----------------------------------------------------------- execution

    def run_query(self, query: SimilarityJoinQuery) -> ExecutedQuery:
        """Admit one query through the coordinator and execute its plan
        (a result-cache hit report short-circuits execution; a planned
        query's computed match count is written back to the tier).
        Traced as one ``query`` span when telemetry is on, whose args are
        the coordinator's query index and the query's device-path
        counters (:data:`QUERY_COUNTERS`), so every span of one request
        can be grouped."""
        with self.telemetry.tracer.span(
                "query", cat="query",
                index=self.coordinator.query_counter + 1) as span:
            report = self.coordinator.process_query(query)
            executed = self.backend.execute(query, report)
            self.coordinator.record_result(query, executed)
            if span is not None:
                for key in QUERY_COUNTERS:
                    value = getattr(executed, key)
                    if value is not None:
                        span.args[key] = value
        return executed

    def run_workload(self, queries: Sequence[SimilarityJoinQuery],
                     batch_size: Optional[int] = None
                     ) -> List[ExecutedQuery]:
        """Run a workload. ``batch_size=N`` admits queries through the
        coordinator's batched planning path (shared raw-file scans, one
        eviction/placement round per batch) and the backend's
        ``execute_batch`` (cross-batch join-task dedup under the ``mqo``
        knob); ``None``/1 preserves the per-query admission of the
        paper's experiments. Traced as a root ``workload`` span whose
        direct children (``query`` / ``batch`` spans) tile the run."""
        tracer = self.telemetry.tracer
        root = tracer.begin("workload", cat="workload",
                            queries=len(queries))
        try:
            if batch_size is None or batch_size <= 1:
                return [self.run_query(q) for q in queries]
            out: List[ExecutedQuery] = []
            for i in range(0, len(queries), batch_size):
                batch = list(queries[i:i + batch_size])
                with tracer.span("batch", cat="query", size=len(batch)):
                    reports = self.coordinator.process_batch(batch)
                    executed = self.backend.execute_batch(batch, reports)
                    for q, e in zip(batch, executed):
                        self.coordinator.record_result(q, e)
                out.extend(executed)
            return out
        finally:
            tracer.end(root)
