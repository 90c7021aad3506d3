"""Pluggable execution backends for the caching cluster.

The planning layers (``repro.core``) decide *what* to scan, ship, and
join; an :class:`~repro.backend.base.ExecutionBackend` decides *how*
those decisions are carried out:

  * ``"simulated"`` — the paper's §4.1 analytical cost model
    (:class:`~repro.backend.simulated.SimulatedBackend`): bytes and
    match counts are exact, wall-clock is modeled from calibrated
    bandwidths. This is the seed behavior, extracted out of
    ``repro.core.cluster``.
  * ``"jax_mesh"`` — real execution over a ``jax.sharding.Mesh``
    (:class:`~repro.backend.jax_mesh.JaxMeshBackend`): one mesh axis
    maps paper *nodes* onto jax devices, cached chunks are committed as
    device-resident buffers via ``jax.device_put``, the join plan's
    ship decisions become actual cross-device transfers with measured
    bytes and wall-clock, and each node's shape-bucketed simjoin batch
    dispatches to the Pallas kernel (compiled on the chip; interpret
    mode only on CPU devices).

Both backends execute the *same* plans from the same coordinator, so
planned byte accounting is identical by construction — the mesh backend
adds measured quantities on top instead of replacing them.
"""
from repro.backend.artifacts import (ChunkView, JoinArtifactCache,
                                     subset_token, task_coords)
from repro.backend.base import (BACKENDS, DeviceBindingListener,
                                ExecutedQuery, ExecutionBackend,
                                workload_summary)
from repro.backend.cost_model import CostModel
from repro.backend.executors import (JOIN_BACKENDS, PRUNE_MODES, JoinTask,
                                     NumpyJoinExecutor, PallasJoinExecutor,
                                     PreparedBatch, count_similar_pairs_np,
                                     make_join_executor)
from repro.backend.simulated import MQO_MODES, SimulatedBackend
from repro.backend.jax_mesh import JaxMeshBackend, make_backend

__all__ = [
    "BACKENDS", "ChunkView", "CostModel", "DeviceBindingListener",
    "ExecutedQuery", "ExecutionBackend", "JOIN_BACKENDS",
    "JaxMeshBackend", "JoinArtifactCache", "JoinTask", "MQO_MODES",
    "NumpyJoinExecutor", "PRUNE_MODES", "PallasJoinExecutor",
    "PreparedBatch", "SimulatedBackend", "count_similar_pairs_np",
    "make_backend", "make_join_executor", "subset_token", "task_coords",
    "workload_summary",
]
