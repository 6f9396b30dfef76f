"""Adaptive-sampling serving subsystem.

The paper's "almost no synchronization" property is exactly what a *service*
needs to run many concurrent approximation queries on one device mesh
without head-of-line blocking: queries only interact with the scheduler at
epoch boundaries, where the engine state is a plain value pytree.

Three pieces:

* :mod:`repro.serve.session` — :class:`AdaptiveSession`, a checkpointable,
  resumable handle on one running query (bit-identical resume).
* :mod:`repro.serve.scheduler` — :class:`EpochScheduler`, epoch-granular
  continuous batching over a pool of heterogeneous sessions with a
  max-in-flight admission policy and per-query τ accounting.
* :mod:`repro.serve.elastic` — elastic re-sharding of SHARED_FRAME sessions
  (resume at a different worker width W′ | W, bit-identical (τ, estimate)),
  plus the train-side :func:`elastic_restore`.
* :mod:`repro.serve.placement` — the device-topology pool: carve pairwise-
  disjoint submeshes with lease/release semantics so concurrent sessions
  run on *different* devices instead of contending for the leading ones,
  and the :class:`PressurePolicy` that resizes SHARED_FRAME sessions under
  queued load.
"""

from .elastic import elastic_restore, reshard_session
from .placement import (DevicePool, DeviceTopology, Lease, PlacementWait,
                        PressurePolicy)
from .scheduler import EpochScheduler, QueryResult
from .session import AdaptiveSession, SessionSpec, StepperCache

__all__ = [
    "AdaptiveSession", "DevicePool", "DeviceTopology", "EpochScheduler",
    "Lease", "PlacementWait", "PressurePolicy", "QueryResult", "SessionSpec",
    "StepperCache", "elastic_restore", "reshard_session",
]
