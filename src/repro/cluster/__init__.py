"""Cluster runtime: DES engine, hardware catalog, workers, the
request-stream scheduler + application front-end, factory, availability
traces, and the dual (sim/live) executors.

MIGRATION (context-plane API): direct ``ContextRegistry`` mutation from
cluster code is gone — every residency write now flows through the
:class:`repro.core.ContextPlane` (``scheduler.plane``), driven by
declarative intents compiled against a read-only
:class:`repro.core.ClusterView` (``scheduler.view()``).  Old entry points
map as follows (direct-mutation shims survive this one PR, then go):

=====================================================  =====================
old direct call                                        context-plane intent
=====================================================  =====================
``registry.mark_staging(key, wid)`` (cold dispatch)    ``Acquire(key, wid)``
    + hand-picked ``Scheduler._pick_peer``             compiled by the plane
``WarmPoolPolicy.plan(sched)`` -> ``_stage_replica``   ``WarmPoolPolicy.intents(view)``
                                                       -> ``Replicate(key, n)``
``registry.mark_spilled`` / manual teardown            ``Release(key, wid)``
``registry.drop_worker(wid)`` (silent delete)          ``plane.drop_worker`` —
                                                       LOST tombstones +
                                                       ``recovery_intents``
=====================================================  =====================

Compiled plans are priced in per-zone bytes over the link classes
``transfer.py`` distinguishes and checked against a sliding
:class:`repro.core.LinkBudget` window (``Scheduler(link_budget=...)``);
proactive replication that would blow a zone's window is deferred, never
dropped.  Both executors run the same plan ops; per-zone byte counters
surface in run summaries via :func:`zone_byte_summary` /
:func:`format_zone_bytes`.
"""
from .events import EventLoop, Timer
from .hardware import (DECODE_FIXED_FRAC, GPU_CATALOG, TPU_CATALOG,
                       PAPER_CLUSTER, ClusterSpec, DeviceModel,
                       cluster_sample, local_device_model,
                       paper_20gpu_pool, pool_rate, REF_ACTIVE_PARAMS)
from .worker import Worker
from .scheduler import (Assignment, DECODE, PREFILL, Request,
                        RequestRecord, Scheduler, Task, TaskRecord)
from .gateway import (BATCH, ClassPolicy, Gateway, INTERACTIVE, REJECTED,
                      SLOClass, TIMED_OUT, format_gateway)
from .executors import LiveExecutor, SimExecutor
from .application import Application
from .factory import (Factory, make_sim, opportunistic_supply,
                      spill_aware_evict_priority)
from .forecast import ChurnInjector, DemandForecaster, ElasticPolicy
from .observability import (ProgressMonitor, Snapshot,
                            class_latency_summary, format_class_latency,
                            format_latency, format_pool, format_snapshot,
                            format_zone_bytes, latency_summary, percentile,
                            pool_summary, zone_byte_summary)
from .traces import (FAULT_KINDS, Fault, Storm, fault_schedule,
                     storm_schedule)
from .faults import FailureDetector, FaultInjector
from . import traces

__all__ = [
    "Application", "Assignment", "BATCH", "ChurnInjector", "ClassPolicy",
    "ClusterSpec",
    "DECODE", "DECODE_FIXED_FRAC", "DemandForecaster", "DeviceModel",
    "ElasticPolicy", "EventLoop", "FAULT_KINDS", "Factory",
    "FailureDetector", "Fault", "FaultInjector", "PREFILL",
    "fault_schedule",
    "GPU_CATALOG", "Gateway", "INTERACTIVE", "LiveExecutor",
    "PAPER_CLUSTER", "REF_ACTIVE_PARAMS", "REJECTED", "Request",
    "RequestRecord", "SLOClass", "Scheduler", "SimExecutor", "Storm",
    "TIMED_OUT", "TPU_CATALOG", "Task", "TaskRecord",
    "Timer", "Worker", "cluster_sample", "format_gateway",
    "local_device_model", "make_sim",
    "opportunistic_supply", "paper_20gpu_pool", "pool_rate",
    "spill_aware_evict_priority", "storm_schedule", "traces",
    "ProgressMonitor", "Snapshot", "class_latency_summary",
    "format_class_latency", "format_latency", "format_pool",
    "format_snapshot", "format_zone_bytes", "latency_summary",
    "percentile", "pool_summary", "zone_byte_summary",
]
