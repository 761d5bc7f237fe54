"""Batched transform-serving engine (plan-bucketed scheduling).

Layered on the PR 1 fused chain compiler: heterogeneous transform requests
bucket by chain structure + backend (+ dtype + padded size class), every
bucket executes as ONE batched fused-kernel launch against one cached
plan, and bucket k+1's host->device staging overlaps bucket k's compute
(the paper's frame-buffer set-0/set-1 discipline).  See
``docs/architecture.md`` for the dataflow diagram and
``repro.serving.engine`` for the mechanics.

Fault tolerance (PR 6): ``submit`` rejects malformed requests with the
typed ``serving.errors`` taxonomy; ``flush`` contains per-bucket launch
failures behind a retry / backend-degradation / bisection ladder so no
request is ever silently lost; ``serving.faults`` is the seeded
fault-injection harness (``run_chaos_soak``) the chaos CI lane gates on.

Continuous batching (PR 7): ``AsyncGeometryServer`` is the async
front-end over the same engine -- ``submit_async`` returns awaitable
``Ticket`` objects, admission control (``serving.admission``: bounded
queue depth, per-tenant fair share + token buckets) sheds load at the
intake boundary with typed rejections, and a flush policy coupling the
``SLOConfig`` max-wait deadline to bucket fill decides when each plan
bucket launches.  All timing flows through the injectable
``serving.clock.Clock`` (``VirtualClock`` = deterministic tests and the
seeded soak benchmark; ``MonotonicClock`` = real traffic).
"""
from repro.serving import errors
from repro.serving.admission import (AdmissionConfig, AdmissionController,
                                     QueueFullError, RateLimitError,
                                     TokenBucket)
from repro.serving.async_engine import (AsyncGeometryServer, SLOConfig,
                                        Ticket)
from repro.serving.bucketing import padded_length, waste_fraction
from repro.serving.clock import (Clock, MonotonicClock, VirtualClock,
                                 percentile)
from repro.serving.engine import (BatchPlan, BucketReport, FaultConfig,
                                  GeometryServer, Projected, Resident,
                                  clear_plan_cache, get_batch_plan,
                                  reset_stats, stats)
from repro.serving.errors import (CorruptionError, InjectedFault, LaunchError,
                                  RequestError, is_error)
from repro.serving.faults import (ChaosReport, FaultInjector, malform,
                                  run_chaos_soak)
from repro.serving.workload import (chain_for, mixed_lane_workload,
                                    random_workload)

__all__ = [
    "AdmissionConfig", "AdmissionController", "AsyncGeometryServer",
    "BatchPlan", "BucketReport", "ChaosReport", "Clock", "CorruptionError",
    "FaultConfig", "FaultInjector", "GeometryServer", "InjectedFault",
    "LaunchError", "MonotonicClock", "Projected", "QueueFullError",
    "RateLimitError", "RequestError", "Resident", "SLOConfig", "Ticket",
    "TokenBucket",
    "VirtualClock", "chain_for", "clear_plan_cache", "errors",
    "get_batch_plan", "is_error", "malform", "mixed_lane_workload",
    "padded_length", "percentile", "random_workload", "reset_stats",
    "run_chaos_soak", "stats", "waste_fraction",
]
