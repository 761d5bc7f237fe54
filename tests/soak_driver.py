"""A seeded Poisson soak through the async front-end on a virtual clock.

Requests arrive on a Poisson timeline, four tenants share per-tenant
token buckets, and the deadline-times-fill flush policy decides every
launch; the timeline is a ``VirtualClock``, so arrivals, admission
decisions, bucket compositions, launch counts and the virtual-time
latency percentiles are pure functions of the seed.  Served under a
tracer on the same clock, every span timestamp is too, so the exported
Chrome-trace JSON and Prometheus text are byte-identical from run to
run.

``tests/test_soak.py`` drives it in-process and, as

    PYTHONPATH=src python tests/soak_driver.py TRACE.json SNAP.prom COUNTERS.json

in fresh processes (the traced soak of ``TRACE_REQUESTS`` arrivals).
"""
from __future__ import annotations

import json
import sys

import numpy as np

from repro import obs
from repro.serving import admission as adm
from repro.serving import engine, workload
from repro.serving.async_engine import AsyncGeometryServer, SLOConfig
from repro.serving.clock import VirtualClock

SEED = 17
#: arrivals in the traced soak
TRACE_REQUESTS = 250
#: distinct requests in the replayed pool (cycled; the pool is seeded)
POOL = 384


def drive_soak(n_requests: int, *, traced: bool = False,
               trace_path: str | None = None,
               prom_path: str | None = None) -> dict:
    """Drive one seeded soak and return its counters.

    The driver alternates between the next arrival and the engine's
    ``next_due_in`` deadline, advancing the clock to whichever comes
    first.  Offered 150k requests/s against four 30k token buckets, the
    rate limiter must shed a share of the arrivals.  ``traced`` serves
    under a tracer on the soak's clock; ``trace_path`` and ``prom_path``
    write its Chrome trace and the engines' registries.
    """
    pool = workload.mixed_lane_workload(SEED, POOL, max_points=48)
    clock = VirtualClock()
    eng = AsyncGeometryServer(
        backend="ref", clock=clock,
        slo=SLOConfig(max_wait_s=0.02, target_rows=32),
        admission=adm.AdmissionConfig(max_queue_depth=1024,
                                      tenant_share=0.5,
                                      tenant_rate=30_000.0,
                                      tenant_burst=64.0))
    rng = np.random.default_rng([0x50AF, SEED])
    base = dict(engine.stats)
    tracer = obs.Tracer(clock=clock) if traced else obs.NullTracer()

    next_arrival = 0.0
    polls = 0
    with obs.installed(tracer):
        for i in range(n_requests):
            while True:
                nd = eng.next_due_in()
                if nd is None or clock.now() + nd >= next_arrival:
                    break
                clock.advance(nd)
                eng.poll()
                polls += 1
            clock.advance_to(next_arrival)
            chain, pts, qname = pool[i % POOL]
            try:
                eng.submit_async(chain, pts,
                                 tenant=f"t{int(rng.integers(4))}",
                                 qformat=qname)
            except (adm.QueueFullError, adm.RateLimitError):
                pass                  # counted by the admission controller
            next_arrival += float(rng.exponential(1.0 / 150_000.0))
        # the flush policy retires the tail on its own deadlines
        while (nd := eng.next_due_in()) is not None:
            clock.advance(nd)
            eng.poll()
            polls += 1

    st = eng.stats
    delta = {k: engine.stats[k] - base[k] for k in base}
    if trace_path is not None:
        obs.dump_chrome_trace(tracer, trace_path)
    if prom_path is not None:
        with open(prom_path, "w") as f:
            f.write(obs.prometheus_text(eng.metrics, eng.server.metrics,
                                        eng._admission.metrics))
    return {
        "requests": n_requests,
        "admitted": st["admitted"],
        "rate_limited": st["rate_limit_rejections"],
        "queue_full": st["queue_full_rejections"],
        "resolved": st["resolved"],
        "failed": st["failed"],
        "queue_depth": st["queue_depth"],
        "launches": delta["launches"],
        "buckets": delta["buckets"],
        "payload_points": delta["payload_points"],
        "padded_points": delta["padded_points"],
        "polls": polls,
        "p50_virtual_us": st["p50_latency_s"] * 1e6,
        "p99_virtual_us": st["p99_latency_s"] * 1e6,
    }


if __name__ == "__main__":
    trace_out, prom_out, counters_out = sys.argv[1:4]
    counters = drive_soak(TRACE_REQUESTS, traced=True,
                          trace_path=trace_out, prom_path=prom_out)
    with open(counters_out, "w") as f:
        json.dump(counters, f)
