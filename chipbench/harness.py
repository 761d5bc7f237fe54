"""One run of one cell: set-up, the measured window, the check, the
metrics, and the result line.

Set-up draws one pass of the cell's flushes and serves it, so every
bucket shape the window serves is compiled (or read from the compile
cache) and traced before the window opens.  The window is the loop the
traffic file names (``loops/<loop>.py``), fed from the same stream, so
its flushes carry fresh values in the shapes set-up warmed.

A traced run installs an ``obs.Tracer`` on the monotonic clock for the
whole window.  After the window it serves the stream for
``trace_seconds`` more under the profiler, with ``chipbench.*``
annotations around the timed part of each flush and around each submit
and flush, so the profiler's cost stays out of the spans.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import discover, reference, traffic as traffic_gen, xplane
from chipbench import yardstick

HERE = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the program's host spans of a flush's layers, which do not nest
HOST_SPANS = ("request.validate", "bucket.pack", "flush.dispatch",
              "flush.unpack")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_parts(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """The cell's entry, its configuration file and its traffic file."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(HERE.parent / entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    traffic_gen.family(config, traffic)     # the pair has to match
    return cell, config, traffic


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones: those that list the cell, and those that list none."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    return discover.module("metrics", name).read


class CompileCounter:
    """Backend compiles (persistent-cache reads included) while on."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


class GcClock:
    """Seconds the garbage collector ran, and its full collections,
    while on."""

    def __init__(self):
        import gc
        self.on, self.seconds, self.full, self._t = False, 0.0, 0, 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2


class Served:
    """What a loop hands back flush by flush: requests attempted and
    failed, latencies, payload bytes, and a reservoir of ``keep``
    flushes with their results, drawn with ``draw``."""

    def __init__(self, keep: int = 0, draw=None):
        self.keep, self.draw = keep, draw
        self.sample, self.latencies, self.flush_s = [], [], []
        self.attempted = self.failed = self.flushes = self.payload = 0

    def __call__(self, flush: list, outs: list, latencies: list) -> None:
        self.attempted += len(flush)
        self.failed += len(flush) - sum(isinstance(o, np.ndarray)
                                        for o in outs)
        self.latencies.extend(latencies)
        self.flush_s.append(max(latencies))
        self.payload += sum(r.payload_bytes for r in flush)
        if self.flushes < self.keep:
            self.sample.append((flush, outs))
        elif self.keep and \
                (j := int(self.draw.integers(self.flushes + 1))) < self.keep:
            self.sample[j] = (flush, outs)
        self.flushes += 1


def check(sample: list, limits: dict, *, failed: int, fallbacks: int,
          tally=reference.Tally) -> tuple[dict, dict]:
    """Compare each sampled flush's results with the reference.
    ``failed`` counts the window's requests that resolved to no result.
    Returns the numbers compared, each beside its limit, and what was
    checked."""
    tally = tally(failed=failed)
    for flush, outs in sample:
        for r, out in zip(flush, outs):
            tally.add(r.spec, r.points, out, getattr(out, "mask", None),
                      limits["err_ulps"])
    checked = {"requests": tally.checked_requests,
               "points": tally.checked_points,
               "undecided_points": tally.undecided_points}
    return tally.numbers(limits, fallbacks), checked


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the annotations, not every call
    opts.host_tracer_level = 1
    return opts


def profile(server, loop, stream, seconds: float,
            out_dir: Path) -> tuple[dict | None, Served, float]:
    """Serve the stream for ``seconds`` under the profiler; returns the
    trace's reduction, what was served, and the seconds it took.  The
    server runs on the default device alone, so the reduction reads
    that one device."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    served = Served()
    jax.profiler.start_trace(str(out_dir), profiler_options=_profile_options())
    window_s = loop.window(server, stream, seconds,
                           jax.profiler.TraceAnnotation, served)
    jax.profiler.stop_trace()
    files = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        return None, served, window_s
    data = jax.profiler.ProfileData.from_file(max(files,
                                                  key=os.path.getmtime))
    return xplane.reduce(data), served, window_s


def run(cell: dict, config: dict, traffic: dict, metrics: list, *,
        seed: int, seconds: float, trace: bool, t_start: float,
        backend: str = "pallas", out_dir: Path = HERE / "out") -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    from repro import obs, serving

    compiles, gc_clock = CompileCounter(), GcClock()
    family = traffic_gen.family(config, traffic)
    stream = family.flushes(config, traffic, seed)
    loop = discover.module("loops", traffic["loop"])
    server = loop.server(backend)
    for _ in range(traffic["pass_flushes"]):   # warm every shape
        loop.warm(server, next(stream))

    tracer = obs.Tracer(clock=serving.MonotonicClock()) if trace else None
    served = Served(traffic["check_flushes"],
                    np.random.default_rng([seed, 0xC4EC]))
    quiet = (lambda _: contextlib.nullcontext())
    before = dict(serving.stats)
    compiles.on = gc_clock.on = True
    obs.install(tracer)
    t0 = time.perf_counter()
    window_s = loop.window(server, stream, seconds, quiet, served)
    t_end = time.perf_counter()
    obs.install(None)
    compiles.on = gc_clock.on = False
    counters = {k: serving.stats[k] - before[k] for k in before}
    device_trace, traced, traced_s = profile(
        server, loop, stream, traffic["trace_seconds"],
        out_dir / "profile") if trace else (None, Served(), 0.0)

    devices = jax.devices()[:cell["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    numbers, checked = check(served.sample, config["limits"],
                             failed=served.failed,
                             fallbacks=counters["launch_failures"]
                             + counters["backend_fallbacks"],
                             tally=getattr(family, "Tally", reference.Tally))
    correct = reference.within(numbers) and checked["requests"] > 0

    spans = collections.Counter()
    for s in (tracer.spans if tracer else ()):
        if not s.instant:
            spans[s.name] += s.duration
    kind = devices[0].device_kind
    record = {
        "setup_s": t0 - t_start, "window_s": window_s,
        "latencies_s": served.latencies,
        "completed": served.attempted - served.failed,
        "spans": dict(spans), "counters": counters,
        "window_compiles": compiles.count, "device": device_trace,
        "traced_payload_bytes": traced.payload,
        # a chip with no published peaks is an error, not a default
        "peaks": yardstick.peaks(kind) if device_trace else None,
    }
    values = {}
    for m in metrics:
        value = reader(m["name"])(record)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    line = {"correct": bool(correct), "attempted": served.attempted,
            "failed": served.failed, "metrics": values, "device": device}
    # where a window's time went on the host, beside its rate
    median = yardstick.percentile(served.flush_s, 50)
    line["host"] = {
        "flushes": served.flushes, "wall_s": t_end - t0,
        "flush_ms_p50": 1e3 * median,
        "flush_ms_max": 1e3 * max(served.flush_s),
        "slow_flush_s": sum(f - median for f in served.flush_s
                            if f > 2 * median),
        "gc_s": gc_clock.seconds, "gc_full": gc_clock.full}
    if trace:
        # what tracing costs: the timed part of a flush in the window
        # (Tracer on), the layers' spans in it, and under the profiler
        host = sum(spans[k] for k in HOST_SPANS)
        line["trace_cost"] = {
            "window_ms_per_flush": 1e3 * window_s / served.flushes,
            "span_ms_per_flush": 1e3 * host / served.flushes,
            "profiled_ms_per_flush":
                1e3 * traced_s / traced.flushes if traced.flushes else None}
    if device_trace is not None:
        device["busy_s"] = device_trace["busy_s"]
        device["window_s"] = device_trace["window_s"]
        line["breakdown"] = {k: device_trace[k]
                             for k in ("device_ops", "idle_gaps")}
    line["checked"] = checked
    line["check"] = numbers
    return line


def report(line: dict) -> None:
    """Print the numbers compared as the last lines of standard error,
    and the result as the last line of standard output."""
    for name, n in line["check"].items():
        print(f"check {name} {n['value']} limit {n['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
