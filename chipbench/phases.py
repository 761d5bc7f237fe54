"""Put a cell's time on the program's own spans: the set-up's warm pass,
the window's flush phases, and the device's idle gaps.

    python3 chipbench/phases.py --workload mixed_stream.flush256 \\
        --seed 7 --seconds 10

Draws the cell's stream and warms every shape as ``run.py`` does, but
under an ``obs.Tracer``, so set-up's time falls into the spans that
spent it (``launch.call`` with ``traced`` set is the plan calls that
traced a new shape or read it from the compile cache).  Then it serves
``--seconds`` of timed parts untraced, the same under a Tracer, and
``trace_seconds`` under the profiler with ``Tracer(annotate=True)``, so
the program's spans land in the ``.xplane.pb`` on the device's clock.
Prints one JSON line: the span sums of each part, the ms per flush of
each, the device's idle time in the profiled part by the innermost
program span open through it (``idle_by_span``), and each span's
summed time on the profiler's clock beside the Tracer's.  Exits 1
where JAX finds no TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: the label of idle time no program span held: the client's own work
CLIENT = "client"


def innermost(spans) -> list:
    """Nested ``(start, end, name)`` intervals as sorted disjoint
    pieces, each labelled by the innermost interval open through it."""
    pieces, stack, t = [], [], None

    def emit(upto):
        if stack and upto > t:
            pieces.append((t, upto, stack[-1][1]))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            t = stack.pop()[0]
        emit(s)
        stack.append((e, name))
        t = s
    while stack:
        emit(stack[-1][0])
        t = stack.pop()[0]
    return pieces


def _host_events(data, keep):
    for p in data.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    if keep(e.name):
                        yield e.start_ns, e.end_ns, e.name


def idle_by_span(data, names) -> dict | None:
    """The device's idle seconds inside the benchmark's traced window
    (``xplane.WINDOW``), split by the innermost program span of
    ``names`` open through each part of each gap, and ``client`` where
    none is, on device 0, where ``harness.profile`` serves.  A gap that
    spans several phases is cut at their edges: one label for a whole
    gap would give the last launch's unpack the time of all of them.
    ``data`` is a ``jax.profiler.ProfileData``; None when the trace has
    no window or no device."""
    from chipbench import xplane
    names = set(names)
    windows = xplane.merge((s, e) for s, e, _ in _host_events(
        data, lambda n: n == xplane.WINDOW))
    window_starts = [s for s, _ in windows]
    pieces = innermost(_host_events(data, names.__contains__))
    piece_starts = [s for s, _, _ in pieces]
    devices = sorted((int(m.group(1)), p) for p in data.planes
                     if (m := xplane._DEVICE.match(p.name)))
    if not windows or not devices:
        return None
    ops = [part for e in xplane._events(devices[0][1], "XLA Ops")
           for part in xplane.intersect((e.start_ns, e.end_ns),
                                        windows, window_starts)]
    busy = xplane.merge(ops)
    idle: dict = collections.Counter()
    for lo, hi in windows:
        for s, e in xplane.gaps(xplane.clip(busy, lo, hi), lo, hi):
            held = 0
            i = max(bisect.bisect_right(piece_starts, s) - 1, 0)
            while i < len(pieces) and pieces[i][0] < e:
                ps, pe, name = pieces[i]
                part = min(e, pe) - max(s, ps)
                if part > 0:
                    idle[name] += part
                    held += part
                i += 1
            idle[CLIENT] += e - s - held
    return {k: v * 1e-9 for k, v in idle.most_common()}


def annotation_sums(data, names) -> dict:
    """Seconds of the host annotations named in ``names``, summed by
    name: the profiler's clock's reading of each program span."""
    sums: dict = collections.Counter()
    for s, e, name in _host_events(data, set(names).__contains__):
        sums[name] += (e - s) * 1e-9
    return dict(sums)


def span_sums(tracer) -> dict:
    """Seconds of the Tracer's extent spans, summed by name."""
    sums: dict = collections.Counter()
    for s in tracer.spans:
        if not s.instant:
            sums[s.name] += s.duration
    return dict(sums)


def traced_call_s(tracer) -> tuple[float, int]:
    """Seconds and count of the ``launch.call`` spans that traced."""
    calls = [s.duration for s in tracer.spans
             if s.name == "launch.call" and s.attrs.get("traced")]
    return sum(calls), len(calls)


def _per_launch_us(spans: dict, launches: int) -> dict:
    return {k: 1e6 * v / launches for k, v in sorted(spans.items())} \
        if launches else {}


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, t_start: float, backend: str = "pallas",
        out_dir: Path = HERE / "out" / "phases") -> dict:
    """Set-up, an untraced, a traced and a profiled part of ``cell``;
    returns the line's object."""
    import jax
    from jax.profiler import ProfileData
    from chipbench import discover, harness, traffic as traffic_gen
    from repro import obs, serving

    family = traffic_gen.family(config, traffic)
    stream = family.flushes(config, traffic, seed)
    loop = discover.module("loops", traffic["loop"])
    server = loop.server(backend)
    setup = obs.Tracer(clock=serving.MonotonicClock())
    with obs.installed(setup):
        for _ in range(traffic["pass_flushes"]):   # warm every shape
            loop.warm(server, next(stream))
    setup_s = time.perf_counter() - t_start
    plan_s, plan_calls = traced_call_s(setup)

    quiet = (lambda _: contextlib.nullcontext())
    untraced = harness.Served()
    untraced_s = loop.window(server, stream, seconds, quiet, untraced)

    window = obs.Tracer(clock=serving.MonotonicClock())
    traced = harness.Served()
    before = serving.stats["launches"]
    with obs.installed(window):
        traced_s = loop.window(server, stream, seconds, quiet, traced)
    launches = serving.stats["launches"] - before
    spans = span_sums(window)

    annotated = obs.Tracer(clock=serving.MonotonicClock(), annotate=True)
    with obs.installed(annotated):
        reduction, profiled, profiled_s = harness.profile(
            server, loop, stream, traffic["trace_seconds"], out_dir)
    files = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    mirrored = span_sums(annotated)
    on_profiler = annotation_sums(data, mirrored)

    device = jax.devices()[0]
    return {
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "setup": {"setup_s": setup_s, "setup_plan_s": plan_s,
                  "traced_calls": plan_calls, "spans": span_sums(setup)},
        "untraced_ms_per_flush": 1e3 * untraced_s / untraced.flushes,
        "traced": {"ms_per_flush": 1e3 * traced_s / traced.flushes,
                   "flushes": traced.flushes, "launches": launches,
                   "completed": traced.attempted - traced.failed,
                   "spans": spans,
                   "us_per_launch": _per_launch_us(spans, launches)},
        "profiled": {
            "ms_per_flush": 1e3 * profiled_s / profiled.flushes,
            "window_s": reduction["window_s"] if reduction else None,
            "busy_s": reduction["busy_s"] if reduction else None,
            "idle_by_span": idle_by_span(data, mirrored),
            "profiler_vs_tracer_s": {
                k: [on_profiler.get(k, 0.0), v]
                for k, v in sorted(mirrored.items())}},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell, config, traffic = harness.cell_parts(bench, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("phases: no TPU", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print(json.dumps(run(cell, config, traffic, seed=args.seed,
                         seconds=args.seconds, t_start=T_START)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
