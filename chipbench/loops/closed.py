"""One client in a closed loop: it submits a flush's requests, flushes,
waits for the results, and goes on with the next flush.

The timed part of each flush runs from its first ``submit`` call to the
return of its ``flush``, when the results are host arrays; the window
is the sum of those parts.  The client draws the next flush's requests
between them, outside the window, so fresh values cost the system
nothing.  A request's latency runs from its own ``submit`` call to the
return of the flush that holds it.
"""
import time

from chipbench import xplane


def server(backend: str):
    """The server this loop drives."""
    from repro import serving
    return serving.GeometryServer(backend=backend)


def warm(server, flush: list) -> None:
    """Serve one flush untimed: set-up's pass over every shape."""
    for r in flush:
        server.submit(r.chain, r.points, **r.options)
    server.flush()


def window(server, stream, seconds: float, mark, on_flush) -> float:
    """Serve flushes from ``stream`` until ``seconds`` of timed parts
    have passed, at least one; hand each flush, its results and its
    requests' latencies to ``on_flush``.  Returns the window's
    seconds."""
    served, first = 0.0, True
    while first or served < seconds:
        flush, first = next(stream), False
        submitted = []
        with mark(xplane.WINDOW):
            for r in flush:
                submitted.append(time.perf_counter())
                with mark("chipbench.submit"):
                    server.submit(r.chain, r.points, **r.options)
            with mark("chipbench.flush"):
                outs = server.flush()
            done = time.perf_counter()
        served += done - submitted[0]
        on_flush(flush, outs, [done - t for t in submitted])
    return served
