"""A renderer as the client: it keeps its meshes on the device and
draws instances of them, in the closed loop of ``closed.py``.

Each distinct host mesh is uploaded once (``GeometryServer.upload``),
the first time one of its requests is submitted, which is in set-up's
warm pass; the renderer keeps the mesh array and its handle, keyed by
the array's identity.  Every request after that submits the handle, so
the window sends no points: only each instance's chain.  The window,
its timed parts and its latencies are ``closed.py``'s.
"""
from chipbench import discover

_closed = discover.module("loops", "closed")


class Renderer:
    """The server behind a renderer's own table of resident meshes; it
    offers the ``submit`` and ``flush`` the closed loop calls."""

    def __init__(self, server):
        self.server = server
        self._handles: dict = {}      # id(mesh) -> (mesh, handle)

    def submit(self, chain, points, **options) -> int:
        kept = self._handles.get(id(points))
        if kept is None:
            kept = self._handles[id(points)] = (points,
                                                self.server.upload(points))
        return self.server.submit(chain, kept[1], **options)

    def flush(self) -> list:
        return self.server.flush()


def server(backend: str) -> Renderer:
    """The renderer this loop drives, over a fresh server."""
    return Renderer(_closed.server(backend))


warm = _closed.warm
window = _closed.window
