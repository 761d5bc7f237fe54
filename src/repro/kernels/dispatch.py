"""Kernel backend dispatch.

Every kernel family exposes its public entry points through ``ops.py`` with a
``backend`` argument resolved here:

  * ``pallas``    -- compiled Pallas TPU kernel (the deployment path),
  * ``interpret`` -- the same Pallas kernel body executed with
                     ``interpret=True`` (CPU-correctness path; how this
                     container validates the TPU kernels),
  * ``ref``       -- the pure-jnp oracle in ``ref.py`` (also the lowering
                     path for the CPU dry-run, and the autodiff path).

This mirrors the paper's context-memory discipline: the *function* is fixed
("the context word"), only the execution substrate changes.
"""
from __future__ import annotations

import contextlib

import jax

_BACKEND: str = "auto"
_VALID = ("auto", "pallas", "interpret", "ref")

#: the degradation ladders, fastest substrate first: a launch that keeps
#: failing on one rung falls to the next.  ``interpret`` (the kernel body
#: in the Pallas interpreter) degrades to ``ref`` (the pure-jnp oracle:
#: survives kernel-body faults).  ``pallas`` (compiled TPU kernel)
#: degrades straight to ``ref``: the interpreter is a CPU-correctness
#: tool, and a request served by it on a chip would hide a kernel the
#: chip's compiler refused behind a result thousands of times slower.
#: Every rung computes the same function (the paper's context-word
#: discipline), so degrading trades speed, never results.
_LADDERS = {"pallas": ("pallas", "ref"), "interpret": ("interpret", "ref"),
            "ref": ("ref",)}


def fallback_ladder(backend: str | None = None) -> tuple[str, ...]:
    """The rungs a failing launch may degrade through, starting at (and
    including) the resolved ``backend``: ``("pallas", "ref")`` for a
    pallas server, ``("interpret", "ref")`` for an interpret one, just
    ``("ref",)`` at the bottom.  The serving engine walks this per
    failing bucket (see ``serving.engine``)."""
    return _LADDERS[resolve(backend)]


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def resolve(backend: str | None = None) -> str:
    b = backend or _BACKEND
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return b


@contextlib.contextmanager
def use_backend(name: str):
    global _BACKEND
    prev = _BACKEND
    set_backend(name)
    try:
        yield
    finally:
        _BACKEND = prev
