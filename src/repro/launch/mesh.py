"""Production mesh factories (functions, never module-level constants --
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding pass
    propagates layouts, as the model and serving code expect (the
    library default is ``Explicit``)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, auto, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Dev/test mesh over whatever devices exist (CPU included)."""
    n = len(jax.devices())
    assert n % model_axis == 0, (n, model_axis)
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
