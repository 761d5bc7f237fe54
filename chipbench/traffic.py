"""The one traffic generator: a configuration file and a traffic file in,
an endless stream of flushes out.

A traffic file (``traffic/<mix>.json``) holds only parameters:

  family         the request family it drives (``families/<family>.py``),
                 the same as its configuration's
  loop           the window's loop (``loops/<loop>.py``)
  per_flush      requests submitted before each flush
  pass_flushes   flushes in one pass: their shapes repeat pass after pass
  shape_seed     seeds every shape: template order and point counts
  check_flushes  window flushes whose results are compared, drawn from
                 the run's seed
  trace_seconds  seconds of serving that the traced run's profiler
                 records

Shapes come from ``shape_seed`` and repeat every ``pass_flushes``
flushes, so set-up serves one pass and has warmed every bucket shape
the window serves.  Values (chain parameters, coordinates, geometry)
come from the run's ``--seed`` and are drawn afresh for every flush of
every pass: no two flushes of a window carry the same chains or points.

A family is a file ``families/<family>.py`` with
``flushes(config, traffic, seed)``, an endless iterator of flushes
(lists of ``Request``), and ``SMALL``, the keys a test run overrides to
hold it at a test's size.  A family whose results the float64
reference cannot judge (an integer lane, say) also brings ``Tally``,
the comparison in ``reference.Tally``'s place.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import discover, yardstick


@dataclasses.dataclass
class Request:
    chain: object              # the program's TransformChain
    points: np.ndarray         # float32 (n, dim)
    spec: tuple                # the primitives as drawn, for the reference
    payload_bytes: int
    options: dict              # keyword arguments of ``submit``


def request(chain, points, spec, **options) -> Request:
    kind = yardstick.plan_kind(spec)
    return Request(chain, points, tuple(spec),
                   yardstick.payload_bytes(kind, points.shape[-1],
                                           len(points)), options)


def family(config: dict, traffic: dict):
    """The family module that makes the cell's requests."""
    if traffic["family"] != config["family"]:
        raise ValueError(f"traffic for {traffic['family']!r} on a "
                         f"{config['family']!r} configuration")
    return discover.module("families", config["family"])

