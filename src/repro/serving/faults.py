"""Seeded fault injection + the chaos soak harness for GeometryServer.

The fault model (``docs/architecture.md`` section 6) has three injection
points, each mapped to a hook the engine already calls on the REAL
execution path -- the injector never gets a private code path to make
itself pass:

  * **launch faults** -- ``FaultInjector.before_launch`` raises
    ``InjectedFault`` exactly where a Mosaic compile error or device
    abort would surface; the engine's retry / backend-ladder / bisection
    machinery cannot tell the difference.
  * **staging corruption** -- ``corrupt_staging`` flips words in the
    packed operand buffer on its way to the device (the DMA-corruption
    failure mode); the engine detects it downstream through the output
    finiteness check and re-packs from the pristine host copies.
  * **malformed requests** -- ``malform`` produces the intake garbage
    (wrong dim, empty set, float64, NaN) that ``submit`` must reject
    with a typed error before it can poison a packed bucket.

Every decision is a pure function of ``(seed, ticket)`` -- roles come
from ``np.random.default_rng([SALT, seed, ticket])`` -- so a soak run
is bit-reproducible: ``tests/test_faults.py::TestChaosSoak`` asserts
equal counters from equal seeds, not "some faults happened".

``run_chaos_soak`` is the harness: a seeded mixed-lane workload (all
three plan kinds, float + q dtype lanes) served under injection, every
result verified against per-request ``TransformChain.apply`` oracles,
and the full counter set returned as a ``ChaosReport``.  Its invariant
is the PR's headline contract: zero lost requests -- every submission
resolves to a verified result or a typed, ticket-named error.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro import quantize
from repro.core import transform_chain as tc
from repro.obs import recorder as obsrec
from repro.obs import trace as obst
from repro.serving import engine, workload
from repro.serving.clock import VirtualClock
from repro.serving.errors import InjectedFault, LaunchError, RequestError

#: role-draw salt: keeps the injector's stream disjoint from every other
#: seeded stream in the repo (workloads use their own salts)
_SALT = 0xFA17

#: what a ticket can be scheduled to do, and which recovery mechanism it
#: exercises:
#:   flaky   -- launch fails while attempt < flaky_attempts (same rung):
#:              recovered by RETRY with backoff
#:   backend -- launch fails on ladder rung 0, any attempt: recovered by
#:              BACKEND DEGRADATION (pallas -> ref, interpret -> ref)
#:   corrupt -- staged words NaN out at (rung 0, attempt 0): detected by
#:              the output finiteness check, recovered by a pristine
#:              re-pack RETRY
#:   poison  -- launch fails at every rung and attempt: isolated by
#:              BISECTION, resolves to a typed LaunchError; its bucket
#:              neighbours all recover
ROLES = ("flaky", "backend", "corrupt", "poison")


@dataclasses.dataclass
class FaultInjector:
    """Deterministic per-ticket fault scheduler.

    Roles are assigned per TICKET (not per bucket): a launch group fails
    when any member request's role says so at this (rung, attempt), which
    is exactly how a real poison request takes a packed bucket down.
    Explicit ``*_tickets`` overrides win over the seeded rate draw --
    tests pin scenarios with them; the soak uses rates."""
    seed: int = 0
    flaky_rate: float = 0.0
    backend_rate: float = 0.0
    corrupt_rate: float = 0.0
    poison_rate: float = 0.0
    flaky_attempts: int = 2        # flaky launches fail attempts < this
    flaky_tickets: frozenset = frozenset()
    backend_tickets: frozenset = frozenset()
    corrupt_tickets: frozenset = frozenset()
    poison_tickets: frozenset = frozenset()

    def __post_init__(self):
        self.injected_launch_faults = 0
        self.injected_corruptions = 0
        self._roles: dict[int, str | None] = {}

    def role(self, ticket: int) -> str | None:
        """This ticket's scheduled role (None = clean), memoised; the
        draw itself depends only on (seed, ticket)."""
        if ticket not in self._roles:
            for name in ROLES:
                if ticket in getattr(self, f"{name}_tickets"):
                    self._roles[ticket] = name
                    break
            else:
                u = np.random.default_rng([_SALT, self.seed, ticket]).random()
                edge = 0.0
                self._roles[ticket] = None
                for name, rate in (("poison", self.poison_rate),
                                   ("backend", self.backend_rate),
                                   ("flaky", self.flaky_rate),
                                   ("corrupt", self.corrupt_rate)):
                    edge += rate
                    if u < edge:
                        self._roles[ticket] = name
                        break
        return self._roles[ticket]

    # -- engine hooks --------------------------------------------------------

    def before_launch(self, tickets: tuple, rung_index: int,
                      attempt: int) -> None:
        """Called by the engine immediately before dispatching a launch
        (initial, retry, degraded, or bisected); raises to fail it."""
        for t in tickets:
            r = self.role(t)
            fail = (r == "poison"
                    or (r == "backend" and rung_index == 0)
                    or (r == "flaky" and rung_index == 0
                        and attempt < self.flaky_attempts))
            if fail:
                self.injected_launch_faults += 1
                raise InjectedFault(
                    f"injected {r} fault (ticket {t}, rung {rung_index}, "
                    f"attempt {attempt})")

    def corrupt_staging(self, packed: np.ndarray, tickets: tuple,
                        rung_index: int, attempt: int) -> np.ndarray:
        """Called by the engine while staging a float affine bucket; may
        return a corrupted COPY of the packed operand buffer (the host
        copies in the queue stay pristine -- that is what recovery
        re-packs from)."""
        if rung_index != 0 or attempt != 0:
            return packed
        rows = [i for i, t in enumerate(tickets)
                if self.role(t) == "corrupt"]
        if not rows:
            return packed
        out = np.array(packed, copy=True)
        out[rows, 0, 0] = np.nan
        self.injected_corruptions += len(rows)
        return out


#: malformed-submission modes and how ``submit`` must answer each --
#: (mode, expected error code from the repro.errors taxonomy)
MALFORM_MODES = (("empty", "empty"), ("shape", "shape"),
                 ("float64", "dtype"), ("nan", "nonfinite"))


def malform(points: np.ndarray, mode: str) -> np.ndarray:
    """Turn a valid point set into intake garbage of the given mode."""
    if mode == "empty":
        return np.zeros((0, points.shape[-1]), np.float32)
    if mode == "shape":
        return np.asarray(points)[..., :-1] if points.shape[-1] > 1 \
            else np.repeat(np.asarray(points), 2, axis=-1)
    if mode == "float64":
        return np.asarray(points, dtype=np.float64)
    if mode == "nan":
        bad = np.array(points, copy=True)
        bad.reshape(-1)[0] = np.nan
        return bad
    raise ValueError(f"unknown malform mode {mode!r}")


@dataclasses.dataclass
class ChaosReport:
    """One soak run's full accounting, deterministic for a fixed (seed,
    n_requests, rates, backend); ``lost == 0`` and ``mismatches == 0``
    are the invariants (tests/test_faults.py::TestChaosSoak)."""
    seed: int
    backend: str
    requests: int                  # well-formed submissions
    malformed: int                 # deliberately-garbage submissions
    rejected_at_submit: int        # typed RequestErrors raised at intake
    resolved: int                  # result slots holding verified points
    failed_requests: int           # result slots holding a LaunchError
    lost: int                      # submissions with NO resolution (must be 0)
    mismatches: int                # resolved results that failed the oracle
    faulted_buckets: int           # buckets that needed any recovery
    launches: int
    launch_failures: int
    retries: int
    backend_fallbacks: int
    bisections: int
    recovered_requests: int
    q_fallbacks: int
    injected_launch_faults: int
    injected_corruptions: int
    #: per-recovery-ladder flight-recorder post-mortems: one entry per
    #: recovery track, each the span/event dicts of that ladder's walk
    #: (deterministic under the soak's auto-installed virtual-clock
    #: tracer) -- a chaos failure in CI is debuggable from the report
    postmortems: list = dataclasses.field(default_factory=list)

    def counters(self) -> dict:
        """The counters, name -> value."""
        d = dataclasses.asdict(self)
        d.pop("backend")
        d.pop("postmortems")
        return d


def _expected_lane(chain: tc.TransformChain, pts: np.ndarray,
                   fmt: quantize.QFormat, cfg: engine.FaultConfig) -> str:
    """Which lane a q-tagged request lands in under the server's
    overflow policy -- the same fits() the engine consults at submit."""
    if cfg.on_q_overflow == "wrap" or not len(chain):
        return "q"
    kind = tc.plan_kind_of(chain.structure)
    return "q" if quantize.fits(chain.fold(), kind, fmt,
                                float(np.abs(pts).max())) else "float"


def _verify_one(chain: tc.TransformChain, pts: np.ndarray,
                qname: str | None, res,
                cfg: engine.FaultConfig) -> bool:
    """One request's oracle check against per-request apply on the ref
    backend: bitwise for the q lane (integer arithmetic is exact),
    tolerance-based for float lanes (packed vs single-request float
    contraction differs in the last ULPs), mask equality + tolerance for
    projective results."""
    if qname is not None:
        fmt = quantize.as_qformat(qname)
        if _expected_lane(chain, pts, fmt, cfg) == "q":
            ref = chain.apply(pts, dtype=qname, backend="ref")
            return np.array_equal(np.asarray(res), np.asarray(ref))
        # q->float fallback: served through the float32 lane
        ref = chain.apply(pts, backend="ref")
        return np.allclose(res, np.asarray(ref), rtol=2e-4, atol=2e-4)
    if chain.is_projective:
        ref, ref_mask = chain.project(pts, backend="ref")
        ok = np.allclose(res, np.asarray(ref), rtol=1e-4, atol=1e-4)
        if getattr(res, "mask", None) is not None:
            ok = ok and np.array_equal(np.asarray(res.mask),
                                       np.asarray(ref_mask))
        return bool(ok)
    ref = chain.apply(pts, backend="ref")
    return np.allclose(res, np.asarray(ref), rtol=2e-4, atol=2e-4)


def run_chaos_soak(seed: int = 0, n_requests: int = 64, *,
                   backend: str = "interpret", q_fraction: float = 0.25,
                   qformat: str = "q8.7", malformed_every: int = 9,
                   flaky_rate: float = 0.06, backend_rate: float = 0.05,
                   corrupt_rate: float = 0.05, poison_rate: float = 0.03,
                   fault_config: engine.FaultConfig | None = None
                   ) -> ChaosReport:
    """Serve a seeded mixed-lane workload under seeded fault injection
    and account for every request.

    The workload mixes diagonal / matrix / projective structures and the
    float + fixed-point lanes; every ``malformed_every``-th submission is
    deliberately garbage (cycling ``MALFORM_MODES``).  The injector's
    default rates put a fault in roughly 20% of buckets.  ``backend``
    defaults to "interpret" so the degradation ladder has a live rung
    below it ("ref") in every environment, including CPU CI.

    Every resolved result is checked against its per-request ``apply``
    oracle and every failure slot must be a ``LaunchError`` naming its
    own ticket; ``lost`` counts submissions with neither, and the
    invariant is ``lost == 0``.

    Runs traced: if no tracer is installed, the soak installs its own
    (virtual clock at 0, so recovery post-mortems are a pure function of
    the seed) for the duration and attaches per-ladder flight-recorder
    windows to ``ChaosReport.postmortems``."""
    if not obst.active().enabled:
        tracer = obst.Tracer(clock=VirtualClock(),
                             recorder=obsrec.FlightRecorder(512))
        with obst.installed(tracer):
            return _chaos_soak_traced(
                seed, n_requests, backend=backend, q_fraction=q_fraction,
                qformat=qformat, malformed_every=malformed_every,
                flaky_rate=flaky_rate, backend_rate=backend_rate,
                corrupt_rate=corrupt_rate, poison_rate=poison_rate,
                fault_config=fault_config)
    return _chaos_soak_traced(
        seed, n_requests, backend=backend, q_fraction=q_fraction,
        qformat=qformat, malformed_every=malformed_every,
        flaky_rate=flaky_rate, backend_rate=backend_rate,
        corrupt_rate=corrupt_rate, poison_rate=poison_rate,
        fault_config=fault_config)


def _recovery_postmortems(trc) -> list:
    """Group the trace's recovery-track events into one post-mortem per
    ladder (insertion order = first failure order, so deterministic)."""
    tracks: dict = {}
    for s in trc.spans:
        if s.track is not None and str(s.track).startswith("recovery"):
            tracks.setdefault(s.track, []).append(s.as_dict())
    return [{"track": t, "events": evs} for t, evs in tracks.items()]


def _chaos_soak_traced(seed, n_requests, *, backend, q_fraction, qformat,
                       malformed_every, flaky_rate, backend_rate,
                       corrupt_rate, poison_rate, fault_config):
    cfg = fault_config or engine.FaultConfig()
    srv = engine.GeometryServer(
        backend=backend, fault_config=cfg,
        injector=FaultInjector(seed=seed, flaky_rate=flaky_rate,
                               backend_rate=backend_rate,
                               corrupt_rate=corrupt_rate,
                               poison_rate=poison_rate))
    triples = workload.mixed_lane_workload(seed, n_requests,
                                           q_fraction=q_fraction,
                                           qformat=qformat)
    base = {k: engine.stats[k] for k in engine.stats}
    rejected = malformed = 0
    submitted = []                 # (ticket, chain, pts, qname)
    for i, (chain, pts, qname) in enumerate(triples):
        if malformed_every and i % malformed_every == malformed_every - 1:
            mode, _code = MALFORM_MODES[(i // malformed_every)
                                        % len(MALFORM_MODES)]
            malformed += 1
            try:
                srv.submit(chain, malform(pts, mode))
            except RequestError:
                rejected += 1      # the only acceptable outcome
        try:
            ticket = srv.submit(chain, pts, qformat=qname)
        except RequestError:
            # default rates + workload never reject a well-formed
            # request; count it rather than crash if a config does
            rejected += 1
            continue
        submitted.append((ticket, chain, pts, qname))
    if q_fraction > 0:
        # one guaranteed-overflow q request: q8.7 spans [-256, 256), so a
        # x1000 scale must trip the wrap prediction (reject or float32
        # reroute, per policy) -- exercised, and gateable, in every soak
        probe = tc.TransformChain(dim=2).scale(1000.0).translate([1.0, -1.0])
        probe_pts = np.linspace(-1, 1, 16, dtype=np.float32).reshape(8, 2)
        try:
            t = srv.submit(probe, probe_pts, qformat=qformat)
            submitted.append((t, probe, probe_pts, qformat))
        except RequestError:
            rejected += 1          # the "reject" overflow policy
    results = srv.flush()

    by_ticket = {}
    for (ticket, *_), res in zip(submitted, results):
        by_ticket[ticket] = res
    resolved = failed = lost = mismatches = 0
    for ticket, chain, pts, qname in submitted:
        res = by_ticket.get(ticket)
        if isinstance(res, LaunchError):
            failed += 1
            if res.ticket != ticket:
                mismatches += 1    # a mis-addressed error is a lost result
        elif res is None:
            lost += 1
        else:
            resolved += 1
            if not _verify_one(chain, pts, qname, res, cfg):
                mismatches += 1
    lost += len(submitted) - len(results) if len(results) < len(submitted) \
        else 0

    delta = {k: engine.stats[k] - base[k] for k in engine.stats}
    faulted = sum(1 for r in srv.last_report
                  if r.retries or r.bisections or r.backend_fallbacks
                  or r.failed_requests or r.recovered_requests)
    return ChaosReport(
        seed=seed, backend=backend, requests=len(submitted),
        malformed=malformed, rejected_at_submit=rejected,
        resolved=resolved, failed_requests=failed, lost=lost,
        mismatches=mismatches, faulted_buckets=faulted,
        launches=delta["launches"],
        launch_failures=delta["launch_failures"],
        retries=delta["retries"],
        backend_fallbacks=delta["backend_fallbacks"],
        bisections=delta["bisections"],
        recovered_requests=delta["recovered_requests"],
        q_fallbacks=delta["q_fallbacks"],
        injected_launch_faults=srv.injector.injected_launch_faults,
        injected_corruptions=srv.injector.injected_corruptions,
        postmortems=_recovery_postmortems(obst.active()))
