"""Observability-layer tests: span-tree tracer semantics, the typed
metrics registry and its back-compat ``StatsView``, Chrome-trace /
Prometheus export determinism, the flight recorder, per-server counter
isolation, and the span-tree completeness invariants under seeded fault
injection (every submitted ticket's tree accounts for its outcome --
success, rejection, recovery, or bisection -- and the ``launch``
instant count equals ``stats["launches"]`` exactly).
"""
import json
import math

import numpy as np
import pytest

from repro import obs, serving
from repro.core import transform_chain as tc
from repro.serving import engine, faults, workload
from repro.serving.async_engine import AsyncGeometryServer, SLOConfig
from repro.serving.clock import VirtualClock

RNG = np.random.default_rng(80)


def _fresh(**kw):
    serving.reset_stats()
    serving.clear_plan_cache()
    return serving.GeometryServer(**kw)


def _cfg(**kw):
    kw.setdefault("backoff_base_s", 0.0)
    return engine.FaultConfig(**kw)


def _chain2():
    return tc.TransformChain.identity(2).translate(0.5, -0.25).scale(1.5)


def _pts(n=8, dim=2):
    return RNG.uniform(-1, 1, (n, dim)).astype(np.float32)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_begin_end_nest_and_stack(self):
        clk = VirtualClock()
        trc = obs.Tracer(clock=clk)
        a = trc.begin("outer")
        clk.advance(1.0)
        b = trc.begin("inner", ticket=7)
        clk.advance(0.5)
        trc.end(b)
        trc.end(a)
        outer, inner = trc.spans[0], trc.spans[1]
        assert outer.name == "outer" and outer.t0 == 0.0 and outer.t1 == 1.5
        assert inner.parent == outer.sid and inner.duration == 0.5
        assert inner.ticket == 7

    def test_end_merges_attrs_and_late_ticket(self):
        trc = obs.Tracer(clock=VirtualClock())
        sid = trc.begin("s", a=1)
        trc.end(sid, ticket=3, b=2)
        (s,) = trc.spans
        assert s.ticket == 3 and s.attrs == {"a": 1, "b": 2}

    def test_instant_and_complete(self):
        trc = obs.Tracer(clock=VirtualClock(start=2.0))
        trc.instant("mark", ticket=1, k="v")
        trc.complete("retro", 0.25, 0.75, ticket=1)
        mark, retro = trc.spans
        assert mark.instant and mark.t0 == 2.0
        assert not retro.instant and (retro.t0, retro.t1) == (0.25, 0.75)
        assert trc.n_events == 2 and trc.n_spans == 1

    def test_span_contextmanager_closes_on_error(self):
        trc = obs.Tracer(clock=VirtualClock())
        with pytest.raises(RuntimeError):
            with trc.span("work", ticket=5):
                raise RuntimeError("boom")
        (s,) = trc.spans
        assert s.t1 is not None and s.ticket == 5

    def test_span_tree_reconstructs_per_ticket(self):
        trc = obs.Tracer(clock=VirtualClock())
        a = trc.begin("shared")              # untagged: drops out of trees
        b = trc.begin("request.validate", ticket=1)
        trc.end(b)
        c = trc.begin("bucket", tickets=(1, 2))
        trc.instant("launch", tickets=(1, 2))
        trc.end(c)
        trc.end(a)
        roots = trc.span_tree(1)
        names = [n.name for n in roots]
        assert names == ["request.validate", "bucket"]
        # the launch instant re-nests under the bucket span, not the
        # uncollected "shared" ancestor
        assert [ch.name for ch in roots[1].children] == ["launch"]
        assert trc.span_tree(3) == []

    def test_install_and_restore(self):
        trc = obs.Tracer(clock=VirtualClock())
        assert not obs.active().enabled
        with obs.installed(trc):
            assert obs.active() is trc
            inner = obs.Tracer(clock=VirtualClock())
            with obs.installed(inner):
                assert obs.active() is inner
            assert obs.active() is trc
        assert not obs.active().enabled

    @staticmethod
    def _record_annotations(monkeypatch):
        """Stand in for ``jax.profiler.TraceAnnotation``: each instance
        logs its enter and exit."""
        import jax.profiler
        log = []

        class Mark:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                log.append(("exit", self.name))
                return False
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Mark)
        return log

    def test_annotate_mirrors_each_extent_span_in_lifo_order(
            self, monkeypatch):
        log = self._record_annotations(monkeypatch)
        trc = obs.Tracer(clock=VirtualClock(), annotate=True)
        a = trc.begin("outer")
        trc.instant("mark")                  # instants are not mirrored
        trc.complete("retro", 0.0, 1.0)      # nor retroactive spans
        with pytest.raises(RuntimeError):
            with trc.span("work"):
                trc.begin("inner")           # left open by the raise
                raise RuntimeError("boom")
        b = trc.begin("late")
        trc.begin("orphan")
        trc.end(b)                           # out of order: pops through
        trc.end(a)
        assert log == [("enter", "outer"), ("enter", "work"),
                       ("enter", "inner"), ("exit", "inner"),
                       ("exit", "work"), ("enter", "late"),
                       ("enter", "orphan"), ("exit", "orphan"),
                       ("exit", "late"), ("exit", "outer")]
        assert trc.n_spans == 6 and trc.n_events == 7

    def test_annotate_off_never_touches_jax(self, monkeypatch):
        log = self._record_annotations(monkeypatch)
        trc = obs.Tracer(clock=VirtualClock())
        with trc.span("work"):
            trc.end(trc.begin("inner"))
        assert log == [] and trc.n_spans == 2

    def test_null_tracer_is_inert(self):
        n = obs.NullTracer()
        assert not n.enabled and n.spans == ()
        sid = n.begin("x")
        n.end(sid)
        n.instant("y")
        with n.span("z"):
            pass
        assert n.spans == ()


# ---------------------------------------------------------------------------
# metrics registry + back-compat views
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs.MetricsRegistry("t")
        c = reg.counter("hits")
        c.inc()
        c.inc(4)
        g = reg.gauge("depth")
        g.track_max(3)
        g.track_max(1)
        h = reg.histogram("lat")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert reg.value("hits") == 5 and reg.value("depth") == 3
        assert h.count == 4 and h.sum == 10.0 and h.max == 4.0
        assert h.percentile(50) == 2.0

    def test_labels_fan_out(self):
        reg = obs.MetricsRegistry()
        fam = reg.counter("req", labels=("tenant",))
        fam.labels(tenant="a").inc(2)
        fam.labels(tenant="b").inc()
        assert reg.value("req", tenant="a") == 2
        assert reg.value("req", tenant="b") == 1
        with pytest.raises(ValueError):
            fam.labels(nope="x")

    def test_redeclare_must_be_consistent(self):
        reg = obs.MetricsRegistry()
        reg.counter("n")
        assert reg.counter("n") is not None    # same family: fine
        with pytest.raises(ValueError):
            reg.gauge("n")

    def test_reset_zeroes_in_place(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("n")
        c.inc(9)
        reg.reset()
        assert c.value == 0 and reg.counter("n") is c

    def test_stats_view_is_a_mutable_mapping(self):
        reg = obs.MetricsRegistry()
        view = obs.StatsView(reg, ("a", "b"))
        view["a"] += 2
        view["b"] = 5
        assert dict(view) == {"a": 2, "b": 5}
        assert view == {"a": 2, "b": 5} and len(view) == 2
        assert sorted(view) == ["a", "b"]
        with pytest.raises(KeyError):
            view["nope"] = 1

    def test_percentile_reexported_by_clock(self):
        from repro.serving.clock import percentile
        assert percentile is obs.percentile
        assert percentile([3.0, 1.0, 2.0], 50) == 2.0

    def test_percentile_empty_is_nan(self):
        assert math.isnan(obs.percentile([], 50))
        assert math.isnan(obs.percentile([], 0))
        assert math.isnan(obs.percentile([], 100))

    def test_percentile_single_sample_is_that_sample(self):
        for q in (0, 1, 50, 99, 100):
            assert obs.percentile([7.0], q) == 7.0

    def test_percentile_all_equal(self):
        for q in (0, 50, 99, 100):
            assert obs.percentile([3.0] * 5, q) == 3.0

    def test_percentile_nearest_rank_ties(self):
        # nearest rank is exact set membership: p50 of an even-length
        # sample is the LOWER middle element (rank ceil(0.5*4) = 2),
        # and p99 of any sample shorter than 100 is its maximum
        assert obs.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
        assert obs.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
        assert obs.percentile([1.0, 2.0, 3.0, 4.0], 99) == 4.0
        assert obs.percentile(range(1, 101), 99) == 99
        assert obs.percentile(range(1, 101), 50) == 50
        # duplicated median: ties collapse to the shared value
        assert obs.percentile([1.0, 2.0, 2.0, 9.0], 50) == 2.0
        with pytest.raises(ValueError):
            obs.percentile([1.0], 101)
        with pytest.raises(ValueError):
            obs.percentile([1.0], -1)

    def test_histogram_edge_cases(self):
        h = obs.Histogram()
        # empty: count/sum/max well-defined, quantile nan, buckets zero
        assert h.count == 0 and h.sum == 0.0 and h.max == 0.0
        assert math.isnan(h.percentile(99))
        assert h.bucket_counts() == [0] * len(obs.Histogram.BOUNDS)
        # single sample sits in every bucket at or above its bound
        h.observe(0.01)
        assert h.percentile(50) == 0.01 and h.percentile(99) == 0.01
        assert h.bucket_counts((0.005, 0.01, 0.05)) == [0, 1, 1]
        # all-equal: every quantile is the shared value
        h2 = obs.Histogram()
        for _ in range(8):
            h2.observe(2.0)
        assert h2.percentile(50) == 2.0 == h2.percentile(99)
        assert h2.count == 8 and h2.sum == 16.0 and h2.max == 2.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestExport:
    def _tracer(self):
        clk = VirtualClock()
        trc = obs.Tracer(clock=clk)
        sid = trc.begin("flush")
        b = trc.begin("bucket.assemble", track="2D:TS|ref|<f4|8",
                      tickets=(0, 1))
        clk.advance(0.001)
        trc.instant("launch", track="2D:TS|ref|<f4|8", rows=2)
        trc.end(b)
        trc.end(sid)
        return trc

    def test_chrome_events_shape(self):
        evs = obs.chrome_trace_events(self._tracer())
        meta = [e for e in evs if e["ph"] == "M"]
        assert [m["args"]["name"] for m in meta] == \
            ["serve", "2D:TS|ref|<f4|8"]     # first-seen track order
        x = [e for e in evs if e["ph"] == "X"]
        i = [e for e in evs if e["ph"] == "i"]
        assert len(x) == 2 and len(i) == 1 and i[0]["s"] == "t"
        assert x[0]["tid"] == 0 and x[1]["tid"] == 1

    def test_dump_is_byte_deterministic(self, tmp_path):
        p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
        obs.dump_chrome_trace(self._tracer(), str(p1))
        obs.dump_chrome_trace(self._tracer(), str(p2))
        assert p1.read_bytes() == p2.read_bytes()
        doc = json.loads(p1.read_text())
        assert doc["displayTimeUnit"] == "ms"

    def test_prometheus_text_sorted_and_typed(self):
        reg = obs.MetricsRegistry("srv")
        reg.counter("zeta").inc(2)
        reg.counter("alpha", help="first").inc()
        fam = reg.counter("by_tenant", labels=("tenant",))
        fam.labels(tenant="b").inc()
        fam.labels(tenant="a").inc(3)
        h = reg.histogram("lat")
        h.observe(0.5)
        text = obs.prometheus_text(reg)
        lines = text.splitlines()
        assert "# HELP srv_alpha first" in lines
        assert lines.index("# TYPE srv_alpha counter") < \
            lines.index("# TYPE srv_zeta counter")
        # label children sort by value; histograms render as cumulative
        # bucket series
        ia = lines.index('srv_by_tenant{tenant="a"} 3')
        ib = lines.index('srv_by_tenant{tenant="b"} 1')
        assert ia < ib
        assert "# TYPE srv_lat histogram" in lines
        assert 'srv_lat_bucket{le="0.25"} 0' in lines
        assert 'srv_lat_bucket{le="0.5"} 1' in lines      # 0.5 <= 0.5
        assert 'srv_lat_bucket{le="2.5"} 1' in lines
        assert 'srv_lat_bucket{le="+Inf"} 1' in lines
        assert "srv_lat_sum 0.5" in lines
        assert "srv_lat_count 1" in lines
        # bucket lines are cumulative and ordered bound-ascending
        bucket_vals = [int(ln.rsplit(" ", 1)[1]) for ln in lines
                       if ln.startswith("srv_lat_bucket")]
        assert bucket_vals == sorted(bucket_vals)
        assert obs.prometheus_text(reg) == text    # deterministic


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_bounded_window(self):
        rec = obs.FlightRecorder(capacity=4)
        trc = obs.Tracer(clock=VirtualClock(), recorder=rec)
        for k in range(10):
            trc.instant("e", k=k)
        assert len(rec) == 4 and rec.recorded == 10 and rec.dropped == 6
        snap = rec.snapshot()
        assert [e["attrs"]["k"] for e in snap] == [6, 7, 8, 9]
        rec.clear()
        assert len(rec) == 0 and rec.recorded == 0

    def test_capacity_validates(self):
        with pytest.raises(ValueError):
            obs.FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# engine tracing: span trees, exact launch accounting, zero steering
# ---------------------------------------------------------------------------

class TestEngineTracing:
    def test_launch_instants_equal_launch_counter(self):
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            for _ in range(6):
                srv.submit(_chain2(), _pts(int(RNG.integers(4, 24))))
            srv.submit(tc.TransformChain.identity(2), _pts(5))
            srv.flush()
        assert trc.count("launch") == serving.stats["launches"] > 0
        assert trc.count("request.resolve") == 7

    def test_every_ticket_tree_complete_on_success(self):
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            tickets = [srv.submit(_chain2(), _pts(8)) for _ in range(4)]
            tickets.append(srv.submit(tc.TransformChain.identity(2),
                                      _pts(3)))
            srv.flush()
        for t in tickets:
            names = [s.name for root in trc.span_tree(t)
                     for s in root.walk()]
            assert "request.validate" in names
            assert "request.resolve" in names

    def test_rejection_tree(self):
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            with pytest.raises(serving.RequestError):
                srv.submit(_chain2(), np.zeros((0, 2), np.float32))
        (s,) = trc.spans_for(trc.tickets_seen()[0])
        assert s.name == "request.validate"
        assert s.attrs["outcome"] == "rejected"
        assert s.attrs["code"] == "empty"

    def test_tracing_never_steers_the_counters(self):
        # identical seeded workload, untraced vs traced: every counter
        # bit-identical (instrumentation observes, never steers)
        def serve():
            srv = _fresh(backend="ref")
            rng = np.random.default_rng(7)
            for _ in range(12):
                n = int(rng.integers(2, 40))
                pts = rng.uniform(-1, 1, (n, 2)).astype(np.float32)
                srv.submit(_chain2(), pts)
            srv.flush()
            return dict(serving.stats)

        untraced = serve()
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            traced = serve()
        assert untraced == traced
        assert trc.n_events > 0

    def test_unpack_splits_into_wait_fetch_copy(self):
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            for chain, pts, qname in workload.mixed_lane_workload(
                    3, 24, max_points=40):
                srv.submit(chain, pts, qformat=qname)
            srv.flush()
        unpacks = [s for s in trc.spans if s.name == "unpack"]
        assert len(unpacks) == serving.stats["launches"] > 1
        assert {s.attrs["kind"] for s in trc.spans
                if s.name == "launch"} == {"diag", "matrix", "projective"}
        for u in unpacks:
            kids = [s for s in trc.spans if s.parent == u.sid]
            assert [k.name for k in kids] == ["unpack.wait", "unpack.fetch",
                                              "unpack.copy"]
            # bucket-track spans that no request's tree collects
            assert all(k.track == u.track and k.ticket is None
                       and not k.tickets for k in kids)
        # the per-request resolutions happen in the copy
        copies = {s.sid for s in trc.spans if s.name == "unpack.copy"}
        assert all(trc.spans[s.parent].sid in copies for s in trc.spans
                   if s.name == "request.resolve")
        for t in trc.tickets_seen():
            names = {s.name for root in trc.span_tree(t)
                     for s in root.walk()}
            assert not names & {"unpack.wait", "unpack.fetch",
                                "unpack.copy", "launch.call"}

    def test_launch_call_marks_the_calls_that_traced(self):
        srv = _fresh(backend="ref")
        engine.clear_plan_cache()
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            for sizes in ((4, 9, 30), (4, 9, 30), (4, 70)):
                for n in sizes:
                    srv.submit(_chain2(), _pts(n))
                srv.flush()
        calls = [s for s in trc.spans if s.name == "launch.call"]
        assert len(calls) == trc.count("launch") == \
            serving.stats["launches"] > 0
        # a call traced exactly when a plan.trace instant fell inside it
        for c in calls:
            traced = any(s.parent == c.sid for s in trc.spans
                         if s.name == "plan.trace")
            assert c.attrs["traced"] is traced
            assert c.track and c.ticket is None and not c.tickets
            assert trc.spans[c.parent].name == "flush.dispatch"
        assert sum(c.attrs["traced"] for c in calls) == \
            serving.stats["traces"] > 0
        assert not all(c.attrs["traced"] for c in calls)

    def test_dispatch_end_carries_prefetched(self):
        """``flush.dispatch`` ends with ``prefetched``: the launches of
        phase 1 whose copy back started, one per ``launch`` instant
        there; recovery's launches add instants and calls, not
        prefetches."""
        inj = faults.FaultInjector(flaky_tickets=frozenset({0}),
                                   flaky_attempts=2)
        srv = _fresh(backend="ref", fault_config=_cfg(), injector=inj)
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            for chain, pts, qname in workload.mixed_lane_workload(
                    5, 24, max_points=40):
                srv.submit(chain, pts, qformat=qname)
            srv.flush()
        (dispatch,) = [s for s in trc.spans if s.name == "flush.dispatch"]
        phase1 = [s for s in trc.spans
                  if s.name == "launch" and s.parent == dispatch.sid]
        assert dispatch.attrs["prefetched"] == len(phase1) == \
            serving.stats["prefetches"] > 1
        # ticket 0's bucket was blocked at dispatch and relaunched
        assert serving.stats["launches"] > serving.stats["prefetches"]
        calls = [s for s in trc.spans if s.name == "launch.call"]
        assert len(calls) == serving.stats["launches"]
        assert sum(c.parent == dispatch.sid for c in calls) == len(phase1)
        for u in [s for s in trc.spans if s.name == "unpack"]:
            kids = [s.name for s in trc.spans if s.parent == u.sid]
            assert kids == (["unpack.wait", "unpack.fetch", "unpack.copy"]
                            if u.attrs["outcome"] == "ok" else [])

    def test_launch_instant_carries_no_prediction(self, monkeypatch):
        from repro.autotune import costmodel

        def refuse(*a, **k):
            raise AssertionError("cost model called on the serving path")
        monkeypatch.setattr(costmodel, "predict_launch", refuse)
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            srv.submit(_chain2(), _pts(8))
            srv.flush()
        (launch,) = [s for s in trc.spans if s.name == "launch"]
        assert set(launch.attrs) == {"backend", "kind", "q", "rung",
                                     "attempt", "rows", "lpad", "dim",
                                     "itemsize", "hbm_bytes"}

    def test_resident_flush_binds_and_stages_only_folds(self):
        """A traced flush over a handle: one ``resident.upload`` span at
        upload, ``bucket.bind`` in place of ``bucket.pack`` for the
        resident bucket, and 88 staged bytes per 3-D projective request
        (the 22 float32 fold words) beside an array request's points."""
        from repro import graphics
        cam = graphics.Camera(eye=(0.0, 0.5, 4.0), target=(0.0, 0.0, 0.0),
                              up=(0.0, 1.0, 0.0), fov_y=0.8, aspect=1.5,
                              near=0.1, far=10.0)
        chains = [graphics.viewing_chain(
            3, model=tc.TransformChain.identity(3).rotate(0.2 * i, axis=1),
            camera=cam, viewport=graphics.Viewport(width=64.0, height=48.0))
            for i in range(5)]
        mesh = _pts(300, 3)
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            handle = srv.upload(mesh)
            for c in chains:
                srv.submit(c, handle)
            srv.flush()
        assert trc.count("resident.upload") == 1
        assert trc.count("bucket.bind") == 1 and trc.count("bucket.pack") == 0
        assert serving.stats["upload_bytes"] == 88 * 5
        assert serving.stats["resident_requests"] == 5
        assert serving.stats["uploads"] == srv.metrics.value("uploads") == 1
        serving.reset_stats()
        with obs.installed(trc):
            srv.submit(chains[0], mesh)
            srv.flush()
        assert trc.count("bucket.pack") == 1
        lpad = srv.last_report[0].lpad
        assert serving.stats["upload_bytes"] == 88 + 4 * 3 * lpad
        assert serving.stats["resident_requests"] == 0

    def test_bucket_tracks_and_labeled_dimensions(self):
        srv = _fresh(backend="ref")
        trc = obs.Tracer(clock=VirtualClock())
        with obs.installed(trc):
            srv.submit(_chain2(), _pts(8))
            srv.flush()
        tracks = {s.track for s in trc.spans if s.track}
        assert len(tracks) == 1
        track = tracks.pop()
        assert "ref" in track                 # plan|backend|dtype|lpad
        # the per-server labeled counter saw the bucket's rows
        kind, backend, dt, lpad = None, "ref", None, None
        for s in trc.spans:
            if s.name == "bucket.assemble":
                kind = s.attrs["kind"]
                lpad = str(s.attrs["lpad"])
        dt = track.split("|")[2]
        assert srv.metrics.value("bucket_requests", kind=kind,
                                 backend=backend, dtype=dt,
                                 size_class=lpad) == 1


#: (dim, template, lane) of an array bucket of each plan kind and lane
_ARRAY_BUCKETS = [(2, "TST", None), (3, "SAT", "q8.7"), (3, "TRS", None),
                  (2, "TSRT", "q8.7"), (3, "TSRP", None)]


def _bucket_id(case):
    dim, letters, lane = case
    return f"{dim}D-{letters}-{lane or 'f32'}"


class TestHostOperands:
    """``host_operands``: the host arrays each dispatched launch stages,
    one fold row and, off a handle, the packed points."""

    @pytest.mark.parametrize("case", _ARRAY_BUCKETS, ids=_bucket_id)
    def test_an_array_launch_stages_two_host_arrays(self, case):
        dim, letters, lane = case
        rng = np.random.default_rng(81)
        srv = _fresh(backend="ref")
        for n in (5, 40, 40, 200):
            srv.submit(workload.chain_for(rng, dim, letters),
                       rng.uniform(-1, 1, (n, dim)).astype(np.float32),
                       qformat=lane)
        srv.flush()
        assert serving.stats["launches"] == 3
        assert serving.stats["host_operands"] == \
            srv.metrics.value("host_operands") == 2 * 3

    @pytest.mark.parametrize("case", _ARRAY_BUCKETS, ids=_bucket_id)
    def test_upload_bytes_are_each_launchs_fold_rows_and_points(self, case):
        """The bytes staged are what they were with the folds stacked
        part by part: each request's fold words and its padded
        points."""
        from repro import quantize
        dim, letters, lane = case
        rng = np.random.default_rng(82)
        chains = [workload.chain_for(rng, dim, letters) for _ in range(3)]
        srv = _fresh(backend="ref")
        for c in chains:
            srv.submit(c, rng.uniform(-1, 1, (20, dim)).astype(np.float32),
                       qformat=lane)
        srv.flush()
        (rep,) = srv.last_report
        folds = [c.fold() for c in chains]
        if lane is not None:
            folds = [quantize.quantize_fold(f, rep.kind, lane)
                     for f in folds]
        itemsize = 2 if lane else 4
        assert serving.stats["upload_bytes"] == sum(
            part.nbytes for f in folds for part in f) + \
            3 * rep.lpad * dim * itemsize

    def test_a_resident_launch_stages_one_host_array(self):
        from repro import graphics
        cam = graphics.Camera(eye=(0.0, 0.5, 4.0), target=(0.0, 0.0, 0.0),
                              up=(0.0, 1.0, 0.0), fov_y=0.8, aspect=1.5,
                              near=0.1, far=10.0)
        chains = [graphics.viewing_chain(
            3, model=tc.TransformChain.identity(3).rotate(0.2 * i, axis=1),
            camera=cam, viewport=graphics.Viewport(width=64.0, height=48.0))
            for i in range(6)]
        srv = _fresh(backend="ref")
        handle = srv.upload(_pts(300, 3))
        # three instances a launch: two launches on one handle
        srv.max_points_per_launch = 3 * handle.lpad
        srv.serve([(c, handle) for c in chains])
        assert serving.stats["launches"] == 2
        assert serving.stats["host_operands"] == serving.stats["launches"]
        assert serving.stats["upload_bytes"] == 88 * 6

    def test_recovery_launches_are_counted(self):
        """A launch that recovery re-stages counts its host arrays as
        any other: blocked attempts stage nothing, retried, bisected and
        fallen-back launches two each."""
        inj = faults.FaultInjector(flaky_tickets=frozenset({0}),
                                   flaky_attempts=2,
                                   poison_tickets=frozenset({3}))
        srv = _fresh(backend="ref", fault_config=_cfg(max_launch_attempts=2),
                     injector=inj)
        results = srv.serve([(_chain2(), _pts(8)) for _ in range(6)])
        assert isinstance(results[3], serving.LaunchError)
        assert serving.stats["retries"] > 0 and \
            serving.stats["bisections"] > 0
        assert serving.stats["launches"] > serving.stats["prefetches"] == 0
        assert serving.stats["host_operands"] == \
            2 * serving.stats["launches"]


class TestSpanTreesUnderFaults:
    def _traced_faulty(self, inj, n=6, **srv_kw):
        srv = _fresh(backend="ref", fault_config=_cfg(max_launch_attempts=2),
                     injector=inj, **srv_kw)
        rec = obs.FlightRecorder(capacity=128)
        trc = obs.Tracer(clock=VirtualClock(), recorder=rec)
        with obs.installed(trc):
            tickets = [srv.submit(_chain2(), _pts(8)) for _ in range(n)]
            results = srv.flush()
        return srv, trc, tickets, results

    def test_recovery_tree_for_flaky_ticket(self):
        inj = faults.FaultInjector(flaky_tickets=frozenset({0}),
                                   flaky_attempts=1)
        srv, trc, tickets, results = self._traced_faulty(inj)
        names = [s.name for root in trc.span_tree(0)
                 for s in root.walk()]
        assert "recover" in names and "request.resolve" in names
        rec_spans = [s for s in trc.spans_for(0) if s.name == "recover"]
        assert rec_spans[0].attrs["outcome"] == "recovered"
        assert str(rec_spans[0].track).startswith("recovery:")
        assert trc.count("launch") == serving.stats["launches"]

    def test_bisection_and_terminal_failure_trees(self):
        inj = faults.FaultInjector(poison_tickets=frozenset({2}))
        srv, trc, tickets, results = self._traced_faulty(inj)
        assert trc.count("recover.bisect") == serving.stats["bisections"] > 0
        # the poisoned ticket: recover spans + a launch-error resolve
        res = [s for s in trc.spans_for(2) if s.name == "request.resolve"]
        assert len(res) == 1 and res[0].attrs["outcome"] == "launch-error"
        assert isinstance(results[2], serving.LaunchError)
        # its terminal error carries the flight-recorder window
        assert isinstance(results[2].flight, list) and results[2].flight
        assert all("name" in e for e in results[2].flight)
        # the bucket neighbours all recovered, each with a complete tree
        for t in [t for t in tickets if t != 2]:
            outs = [s.attrs["outcome"] for s in trc.spans_for(t)
                    if s.name == "request.resolve"]
            assert outs == ["ok"]
        assert trc.count("launch") == serving.stats["launches"]

    def test_launch_call_per_launch_through_recovery(self):
        inj = faults.FaultInjector(flaky_tickets=frozenset({1}),
                                   flaky_attempts=1,
                                   poison_tickets=frozenset({4}))
        srv, trc, tickets, results = self._traced_faulty(inj)
        assert serving.stats["retries"] > 0 and \
            serving.stats["bisections"] > 0
        assert trc.count("launch.call") == trc.count("launch") == \
            serving.stats["launches"]
        recovered = [s for s in trc.spans if s.name == "launch.call"
                     and trc.spans[s.parent].name == "recover.attempt"]
        assert recovered and all(str(s.track).startswith("recovery:")
                                 for s in recovered)

    def test_every_ticket_accounted_under_mixed_faults(self):
        inj = faults.FaultInjector(flaky_tickets=frozenset({0}),
                                   backend_tickets=frozenset({1}),
                                   corrupt_tickets=frozenset({3}),
                                   poison_tickets=frozenset({4}),
                                   flaky_attempts=1)
        srv, trc, tickets, results = self._traced_faulty(inj, n=8)
        for t in tickets:
            spans = trc.spans_for(t)
            assert any(s.name == "request.validate"
                       and s.attrs["outcome"] == "admitted" for s in spans)
            resolves = [s for s in spans if s.name == "request.resolve"]
            assert len(resolves) == 1, f"ticket {t} must resolve exactly once"
            assert resolves[0].attrs["outcome"] in ("ok", "launch-error")
        assert trc.count("launch") == serving.stats["launches"]
        # the poisoned ticket is terminally failed; the corrupted one may
        # also fail after bisection isolates it -- both resolve exactly
        # once (asserted above), which is the invariant under test
        assert serving.stats["failed_requests"] >= 1
        assert isinstance(results[4], serving.LaunchError)


# ---------------------------------------------------------------------------
# per-server counters vs the module aggregate (the multi-server drift fix)
# ---------------------------------------------------------------------------

class TestPerServerCounters:
    def test_two_servers_do_not_blur(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        a = serving.GeometryServer(backend="ref")
        b = serving.GeometryServer(backend="ref")
        for _ in range(3):
            a.submit(_chain2(), _pts(8))
        for _ in range(5):
            b.submit(_chain2(), _pts(8))
        a.flush()
        b.flush()
        assert a.metrics.value("requests") == 3
        assert b.metrics.value("requests") == 5
        assert a.metrics.value("launches") == 1
        assert b.metrics.value("launches") == 1
        # the module view is the explicit aggregate across servers
        assert serving.stats["requests"] == 8
        assert serving.stats["launches"] == \
            a.metrics.value("launches") + b.metrics.value("launches")

    def test_reset_stats_clears_server_registry(self):
        srv = _fresh(backend="ref")
        srv.submit(_chain2(), _pts(4))
        srv.flush()
        assert srv.metrics.value("requests") == 1
        srv.reset_stats()
        assert srv.metrics.value("requests") == 0

    def test_two_async_engines_mirror_rejections_by_delta(self):
        # the old absolute mirror clobbered the module counters when two
        # engines served side by side; deltas must sum
        serving.reset_stats()
        serving.clear_plan_cache()
        clock = VirtualClock()
        cfg = serving.AdmissionConfig(max_queue_depth=1, tenant_share=1.0)
        e1 = AsyncGeometryServer(backend="ref", clock=clock, admission=cfg)
        e2 = AsyncGeometryServer(backend="ref", clock=clock, admission=cfg)
        for eng_ in (e1, e2):
            eng_.submit_async(_chain2(), _pts(4))
            for _ in range(2):
                with pytest.raises(serving.QueueFullError):
                    eng_.submit_async(_chain2(), _pts(4))
        assert e1.stats["queue_full_rejections"] == 2
        assert e2.stats["queue_full_rejections"] == 2
        assert serving.stats["queue_full_rejections"] == 4
        e1.drain()
        e2.drain()


# ---------------------------------------------------------------------------
# async front-end tracing
# ---------------------------------------------------------------------------

class TestAsyncTracing:
    def test_queue_wait_and_policy_spans(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        clock = VirtualClock()
        eng_ = AsyncGeometryServer(
            backend="ref", clock=clock,
            slo=SLOConfig(max_wait_s=0.01, target_rows=4))
        trc = obs.Tracer(clock=clock)
        with obs.installed(trc):
            t = eng_.submit_async(_chain2(), _pts(6), tenant="a")
            due = eng_.next_due_in()
            clock.advance(due)
            eng_.poll()
        assert t.done()
        waits = [s for s in trc.spans if s.name == "queue.wait"]
        assert len(waits) == 1 and waits[0].ticket == t.id
        assert waits[0].duration == pytest.approx(due)
        assert 0.0 < waits[0].duration <= 0.01
        pol = [s for s in trc.spans if s.name == "policy.launch"]
        assert len(pol) == 1 and pol[0].attrs["reason"] == "deadline"
        subs = [s for s in trc.spans if s.name == "request.submit"]
        assert subs[0].attrs["outcome"] == "admitted"
        assert subs[0].ticket == t.id

    def test_fill_reason_and_admission_reject_instant(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        clock = VirtualClock()
        eng_ = AsyncGeometryServer(
            backend="ref", clock=clock,
            slo=SLOConfig(max_wait_s=1.0, target_rows=2),
            admission=serving.AdmissionConfig(max_queue_depth=2,
                                              tenant_share=1.0))
        trc = obs.Tracer(clock=clock)
        with obs.installed(trc):
            eng_.submit_async(_chain2(), _pts(4))
            eng_.submit_async(_chain2(), _pts(4))
            with pytest.raises(serving.QueueFullError):
                eng_.submit_async(_chain2(), _pts(4))
            eng_.poll()                      # full bucket: due immediately
        pol = [s for s in trc.spans if s.name == "policy.launch"]
        assert [s.attrs["reason"] for s in pol] == ["fill"]
        rej = [s for s in trc.spans if s.name == "admission.reject"]
        assert len(rej) == 1 and rej[0].attrs["code"] == "queue-full"
        assert rej[0].attrs["gate"] == "depth"

    def test_registry_backed_stats_view_unchanged(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        clock = VirtualClock()
        eng_ = AsyncGeometryServer(backend="ref", clock=clock)
        t = eng_.submit_async(_chain2(), _pts(4), tenant="r")
        eng_.drain()
        st = eng_.stats
        assert st["admitted"] == 1 and st["resolved"] == 1
        assert st["failed"] == 0 and st["queue_depth"] == 0
        assert st["p50_latency_s"] == st["p99_latency_s"] >= 0.0
        assert eng_.metrics.value("tenant_requests", tenant="r") == 1
        assert not serving.is_error(t.result())


# ---------------------------------------------------------------------------
# chaos soak post-mortems
# ---------------------------------------------------------------------------

class TestChaosPostmortems:
    def test_soak_attaches_postmortems(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        rep = faults.run_chaos_soak(seed=3, n_requests=48)
        assert rep.lost == 0
        assert rep.postmortems, "faults fired, so post-mortems must exist"
        for pm in rep.postmortems:
            assert str(pm["track"]).startswith("recovery")
            assert pm["events"] and all("name" in e for e in pm["events"])
        json.dumps(rep.postmortems)           # plain-JSON by construction
        assert "postmortems" not in rep.counters()

    def test_soak_is_deterministic_with_postmortems(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        r1 = faults.run_chaos_soak(seed=5, n_requests=32)
        serving.reset_stats()
        serving.clear_plan_cache()
        r2 = faults.run_chaos_soak(seed=5, n_requests=32)
        assert r1.counters() == r2.counters()
        assert [pm["track"] for pm in r1.postmortems] == \
            [pm["track"] for pm in r2.postmortems]
