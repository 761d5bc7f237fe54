"""Profiler + bench-trajectory tests: attribution exactness against the
engine's own counters, cost-model prediction parity, span-stream
round-trips, and the directional trend gate.

The load-bearing invariants:

  * the attribution tree's launch count equals ``serving.stats
    ["launches"]`` exactly (the tracer emits the launch instant inside
    ``_count_launch``, the ONE place the counter moves);
  * every launch's observed/predicted HBM byte ratio is exactly 1.0 --
    ``kernels.opcount`` and ``autotune.costmodel`` share the byte
    formula, so drift is an accounting bug, not noise;
  * ``tools/bench_trend.py`` exits 0 on the real committed trajectory
    and 1 on a synthetic worsened-counter fixture.
"""
import dataclasses
import json
import os

import pytest

from repro import obs, serving
from repro.autotune import costmodel
from repro.kernels import opcount
from repro.obs import bench_history
from repro.obs.profile import Profile, profile_smoke_workload
from repro.serving import engine

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def smoke():
    """One traced smoke workload from a clean counter origin."""
    engine.reset_stats()
    tracer, server = profile_smoke_workload()
    return tracer, server, Profile.from_tracer(tracer)


# ---------------------------------------------------------------------------
# cost-model prediction API
# ---------------------------------------------------------------------------

class TestPredictLaunch:
    def test_bytes_match_opcount_exactly(self):
        # the profiler's ratio==1.0 guarantee, checked at the source:
        # the prediction IS the opcount byte formula
        for kind in ("diag", "matrix", "projective"):
            for bsz, lpad, d in ((1, 8, 2), (4, 16, 2), (3, 32, 3)):
                p = costmodel.predict_launch(kind, bsz, lpad, d)
                assert p.hbm_bytes == opcount.packed_chain_bytes(
                    bsz, lpad, d, itemsize=4, kind=kind)

    def test_q_lane_bytes_and_kernel(self):
        p = costmodel.predict_launch("diag", 4, 16, 2, qformat="q8.7",
                                     itemsize=2)
        assert p.kernel == "chain_diag_batch_q"
        assert p.hbm_bytes == opcount.packed_chain_bytes(
            4, 16, 2, itemsize=2, kind="diag")
        assert p.hbm_bytes == 544    # pinned: int16 halves the float lane

    def test_pinned_prediction(self):
        p = costmodel.predict_launch("matrix", 3, 32, 3)
        assert (p.kernel, p.hbm_bytes, p.flops, p.m1_cycles) == \
            ("chain_apply_batch", 2448, 2880, 506)

    def test_m1_cycles_monotone_in_shape(self):
        for kind in ("diag", "matrix", "projective"):
            c8 = costmodel.m1_chain_cycles(kind, 8, 2)
            c64 = costmodel.m1_chain_cycles(kind, 64, 2)
            assert 0 < c8 < c64
        # pinned representative values for the three plan kinds
        assert costmodel.m1_chain_cycles("diag", 64, 2) == 166
        assert costmodel.m1_chain_cycles("matrix", 64, 2) == 198
        assert costmodel.m1_chain_cycles("projective", 64, 2) == 342
        with pytest.raises(ValueError):
            costmodel.m1_chain_cycles("nope", 8, 2)


# ---------------------------------------------------------------------------
# attribution exactness
# ---------------------------------------------------------------------------

class TestProfileAttribution:
    def test_launch_counts_match_engine_counters(self, smoke):
        tracer, _server, prof = smoke
        assert prof.launches == serving.stats["launches"] > 0
        assert prof.launches == tracer.count("launch")
        # every aggregation axis accounts for every launch
        assert sum(g.launches for g in prof.buckets.values()) == \
            prof.launches
        assert sum(g.launches for g in prof.kinds.values()) == \
            prof.launches

    def test_per_bucket_attribution_is_exact(self, smoke):
        tracer, _server, prof = smoke
        # the bucket table reproduces the per-track launch-instant
        # distribution of the raw stream, bucket by bucket
        by_track = {}
        for s in tracer.spans:
            if s.instant and s.name == "launch":
                by_track[s.track] = by_track.get(s.track, 0) + 1
        assert {k: g.launches for k, g in prof.buckets.items()} == by_track
        assert len(prof.buckets) > 1    # mixed lanes: several buckets

    def test_tree_self_time_sums_to_total(self, smoke):
        _tracer, _server, prof = smoke
        # self times partition each root span's extent: summing self_s
        # over the whole tree recovers the total root extents
        total_roots = sum(n.total_s for n in prof.root.children.values())
        total_self = sum(n.self_s for _d, n in prof.root.walk()
                         if n is not prof.root)
        assert total_self == pytest.approx(total_roots, rel=1e-9)

    def test_byte_ratio_exact(self, smoke):
        _tracer, _server, prof = smoke
        assert prof.byte_ratio_exact
        assert len(prof.byte_ratios) == prof.launches
        c = prof.counters()
        assert c["byte_ratio_exact"] == 1
        assert c["hbm_bytes"] == c["pred_hbm_bytes"] > 0
        assert c["pred_flops"] > 0 and c["pred_m1_cycles"] > 0

    def test_counters_pinned(self, smoke):
        # buckets key on the plan identity (dim, kind): 26 launches where
        # a bucket per chain structure made 40, moving the same padded
        # bytes and flops; each launch has its launch.call and the three
        # unpack.* spans
        _tracer, _server, prof = smoke
        assert prof.counters() == {
            "events": 391, "spans": 249, "launches": 26, "kernels": 5,
            "launch_buckets": 26, "hbm_bytes": 21872,
            "pred_hbm_bytes": 21872, "pred_flops": 22224,
            "pred_m1_cycles": 4561, "byte_ratio_exact": 1}

    def test_fold_predicts_from_the_launch_shape(self, smoke):
        tracer, _server, prof = smoke
        launches = [s for s in tracer.spans if s.name == "launch"]
        preds = [costmodel.predict_launch(
            s.attrs["kind"], s.attrs["rows"], s.attrs["lpad"],
            s.attrs["dim"], qformat=s.attrs["q"],
            itemsize=s.attrs["itemsize"]) for s in launches]
        assert set(prof.kernels) == {p.kernel for p in preds}
        assert sum(g.pred_flops for g in prof.kernels.values()) == \
            sum(p.flops for p in preds)
        # a stream whose launches lack their shape folds no prediction
        bare = [dataclasses.replace(s, attrs={
            k: v for k, v in s.attrs.items() if k not in ("dim",
                                                          "itemsize")})
            for s in tracer.spans]
        old = Profile.from_spans(bare)
        assert old.launches == prof.launches and old.byte_ratios == []
        assert old.counters()["pred_hbm_bytes"] == 0
        assert set(old.kernels) == set(old.kinds)

    def test_deterministic_across_runs(self, smoke):
        _tracer, _server, prof = smoke
        engine.reset_stats()
        tracer2, _ = profile_smoke_workload()
        assert Profile.from_tracer(tracer2).counters() == prof.counters()

    def test_markdown_report_shape(self, smoke):
        _tracer, _server, prof = smoke
        md = prof.render_markdown()
        assert "## Attribution tree" in md
        assert "## Launches by kernel" in md
        assert "## Model error" in md
        assert "exact (every ratio == 1.0): True" in md


# ---------------------------------------------------------------------------
# span-stream persistence
# ---------------------------------------------------------------------------

class TestSpanStreamRoundTrip:
    def test_dump_load_preserves_counters(self, smoke, tmp_path):
        tracer, _server, prof = smoke
        path = str(tmp_path / "spans.jsonl")
        n = obs.dump_span_stream(tracer, path)
        spans = obs.load_span_stream(path)
        assert len(spans) == n == len(tracer.spans)
        assert Profile.from_spans(spans).counters() == prof.counters()

    def test_dump_is_byte_deterministic(self, smoke, tmp_path):
        tracer, _server, _prof = smoke
        p1, p2 = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        obs.dump_span_stream(tracer, str(p1))
        obs.dump_span_stream(tracer, str(p2))
        assert p1.read_bytes() == p2.read_bytes()


# ---------------------------------------------------------------------------
# bench trajectory analytics
# ---------------------------------------------------------------------------

def _record(tmp_path, stamp, rows):
    doc = {"timestamp": stamp, "smoke": True,
           "rows": [dict(r, name=name) for name, r in rows.items()]}
    path = tmp_path / f"BENCH_{stamp}.json"
    path.write_text(json.dumps(doc))
    return str(path)


class TestBenchHistory:
    def test_real_committed_trajectory_is_clean(self):
        history = bench_history.load_history(
            os.path.join(REPO_ROOT, "benchmarks"))
        assert len(history) >= 2
        assert bench_history.find_regressions(history) == []

    def test_synthetic_regression_detected(self, tmp_path):
        _record(tmp_path, "20260101_000000",
                {"chain_smoke": {"launches": 10, "lost": 0,
                                 "us_per_call": 5.0}})
        _record(tmp_path, "20260102_000000",
                {"chain_smoke": {"launches": 12, "lost": 0,
                                 "us_per_call": 4.0}})
        history = bench_history.load_history(str(tmp_path))
        regs = bench_history.find_regressions(history)
        assert len(regs) == 1
        r = regs[0]
        assert (r.row, r.field, r.prev, r.value) == \
            ("chain_smoke", "launches", 10, 12)
        assert "worsened" in str(r)

    def test_improvement_and_new_rows_are_not_regressions(self, tmp_path):
        _record(tmp_path, "20260101_000000",
                {"a": {"launches": 10}})
        _record(tmp_path, "20260102_000000",
                {"a": {"launches": 8}, "b": {"launches": 99}})
        history = bench_history.load_history(str(tmp_path))
        assert bench_history.find_regressions(history) == []

    def test_wallclock_fields_never_gate(self, tmp_path):
        _record(tmp_path, "20260101_000000",
                {"a": {"us_per_call": 1.0, "wall_s": 1.0}})
        _record(tmp_path, "20260102_000000",
                {"a": {"us_per_call": 9.0, "wall_s": 9.0}})
        history = bench_history.load_history(str(tmp_path))
        assert bench_history.find_regressions(history) == []

    def test_series_and_drift_report(self, tmp_path):
        _record(tmp_path, "20260101_000000", {"a": {"launches": 10}})
        _record(tmp_path, "20260102_000000", {"a": {"launches": 8}})
        history = bench_history.load_history(str(tmp_path))
        assert bench_history.series(history, "a", "launches") == [
            ("BENCH_20260101_000000.json", 10),
            ("BENCH_20260102_000000.json", 8)]
        report = bench_history.drift_report(history)
        assert "| a | launches | 10 | 8 | IMPROVED |" in report


class TestBenchTrendCLI:
    def _main(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_trend", os.path.join(REPO_ROOT, "tools",
                                        "bench_trend.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main

    def test_exit_codes(self, tmp_path, capsys):
        main = self._main()
        # fewer than two records: nothing to compare
        assert main(["--bench-dir", str(tmp_path)]) == 2
        _record(tmp_path, "20260101_000000", {"a": {"launches": 10}})
        _record(tmp_path, "20260102_000000", {"a": {"launches": 12}})
        assert main(["--bench-dir", str(tmp_path)]) == 1
        assert "REGRESSION" in capsys.readouterr().err
        # real committed trajectory stays clean
        assert main(["--bench-dir",
                     os.path.join(REPO_ROOT, "benchmarks")]) == 0

    def test_report_written(self, tmp_path):
        main = self._main()
        _record(tmp_path, "20260101_000000", {"a": {"launches": 10}})
        _record(tmp_path, "20260102_000000", {"a": {"launches": 10}})
        out = tmp_path / "drift.md"
        assert main(["--bench-dir", str(tmp_path),
                     "--report", str(out)]) == 0
        assert "# Bench trajectory" in out.read_text()
