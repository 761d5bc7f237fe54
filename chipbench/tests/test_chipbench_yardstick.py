"""The benchmark's arithmetic: percentiles and rates over the whole
window, and the bytes a request's results require."""
import math

import pytest

from chipbench import yardstick


def test_percentile_is_nearest_rank_over_all_values():
    values = list(range(1, 101))          # 1..100
    assert yardstick.percentile(values, 50) == 50
    assert yardstick.percentile(values, 95) == 95
    assert yardstick.percentile(values, 100) == 100
    assert yardstick.percentile([7.0], 95) == 7.0
    assert yardstick.percentile([3, 1, 2], 50) == 2


def test_percentile_is_not_a_median_of_chunks():
    # four flushes of 10 latencies; one slow flush holds the tail
    chunks = [[1.0] * 10, [1.0] * 10, [1.0] * 10, [9.0] * 10]
    everything = [v for c in chunks for v in c]
    chunk_p95 = sorted(yardstick.percentile(c, 95) for c in chunks)
    assert yardstick.percentile(everything, 95) == 9.0
    assert chunk_p95[len(chunk_p95) // 2] == 1.0


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_rejects_out_of_range(bad):
    with pytest.raises(ValueError):
        yardstick.percentile([1.0], bad)
    with pytest.raises(ValueError):
        yardstick.percentile([], 50)


def test_rate_is_all_work_over_all_time():
    assert yardstick.rate(300, 1.5) == 200.0
    with pytest.raises(ValueError):
        yardstick.rate(1, 0.0)


@pytest.mark.parametrize("spec,kind", [
    ((("T", (1.0, 2.0)), ("S", (2.0, 2.0))), "diag"),
    ((("A", (1.0,), (0.0,)),), "diag"),
    ((("T", (1.0, 2.0)), ("R", None, 0.3)), "matrix"),
    ((("M", None),), "matrix"),
    ((("P", None),), "projective"),
    ((("M", None), ("C", -1.0, 1.0)), "projective"),
    ((("LOOKAT", 0, 0, 0), ("PERSP", 1, 1, 0.1, 10)), "projective"),
])
def test_plan_kind_from_primitives(spec, kind):
    assert yardstick.plan_kind(spec) == kind


@pytest.mark.parametrize("kind,dim,n,expected", [
    ("diag", 2, 100, 2 * 100 * 2 * 4 + 4 * 4),
    ("matrix", 3, 10, 2 * 10 * 3 * 4 + 12 * 4),
    ("projective", 3, 35947, 2 * 35947 * 3 * 4 + 35947 + 22 * 4),
    ("projective", 2, 1, 2 * 1 * 2 * 4 + 1 + 13 * 4),
])
def test_payload_bytes_count_what_results_require(kind, dim, n, expected):
    assert yardstick.payload_bytes(kind, dim, n) == expected


def test_peaks_are_keyed_by_device_kind():
    chip = yardstick.peaks("TPU v5 lite")
    assert chip["hbm_bw"] == 819e9 and "TPU v5e" in chip["source"]
    with pytest.raises(ValueError):
        yardstick.peaks("cpu")
    assert math.isclose(chip["bf16_flops"], 197e12)
