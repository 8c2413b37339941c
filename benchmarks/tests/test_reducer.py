"""The trace -> device numbers reduction, on a small trace recorded on a
TPU v5e: three matmul and three add executables inside one
`bench/trace_window`, three `bench/op` spans each with a `bench/inner`."""

import os

import pytest

from benchmarks import trace_reduce

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_reduction_of_the_recorded_trace():
    out = trace_reduce.reduce_trace(SMALL, "tpu")
    assert out["ops"] == 3
    # six executables of ~2.4-3.6 us each on the device
    assert out["busy_s"] == pytest.approx(18.066e-6, rel=1e-3)
    assert out["window_s"] == pytest.approx(0.158423706, rel=1e-6)
    idle = 1.0 - out["busy_s"] / out["window_s"]
    assert 0.999 < idle < 1.0
    names = [n for n, _ in out["device_ops"]]
    assert "module:jit_small_matmul" in names and "module:jit_small_add" in names
    assert all(len(n) <= 70 and "=" not in n for n in names)   # short names
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    gaps = dict(out["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=0.02)
    assert gaps["bench/inner"] == pytest.approx(0.0637, rel=0.01)
    assert all(n.startswith("bench/") or n in (
        "outside_any_benchmark_span", "between_device_ops_under_10us")
        for n in gaps)


def test_no_device_plane_is_an_error_not_an_idle_device():
    with pytest.raises(trace_reduce.TraceError, match="device plane"):
        trace_reduce.reduce_trace(SMALL, "gpu")


@pytest.mark.parametrize("raw, short", [
    ("%fusion.65 = s32[512000]{0:T(1024)S(1)} fusion(s32[43392]{0} %r)",
     "fusion.65"),
    ("jit_subsolve(12284469197797409617)", "jit_subsolve"),
    ("jit_merge_4772982463202182388_", "jit_merge"),
    ("%copy-start = (f32[512,512]{1,0}, u32[]) copy-start(%x)", "copy-start"),
])
def test_short_names(raw, short):
    assert trace_reduce.short_name(raw) == short
