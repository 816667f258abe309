"""The reduction from a profiler trace to device numbers, on the trace the
repo recorded on a v5e in round 5 (three 8,192-item fused verify passes)."""

import os

import pytest

import trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDED = os.path.join(
    REPO, "bench_results", "profile_r05", "plugins", "profile",
    "2026_07_31_19_05_06", "vm.xplane.pb")


def test_recorded_trace_reduces_to_three_passes():
    got = trace_reduce.reduce_file(RECORDED)
    assert got["planes"] == 1  # /device:TPU:0
    assert got["module_events"] == 3
    assert got["busy_s"] * 1e3 == pytest.approx(27.09, abs=0.005)
    assert got["kernel_ms_per_pass"] == pytest.approx(9.03, abs=0.005)
    assert got["module_time_s"] == pytest.approx(got["busy_s"])
    # no window given: first module start to last module end
    assert got["window_s"] * 1e3 == pytest.approx(27.101, abs=0.005)
    assert 0.0 <= got["idle_share"] < 0.1
    assert "kernel_items_per_s" not in got  # nothing to divide
    assert len(got["device_ops"]) == trace_reduce.TOP
    seconds = [s for _name, s in got["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) <= got["busy_s"]
    assert all(" " not in name for name, _s in got["device_ops"])
    assert [name for name, _s in got["idle_gaps"]] == ["between_modules"] * 2


def test_window_and_items_give_idle_share_and_rate():
    got = trace_reduce.reduce_file(RECORDED, window_s=0.1, device_items=3 * 8192)
    assert got["window_s"] == 0.1
    assert got["idle_share"] == pytest.approx(100 * (1 - got["busy_s"] / 0.1))
    assert got["kernel_items_per_s"] == pytest.approx(
        3 * 8192 / got["module_time_s"])
    # a host window shorter than the device's own span is held to the span
    short = trace_reduce.reduce_file(RECORDED, window_s=0.001)
    assert short["window_s"] == pytest.approx(0.027101, abs=5e-6)
    assert short["idle_share"] >= 0.0


def test_a_trace_without_a_device_plane_is_an_error(tmp_path, monkeypatch):
    """Never an idle share of 100%."""
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", "/device:NOSUCH:")
    with pytest.raises(trace_reduce.NoDevicePlane, match="no /device:NOSUCH"):
        trace_reduce.reduce_file(RECORDED)
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))


def test_union_counts_overlap_once():
    assert trace_reduce._union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace_reduce.op_label(
        "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p)") == "fusion.3"
