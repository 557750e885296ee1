"""The trace reduction: busy union, idle share, per-operation time and the
attribution of idle gaps to the harness's spans."""
import os

import pytest

from chipbench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data", "v5e_gemm")


def _events():
    ms = 1_000_000
    return {
        "spans": [(0, 100 * ms, "chipbench.window"),
                  (0, 60 * ms, "chipbench.gemm.call"),
                  (60 * ms, 100 * ms, "chipbench.host")],
        "devices": {"/device:TPU:0": [
            (5 * ms, 25 * ms, "jit_dot/dot.1", True),
            (20 * ms, 40 * ms, "jit_add/add.2", True),   # overlaps the dot
            (70 * ms, 80 * ms, "jit_dot/dot.1", True),
            (95 * ms, 130 * ms, "jit_dot/dot.1", True),  # runs past the end
            (94 * ms, 131 * ms, "jit_dot/while.3", False),   # holds the dot
        ]},
    }


def test_busy_is_the_union_clipped_to_the_window():
    r = trace.reduce_events(_events())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((35 + 10 + 6) / 1000)


def test_time_per_operation_is_summed_and_ranked():
    """Innermost operations only: the loop holding the dot is counted
    through it."""
    r = trace.reduce_events(_events())
    assert "jit_dot/while.3" not in dict(r["device_ops"])
    assert r["device_ops"][0] == ["jit_dot/dot.1", pytest.approx(0.035)]
    assert r["device_ops"][1] == ["jit_add/add.2", pytest.approx(0.020)]


def test_idle_gaps_go_to_the_innermost_span():
    r = trace.reduce_events(_events())
    gaps = dict(r["idle_gaps"])
    # [0, 5) and [40, 60) in the call, [60, 70) and [80, 94) on the host
    assert gaps["gemm.call"] == pytest.approx(0.025)
    assert gaps["host"] == pytest.approx(0.024)
    idle = sum(gaps.values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])


def test_one_window_span_is_required():
    ev = _events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError):
        trace.reduce_events(ev)


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e: three 1024^3 ``mp_matmul`` calls
    (``chipbench.gemm.call``) separated by 5 ms host sleeps
    (``chipbench.host``) inside one ``chipbench.window``."""
    r = trace.reduce(RECORDED, [0])
    assert r["window_s"] == pytest.approx(0.061871241)
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    # the device idles through each sleep, and between the eager ops of
    # each call while the host prepares the next one
    assert gaps["host"] >= 3 * 0.005
    assert set(gaps) <= {"host", "gemm.call", "outside harness spans"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    names = [n for n, _ in r["device_ops"]]
    assert any(n.startswith("jit_dot_general/") for n in names)
    assert all("/" in n and " " not in n for n in names)
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * 1.001
