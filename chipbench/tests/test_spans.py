"""Idle time split by the host's spans of both kinds (``chipbench/spans.py``)
and the two metrics that read it."""
import os
import random

import pytest

from chipbench import spans, trace
from chipbench.run import load_module
from chipbench.tests.test_trace import RECORDED, _events

MS = 1_000_000


def _nested_events():
    """A harness call holding the program's dispatch, which holds the map
    expansion and its upload; one device op in the middle of the call."""
    return {
        "spans": [(0, 100 * MS, "chipbench.window"),
                  (10 * MS, 90 * MS, "chipbench.gemm.call"),
                  (20 * MS, 80 * MS, "repro.gemm.dispatch"),
                  (30 * MS, 50 * MS, "repro.gemm.expand_map"),
                  (50 * MS, 55 * MS, "repro.gemm.upload_map")],
        "devices": {"/device:TPU:0": [
            (40 * MS, 60 * MS, "jit_dot/dot.1", True)]},
    }


def test_idle_goes_to_the_innermost_span_of_either_kind():
    r = spans.reduce_events(_nested_events())
    gaps = dict(r["idle_gaps"])
    # [0, 10) and [90, 100) outside; [10, 20) and [80, 90) in the call;
    # [20, 30) and [60, 80) in dispatch; [30, 40) in the expansion; the
    # upload is covered by the op
    assert gaps == pytest.approx({
        spans.OUTSIDE: 0.020, "gemm.call": 0.020,
        "repro.gemm.dispatch": 0.030, "repro.gemm.expand_map": 0.010})
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.020)


def test_inclusive_idle_counts_a_span_and_all_inside_it():
    r = spans.reduce_events(_nested_events())
    inc = r["idle_by_span"]
    assert inc["gemm.call"] == pytest.approx(0.060)
    assert inc["repro.gemm.dispatch"] == pytest.approx(0.040)
    assert inc["repro.gemm.expand_map"] == pytest.approx(0.010)
    assert "repro.gemm.upload_map" not in inc
    assert r["host_spans"]["repro.gemm.upload_map"] == [1, pytest.approx(
        0.005)]


def test_sweep_matches_the_scan_on_harness_spans():
    assert spans.reduce_events(_events())["idle_gaps"] == \
        trace.reduce_events(_events())["idle_gaps"]


@pytest.mark.parametrize("seed", range(5))
def test_sweep_matches_the_scan_on_random_spans(seed):
    """Overlapping, nested, equal-length and zero-length spans, ops on
    two devices: the sweep gives what the scan over every span gives."""
    g = random.Random(seed)
    ev = {"spans": [(0, 1000, "chipbench.window")], "devices": {}}
    for i in range(40):
        s = g.randrange(-50, 1050)
        ev["spans"].append((s, s + g.choice([0, 5, 20, 20, 100, 400]),
                            f"chipbench.s{i % 7}"))
    for d in range(2):
        ops = []
        for _ in range(30):
            s = g.randrange(-20, 1020)
            ops.append((s, s + g.randrange(1, 40), "jit_f/op", True))
        ev["devices"][f"/device:TPU:{d}"] = ops
    want = trace.reduce_events(ev)
    got = spans.reduce_events(ev)
    assert [k for k, _ in got["idle_gaps"]] == \
        [k for k, _ in want["idle_gaps"]]
    assert dict(got["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(
        want["window_s"] - want["busy_s"])


def test_recorded_v5e_trace_splits_as_the_harness_does():
    """The recording predates program spans: the split is the harness's
    own, and sums to the window's idle time."""
    want = trace.reduce(RECORDED, [0])
    got = spans.reduce(RECORDED, [0])
    assert dict(got["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(
        want["window_s"] - want["busy_s"])
    assert got["host_spans"]["gemm.call"][0] == 3


def _ctx(workload, names_idle, window_s=10.0):
    """A traced run's context whose program-span split is already read."""
    return {"spec": {"workload": workload, "cell": {"chips": 1}},
            "trace": {"window_s": window_s, "busy_s": 4.0},
            "spans": {"idle_by_span": dict(names_idle),
                      "host_spans": {n: [1, 1.0] for n in names_idle}}}


@pytest.mark.parametrize("metric,names,want", [
    ("gemm_map_idle_pct",
     {"repro.gemm.expand_map": 2.5, "repro.gemm.upload_map": 0.5,
      "repro.gemm.dispatch": 3.5}, 30.0),
    ("serve_retire_idle_pct.backlog",
     {"repro.serve.retire_pass": 0.4, "repro.serve.refill": 0.3}, 4.0),
])
def test_metric_reads_inclusive_idle_under_its_spans(metric, names, want):
    read = load_module("metrics", metric).read
    assert read(_ctx("cell", names)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["gemm_map_idle_pct",
                                    "serve_retire_idle_pct.backlog"])
def test_metric_is_silent_without_program_spans(metric):
    """A program that does not annotate its phases (an older commit), or
    an untraced run, reads nothing and raises nothing."""
    read = load_module("metrics", metric).read
    assert read(_ctx("cell", {"gemm.call": 5.0})) is None
    assert read({"trace": None}) is None


def test_trace_root_is_where_run_writes_traces():
    from chipbench import run
    assert spans.TRACE_ROOT == os.path.join(run.STATE, "trace")
