"""Observability subsystem: metrics registry, tracer round-trip, hygiene,
disabled-mode no-op guarantees, stats parity, provenance, trajectory."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import hygiene as OH
from repro.obs import trace as OT
from repro.obs.metrics import MetricsRegistry, label_key


@pytest.fixture(autouse=True)
def _tracer_off_after():
    """Every test leaves the process-global tracer disabled."""
    yield
    obs.configure(enabled=False)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_label_key_canonical():
    assert label_key({}) == ""
    assert label_key({"b": "x", "a": 1}) == "a=1,b=x"


def test_counter_labels_are_distinct_series():
    reg = MetricsRegistry()
    reg.counter("dispatch.calls", path="grouped").inc()
    reg.counter("dispatch.calls", path="grouped").inc(2)
    reg.counter("dispatch.calls", path="ref").inc()
    assert reg.value("dispatch.calls", path="grouped") == 3
    assert reg.value("dispatch.calls", path="ref") == 1
    series = {label_key(lab): c.value
              for lab, c in reg.series("dispatch.calls")}
    assert series == {"path=grouped": 3.0, "path=ref": 1.0}


def test_gauge_and_histogram_semantics():
    reg = MetricsRegistry()
    reg.gauge("serve.queue_depth").set(7)
    reg.gauge("serve.queue_depth").set(3)
    assert reg.value("serve.queue_depth") == 3.0
    h = reg.histogram("serve.request.latency_s")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    assert h.count == 3 and h.sum == 6.0
    assert h.min == 1.0 and h.max == 3.0 and h.mean == 2.0
    assert reg.histogram("serve.request.latency_s").summary()["mean"] == 2.0
    empty = reg.histogram("other")
    assert empty.mean == 0.0
    assert empty.summary() == {"count": 0, "sum": 0.0, "mean": 0.0,
                               "min": 0.0, "max": 0.0}


def test_value_does_not_create_series():
    reg = MetricsRegistry()
    assert reg.value("nope", default=-1.0, path="x") == -1.0
    assert reg.names() == []


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_snapshot_and_reset():
    reg = MetricsRegistry()
    reg.counter("a", k="1").inc()
    reg.histogram("b").observe(2.0)
    snap = reg.snapshot()
    assert snap["a"] == [{"labels": {"k": "1"}, "value": 1.0}]
    assert snap["b"][0]["value"]["count"] == 1
    json.dumps(snap)                      # plain JSON-able data
    reg.reset("a")
    assert reg.value("a", default=0.0, k="1") == 0.0
    assert reg.names() == ["b"]
    reg.reset()
    assert reg.names() == []


# ---------------------------------------------------------------------------
# tracer: emit -> JSONL -> parse -> chrome export
# ---------------------------------------------------------------------------

def test_tracer_roundtrip_and_chrome_export(tmp_path):
    p = str(tmp_path / "trace.jsonl")
    obs.configure(enabled=True, trace_path=p)
    assert obs.is_enabled()
    with obs.span("solve.run", "solve", method="lu"):
        with obs.span("gemm.dispatch", "gemm", path="ref"):
            pass
        obs.event("plan.resolve", "plan", source="cache")
    obs.tracer().counter("pending", "serve", depth=3)
    obs.configure(enabled=False)          # closes + flushes the file
    assert not obs.is_enabled()

    events = OT.read_events(p)
    assert OH.validate_events(events) == []
    assert OT.span_types(events) == ["gemm.dispatch", "solve.run"]
    phases = sorted(e["ph"] for e in events)
    assert phases == ["C", "X", "X", "i"]
    # nested span closed before its parent: child ts+dur within parent
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    parent, child = spans["solve.run"], spans["gemm.dispatch"]
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1

    chrome = OT.export_chrome(p)
    assert chrome.endswith(".trace.json")
    payload = json.load(open(chrome))
    assert payload["traceEvents"] == events


def test_tracer_in_memory_buffer():
    tr = OT.Tracer()
    with tr.span("serve.microbatch", "serve", n_real=2):
        tr.event("serve.admit", "serve", bucket="S16/default")
    assert [e["name"] for e in tr.buffer] == ["serve.admit",
                                              "serve.microbatch"]
    assert OH.validate_events(tr.buffer) == []


def test_bad_category_rejected_at_emit():
    tr = OT.Tracer()
    with pytest.raises(ValueError):
        tr.event("x", "not-a-category")


# ---------------------------------------------------------------------------
# disabled mode: strict no-op
# ---------------------------------------------------------------------------

def test_disabled_is_noop(tmp_path):
    obs.configure(enabled=False)
    with obs.span("gemm.dispatch", "gemm", path="ref"):
        obs.event("plan.resolve", "plan")
    assert obs.tracer() is OT.NULL_TRACER
    assert list(tmp_path.iterdir()) == []   # nothing written anywhere


def _tiny_mp_operands(n=32, t=16):
    from repro.core import MPMatrix, make_map
    from repro.core.precision import Policy
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n))
    pa = make_map((n, n), t, Policy(kind="ratio", ratio_high=0.5))
    A = MPMatrix.from_dense(a, pa, t)
    C = MPMatrix.from_dense(jnp.zeros((n, n)), pa, t)
    return A, C


def test_dispatch_bitwise_identical_with_tracing(tmp_path):
    from repro.tune import dispatch as TD
    A, C = _tiny_mp_operands()
    obs.configure(enabled=False)
    base = np.asarray(TD.mp_matmul(A, A, C).to_dense())
    p = str(tmp_path / "t.jsonl")
    obs.configure(enabled=True, trace_path=p)
    traced = np.asarray(TD.mp_matmul(A, A, C).to_dense())
    obs.configure(enabled=False)
    np.testing.assert_array_equal(base, traced)
    names = {e["name"] for e in OT.read_events(p)}
    assert "gemm.dispatch" in names


# ---------------------------------------------------------------------------
# spans on the profiler's timeline (JSONL on or off)
# ---------------------------------------------------------------------------

def _profiled(fn, trace_dir):
    """Run ``fn`` under a ``jax.profiler`` session; return its result and
    the host events named ``repro.*`` as ``(start, end, name)``."""
    import glob

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(OT.PROFILER_PREFIX)]
    return out, spans


def _nested(spans, child: str, parent: str) -> bool:
    """Every ``child`` span lies inside some ``parent`` span (and there is
    at least one child)."""
    kids = [(s, e) for s, e, n in spans if n == child]
    outer = [(s, e) for s, e, n in spans if n == parent]
    return bool(kids) and all(any(ps <= s and e <= pe for ps, pe in outer)
                              for s, e in kids)


def test_ref_gemm_spans_reach_the_profiler(tmp_path):
    """With JSONL tracing off, a ``ref`` GEMM's resolve and dispatch spans
    appear on the profiler's timeline; its work is one device program, so
    no host phase (map expansion or upload) is spanned under dispatch."""
    from repro.tune import dispatch as TD
    from repro.tune.costmodel import GemmPlan
    A, C = _tiny_mp_operands()
    obs.configure(enabled=False)
    _, spans = _profiled(
        lambda: jax.block_until_ready(
            TD.mp_matmul(A, A, C, plan=GemmPlan("ref")).bufs),
        tmp_path)
    names = {n for _, _, n in spans}
    assert {"repro.gemm.resolve", "repro.gemm.dispatch"} <= names
    gemm = {n for n in names if n.startswith("repro.gemm.")}
    assert gemm == {"repro.gemm.resolve", "repro.gemm.dispatch"}, gemm
    for gone in ("repro.gemm.expand_map", "repro.gemm.upload_map"):
        assert not _nested(spans, gone, "repro.gemm.dispatch"), gone


def test_serve_refill_and_drain_nest_in_the_retirement_pass(tmp_path):
    from repro.configs import load_all, reduced
    from repro.models import transformer as T
    from repro.serve import ServeConfig
    from repro.serve.engine import Engine, Request

    cfg = reduced(load_all()["llama3-8b"], tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params,
                 ServeConfig(max_batch=2, max_seq=32, buckets=(4,)))
    eng.warmup()
    reqs = [Request(np.asarray(p, np.int32), max_new_tokens=n)
            for p, n in zip([[1, 2, 3], [4, 5], [6, 7, 8], [2, 2]],
                            [1, 3, 2, 2])]
    _, spans = _profiled(lambda: eng.generate(reqs), tmp_path)
    assert eng.stats()["microbatches"]["refills"] >= 1
    assert all(r.done for r in reqs)
    for child in ("repro.serve.refill", "repro.serve.drain"):
        assert _nested(spans, child, "repro.serve.retire_pass"), child
    # each refill runs one prefill of the freed rows
    refills = [(s, e) for s, e, n in spans if n == "repro.serve.refill"]
    assert all(sum(n == "repro.serve.prefill" and rs <= s and e <= re
                   for s, e, n in spans) == 1 for rs, re in refills)
    names = {n for _, _, n in spans}
    assert {"repro.serve.schedule", "repro.serve.sync_state",
            "repro.serve.microbatch", "repro.serve.decode"} <= names


def test_jsonl_sink_still_clean_with_profiler_spans(tmp_path):
    """The JSONL sink keeps its schema (hygiene passes) while the same
    spans reach a profiler session; the instant stays JSONL-only."""
    p = str(tmp_path / "t.jsonl")
    obs.configure(enabled=True, trace_path=p)

    def work():
        with obs.span("solve.run", "solve", method="lu"):
            with obs.span("gemm.dispatch", "gemm", path="ref"):
                obs.event("plan.resolve", "plan", source="cache")

    _, spans = _profiled(work, tmp_path / "prof")
    obs.configure(enabled=False)
    assert OH.validate_trace(p, min_span_types=2) == []
    events = OT.read_events(p)
    assert OT.span_types(events) == ["gemm.dispatch", "solve.run"]
    assert _nested(spans, "repro.gemm.dispatch", "repro.solve.run")
    assert "repro.plan.resolve" not in {n for _, _, n in spans}


def test_spans_reach_the_profiler_with_jsonl_off(tmp_path):
    obs.configure(enabled=False)

    def work():
        with obs.span("serve.microbatch", "serve", n_real=2, bucket="S8"):
            with obs.span("serve.decode", "serve"):
                pass

    _, spans = _profiled(work, tmp_path)
    assert obs.tracer() is OT.NULL_TRACER
    assert _nested(spans, "repro.serve.decode", "repro.serve.microbatch")
    assert not list(tmp_path.glob("*.jsonl"))


# ---------------------------------------------------------------------------
# dispatch resolution counters: registry-backed, compat API intact
# ---------------------------------------------------------------------------

def test_resolution_counters_compat():
    from repro.tune import dispatch as TD
    TD.reset_resolution_counters()
    assert TD.resolution_counters() == {}
    assert TD.fresh_resolutions() == 0
    A, C = _tiny_mp_operands()
    TD.mp_matmul(A, A, C)
    c = TD.resolution_counters()
    assert sum(c.values()) >= 1
    assert set(c) <= {"registry", "cache", "model", "default",
                      "summa_registry", "summa_cache", "summa_model",
                      "summa_default"}
    # the registry view and the compat dict agree
    reg = obs.metrics_registry()
    for src, n in c.items():
        assert reg.value(TD.RESOLUTION_METRIC, source=src) == n
    TD.reset_resolution_counters()
    assert TD.resolution_counters() == {}


# ---------------------------------------------------------------------------
# hygiene validator: negatives
# ---------------------------------------------------------------------------

def test_hygiene_rejects_schema_drift(tmp_path):
    ok = {"name": "s", "cat": "serve", "ph": "X", "ts": 1.0, "dur": 2.0,
          "pid": 1, "tid": 1}
    assert OH.validate_events([ok]) == []
    bad_cat = dict(ok, cat="rogue")
    bad_phase = dict(ok, ph="B")
    no_dur = {k: v for k, v in ok.items() if k != "dur"}
    missing = {"name": "s", "ph": "i"}
    bad_args = dict(ok, args=[1, 2])
    for ev in (bad_cat, bad_phase, no_dur, missing, bad_args):
        assert OH.validate_events([ev]), ev
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps(ok) + "\n")
    assert OH.validate_trace(str(p)) == []
    assert OH.validate_trace(str(p), min_span_types=2)  # only 1 span type
    p.write_text("not json\n")
    assert OH.validate_trace(str(p))
    assert OH.validate_trace(str(tmp_path / "absent.jsonl"))


# ---------------------------------------------------------------------------
# Engine.stats(): registry view keeps the pre-migration dict shape
# ---------------------------------------------------------------------------

def test_engine_stats_shape_parity():
    from repro.configs import load_all, reduced
    from repro.models import transformer as T
    from repro.serve import ServeConfig
    from repro.serve.engine import Engine, Request

    cfg = reduced(load_all()["llama3-8b"], tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params,
                 ServeConfig(max_batch=2, max_seq=32, buckets=(8,)))
    reqs = [Request(np.array([1, 2, 3], np.int32), max_new_tokens=2),
            Request(np.array([4, 5], np.int32), max_new_tokens=2)]
    eng.generate(reqs)
    st = eng.stats()

    assert set(st) == {"mode", "requests", "tokens", "padding_waste",
                       "microbatches", "bucket_hits", "bucket_misses",
                       "bucket_hit_rate", "compile", "decode_steps",
                       "chunked_prefills", "latency_s",
                       "prefix_cache", "kv_pages", "scheduler"}
    assert set(st["requests"]) == {"served", "rejected"}
    assert set(st["tokens"]) == {"prompt", "padded", "generated"}
    assert set(st["microbatches"]) == {"total", "multi_request",
                                       "mean_size", "max_size", "refills"}
    assert set(st["compile"]) == {"warmup_traces", "steady_traces",
                                  "reference_traces",
                                  "post_warmup_recompiles"}
    assert set(st["latency_s"]) == {"mean", "max"}
    # value types match the pre-migration implementation
    assert isinstance(st["requests"]["served"], int)
    assert isinstance(st["microbatches"]["total"], int)
    assert isinstance(st["microbatches"]["max_size"], int)
    assert isinstance(st["microbatches"]["mean_size"], float)
    assert isinstance(st["decode_steps"], int)
    assert isinstance(st["latency_s"]["mean"], float)
    # and the values are self-consistent with what was served
    assert st["requests"]["served"] == 2
    assert st["tokens"]["generated"] == sum(len(r.out_tokens)
                                            for r in reqs)
    assert st["microbatches"]["total"] == 1
    assert st["microbatches"]["max_size"] == 2
    assert st["microbatches"]["multi_request"] == 1
    assert st["latency_s"]["max"] >= st["latency_s"]["mean"] > 0.0
    # EXACT step accounting: prefill samples token 0 on device, so
    # max_new=2 costs exactly ONE decode step (the old engine ran
    # max_new−1 steps but counted max_new — the off-by-one is fixed by
    # incrementing once per actual jitted decode dispatch)
    assert st["decode_steps"] == 1
    # scheduler stream counters ride the same registry
    assert st["scheduler"]["rejected"] == eng.scheduler.rejected == 0
    json.dumps(st)                         # stats stay JSON-serializable


# ---------------------------------------------------------------------------
# SolveReport: per-sweep wall-time + promotion records
# ---------------------------------------------------------------------------

def test_solve_report_sweep_and_promotion_stats():
    from repro.solve import SolveConfig, graded_spd, rhs_for_solution, solve
    a = graded_spd(64, cond=1e4, seed=0)
    _, b = rhs_for_solution(a, nrhs=1, seed=1)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.0, ratio_low8=0.2,
                                  max_sweeps=20))
    assert len(rep.sweep_seconds) == rep.sweeps
    assert all(s >= 0.0 for s in rep.sweep_seconds)
    assert len(rep.promotions) == rep.escalations
    for p in rep.promotions:
        assert p["tiles"] >= 1
        assert len(p["coords"]) == min(p["tiles"], 128)
        assert all(len(c) == 2 for c in p["coords"])
        assert {"escalation", "mode", "rung", "ratio"} <= set(p)
    json.dumps(rep.promotions)
