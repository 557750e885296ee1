"""Shape-bucketed continuous-batching scheduler + plan-warmed engine.

Host-side policy edges (bucket selection, waste cap, overflow, eviction)
run without jax; the engine batteries assert the ISSUE acceptance gate —
mixed-shape/mixed-format streams match the unbatched engine bit-exactly
with zero post-warmup recompiles and ≥1 multi-request microbatch.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import load_all, reduced
from repro.models import transformer as T
from repro.serve import ServeConfig
from repro.serve.engine import Engine, Request
from repro.serve.scheduler import (AdmissionError, BucketKey, QueueFullError,
                                   SchedulerConfig, ShapeBucketScheduler)


# ---------------------------------------------------------------------------
# pure scheduler policy (no jax)
# ---------------------------------------------------------------------------

def _sched(**kw):
    defaults = dict(pad_lens=(8, 16, 32), waste_cap=0.5, max_batch=4,
                    max_queue=8, max_dynamic=2)
    defaults.update(kw)
    return ShapeBucketScheduler(SchedulerConfig(**defaults))


def test_best_fit_bucket_selection():
    s = _sched()
    assert s.bucket_for(8, "default") == BucketKey(8, "default")
    assert s.bucket_for(5, "default") == BucketKey(8, "default")
    assert s.bucket_for(9, "default") == BucketKey(16, "default")
    assert s.bucket_for(32, "default") == BucketKey(32, "default")


def test_waste_cap_rejects_warm_bucket():
    s = _sched()          # waste_cap=0.5
    # L=3 → best fit 8 wastes 5/8 = 0.625 > 0.5 → cold exact-length bucket
    key = s.bucket_for(3, "default")
    assert key == BucketKey(3, "default")
    assert not s.buckets[key].configured
    assert s.waste_redirects == 1
    # L=4 → waste 4/8 = 0.5 ≤ cap → stays on the warm bucket
    assert s.bucket_for(4, "default") == BucketKey(8, "default")
    assert s.waste_redirects == 1


def test_admission_rejects_oversized_and_unknown_fset():
    s = _sched()
    with pytest.raises(AdmissionError):
        s.bucket_for(33, "default")      # beyond the largest bucket
    with pytest.raises(AdmissionError):
        s.bucket_for(0, "default")
    with pytest.raises(AdmissionError):
        s.bucket_for(4, "nope")
    with pytest.raises(AdmissionError):
        s.admit(object(), 33, "default")
    assert s.rejected == 1


def test_queue_overflow_backpressure():
    s = _sched(max_queue=3)
    for i in range(3):
        s.admit(f"r{i}", 8, "default")
    with pytest.raises(QueueFullError):
        s.admit("r3", 8, "default")
    assert s.rejected == 1
    # draining frees capacity
    assert s.next_microbatch() is not None
    s.admit("r4", 8, "default")


def test_dynamic_bucket_lru_eviction():
    s = _sched(max_dynamic=2)
    k1 = s.bucket_for(1, "default")      # cold (waste 7/8)
    k2 = s.bucket_for(2, "default")      # cold
    assert s.evictions == 0
    s.bucket_for(1, "default")           # touch k1 → k2 becomes LRU
    k3 = s.bucket_for(3, "default")      # cold → evicts k2
    assert s.evictions == 1
    assert k2 not in s.buckets and k1 in s.buckets and k3 in s.buckets
    # a request re-arriving at the evicted shape recreates the bucket cold
    k2b = s.bucket_for(2, "default")
    assert k2b == k2 and not s.buckets[k2b].warmed


def test_eviction_spares_busy_buckets():
    s = _sched(max_dynamic=1)
    k1 = s.bucket_for(1, "default")
    s.admit("r", 1, "default")           # k1 has pending work
    k2 = s.bucket_for(2, "default")      # would evict k1, but it's busy
    assert k1 in s.buckets and k2 in s.buckets
    assert s.evictions == 0


def test_fifo_fair_microbatch_formation():
    s = _sched(max_batch=2)
    s.admit("a1", 8, "default")
    s.admit("b1", 16, "default")
    s.admit("a2", 8, "default")
    s.admit("a3", 8, "default")
    bucket, batch = s.next_microbatch()
    assert bucket.key.pad_len == 8 and batch == ["a1", "a2"]
    bucket, batch = s.next_microbatch()   # b1 is now the oldest
    assert bucket.key.pad_len == 16 and batch == ["b1"]
    bucket, batch = s.next_microbatch()
    assert bucket.key.pad_len == 8 and batch == ["a3"]
    assert s.next_microbatch() is None and s.pending() == 0


def test_equal_mode_buckets_are_exact_length():
    s = ShapeBucketScheduler(
        SchedulerConfig(pad_lens=(8, 16), waste_cap=0.5), mode="equal")
    assert s.bucket_for(8, "default") == BucketKey(8, "default")
    assert s.buckets[BucketKey(8, "default")].configured
    key = s.bucket_for(5, "default")     # never padded up to 8
    assert key == BucketKey(5, "default")
    assert not s.buckets[key].configured


# ---------------------------------------------------------------------------
# engine batteries
# ---------------------------------------------------------------------------

def _mk_engine(arch="llama3-8b", **kw):
    cfg = reduced(load_all()[arch], tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params, Engine(cfg, params, ServeConfig(**kw))


def _reqs(prompts, max_new=3, fsets=None):
    return [Request(np.asarray(p, np.int32), max_new_tokens=max_new,
                    fset=(fsets[i] if fsets else "default"))
            for i, p in enumerate(prompts)]


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 2, 2]]


def test_warmed_mixed_shape_stream_exact_and_no_recompiles():
    cfg, params, eng = _mk_engine(
        max_batch=3, max_seq=32, buckets=(4,), waste_cap=0.75)
    assert eng.mode == "masked"
    eng.warmup()
    assert eng.stats()["compile"]["warmup_traces"] > 0
    reqs = _reqs(PROMPTS)
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs(PROMPTS))
    for r, ref in zip(reqs, refs):
        assert r.done and len(r.out_tokens) == 3
        assert r.out_tokens == ref.out_tokens      # bit-exact vs unbatched
    st = eng.stats()
    assert st["compile"]["post_warmup_recompiles"] == 0
    assert st["microbatches"]["multi_request"] >= 1
    assert st["bucket_misses"] == 0 and st["bucket_hits"] >= 1


def test_parity_holds_at_width_where_rounding_decides_tokens():
    """At d_model 256 / vocab 8192 a random model's greedy argmax flips
    on last-bit differences, so batched serving stays token-exact only if
    every row sees the reference's arithmetic — here, the same matmul row
    count for prefill, refill and reference."""
    load_all()
    from repro.configs import get
    cfg = dataclasses.replace(get("internlm2-1.8b"), n_layers=2,
                              d_model=256, n_heads=4, n_kv_heads=2,
                              d_ff=1024, vocab=8192, tp=1)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, ServeConfig(buckets=(16, 32, 64),
                                          max_batch=4, max_seq=80))
    eng.warmup()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).tolist()
               for n in np.random.default_rng(0).integers(8, 61, size=8)]
    reqs = _reqs(prompts, max_new=16)
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs(prompts, max_new=16))
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in refs]
    assert eng.stats()["compile"]["post_warmup_recompiles"] == 0


def test_decode_writes_kv_over_the_padding():
    """A right-padded row's generated KV lands at its true position, so its
    cache matches unpadded decoding slot by slot.  A TPU reduces attention
    lane-parallel, so KV placed after the padding would change the
    rounding even though the padding is masked out."""
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(8,))
    L, S, B = 3, 8, 2
    toks = np.zeros((B, S), np.int32)
    toks[:, :L] = [5, 6, 7]
    lengths = jnp.full((B,), L, jnp.int32)
    temps = jnp.zeros((B,), jnp.float32)
    keys = jnp.zeros((B, 2), jnp.uint32)
    tok, caches = eng._prefill(params, jnp.asarray(toks),
                               T.init_cache(cfg, B, 32), lengths, temps,
                               keys)
    _, caches, _ = eng._decode_cont(
        params, tok[:, None], caches, lengths, jnp.full((B,), S, jnp.int32),
        jnp.ones((B,), jnp.int32), temps, keys, jnp.int32(S))
    rtok, ref = eng._prefill(params, jnp.asarray(toks[:, :L]),
                             T.init_cache(cfg, B, 32), lengths, temps, keys)
    _, ref = eng._decode_sample(params, rtok[:, None], ref, jnp.int32(L),
                                temps, keys, jnp.ones((B,), jnp.int32))
    for got, want in zip(jax.tree.leaves(caches), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(got)[:, :, :L + 1],
                                      np.asarray(want)[:, :, :L + 1])


def test_cold_bucket_fallback_records_miss_not_crash():
    cfg, params, eng = _mk_engine(
        max_batch=2, max_seq=32, buckets=(4, 8), waste_cap=0.5)
    eng.warmup([BucketKey(4, "default")])   # bucket 8 deliberately skipped
    reqs = _reqs([[1, 2, 3, 4], [9, 8, 7, 6, 5]])   # L=4 warm, L=5 → 8 cold
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs([[1, 2, 3, 4], [9, 8, 7, 6, 5]]))
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref.out_tokens
    assert reqs[0].cold is False and reqs[1].cold is True
    st = eng.stats()
    assert st["bucket_misses"] == 1
    assert st["compile"]["post_warmup_recompiles"] > 0   # honest accounting
    # the cold bucket is compiled now: serving it again is a hit
    more = _reqs([[3, 3, 3, 3, 3]])
    eng.generate(more)
    assert eng.stats()["bucket_misses"] == 1
    assert more[0].cold is False


def test_engine_rejects_unservable_requests():
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=16, buckets=(4, 8))
    with pytest.raises(AdmissionError):
        # 12 + 16 (default max_new) − 1 > max_seq even at the exact length
        eng.submit(Request(np.arange(12, dtype=np.int32)))
    with pytest.raises(AdmissionError):
        # 8 + 12 − 1 > max_seq even at the exact length
        eng.submit(Request(np.asarray([1] * 8, np.int32),
                           max_new_tokens=12))
    assert eng.scheduler.rejected == 2
    # rejected requests must not have created any dynamic bucket
    assert all(b.configured for b in eng.scheduler.buckets.values())
    # longer than every configured bucket but within the KV bound →
    # served through an exact-length cold bucket, like the old engine
    key = eng.submit(Request(np.arange(1, 11, dtype=np.int32),
                             max_new_tokens=4))
    assert key == BucketKey(10, "default")
    # exactly at the KV bound: pad 4 + 13 new − 1 == 16 is servable
    key = eng.submit(Request(np.asarray([1, 2, 3], np.int32),
                             max_new_tokens=13))
    assert key == BucketKey(4, "default")
    # padded length breaks the bound but the exact length fits → the
    # request falls back to a cold exact-length bucket, not a rejection
    key = eng.submit(Request(np.asarray([1] * 7, np.int32),
                             max_new_tokens=10))
    assert key == BucketKey(7, "default")
    assert not eng.scheduler.buckets[key].configured
    assert eng.scheduler.rejected == 2


def test_generate_serves_admissible_and_flags_rejects():
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=16, buckets=(4,))
    good = Request(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
    bad = Request(np.arange(12, dtype=np.int32), max_new_tokens=8)
    eng.generate([good, bad])    # 12 + 8 − 1 > max_seq even unpadded
    assert good.done and len(good.out_tokens) == 2 and good.error == ""
    assert not bad.done and bad.out_tokens == []
    assert bad.error.startswith("AdmissionError")
    assert eng.scheduler.pending() == 0    # nothing stranded


def test_duplicate_admission_rejected():
    s = _sched()
    r = object()
    s.admit(r, 8, "default")
    with pytest.raises(AdmissionError):
        s.admit(r, 8, "default")       # same object queued twice
    bucket, batch = s.next_microbatch()
    assert batch == [r]                # exactly one copy drained
    assert s.next_microbatch() is None
    s.admit(r, 8, "default")           # re-admissible once drained


def test_eviction_folds_counters_into_totals():
    s = _sched(max_dynamic=1)
    k1 = s.bucket_for(1, "default")    # cold
    b1 = s.buckets[k1]
    b1.misses, b1.served, b1.real_tokens, b1.padded_tokens = 1, 2, 5, 0
    s.bucket_for(2, "default")         # evicts k1
    assert s.evictions == 1
    t = s.totals()
    assert (t["misses"], t["served"], t["real_tokens"]) == (1, 2, 5)
    assert s.stats()["evicted_totals"]["served"] == 2


def test_engine_filters_buckets_that_cannot_fit_max_seq():
    # a configured pad_len with no decode head-room (pad+1 > max_seq) is
    # dropped at engine construction instead of crashing warmup — the
    # launcher's default (buckets up to 128, --max-seq 128) relies on this
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=16,
                                  buckets=(4, 8, 16, 128))
    assert sorted(k.pad_len for k in eng.scheduler.buckets) == [4, 8]
    eng.warmup()          # must not raise
    with pytest.raises(ValueError):
        Engine(cfg, params, ServeConfig(max_batch=2, max_seq=4,
                                        buckets=(16, 32)))


def test_stats_counter_correctness():
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(4,))
    eng.warmup()
    # 4 requests at max_batch 2: retire-and-refill serves the whole wave
    # through ONE resident microbatch (2 initial rows + 2 refills)
    reqs = _reqs(PROMPTS, max_new=2)
    eng.generate(reqs)
    st = eng.stats()
    assert st["requests"]["served"] == 4
    assert st["microbatches"]["total"] == 1
    assert st["microbatches"]["multi_request"] == 1
    assert st["microbatches"]["max_size"] == 2
    assert st["microbatches"]["refills"] == 2
    assert st["tokens"]["generated"] == 8
    assert st["tokens"]["prompt"] == sum(len(p) for p in PROMPTS)
    assert st["tokens"]["padded"] == sum(4 - len(p) for p in PROMPTS)
    assert 0.0 < st["padding_waste"] < 1.0
    assert st["bucket_hits"] == 1 and st["bucket_misses"] == 0
    # prefill samples token 0, then one decode step per remaining token:
    # 2 steps total (initial rows step once, refilled rows step once)
    assert st["decode_steps"] == 2
    assert all(r.latency_s > 0 for r in reqs)
    assert all(r.bucket == "S4/default" and r.padded_to == 4 for r in reqs)
    sched = st["scheduler"]
    assert sched["pending"] == 0 and sched["mode"] == "masked"
    assert sched["buckets"]["S4/default"]["served"] == 4


def test_refill_disabled_restores_microbatch_per_wave():
    # --no-refill fallback: each wave of max_batch requests runs as its
    # own microbatch, exactly the pre-continuous-decode schedule
    cfg, params, eng = _mk_engine(
        max_batch=2, max_seq=32, refill=False, buckets=(4,))
    assert not eng.refill_enabled
    eng.warmup()
    reqs = _reqs(PROMPTS, max_new=2)
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs(PROMPTS, max_new=2))
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref.out_tokens
    st = eng.stats()
    assert st["microbatches"]["total"] == 2
    assert st["microbatches"]["multi_request"] == 2
    assert st["microbatches"]["refills"] == 0
    assert st["compile"]["post_warmup_recompiles"] == 0


def test_mixed_max_new_early_retirement_and_refill():
    # rows retire the step they reach their own max_new — including one
    # that finishes at prefill (max_new=1) — and pending requests are
    # admitted into freed slots mid-decode; everything stays bit-exact
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(4,))
    eng.warmup()
    max_news = [1, 5, 2, 3]

    def mk():
        return [Request(np.asarray(p, np.int32), max_new_tokens=n)
                for p, n in zip(PROMPTS, max_news)]

    reqs = mk()
    eng.generate(reqs)
    refs = eng.generate_reference(mk())
    for r, ref, n in zip(reqs, refs, max_news):
        assert r.done and len(r.out_tokens) == n
        assert r.out_tokens == ref.out_tokens
    st = eng.stats()
    assert st["microbatches"]["total"] == 1
    assert st["microbatches"]["refills"] == 2
    assert st["requests"]["served"] == 4
    assert st["tokens"]["generated"] == sum(max_news)
    # schedule: prefill retires r0 (refill r2) → step1 retires r2 (refill
    # r3) → step2 → step3 retires r3 → step4 retires r1
    assert st["decode_steps"] == 4
    assert st["compile"]["post_warmup_recompiles"] == 0
    # latency is stamped at each request's OWN retirement, not microbatch
    # end: r1 (admitted first wave, retired last) must dominate them all
    lat = eng.metrics.histogram("serve.request.latency_s")
    assert lat.count == 4
    assert lat.max == max(r.latency_s for r in reqs)
    assert all(reqs[i].latency_s < reqs[1].latency_s for i in (0, 2, 3))


def test_double_refill_with_instant_retire_stays_exact():
    # two slots retire together and BOTH are refilled, and one refill has
    # max_new_tokens == 1 — it retires in the next iteration of the same
    # retirement pass, whose refill rebuilds the decode input.  The rebuild
    # must preserve the OTHER refilled slot's first token (regression:
    # seeding the rebuild from hist[-1] reverted that slot to its retired
    # predecessor's last token, silently breaking parity)
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(4,))
    eng.warmup()
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 2, 2], [3, 1]]
    max_news = [1, 1, 1, 3, 2]
    # schedule: prefill retires r0+r1 together (refill r2 → slot 0,
    # r3 → slot 1); r2 retires instantly (max_new=1), refilling r4 into
    # slot 0 while slot 1 has emitted nothing beyond its prefill token

    def mk():
        return [Request(np.asarray(p, np.int32), max_new_tokens=n)
                for p, n in zip(prompts, max_news)]

    reqs = mk()
    eng.generate(reqs)
    refs = eng.generate_reference(mk())
    for r, ref, n in zip(reqs, refs, max_news):
        assert r.done and len(r.out_tokens) == n
        assert r.out_tokens == ref.out_tokens
    st = eng.stats()
    assert st["microbatches"]["total"] == 1
    assert st["microbatches"]["refills"] == 3
    assert st["requests"]["served"] == 5
    assert st["compile"]["post_warmup_recompiles"] == 0


def test_refills_of_one_retirement_pass_share_one_prefill():
    # slots freed in the same retirement pass are refilled by ONE prefill
    # at the microbatch width, not one call per slot: the schedule of
    # test_double_refill_with_instant_retire_stays_exact needs the wave's
    # prefill, one call for the two slots freed together, and one for
    # the slot its instant retirement frees
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(4,))
    eng.warmup()
    calls = []
    for name in ("_prefill", "_prefill_sfx"):
        fn = getattr(eng, name)

        def counted(*a, _fn=fn, **k):
            calls.append(a[1].shape)
            return _fn(*a, **k)
        setattr(eng, name, counted)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 2, 2], [3, 1]]
    reqs = [Request(np.asarray(p, np.int32), max_new_tokens=n)
            for p, n in zip(prompts, [1, 1, 1, 3, 2])]
    eng.generate(reqs)
    assert eng.stats()["microbatches"]["refills"] == 3
    assert calls == [(2, 4)] * 3


def test_prefix_reuse_prefill_exact_and_counted():
    # shared system prompt: wave 1 populates the prefix cache (P = pad//2
    # leading tokens, keyed by digest); wave 2's rows ALL hit, so only the
    # suffix is prefilled — and the tokens stay bit-exact vs unbatched
    # (causal KV for positions < P depends only on tokens < P)
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(8,))
    eng.warmup()
    sys_prefix = [9, 8, 7, 6]     # == padded prefix: P = 8 // 2 = 4
    wave1 = [sys_prefix + [1, 2], sys_prefix + [3]]
    wave2 = [sys_prefix + [5, 5, 5], sys_prefix + [2, 9]]
    r1 = _reqs(wave1)
    eng.generate(r1)
    assert eng.prefix.stats()["inserts"] == 1    # one digest, stored once
    r2 = _reqs(wave2)
    eng.generate(r2)
    refs = eng.generate_reference(_reqs(wave1 + wave2))
    for r, ref in zip(r1 + r2, refs):
        assert r.out_tokens == ref.out_tokens
    st = eng.stats()
    pc = st["prefix_cache"]
    assert pc["hits"] >= 2 and pc["hit_rate"] > 0.0
    assert int(eng.metrics.value("serve.prefix.reused_prefills")) >= 1
    assert st["compile"]["post_warmup_recompiles"] == 0


def test_prefix_cache_accounting_mixed_wave():
    # mixed hit/miss wave (suffix-only prefill unusable): rows whose
    # digest IS cached still count per-row hits, and rows sharing one
    # uncached digest count a SINGLE miss — mirroring the one insert the
    # wave performs — so stats()["prefix_cache"]["hit_rate"] reflects
    # actual reuse potential
    cfg, params, eng = _mk_engine(max_batch=3, max_seq=32, buckets=(8,))
    eng.warmup()
    pre_a, pre_b = [9, 8, 7, 6], [5, 5, 5, 5]       # P = 8 // 2 = 4
    eng.generate(_reqs([pre_a + [1, 2]]))           # miss → inserts A
    pc = eng.prefix.stats()
    assert (pc["hits"], pc["misses"], pc["inserts"]) == (0, 1, 1)
    # wave 2: A cached (1 hit), B uncached on TWO rows (1 miss, 1 insert)
    eng.generate(_reqs([pre_a + [3], pre_b + [1], pre_b + [2, 2]]))
    pc = eng.prefix.stats()
    assert (pc["hits"], pc["misses"], pc["inserts"]) == (1, 2, 2)


def test_sampled_decode_batched_unbatched_parity():
    # temperature > 0: per-request PRNG streams keyed by (engine seed,
    # request seed, token index) make sampled decoding batch-invariant —
    # and filler slots must not consume or perturb any real row's stream
    cfg, params, eng = _mk_engine(max_batch=3, max_seq=32, buckets=(4,))
    eng.warmup()

    def mk():
        return [Request(np.asarray(p, np.int32), max_new_tokens=4,
                        temperature=t, seed=s)
                for p, t, s in [([1, 2, 3], 0.8, 1), ([1, 2, 3], 0.8, 2),
                                ([4, 5], 0.0, 3), ([2, 2, 2], 1.3, 4)]]

    reqs = mk()
    eng.generate(reqs)        # waves of 3 + 1 → one wave has 2 fillers
    refs = eng.generate_reference(mk())
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref.out_tokens
    # same prompt + same temperature, different seed → streams diverge
    # (otherwise this parity test would be vacuous)
    assert reqs[0].out_tokens != reqs[1].out_tokens
    assert eng.stats()["compile"]["post_warmup_recompiles"] == 0


def test_legacy_kwargs_shim_maps_and_warns_once():
    # pre-ServeConfig Engine kwargs still construct — mapped onto a
    # ServeConfig with ONE process-wide DeprecationWarning — but mixing
    # them with a ServeConfig (or typo-ing them) stays a TypeError
    import warnings

    import repro.serve.config as serve_config

    cfg = reduced(load_all()["llama3-8b"], tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    serve_config._warned_legacy = False
    with pytest.warns(DeprecationWarning):
        eng = Engine(cfg, params, max_batch=2, max_seq=16, refill=False,
                     scheduler=SchedulerConfig(pad_lens=(4,), max_batch=2),
                     prefix_entries=8)
    sc = eng.config
    assert isinstance(sc, ServeConfig)
    assert sc.max_batch == 2 and sc.max_seq == 16 and sc.refill is False
    assert sc.buckets == (4,)
    assert sc.prefix_pages == 32      # 8 legacy entries, 4 pages apiece
    # second legacy construction in the same process is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Engine(cfg, params, max_batch=2, max_seq=16)
    with pytest.raises(TypeError):
        Engine(cfg, params, ServeConfig(), max_batch=2)   # both paths
    with pytest.raises(TypeError):
        Engine(cfg, params, max_batsh=2)                  # unknown kwarg


def test_chunked_long_prompt_prefill_exact_and_page_reused():
    # prompts longer than every configured bucket serve through chunked
    # prefill at a rounded-up dynamic bucket — bit-exact, zero recompiles
    # (the [B, C] chunk executable has a traced offset, decode a traced
    # pad) — and a repeat wave skips leading chunks via the page cache
    cfg, params, eng = _mk_engine(max_batch=2, max_seq=32, buckets=(4, 8))
    eng.warmup()
    prompts = [list(range(1, 12)), [7] * 10]       # L = 11, 10 > pad 8
    reqs = _reqs(prompts, max_new=3)
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs(prompts, max_new=3))
    for r, ref in zip(reqs, refs):
        assert r.done and r.out_tokens == ref.out_tokens
        assert r.bucket == "S16/default" and r.padded_to == 16
        assert r.cold is False         # pre-warmed chunk path, not cold
    st = eng.stats()
    assert st["compile"]["post_warmup_recompiles"] == 0
    assert st["chunked_prefills"] >= 1
    # repeat wave: both rows' leading whole chunk is page-cached now
    again = _reqs(prompts, max_new=3)
    eng.generate(again)
    for r, ref in zip(again, refs):
        assert r.out_tokens == ref.out_tokens
    st = eng.stats()
    assert st["compile"]["post_warmup_recompiles"] == 0
    assert st["prefix_cache"]["hits"] >= 2
    # no page leak: every retired row released its block table — the only
    # live references left are the cache entries themselves
    assert st["kv_pages"]["in_use"] == st["prefix_cache"]["entries"]
    assert st["kv_pages"]["in_use"] <= eng.config.prefix_pages


@pytest.mark.slow
def test_mixed_format_stream_parity():
    cfg = reduced(load_all()["llama3-8b"], tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    alt_tag = "fp8_e5m2+fp16+fp32"
    alt = T.init_model(jax.random.PRNGKey(0),
                       dataclasses.replace(cfg, mp_formats=alt_tag))
    eng = Engine(cfg, params,
                 ServeConfig(max_batch=2, max_seq=32, buckets=(4,)),
                 variants={alt_tag: alt})
    eng.warmup()
    fsets = ["default", alt_tag, alt_tag, "default"]
    reqs = _reqs(PROMPTS, fsets=fsets)
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs(PROMPTS, fsets=fsets))
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref.out_tokens
    # different format sets quantize the same weights differently — the
    # streams must have actually diverged for this test to mean anything
    assert (reqs[0].out_tokens != reqs[1].out_tokens
            or reqs[3].out_tokens != reqs[2].out_tokens)
    st = eng.stats()
    assert st["compile"]["post_warmup_recompiles"] == 0
    assert st["microbatches"]["multi_request"] >= 1
    keys = {r.bucket for r in reqs}
    assert keys == {"S4/default", f"S4/{alt_tag}"}


@pytest.mark.slow
def test_equal_mode_family_parity():
    # local:global attention (gemma3) cannot mask padding → "equal" mode:
    # only same-length requests share a microbatch, rows stay independent
    cfg, params, eng = _mk_engine(
        "gemma3-4b", max_batch=2, max_seq=32, buckets=(4,))
    assert eng.mode == "equal"
    eng.warmup()
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 9]]
    reqs = _reqs(prompts)
    eng.generate(reqs)
    refs = eng.generate_reference(_reqs(prompts))
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref.out_tokens
    st = eng.stats()
    assert st["microbatches"]["multi_request"] == 1   # the two L=4 requests
    assert st["compile"]["post_warmup_recompiles"] > 0  # L=2 was cold
    assert st["scheduler"]["buckets"]["S2/default"]["misses"] == 1
