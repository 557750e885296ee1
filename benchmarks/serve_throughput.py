"""Serve-throughput microbench: the scheduler acceptance gate.

Drives a mixed-shape, mixed-format request stream through the
token-level continuous-batching engine (warmed) and through the engine's
reference, reporting tokens/s, microbatch occupancy, mid-decode refills,
prefix-cache reuse, bucket hit rate, padding waste, post-warmup
recompiles, and batched-vs-reference parity.  Parity is checked against
the reference at the serving width (``generate_reference``'s default,
the exactness oracle); the throughput baseline is the reference at
width 1, one request per call.  Both timed paths run in the steady state
(each runs the stream once untimed first) and the batched path must
BEAT the width-1 run: ``speedup >= 1.5`` is asserted here and floored at
1.0 by ``compare.py`` in CI.  The CI ``perf-trajectory`` lane runs
``--smoke`` and records the rows to ``BENCH_serve.json`` (see
``bench_io``).

    PYTHONPATH=src python benchmarks/serve_throughput.py --smoke \
        --out BENCH_serve.json
"""
from __future__ import annotations

import argparse
import dataclasses
import time


def _requests(vocab: int, *, n: int, alt_tag: str | None, seed: int = 0):
    """Mixed-shape, mixed-format stream with a shared 8-token system
    prefix per format set — the prefix equals the S16 bucket's reusable
    prefix length (16 // 2), so prefix-reuse prefill gets real traffic
    (long prompts overflow into S32, where the 16-token prefix diverges
    per request: a realistic mix of hits and misses)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sys_prefix = {"default": rng.integers(1, vocab, size=8).astype(np.int32)}
    if alt_tag:
        sys_prefix[alt_tag] = rng.integers(1, vocab, size=8).astype(np.int32)
    tails = [2, 3, 4, 6, 7, 8, 12, 3]
    reqs = []
    for i in range(n):
        tail = (rng.integers(1, vocab,
                             size=tails[i % len(tails)])).astype(np.int32)
        fset = alt_tag if (alt_tag and i % 3 == 2) else "default"
        reqs.append((np.concatenate([sys_prefix[fset], tail]), fset))
    return reqs


def bench(smoke: bool = True, n_requests: int = 12, max_new: int = 16
          ) -> list[tuple]:
    import jax
    import numpy as np

    if not smoke:      # full mode: a longer stream, longer generations
        n_requests, max_new = n_requests * 4, max_new * 2

    from repro.configs import get, load_all, reduced
    from repro.models import transformer as T
    from repro.serve import Engine, Request, ServeConfig

    load_all()
    cfg = reduced(get("llama3-8b"), tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    alt_tag = "fp8_e5m2+fp16+fp32"
    alt_params = T.init_model(
        jax.random.PRNGKey(0),
        dataclasses.replace(cfg, mp_formats=alt_tag))

    eng = Engine(cfg, params, ServeConfig(max_batch=4, max_seq=64),
                 variants={alt_tag: alt_params})
    t0 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t0

    # steady state on BOTH sides: each path runs the stream once untimed
    # (first-call costs — process-level jit/dispatch setup — fold into
    # warmup), then the identical stream again, timed.  Stats rows report
    # the timed pass via counter deltas.
    stream = _requests(cfg.vocab, n=n_requests, alt_tag=alt_tag)
    eng.generate([Request(p, max_new_tokens=max_new, fset=f)
                  for p, f in stream])
    st0 = eng.stats()
    reqs = [Request(p, max_new_tokens=max_new, fset=f) for p, f in stream]
    t0 = time.perf_counter()
    eng.generate(reqs)
    serve_s = time.perf_counter() - t0
    st = eng.stats()

    def ref_stream():
        return [Request(np.asarray(p), max_new_tokens=max_new, fset=f)
                for p, f in stream]

    refs = eng.generate_reference(ref_stream())      # parity oracle
    eng.generate_reference(ref_stream(), width=1)    # untimed warm pass
    t0 = time.perf_counter()
    eng.generate_reference(ref_stream(), width=1)
    unbatched_s = time.perf_counter() - t0
    parity = all(r.out_tokens == ref.out_tokens
                 for r, ref in zip(reqs, refs))

    def delta(*path):
        a, b = st, st0
        for k in path:
            a, b = a[k], b[k]
        return a - b

    served = delta("requests", "served")
    gen = delta("tokens", "generated")
    n_mb = delta("microbatches", "total")
    waste_pad = delta("tokens", "padded")
    waste_real = delta("tokens", "prompt")
    speedup = unbatched_s / serve_s
    pc, pc0 = st["prefix_cache"] or {}, st0["prefix_cache"] or {}
    pc_hits = pc.get("hits", 0) - pc0.get("hits", 0)
    pc_miss = pc.get("misses", 0) - pc0.get("misses", 0)
    rows = [
        ("serve_warmup", warmup_s * 1e6,
         "buckets="
         f"{len([b for b in eng.scheduler.buckets.values() if b.warmed])};"
         f"traces={st['compile']['warmup_traces']}"),
        ("serve_stream_batched", serve_s * 1e6,
         f"requests={served};tokens_per_s="
         f"{gen / serve_s:.1f};microbatches={n_mb};"
         f"multi={delta('microbatches', 'multi_request')};"
         f"mean_mb={served / max(n_mb, 1):.2f};"
         f"refills={delta('microbatches', 'refills')}"),
        ("serve_stream_unbatched", unbatched_s * 1e6,
         f"tokens_per_s={gen / unbatched_s:.1f};"
         f"speedup={speedup:.2f}x"),
        ("serve_prefix_reuse", 0.0,
         f"hits={pc_hits};misses={pc_miss};"
         f"hit_rate={pc_hits / max(pc_hits + pc_miss, 1):.2f};"
         f"entries={pc.get('entries', 0)}"),
        ("serve_bucket_hit_rate", 0.0,
         f"rate={st['bucket_hit_rate']:.2f};hits={st['bucket_hits']};"
         f"misses={st['bucket_misses']}"),
        ("serve_padding_waste", 0.0,
         f"waste={waste_pad / max(waste_pad + waste_real, 1):.3f};"
         f"padded={waste_pad};real={waste_real}"),
        ("serve_post_warmup_recompiles", 0.0,
         f"n={st['compile']['post_warmup_recompiles']};"
         f"parity={'ok' if parity else 'MISMATCH'};mode={eng.mode}"),
    ]
    # acceptance gate: the plan-warmed scheduler must batch, must not
    # recompile, must match the reference per request — and, with
    # continuous decode, batching must actually PAY: on-device sampling +
    # retire-and-refill + prefix reuse put the floor well above 1×
    assert st["compile"]["post_warmup_recompiles"] == 0, st["compile"]
    assert st["microbatches"]["multi_request"] >= 1, st["microbatches"]
    assert parity, "batched outputs diverged from the reference"
    assert speedup >= 1.5, (
        f"batched serving is only {speedup:.2f}x the unbatched reference "
        f"(must be >= 1.5x)")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--out", default="",
                    help="write rows to this bench-schema JSON path")
    args = ap.parse_args(argv)

    rows = bench(smoke=args.smoke, n_requests=args.requests,
                 max_new=args.max_new)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.out:
        from benchmarks.bench_io import write_bench
        write_bench(args.out, "serve", rows,
                    meta={"smoke": args.smoke,
                          "requests": args.requests,
                          "max_new": args.max_new})
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    raise SystemExit(main())
