"""Multi-replica cluster saturation smoke: the scale-out acceptance gate.

Drives the SAME mixed-shape stream (including prompts longer than every
configured bucket) through one warmed engine and through a 2-replica
:class:`repro.serve.Cluster`, and reports aggregate tokens/s on each
path.  The stream is sized to saturate a single engine (requests >>
max_batch), so on a multi-core host the data-parallel replicas must pay:
``speedup >= 1.5`` is asserted here whenever the host has >= 2 CPU cores
(the row carries ``gated=1`` and ``compare.py`` floors the ratio at 1.0
in CI); on a single-core host the row is stamped ``gated=0`` and only
the functional gates run.

Always asserted, gated or not:

* cluster outputs are bit-exact with the single engine's
  ``generate_reference`` (one request per call, at the serving width),
  regardless of which replica served each request;
* zero post-warmup recompiles on every replica AND on the single engine
  (long prompts ride chunked paged prefill, not cold exact compiles);
* routing is a deterministic function of the submission sequence and
  actually uses both replicas;
* the long prompts in the stream were served through chunked prefill.

The CI ``perf-trajectory`` lane runs ``--smoke`` and records the rows to
``BENCH_serve_cluster.json`` under the bench-baseline regression gate.

    PYTHONPATH=src python benchmarks/serve_cluster.py --smoke \
        --out BENCH_serve_cluster.json
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time


def bench(smoke: bool = True, n_requests: int = 16, max_new: int = 8
          ) -> list[tuple]:
    import jax
    import numpy as np

    if not smoke:      # full mode: longer stream, longer generations
        n_requests, max_new = n_requests * 2, max_new * 2

    from repro.configs import get, load_all, reduced
    from repro.models import transformer as T
    from repro.serve import Cluster, Engine, Request, ServeConfig

    load_all()
    cfg = reduced(get("llama3-8b"), tp=2)
    params = T.init_model(jax.random.PRNGKey(0), cfg)

    # mixed shapes; L=11 overflows every configured bucket (max 8) and
    # must serve through chunked paged prefill on both paths
    rng = np.random.default_rng(0)
    lens = [2, 3, 5, 7, 3, 6, 11, 4]
    prompts = [rng.integers(1, cfg.vocab,
                            size=lens[i % len(lens)]).astype(np.int32)
               for i in range(n_requests)]
    long_idx = [i for i, p in enumerate(prompts) if len(p) > 8]

    def stream():
        return [Request(p.copy(), max_new_tokens=max_new, seed=i)
                for i, p in enumerate(prompts)]

    sc = ServeConfig(buckets=(4, 8), max_batch=2, max_seq=64, replicas=2)

    # -- single engine: the saturation baseline (requests >> max_batch) --
    eng = Engine(cfg, params, dataclasses.replace(sc, replicas=1))
    t0 = time.perf_counter()
    eng.warmup()
    warm_single_s = time.perf_counter() - t0
    eng.generate(stream())                     # untimed steady-state pass
    reqs1 = stream()
    t0 = time.perf_counter()
    eng.generate(reqs1)
    single_s = time.perf_counter() - t0
    st_eng = eng.stats()

    # -- 2-replica cluster: same stream, same per-replica config ---------
    cl = Cluster(cfg, params, sc)
    t0 = time.perf_counter()
    cl.warmup()
    warm_cluster_s = time.perf_counter() - t0
    cl.generate(stream())                      # untimed steady-state pass
    reqs2 = stream()
    t0 = time.perf_counter()
    cl.generate(reqs2)
    cluster_s = time.perf_counter() - t0
    st = cl.stats()

    # -- parity oracle: the engine's reference (placement-independent) ---
    refs = eng.generate_reference(stream())
    parity = (all(r.out_tokens == ref.out_tokens
                  for r, ref in zip(reqs1, refs))
              and all(r.out_tokens == ref.out_tokens
                      for r, ref in zip(reqs2, refs)))

    # -- routing determinism: same submission sequence → same placement --
    cl_a, cl_b = Cluster(cfg, params, sc), Cluster(cfg, params, sc)
    pa = [cl_a.submit(r) for r in stream()]
    pb = [cl_b.submit(r) for r in stream()]
    deterministic = pa == pb
    spread = len({r.replica for r in reqs2})

    gen = sum(len(r.out_tokens) for r in reqs2)
    speedup = single_s / cluster_s
    gated = 1 if (os.cpu_count() or 1) >= 2 else 0
    served_per = [p["requests"]["served"] for p in st["per_replica"]]
    chunked = sum(p["chunked_prefills"] for p in st["per_replica"])
    pages = [p["kv_pages"]["in_use"] for p in st["per_replica"]
             if p["kv_pages"]]

    rows = [
        ("cluster_warmup", warm_cluster_s * 1e6,
         f"replicas={st['replicas']};"
         f"single_warmup_us={warm_single_s * 1e6:.0f}"),
        ("cluster_single_engine", single_s * 1e6,
         f"tokens_per_s={gen / single_s:.1f};requests={n_requests};"
         f"max_batch={sc.max_batch}"),
        ("cluster_replicas2", cluster_s * 1e6,
         f"tokens_per_s={gen / cluster_s:.1f};speedup={speedup:.2f}x;"
         f"gated={gated};healthy={st['healthy']}"),
        ("cluster_routing", 0.0,
         f"deterministic={'ok' if deterministic else 'MISMATCH'};"
         f"spread={spread};served_min={min(served_per)}"),
        ("cluster_long_prompt", 0.0,
         f"chunked_prefills={chunked};"
         f"bucket={reqs2[long_idx[0]].bucket};"
         f"cold={int(reqs2[long_idx[0]].cold)}"),
        ("cluster_recompiles", 0.0,
         f"n={st['post_warmup_recompiles']};"
         f"single_n={st_eng['compile']['post_warmup_recompiles']};"
         f"parity={'ok' if parity else 'MISMATCH'};"
         f"pages_in_use={sum(pages)}"),
    ]

    # functional gates — these hold on ANY host
    assert parity, "cluster outputs diverged from the reference"
    assert st["post_warmup_recompiles"] == 0, st["per_replica"]
    assert st_eng["compile"]["post_warmup_recompiles"] == 0, st_eng
    assert st["healthy"] == st["replicas"] == 2
    assert deterministic, f"routing not deterministic: {pa} vs {pb}"
    assert spread == 2 and min(served_per) >= 1, served_per
    assert chunked >= 1, "long prompts never took chunked prefill"
    assert all(not r.cold for r in reqs2), "cold exact-length compile leak"
    # perf gate — only where the hardware can possibly deliver it
    if gated:
        assert speedup >= 1.5, (
            f"2 replicas are only {speedup:.2f}x one saturated engine "
            f"on a {os.cpu_count()}-core host (must be >= 1.5x)")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
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
        write_bench(args.out, "serve_cluster", rows,
                    meta={"smoke": args.smoke,
                          "requests": args.requests,
                          "max_new": args.max_new,
                          "cpus": os.cpu_count() or 1})
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    raise SystemExit(main())
