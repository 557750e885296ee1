"""Serving launcher: load (or init) a model and serve batched requests
through the shape-bucketed scheduler — one engine, or a multi-replica
cluster.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --prompts "1 2 3" "4 5" --max-new 8 --buckets 8,16,32

    # two data-parallel replicas behind the async front-end
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --replicas 2 --prompts "1 2 3" "4 5" "6 7 8" "9 9"

Every knob maps 1:1 onto :class:`repro.serve.ServeConfig` — the launcher
builds one and hands it to ``Engine``/``Cluster``; nothing is passed as
loose kwargs.  Tracing goes through :func:`repro.configure`, the
process-global settings facade.  The stack warms every configured bucket
(plan resolution + compile) before serving unless ``--no-warmup`` is
passed; ``--stats`` dumps the scheduler / compile counters after the
stream drains.
"""
import argparse
import json


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--prompts", nargs="*", default=["1 2 3 4", "7 8"])
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind the async "
                         "front-end (1 → plain single engine)")
    ap.add_argument("--buckets", default="",
                    help="comma-separated padded prompt lengths "
                         "(default: ArchConfig.serve_buckets)")
    ap.add_argument("--waste-cap", type=float, default=0.75,
                    help="max padding-waste fraction before a request is "
                         "redirected to a cold exact-length bucket")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip plan/compile warmup (cold buckets record "
                         "misses instead)")
    ap.add_argument("--no-refill", action="store_true",
                    help="disable mid-decode slot retire-and-refill "
                         "(each wave of requests runs as its own "
                         "microbatch)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable block-paged prefix-KV reuse (every "
                         "prompt is prefilled in full)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="serve prompts longer than every bucket through "
                         "cold exact-length compiles instead of chunked "
                         "paged prefill")
    ap.add_argument("--prefix-pages", type=int, default=128,
                    help="page-pool capacity of the paged prefix-KV cache")
    ap.add_argument("--page-tokens", type=int, default=4,
                    help="KV positions per page")
    ap.add_argument("--request-seed", type=int, default=0,
                    help="base seed for per-request sampling streams "
                         "(request i uses request-seed + i)")
    ap.add_argument("--formats", default="",
                    help="override the arch's mixed-precision format set, "
                         "e.g. fp8_e4m3+bf16+fp32 or the short form "
                         "q:s:d (aliases: d=fp32 s=bf16 q=fp8_e4m3 "
                         "int8=int8_pt int4=int4_pt)")
    ap.add_argument("--quantize", default="",
                    help="serve every request through an activation-aware "
                         "quantized weight variant under this format-set "
                         "spec (e.g. int8:d or int4:int8:d); loud tiles "
                         "stay in the set's HIGH float format")
    ap.add_argument("--quantize-ratio", type=float, default=0.25,
                    help="fraction of K-blocks the calibrator keeps HIGH "
                         "when --quantize is set")
    ap.add_argument("--stats", action="store_true",
                    help="print stats() JSON after serving")
    ap.add_argument("--trace", default="",
                    help="record a repro.obs JSONL trace to this path "
                         "(a Perfetto-loadable .trace.json is written "
                         "alongside)")
    return ap.parse_args(argv)


def run(args):
    """Init (or restore) the model, build the engine or cluster, warm it
    and serve ``args.prompts``, printing one line per request.  Returns
    ``(cfg, server, requests)``."""
    import dataclasses

    import jax
    import numpy as np

    import repro
    from repro.configs import get, load_all, reduced
    from repro.models import transformer as T
    from repro.serve import Cluster, Engine, Request, ServeConfig

    repro.config.use_compile_cache()
    if args.trace:
        repro.configure(obs_trace=args.trace)

    load_all()
    cfg = get(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    # the engine shards no attention heads, so lay them out for one
    # device: a wider tp would only duplicate KV heads and grow the cache
    cfg = dataclasses.replace(cfg, tp=1)
    if args.formats:
        from repro.core.formats import FormatSet
        cfg = dataclasses.replace(
            cfg, mp_formats=FormatSet.parse(args.formats).key())
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")

    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt:
        from repro.checkpoint import ckpt as CK
        restored, man = CK.restore(args.ckpt, {"params": params})
        params = restored["params"]
        print(f"loaded checkpoint step {man['step']}")

    variants, req_tag = None, "default"
    if args.quantize:
        if args.replicas > 1:
            raise SystemExit("--quantize serves through Engine weight "
                             "variants; not supported with --replicas")
        from repro.core.formats import FormatSet
        from repro.quant import quantize_params
        qset = FormatSet.parse(args.quantize)
        req_tag = qset.key()
        variants = {req_tag: quantize_params(
            params, fset=qset, ratio_high=args.quantize_ratio)}
        print(f"quantized variant {req_tag} "
              f"(ratio_high={args.quantize_ratio})")

    sc = ServeConfig(
        buckets=(tuple(int(b) for b in args.buckets.split(","))
                 if args.buckets else None),
        waste_cap=args.waste_cap,
        max_batch=args.max_batch,
        max_seq=args.max_seq,
        rng_seed=args.seed,
        refill=not args.no_refill,
        prefix_cache=not args.no_prefix_cache,
        chunked_prefill=not args.no_chunked_prefill,
        prefix_pages=args.prefix_pages,
        page_tokens=args.page_tokens,
        warmup=not args.no_warmup,
        replicas=args.replicas,
    )
    if sc.replicas > 1:
        server = Cluster(cfg, params, sc)
        eng0 = server.replicas[0]
        print(f"cluster replicas={sc.replicas} mode={eng0.mode} buckets="
              f"{sorted(k.pad_len for k in eng0.scheduler.buckets)}")
    else:
        server = eng0 = Engine(cfg, params, sc, variants=variants)
        print(f"engine mode={eng0.mode} buckets="
              f"{sorted(k.pad_len for k in eng0.scheduler.buckets)} "
              f"refill={eng0.refill_enabled} "
              f"prefix_cache={eng0.prefix is not None}")
    if sc.warmup:
        rep = server.warmup()
        if sc.replicas > 1:
            traces = {k: v.pop("traces") for k, v in rep.items()}
            print(f"warmup: traces per replica {traces}")
        else:
            print(f"warmup: {rep.pop('traces')} traces; "
                  f"paths={ {k: v['paths'] for k, v in rep.items()} }")
    reqs = [Request(np.array([int(t) % cfg.vocab for t in p.split()],
                             np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature,
                    fset=req_tag,
                    seed=args.request_seed + i)
            for i, p in enumerate(args.prompts)]
    for i, r in enumerate(server.generate(reqs)):
        if r.error:
            print(f"request {i}: prompt={np.asarray(r.prompt).tolist()} "
                  f"REJECTED — {r.error}")
            continue
        where = f" replica={r.replica}" if sc.replicas > 1 else ""
        print(f"request {i}: prompt={np.asarray(r.prompt).tolist()} "
              f"→ out={r.out_tokens}  "
              f"[bucket={r.bucket} padded_to={r.padded_to} "
              f"cold={r.cold}{where} latency={r.latency_s * 1e3:.0f}ms]")
    return cfg, server, reqs


def main():
    args = parse_args()
    import repro

    _, server, reqs = run(args)
    rejected = sum(bool(r.error) for r in reqs)
    st = server.stats()
    if args.replicas > 1:
        print(f"served={st['requests']['served']} over "
              f"{st['healthy']}/{st['replicas']} healthy replicas, "
              f"post_warmup_recompiles={st['post_warmup_recompiles']}")
    else:
        print(f"served={st['requests']['served']} "
              f"microbatches={st['microbatches']['total']} "
              f"(multi={st['microbatches']['multi_request']}) "
              f"hit_rate={st['bucket_hit_rate']:.2f} "
              f"post_warmup_recompiles="
              f"{st['compile']['post_warmup_recompiles']}")
    if args.stats:
        print(json.dumps(st, indent=1, sort_keys=True))
    if args.trace:
        from repro.obs.trace import export_chrome
        repro.configure(obs_trace=None, obs=False)  # flush + close JSONL
        chrome = export_chrome(args.trace)
        print(f"trace: {args.trace} (chrome: {chrome})")
    if rejected:
        raise SystemExit(f"{rejected} request(s) rejected at admission")


if __name__ == "__main__":
    main()
