"""Traffic loop ``closed_rounds``: offline batch inference.  Submit a round
of the traffic's requests to the engine, ``Engine.run()`` until it drains,
and repeat until the window's seconds have passed.  The window ends with
the last round, so the rate counts all the work over all the time."""
from __future__ import annotations

from chipbench import gen


def _requests(spec: dict, round_index: int):
    from repro.serve import Request
    return [Request(prompt, max_new_tokens=new,
                    temperature=spec["traffic"]["temperature"])
            for prompt, new in gen.request_set(
                spec["traffic"], spec["config"]["model"]["vocab_size"],
                spec["seed"], round_index)]


def window(state: dict, seconds: float, span, clock) -> dict:
    engine, spec = state["engine"], state["spec"]
    before = engine.stats()
    done, failed, attempted = [], 0, 0
    rounds = 0
    t0 = clock()
    while clock() - t0 < seconds:
        reqs = _requests(spec, rounds)
        with span("serve.submit"):
            for r in reqs:
                engine.submit(r)
        with span("serve.run"):
            engine.run()
        attempted += len(reqs)
        for r in reqs:
            if r.done and not r.error:
                done.append(r)
            else:
                failed += 1
        rounds += 1
    t1 = clock()
    after = engine.stats()
    state["done"] = done
    return {"t0": t0, "t1": t1, "rounds": rounds, "attempted": attempted,
            "failed": failed,
            "requests": [(len(r.prompt), len(r.out_tokens)) for r in done],
            "generated": sum(len(r.out_tokens) for r in done),
            "tokens_before": before["tokens"],
            "tokens_after": after["tokens"],
            "notes": [f"rounds={rounds} requests={attempted} "
                      f"failed={failed} post_warmup_recompiles="
                      f"{after['compile']['post_warmup_recompiles']} "
                      f"refills={after['microbatches']['refills']} "
                      f"microbatches={after['microbatches']['total']}"]}
