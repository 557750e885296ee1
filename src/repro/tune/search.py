"""Empirical autotuner: model-pruned candidate enumeration, a proper
measurement harness, and a persistent JSON plan cache.

The search mirrors the paper's per-architecture tuning loop: enumerate the
plans the cost model considers viable on this device, *measure* the top few
(warmup, ``block_until_ready``, median of k), and persist the winner keyed by
``(device_kind, op, M, N, K, tile, ratio_string)``.

Settings (via ``repro.configure(...)``, falling back to env vars — see
:mod:`repro.config` for the precedence contract):

* ``tune_cache`` / ``REPRO_TUNE_CACHE`` — path of the JSON plan cache
  (default ``~/.cache/repro-tune/plans.json``).
* ``tune_cache_only`` / ``REPRO_TUNE_CACHE_ONLY=1`` — never measure (CI
  mode): serve cached plans, fall back to the cost model's best valid
  plan on a miss.
* ``device`` / ``REPRO_TUNE_DEVICE`` — see ``tune.device.detect_device``.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Iterable

import jax

from repro import config
from repro.core.formats import DEFAULT_FORMATS, registry_signatures
from repro.tune.costmodel import (GemmPlan, GemmProblem, PATHS, predict_time,
                                  validate_plan)
from repro.tune.device import DeviceSpec, detect_device

_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache",
                              "repro-tune", "plans.json")

#: persisted plan-cache schema.  v1 had no format-set segment in the keys
#: and no registry stamps; v2 adds both so format-registry changes retire
#: stale plans instead of mis-dispatching.
CACHE_SCHEMA = 2


def cache_path() -> str:
    return str(config.get("tune_cache") or _DEFAULT_CACHE)


def cache_only() -> bool:
    return config.get_bool("tune_cache_only")


def plan_key(dev: DeviceSpec, prob: GemmProblem) -> str:
    return (f"{dev.kind}|{prob.op}|M{prob.m}N{prob.n}K{prob.k}"
            f"|t{prob.tile}|{prob.formats}|{prob.ratio_key()}"
            f"|{prob.struct_key()}")


def _key_formats(key: str) -> list[str]:
    """Format names referenced by a v2 plan key (segment 4)."""
    parts = key.split("|")
    return parts[4].split("+") if len(parts) > 4 else []


def _migrate_v1_key(key: str) -> str:
    """v1 keys predate format sets: every plan was tuned on the default
    set, so the upgrade inserts its segment after the tile."""
    parts = key.split("|")
    return "|".join(parts[:4] + [DEFAULT_FORMATS.key()] + parts[4:])


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

class PlanCache:
    """JSON-persisted plan store with in-memory memoization.

    One instance per path; ``load`` is lazy and the file is re-read only on
    construction (tuning processes are expected to own the file)."""

    def __init__(self, path: str | None = None):
        self.path = path or cache_path()
        self._mem: dict[str, GemmPlan] = {}
        self._meta: dict[str, dict] = {}
        # plans whose formats are not registered *in this process* are never
        # served, but they are preserved verbatim (entry + stamps) across
        # save() so loading before a custom register_format() call cannot
        # erase another process's tuning results from disk
        self._shelved: dict[str, dict] = {}
        self._shelved_stamps: dict[str, str] = {}
        self._loaded = False

    def _ensure_loaded(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        if not os.path.exists(self.path):
            return
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError):
            return
        schema = raw.get("schema", raw.get("version", 1))
        stamps = raw.get("formats", {})
        current = registry_signatures()
        for key, ent in raw.get("plans", {}).items():
            if schema < 2:
                key = _migrate_v1_key(key)
            # targeted invalidation: a plan is served only while every
            # format its key references still has the definition it was
            # tuned against (v1 files carry no stamps — their formats are
            # the unmodified builtins, so the current signature stands in)
            names = _key_formats(key)
            if any(stamps.get(n, current.get(n)) != current[n]
                   for n in names if n in current):
                continue   # format redefined since tuning → genuinely stale
            unknown = [n for n in names if n not in current]
            if unknown:
                # format not registered (yet) in this process: shelve the
                # entry and its stamps so save() round-trips it untouched
                self._shelved[key] = dict(ent)
                for n in unknown:
                    if n in stamps:
                        self._shelved_stamps[n] = stamps[n]
                continue
            self._mem[key] = GemmPlan(path=ent["path"], bm=ent["bm"],
                                      bn=ent["bn"], bk=ent["bk"])
            self._meta[key] = {k: v for k, v in ent.items()
                               if k not in ("path", "bm", "bn", "bk")}

    def get(self, key: str) -> GemmPlan | None:
        self._ensure_loaded()
        return self._mem.get(key)

    def meta(self, key: str) -> dict:
        self._ensure_loaded()
        return dict(self._meta.get(key, {}))

    def put(self, key: str, plan: GemmPlan, *, persist: bool = True,
            **meta) -> None:
        self._ensure_loaded()
        self._mem[key] = plan
        self._meta[key] = dict(meta)
        if persist:
            self.save()

    def save(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        plans = {}
        for key, plan in self._mem.items():
            ent = {"path": plan.path, "bm": plan.bm, "bn": plan.bn,
                   "bk": plan.bk}
            ent.update(self._meta.get(key, {}))
            plans[key] = ent
        plans.update(self._shelved)   # preserve unknown-format plans
        stamps = dict(self._shelved_stamps)
        stamps.update(registry_signatures())
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"schema": CACHE_SCHEMA, "formats": stamps,
                       "plans": plans}, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def save_as(self, path: str) -> "PlanCache":
        """Write this cache's full contents — plans, meta, shelved
        unknown-format entries and their stamps — to another path (the
        hygiene validator's round-trip check).  Returns the new cache."""
        self._ensure_loaded()
        out = PlanCache(path)
        out._loaded = True
        out._mem = dict(self._mem)
        out._meta = {k: dict(v) for k, v in self._meta.items()}
        out._shelved = {k: dict(v) for k, v in self._shelved.items()}
        out._shelved_stamps = dict(self._shelved_stamps)
        out.save()
        return out

    def __len__(self) -> int:
        self._ensure_loaded()
        return len(self._mem)

    def keys(self) -> list[str]:
        self._ensure_loaded()
        return sorted(self._mem)


_default_cache: PlanCache | None = None


def default_cache() -> PlanCache:
    """Process-wide cache bound to the current REPRO_TUNE_CACHE path."""
    global _default_cache
    path = cache_path()
    if _default_cache is None or _default_cache.path != path:
        _default_cache = PlanCache(path)
    return _default_cache


# ---------------------------------------------------------------------------
# Candidate enumeration + measurement
# ---------------------------------------------------------------------------

def _block_sizes(dim: int, tile: int, dev: DeviceSpec) -> list[int]:
    """Divisors of ``dim`` that are tile multiples (and alignment multiples
    on real hardware), largest-first, capped at 512."""
    step = tile if dev.interpret else max(tile, dev.alignment)
    out = [b for b in range(step, min(dim, 512) + 1, step) if dim % b == 0]
    if dim <= 512 and dim % step == 0 and dim not in out:
        out.append(dim)
    return sorted(set(out), reverse=True)[:4] or [dim]


def candidate_plans(prob: GemmProblem, dev: DeviceSpec | None = None,
                    paths: Iterable[str] = PATHS) -> list[GemmPlan]:
    """All valid plans for the problem on this device."""
    dev = dev or detect_device()
    t = prob.tile
    cands: list[GemmPlan] = []
    for path in paths:
        if path != "ksplit_pallas":
            # ref/ksplit_xla ignore blocks; tile/grouped are pinned to the
            # precision-map tile
            cands.append(GemmPlan(path=path, bm=t, bn=t, bk=t))
        else:
            # bk must divide the map tile (class K-extents are tile
            # multiples and the kernel clamps bk per class)
            bks = [b for b in (t, t // 2, t // 4)
                   if b >= 1 and t % b == 0
                   and (dev.interpret or b % dev.alignment == 0)] or [t]
            for bm in _block_sizes(prob.m, t, dev):
                for bn in _block_sizes(prob.n, t, dev):
                    for bk in bks:
                        cands.append(GemmPlan(path=path, bm=bm, bn=bn,
                                              bk=bk))
    return [p for p in cands if not validate_plan(p, prob, dev)]


def rank_plans(cands: list[GemmPlan], prob: GemmProblem,
               dev: DeviceSpec | None = None) -> list[tuple[GemmPlan, dict]]:
    """Model-predicted ranking, best first."""
    dev = dev or detect_device()
    scored = [(p, predict_time(p, prob, dev)) for p in cands]
    return sorted(scored, key=lambda pc: pc[1]["total_s"])


def measure(fn: Callable[[], object], *, warmup: int = 1,
            iters: int = 5) -> float:
    """Median wall-clock seconds of ``fn()`` with device sync.

    ``fn`` must return the jax output (or pytree of outputs); every timed
    call blocks until the result is ready so compile time stays in warmup
    and async dispatch cannot flatter the measurement."""

    def run_once() -> float:
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    for _ in range(max(warmup, 1)):
        run_once()
    times = sorted(run_once() for _ in range(max(iters, 1)))
    return times[len(times) // 2]


def autotune_problem(prob: GemmProblem, run_plan: Callable[[GemmPlan], object],
                     *, dev: DeviceSpec | None = None,
                     paths: Iterable[str] = PATHS,
                     cache: PlanCache | None = None,
                     max_measure: int = 4, warmup: int = 1, iters: int = 5,
                     force: bool = False) -> tuple[GemmPlan, dict]:
    """Pick (and persist) the best plan for ``prob``.

    ``run_plan(plan)`` executes the problem under that plan and returns the
    jax output.  Returns ``(plan, report)`` where the report carries the
    model-pruned candidate list and any measurements taken.
    """
    dev = dev or detect_device()
    cache = cache or default_cache()
    key = plan_key(dev, prob)
    if not force:
        hit = cache.get(key)
        if hit is not None:
            return hit, {"key": key, "source": "cache", **cache.meta(key)}

    cands = candidate_plans(prob, dev, paths)
    if not cands:
        raise ValueError(f"no valid plan for {key} (paths={list(paths)})")
    ranked = rank_plans(cands, prob, dev)
    if cache_only():
        best, pred = ranked[0]
        cache.put(key, best, persist=False, source="model",
                  predicted_us=pred["total_s"] * 1e6)
        return best, {"key": key, "source": "model",
                      "predicted_us": pred["total_s"] * 1e6}

    # no candidate's failure is caught: validate_plan is what keeps
    # unrunnable plans out, so a plan the backend refuses is a bug to see
    rows = []
    for plan, pred in ranked[:max_measure]:
        t = measure(lambda p=plan: run_plan(p), warmup=warmup, iters=iters)
        rows.append({"plan": plan.key(), "measured_us": t * 1e6,
                     "predicted_us": pred["total_s"] * 1e6})
    best_row = min(rows, key=lambda r: r["measured_us"])
    best = next(p for p, _ in ranked if p.key() == best_row["plan"])
    cache.put(key, best, source="measured",
              measured_us=best_row["measured_us"],
              predicted_us=best_row["predicted_us"])
    return best, {"key": key, "source": "measured", "candidates": rows,
                  **best_row}


def autotune(a, b, c=None, *, alpha: float = 1.0, beta: float = 0.0,
             **kw) -> GemmPlan:
    """Two-line-API entry: autotune one MPMatrix GEMM and cache the winner.

    ``from repro.tune import autotune, mp_matmul`` — call ``autotune(A, B)``
    once at setup, then every ``mp_matmul(A, B)`` with the same signature is
    routed through the cached plan.
    """
    from repro.tune import dispatch as D
    a, b, c = D.canonical_operands(a, b, c)
    prob = D.problem_of(a, b, c, alpha=alpha, beta=beta)
    plan, _ = autotune_problem(
        prob, lambda p: D.execute_plan(p, a, b, c, alpha=alpha, beta=beta),
        **kw)
    return plan
