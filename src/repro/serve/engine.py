"""Serving engine: shape-bucketed continuous batching with token-level
continuous decode, plan-warmed dispatch, and block-paged prefix-KV reuse.

Requests are admitted into :class:`repro.serve.scheduler.ShapeBucketScheduler`
and drained as fixed-shape microbatches — (bucket batch, padded length,
format-set tag) — so the steady state re-uses pre-compiled executables and
pre-resolved GEMM plans (``tune.resolve_plans_for_buckets``) and never
recompiles or re-plans.  Four mechanisms make batching *pay*:

* **On-device sampling.**  The jitted prefill/decode steps end in a fused
  greedy/categorical sampler (per-request PRNG streams via
  ``jax.random.fold_in``; filler rows never consume a real request's
  draws), so multi-step decode runs dispatch-async with no device→host
  logit round-trip per token.  The host only syncs when a request
  retires — to read its tokens out and stamp its latency.
* **Slot retire-and-refill.**  A request that reaches ``max_new_tokens``
  retires *mid-decode*: its tokens are materialized, its latency stamped
  at that step (not at microbatch end), and the next pending request for
  the same bucket is pulled into the freed row — its prefill chunked into
  the decode stream as a call at the microbatch width — so finished
  requests never squat in their slots while neighbours keep decoding.
* **Block-paged prefix reuse.**  Each bucket has a prefix point
  ``P = pad_len // 2`` aligned down to the KV page size; KV for positions
  ``0..P-1`` is cached as ref-counted fixed-size *pages* keyed by a
  digest chain over the prefix tokens (:mod:`repro.serve.kv_pages`).
  Pages are shared across buckets (and chunked long-prompt prefills)
  within the engine: when every real row of a microbatch (or a refill)
  covers its chain, the pages are scattered in and only the suffix is
  prefilled.  In-flight rows pin their pages through per-row block
  tables, released at retirement — LRU eviction can never free KV a live
  row still references.
* **Chunked long-prompt prefill.**  Prompts longer than every configured
  bucket no longer force a cold exact-length compile: they round up to a
  multiple of the largest bucket width ``C`` and prefill chunk-by-chunk
  through ONE pre-warmed ``[B, C]`` executable with a *traced* position
  offset, then decode through the shared traced-pad-length decode step —
  zero recompiles at any admissible prompt length.  Leading whole chunks
  whose page chains are cached are skipped (paged reuse at chunk scale).

``Engine.stats()`` exposes the counters CI and the serve-throughput
benchmark assert on (bucket hits/misses, post-warmup recompiles,
microbatch occupancy, refills, prefix-cache hit rate, page-pool
residency, per-request latency).

Exactness: microbatches are *right*-padded, so under causal attention a
request's real tokens never attend padding; decode threads per-request
positions (RoPE), per-row cache slots, and a KV visibility mask through
``forward_decode``; generated tokens' KV is written over the padding, at
each row's true position, so attention reduces over the same cache layout
as unpadded decoding.  Every model executable — prefill, refill (all the
refills of one retirement pass share one call), decode and the reference
— runs at the one microbatch width ``max_batch``: XLA's result for a row
depends on how many rows the matmul has (on CPU and TPU alike), though
not on what the other rows hold.  Full-attention non-MoE families are
therefore bit-exact with one-request-at-a-time serving ("masked" mode,
checked against ``generate_reference``) — including refilled
rows, page-reused prefills, and chunked long-prompt prefills (a cached
page is bit-identical to what a fresh prefill would produce; a chunked
scan sees the same caches, tokens, and positions as a monolithic one).
State-carrying mixers (Mamba/xLSTM), sliding windows, and MoE families
batch equal-length-only ("equal" mode, also exact); they cannot mask
per-row progress out of their state, so refill, paging, and chunking are
masked-mode-only.

Construction: ``Engine(cfg, params, ServeConfig(...))`` is the public
path (see :mod:`repro.serve.config`); the pre-ServeConfig kwargs still
work through a deprecation shim that warns once.  Format-set variants:
``Engine(..., variants={tag: params})`` serves a mixed-format request
stream — each request carries a tag and is bucketed by (shape, tag),
dispatching to that tag's weights.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.obs.metrics import MetricsRegistry
from repro.serve.config import (DEFAULT_PAD_LENS, ServeConfig,
                                config_from_legacy)
from repro.serve.kv_pages import (BlockTable, PagePool, PagedPrefixCache,
                                  page_digests)
from repro.serve.scheduler import (AdmissionError, BucketKey, QueueFullError,
                                   ShapeBucketScheduler)

__all__ = ["DEFAULT_PAD_LENS", "Engine", "Request", "ServeConfig"]


@dataclasses.dataclass(eq=False)
class Request:
    prompt: np.ndarray            # int32 [S]
    max_new_tokens: int = 16
    temperature: float = 0.0      # 0 → greedy
    fset: str = "default"         # format-set tag (weight variant)
    seed: int = 0                 # per-request PRNG stream (temperature>0)
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # --- per-request accounting (filled by the engine) -------------------
    bucket: str = ""              # bucket key that served it
    padded_to: int = 0            # right-padded prompt length
    cold: bool = False            # served through an unwarmed bucket
    latency_s: float = 0.0        # admit → retire wall-clock
    dispatch_paths: tuple = ()    # GEMM paths resolved for its bucket
    error: str = ""               # admission failure (generate() sets it)
    replica: int = -1             # cluster: replica id that served it


@dataclasses.dataclass
class _Row:
    """Host-side state of one microbatch slot under continuous decode."""
    req: Optional[Request]        # None → filler / retired slot
    length: int                   # real prompt length
    emitted: int = 0              # tokens sampled so far (incl. prefill's)
    join: int = 0                 # step index of its first decode token
    first_tok: Optional[int] = None   # refill: token sampled at prefill
    active: bool = False
    cold: bool = False
    table: Optional[BlockTable] = None    # pages pinned by this row


def _sample_tokens(logits, temps, keys, n):
    """Fused on-device sampling for one step.  ``logits`` [B, V]; ``temps``
    [B]; ``keys`` [B, 2] per-request base PRNG keys; ``n`` [B] the index of
    the token being sampled within its request (0 = the prefill token).

    temperature 0 → argmax; temperature>0 → Gumbel-max categorical under
    ``fold_in(key_i, n_i)``, so a request's stream depends only on its own
    (seed, token index) — identical batched, refilled, or unbatched."""
    logits = logits.astype(jnp.float32)
    step_keys = jax.vmap(jax.random.fold_in)(keys, n)
    u = jax.vmap(lambda k, row: jax.random.uniform(k, row.shape))(
        step_keys, logits)
    gumbel = -jnp.log(-jnp.log(jnp.clip(u, 1e-20, 1.0 - 1e-12)))
    safe_t = jnp.where(temps > 0, temps, 1.0)
    stoch = jnp.argmax(logits / safe_t[:, None] + gumbel, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temps > 0, stoch, greedy).astype(jnp.int32)


def _prefill_collect(params, cfg: ArchConfig, tokens, caches):
    """Scan the prompt through the decode step, writing KV caches and
    collecting *every* step's logits ([S, B, V]) so the engine can read
    each request's last real position in a right-padded microbatch.

    Scalar per-step positions are exact here: with right-padding, causal
    attention means a real token at step s only ever attends steps < s of
    its own row, which are all real (padding is a suffix)."""
    B, S = tokens.shape

    def step(carry, s):
        caches = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, s, 1, axis=1)
        logits, caches = T.forward_decode(params, cfg, tok, caches, s)
        return caches, logits[:, 0]

    caches, logits = jax.lax.scan(step, caches, jnp.arange(S))
    return logits, caches


def _prefill_suffix_collect(params, cfg: ArchConfig, tokens, caches, start):
    """Continuation prefill: scan tokens for positions ``start .. start+S-1``
    into caches whose rows already hold the (reused) prefix KV for
    positions ``0 .. start-1``.  Numerically identical to the matching
    span of a full prefill — each step sees the same cache contents,
    token, and position.  ``start`` is a *traced* scalar, so one compiled
    executable serves every chunk offset of a chunked long-prompt
    prefill (and every bucket's suffix point)."""
    B, S = tokens.shape

    def step(carry, s):
        caches = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, s, 1, axis=1)
        logits, caches = T.forward_decode(params, cfg, tok, caches,
                                          start + s)
        return caches, logits[:, 0]

    caches, logits = jax.lax.scan(step, caches, jnp.arange(S))
    return logits, caches


class Engine:
    def __init__(self, cfg: ArchConfig, params,
                 config: Optional[ServeConfig] = None, *,
                 variants: Optional[dict] = None, **legacy):
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either a ServeConfig or legacy keyword "
                    "arguments, not both")
            config = config_from_legacy(legacy)
        config = config or ServeConfig()
        self.config = config
        self.cfg, self.params = cfg, params
        self.max_batch, self.max_seq = config.max_batch, config.max_seq
        self.variants = {"default": params, **(variants or {})}
        # tune-once at setup: resolve a GEMM plan for every mixed-precision
        # layer at the decode batch size, so the jitted decode/prefill
        # traces route through fixed, cached dispatch decisions.
        from repro.tune import dispatch as _tune
        self._tune = _tune
        _tune.warm_registry()
        self.gemm_plans = _tune.tune_linear_params(
            params, m_hint=self.max_batch)
        # distributed SUMMA path (selectable from ArchConfig or ServeConfig):
        # validate it against the single-device reference at this config's
        # tile/policy/format set and warm the distributed plan key.
        self.summa_report = None
        grid = config.summa_grid or cfg.summa_grid
        if grid:
            from repro.core.summa import config_selfcheck
            self.summa_report = config_selfcheck(cfg, grid)

        self.mode = ("masked" if (cfg.block_type == "attn"
                                  and cfg.attn_pattern == "full"
                                  and not cfg.encoder_only
                                  and cfg.n_experts == 0
                                  and cfg.frontend == "none")
                     else "equal")
        # retire-and-refill + paged prefix reuse + chunked prefill need
        # per-row cache progress and snapshot-able KV blocks —
        # full-attention masked mode only
        self.refill_enabled = config.refill and self.mode == "masked"
        if config.prefix_cache and self.mode == "masked":
            self.pool = PagePool(config.page_tokens, config.prefix_pages)
            self.prefix = PagedPrefixCache(self.pool)
        else:
            self.pool = None
            self.prefix = None
        sched_cfg = config.scheduler_config(cfg.serve_buckets)
        # drop configured buckets that cannot decode even one token within
        # the KV cache (pad_len + 1 > max_seq) instead of crashing warmup
        fitting = tuple(p for p in sched_cfg.pad_lens
                        if p + 1 <= self.max_seq)
        if not fitting:
            raise ValueError(
                f"no serve bucket fits max_seq={self.max_seq} "
                f"(pad_lens={sched_cfg.pad_lens})")
        if fitting != sched_cfg.pad_lens:
            sched_cfg = dataclasses.replace(sched_cfg, pad_lens=fitting)
        # chunked long-prompt prefill: prompts longer than every configured
        # bucket round up to a multiple of the largest bucket width and
        # prefill through the pre-warmed [B, C] chunk executable
        self._max_cfg_pad = max(fitting)
        self._chunk = (self._max_cfg_pad
                       if config.chunked_prefill and self.mode == "masked"
                       else 0)
        self._chunk_warmed = False
        # per-engine metrics registry (shared with the scheduler) so two
        # engines in one process never clobber each other's counters
        self.metrics = MetricsRegistry()
        # prompts longer than every bucket are still admissible up to the
        # KV-cache bound — chunked when possible, exact-length cold else
        self.scheduler = ShapeBucketScheduler(
            sched_cfg, fsets=tuple(self.variants), mode=self.mode,
            max_prompt=self.max_seq - 1, metrics=self.metrics)

        # --- compile counters (incremented at jit *trace* time only) -----
        self._warmup_active = False
        self._ref_active = False
        self._warmed_once = False

        def note():
            m = self.metrics
            if self._warmup_active:
                m.counter("serve.traces", kind="warmup").inc()
            elif self._ref_active:
                m.counter("serve.traces", kind="reference").inc()
            else:
                m.counter("serve.traces", kind="steady").inc()
                if self._warmed_once:
                    m.counter("serve.post_warmup_recompiles").inc()

        def prefill_fn(p, toks, caches, lengths, temps, keys):
            # gather each request's last-real-position logits and sample
            # its first token on device — only [B] int32 ever crosses to
            # host, and only at retirement
            note()
            all_logits, caches = _prefill_collect(p, cfg, toks, caches)
            last = all_logits[lengths - 1, jnp.arange(toks.shape[0])]
            tok0 = _sample_tokens(last, temps, keys,
                                  jnp.zeros_like(lengths))
            return tok0, caches

        def prefill_sfx_fn(p, toks, caches, lengths, temps, keys, start):
            # continuation prefill at traced offset ``start``: caches
            # already hold positions 0..start-1 (reused pages or earlier
            # chunks).  The sampled token is only meaningful when a row's
            # last real position falls inside this span — mid-chunk calls
            # discard it (the clamped gather reads garbage, harmlessly)
            note()
            logits, caches = _prefill_suffix_collect(p, cfg, toks, caches,
                                                     start)
            last = logits[lengths - 1 - start, jnp.arange(toks.shape[0])]
            tok0 = _sample_tokens(last, temps, keys,
                                  jnp.zeros_like(lengths))
            return tok0, caches

        def decode_cont_fn(p, tok, caches, lengths, slots, active, temps,
                           keys, pad_len):
            # token-level continuous decode: every row carries its own
            # step counter (retire-and-refill) and PRNG stream; positions,
            # visibility mask, sampling AND the counter advance all derive
            # on device, so the steady-state loop feeds (tok, caches, slots)
            # straight back with zero per-step host->device transfers.
            # ``pad_len`` is traced: ONE executable per batch width serves
            # every bucket length, configured or chunked-dynamic.
            # Each row writes its KV at its true position, over the
            # prompt's right padding, so attention reduces over the same
            # cache layout as unpadded decoding (reduction order follows
            # the layout; a gap would change the rounding)
            note()
            positions = lengths + slots - pad_len
            kv_valid = (jnp.arange(self.max_seq)[None, :]
                        <= positions[:, None])
            logits, caches = T.forward_decode(p, cfg, tok, caches,
                                              positions, slot=positions,
                                              kv_valid=kv_valid)
            n = slots - pad_len + 1
            nxt = _sample_tokens(logits[:, 0], temps, keys, n)
            return nxt, caches, slots + active

        def decode_sample_fn(p, tok, caches, position, temps, keys, n):
            # shared-scalar-position decode + sampling: equal mode and the
            # reference
            note()
            logits, caches = T.forward_decode(p, cfg, tok, caches, position)
            nxt = _sample_tokens(logits[:, 0], temps, keys, n)
            return nxt, caches

        self._prefill = jax.jit(prefill_fn)
        self._prefill_sfx = jax.jit(prefill_sfx_fn)
        self._decode_cont = jax.jit(decode_cont_fn)
        self._decode_sample = jax.jit(decode_sample_fn)

        # KV data movement helpers (no model graph → not trace-counted):
        # slice one page out of a cache row / scatter a page into a row /
        # copy the masked rows of a refill cache into the batch cache
        def extract_page_fn(caches, row, start, width):
            def one(c):
                r = jax.lax.dynamic_slice_in_dim(c, row, 1, axis=1)
                return jax.lax.dynamic_slice_in_dim(r, start, width, axis=2)
            return jax.tree.map(one, caches)

        def scatter_page_fn(caches, page, row, start):
            def one(c, s):
                at = ((jnp.int32(0), row, start)
                      + (jnp.int32(0),) * (c.ndim - 3))
                return jax.lax.dynamic_update_slice(
                    c, s.astype(c.dtype), at)
            return jax.tree.map(one, caches, page)

        def copy_rows_fn(caches, src, mask):
            def one(c, s):
                m = mask.reshape((1, -1) + (1,) * (c.ndim - 2))
                return jnp.where(m, s.astype(c.dtype), c)
            return jax.tree.map(one, caches, src)

        self._extract_page = jax.jit(extract_page_fn, static_argnums=(3,))
        self._scatter_page = jax.jit(scatter_page_fn)
        self._copy_rows = jax.jit(copy_rows_fn)
        self._base_key = jax.random.PRNGKey(config.rng_seed)

    def _req_key(self, req: Request) -> np.ndarray:
        """Per-request base PRNG key — a fold of the engine seed and the
        request's ``seed``, so batched/refilled/unbatched serving all draw
        the same stream for the same request (and any replica of a
        same-seeded cluster draws identically)."""
        return np.asarray(jax.random.fold_in(self._base_key,
                                             int(req.seed)))

    def _prefix_len(self, pad_len: int) -> int:
        """Reusable-prefix point of a bucket: ``pad_len // 2`` aligned
        down to whole KV pages (0 → prefix reuse off for this bucket)."""
        if self.prefix is None:
            return 0
        pt = self.pool.page_tokens
        return (pad_len // 2) // pt * pt

    def _is_chunked(self, pad_len: int) -> bool:
        """Buckets wider than every configured pad serve through chunked
        prefill when their width is a whole number of chunks."""
        return bool(self._chunk) and pad_len > self._max_cfg_pad \
            and pad_len % self._chunk == 0

    # ------------------------------------------------------------------
    # warmup: pre-resolve tune plans + pre-compile every configured bucket
    # ------------------------------------------------------------------

    def warmup(self, keys=None) -> dict:
        """Pre-resolve GEMM plans and pre-compile the prefill/decode
        executables for every configured bucket (or the given keys), plus
        the chunk executables that serve arbitrarily long prompts, so
        steady-state serving never recompiles.  Returns a report."""
        keys = list(keys) if keys is not None else [
            k for k, b in self.scheduler.buckets.items() if b.configured]
        plan_table = self._tune.resolve_plans_for_buckets(
            self.variants,
            [(k.fset, self.scheduler.cfg.max_batch, k.pad_len)
             for k in keys])
        report = {}
        self._warmup_active = True
        try:
            for key in keys:
                bucket = self.scheduler.buckets[key]
                if bucket.warmed:
                    continue
                if key.pad_len + 1 > self.max_seq:
                    raise AdmissionError(
                        f"bucket {key} does not fit max_seq {self.max_seq}")
                with obs.span("serve.warmup", "serve", bucket=str(key),
                              batch=bucket.batch):
                    self._compile_bucket(key, bucket.batch)
                bucket.warmed = True
                plans = plan_table.get((key.fset, bucket.batch), {})
                bucket.paths = tuple({p.path for p in plans.values()})
                report[str(key)] = {"paths": sorted(bucket.paths)}
            if self._chunk and keys:
                for fset in sorted({k.fset for k in keys}):
                    with obs.span("serve.warmup", "serve",
                                  bucket=f"chunk{self._chunk}/{fset}",
                                  batch=self.scheduler.cfg.max_batch):
                        self._compile_chunk(fset)
                self._chunk_warmed = True
        finally:
            self._warmup_active = False
            self._warmed_once = True
        # warm the per-request key fold (threefry compiles on first use —
        # without this the first admitted request pays it)
        jax.block_until_ready(jax.random.fold_in(self._base_key, 0))
        report["traces"] = int(self.metrics.value("serve.traces",
                                                  kind="warmup"))
        return report

    def _compile_bucket(self, key: BucketKey, batch: int) -> None:
        """Trace+compile every executable the bucket can dispatch in the
        steady state on dummy data (jit caches all of them): full prefill,
        suffix prefill (page reuse) and the continuous decode step.
        Refills run these same executables."""
        params = self.variants[key.fset]
        S = key.pad_len
        toks = jnp.zeros((batch, S), jnp.int32)
        lengths = jnp.full((batch,), S, jnp.int32)
        temps = jnp.zeros((batch,), jnp.float32)
        kvec = jnp.tile(self._base_key[None], (batch, 1))
        caches = T.init_cache(self.cfg, batch, self.max_seq)
        tok0, caches = self._prefill(params, toks, caches, lengths,
                                     temps, kvec)
        if self.mode == "masked":
            P = self._prefix_len(S)
            if P:
                pt = self.pool.page_tokens
                page = self._extract_page(caches, jnp.int32(0),
                                          jnp.int32(0), pt)
                caches = self._scatter_page(caches, page, jnp.int32(0),
                                            jnp.int32(0))
                tok0, caches = self._prefill_sfx(
                    params, toks[:, P:], caches, lengths, temps, kvec,
                    jnp.int32(P))
            if self.refill_enabled:
                caches = self._copy_rows(caches, caches,
                                         jnp.zeros((batch,), bool))
            slots = jnp.full((batch,), S, jnp.int32)
            active = jnp.ones((batch,), jnp.int32)
            out = self._decode_cont(params, tok0[:, None], caches, lengths,
                                    slots, active, temps, kvec,
                                    jnp.int32(S))
        else:
            out = self._decode_sample(params, tok0[:, None], caches,
                                      jnp.int32(S), temps, kvec,
                                      jnp.ones((batch,), jnp.int32))
        jax.block_until_ready(out[0])

    def _compile_chunk(self, fset: str) -> None:
        """Compile the ``[B, C]`` chunk-prefill executable.  The traced
        start offset means it covers every chunk of every long bucket and
        every refill; the traced-pad decode step compiled by
        ``_compile_bucket`` already covers long-bucket decoding."""
        params = self.variants[fset]
        C = self._chunk
        B = self.scheduler.cfg.max_batch
        toks = jnp.zeros((B, C), jnp.int32)
        lengths = jnp.full((B,), C, jnp.int32)
        temps = jnp.zeros((B,), jnp.float32)
        kvec = jnp.tile(self._base_key[None], (B, 1))
        caches = T.init_cache(self.cfg, B, self.max_seq)
        tok, _ = self._prefill_sfx(params, toks, caches, lengths, temps,
                                   kvec, jnp.int32(0))
        jax.block_until_ready(tok)

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> BucketKey:
        """Admit one request (raises AdmissionError / QueueFullError).

        KV head-room: the last cache slot a microbatch writes is
        ``pad_len + max_new − 2`` (the final sampled token is never written
        back), and every co-batched request passed this same check, so the
        per-request bound ``pad_len + max_new − 1 ≤ max_seq`` covers the
        batch maximum too.

        Prompts longer than every configured bucket round up to a chunk
        multiple and serve through a chunked-prefill bucket (pre-warmed
        executables — no recompile).  A request whose padded/chunked
        length breaks the KV bound but whose exact length fits falls back
        to an exact-length (cold) bucket instead of being rejected.

        All checks run against a *prospective* (commit=False) bucket key,
        so a rejected request never creates/evicts buckets or skews the
        redirect counters as a side effect."""
        L = len(req.prompt)
        if self.scheduler.pending() >= self.scheduler.cfg.max_queue:
            self.scheduler.reject()
            raise QueueFullError(
                f"admission queue full "
                f"({self.scheduler.cfg.max_queue} pending)")
        try:
            key = self.scheduler.bucket_for(L, req.fset, commit=False)
        except AdmissionError:
            self.scheduler.reject()
            raise
        use_exact = use_chunk = False
        if self._chunk and L > self._max_cfg_pad:
            pad = -(-L // self._chunk) * self._chunk
            if pad + req.max_new_tokens - 1 <= self.max_seq:
                use_chunk = True
                chunk_pad = pad
        if not use_chunk \
                and key.pad_len + req.max_new_tokens - 1 > self.max_seq:
            if L + req.max_new_tokens - 1 <= self.max_seq:
                use_exact = True
            else:
                self.scheduler.reject()
                raise AdmissionError(
                    f"prompt {L} (padded {key.pad_len}) + "
                    f"{req.max_new_tokens} new tokens exceeds max_seq "
                    f"{self.max_seq}")
        # definitely admissible — commit the bucket choice
        if use_chunk:
            key = self.scheduler.exact_bucket(chunk_pad, req.fset)
            bucket = self.scheduler.buckets[key]
            if self._chunk_warmed and not bucket.warmed:
                # served entirely through pre-warmed chunk executables
                bucket.warmed = True
        elif use_exact:
            key = self.scheduler.exact_bucket(L, req.fset)
        else:
            key = self.scheduler.bucket_for(L, req.fset)
        req._t_admit = time.perf_counter()
        return self.scheduler.admit(req, L, req.fset, key=key)

    def generate(self, requests: list[Request]) -> list[Request]:
        """Admit a list of requests and drain the queue to completion.

        Inadmissible requests never strand the admissible ones: they are
        returned with ``error`` set (and ``done`` False) while the rest of
        the stream is served; callers needing the exception use
        :meth:`submit` directly."""
        for r in requests:
            try:
                self.submit(r)
            except (AdmissionError, QueueFullError) as e:
                r.error = f"{type(e).__name__}: {e}"
        self.run()
        return requests

    def run(self) -> None:
        """Drain the admission queue, one microbatch at a time (each
        masked-mode microbatch keeps refilling from its bucket's queue
        until the whole stream for that bucket drains)."""
        while True:
            with obs.span("serve.schedule", "serve"):
                mb = self.scheduler.next_microbatch()
            if mb is None:
                return
            bucket, reqs = mb
            if reqs:
                if self.mode == "masked":
                    self._serve_microbatch_masked(bucket, reqs)
                else:
                    self._serve_microbatch_equal(bucket, reqs)

    # -- retirement bookkeeping (shared by both modes) --------------------

    def _finalize(self, row: _Row, i: int, bucket, hist, S: int,
                  t0: float) -> None:
        """Retire the request in slot ``i``: collect its tokens from the
        materialized step history, stamp latency *now* (the step at which
        it finished, not the microbatch end), release the KV pages the row
        pinned, and record accounting."""
        r = row.req
        m = self.metrics
        n_new = r.max_new_tokens
        toks_out = [] if row.first_tok is None else [row.first_tok]
        need = n_new - len(toks_out)
        toks_out += [int(hist[j][i]) for j in range(row.join,
                                                    row.join + need)]
        r.out_tokens = toks_out
        r.done = True
        r.bucket = str(bucket.key)
        r.padded_to = S
        r.cold = row.cold
        r.dispatch_paths = bucket.paths
        r.latency_s = time.perf_counter() - getattr(r, "_t_admit", t0)
        if row.table is not None:
            row.table.release()
            row.table = None
        row.req, row.active = None, False
        bucket.served += 1
        bucket.real_tokens += row.length
        m.counter("serve.requests_served").inc()
        m.counter("serve.tokens_generated").inc(n_new)
        m.histogram("serve.request.latency_s").observe(r.latency_s)
        if obs.is_enabled():
            obs.event("serve.retire", "serve", bucket=str(bucket.key),
                      slot=i, new_tokens=n_new, cold=r.cold,
                      latency_s=round(r.latency_s, 6))

    @staticmethod
    def _drain(devbuf: list, hist: list) -> None:
        """Materialize pending device token vectors into the host history
        (the engine's only device→host sync, paid at retirement)."""
        if devbuf:
            with obs.span("serve.drain", "serve", steps=len(devbuf)):
                hist.extend(np.stack([np.asarray(t) for t in devbuf]))
            devbuf.clear()

    @staticmethod
    def _dev(a: np.ndarray) -> jax.Array:
        """Snapshot a mutable host staging buffer onto the device.

        ``jnp.asarray`` may alias suitably-aligned numpy memory zero-copy
        on the CPU backend, and dispatch is async — so converting a buffer
        the host later mutates (slot advance, retire-and-refill rewriting
        a row of toks/lengths/temps/keys) would let an in-flight step read
        the *post-mutation* values.  Every conversion therefore copies;
        whether the copy is then aliased is irrelevant, it is immutable."""
        return jnp.asarray(np.array(a))

    # -- masked mode: token-level continuous decode -----------------------

    def _serve_microbatch_masked(self, bucket, reqs: list[Request]) -> None:
        key = bucket.key
        params = self.variants[key.fset]
        S = key.pad_len
        B = bucket.batch
        n_real = len(reqs)
        P = self._prefix_len(S)
        was_warm = bucket.warmed
        if was_warm:
            bucket.hits += 1
        else:
            bucket.misses += 1
        m = self.metrics
        t0 = time.perf_counter()

        # fixed-shape microbatch: right-pad prompts to the bucket length
        # and duplicate the last request into unused slots (fillers decode
        # greedily under a null PRNG key — outputs discarded, and they
        # never touch a real request's stream)
        toks = np.zeros((B, S), np.int32)
        lengths = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        keys = np.zeros((B, 2), np.uint32)
        rows: list[_Row] = []
        for i in range(B):
            r = reqs[min(i, n_real - 1)]
            toks[i, : len(r.prompt)] = r.prompt
            lengths[i] = len(r.prompt)
            if i < n_real:
                temps[i] = r.temperature
                keys[i] = self._req_key(r)
                rows.append(_Row(req=r, length=int(lengths[i]),
                                 emitted=1, join=0, active=True,
                                 cold=not was_warm))
            else:
                rows.append(_Row(req=None, length=int(lengths[i])))
        slots = np.full((B,), S, np.int32)
        hist: list[np.ndarray] = []       # materialized [B] token steps
        devbuf: list = []                 # device [B] steps not yet pulled

        with obs.span("serve.microbatch", "serve", bucket=str(key),
                      n_real=n_real, batch=B, pad_len=S, warm=was_warm):
            caches = T.init_cache(self.cfg, B, self.max_seq)
            cur, caches = self._prefill_rows(
                bucket, params, caches, toks, lengths, temps, keys,
                range(n_real), P, rows)
            devbuf.append(cur)

            def process_retirements() -> bool:
                nonlocal cur, caches
                changed = False
                while True:
                    ret = [i for i in range(B)
                           if rows[i].active and rows[i].req is not None
                           and rows[i].emitted
                           >= rows[i].req.max_new_tokens]
                    if not ret:
                        return changed
                    changed = True
                    with obs.span("serve.retire_pass", "serve",
                                  retired=len(ret)):
                        self._drain(devbuf, hist)
                        refills = []
                        for i in ret:
                            self._finalize(rows[i], i, bucket, hist, S, t0)
                            nxt = (self.scheduler.pop_pending(key)
                                   if self.refill_enabled else None)
                            if nxt is not None:
                                refills.append((i, nxt))
                        if refills:
                            cur, caches = self._refill_rows(
                                bucket, params, caches, cur, refills, toks,
                                lengths, temps, keys, slots, rows, hist, P)

            def sync_decode_state():
                # snapshot the host staging buffers onto the device; paid
                # only at microbatch start and after a retire/refill event
                # mutates them — steady-state steps run device-resident
                with obs.span("serve.sync_state", "serve"):
                    return (self._dev(lengths), self._dev(slots),
                            self._dev(np.array(
                                [1 if r.active else 0 for r in rows],
                                np.int32)),
                            self._dev(temps), self._dev(keys))

            with obs.span("serve.decode", "serve", bucket=str(key)):
                process_retirements()
                lengths_d, slots_d, active_d, temps_d, keys_d = \
                    sync_decode_state()
                steps = 0
                while any(row.active for row in rows):
                    cur, caches, slots_d = self._decode_cont(
                        params, cur[:, None], caches, lengths_d, slots_d,
                        active_d, temps_d, keys_d, jnp.int32(S))
                    devbuf.append(cur)
                    steps += 1
                    for i in range(B):
                        if rows[i].active:
                            rows[i].emitted += 1
                            slots[i] += 1
                    if process_retirements():
                        lengths_d, slots_d, active_d, temps_d, keys_d = \
                            sync_decode_state()
                m.counter("serve.decode_steps").inc(steps)
        bucket.warmed = True        # compiled now — next time is a hit
        m.histogram("serve.microbatch.size").observe(n_real)
        if n_real > 1:
            m.counter("serve.microbatch.multi").inc()

    # -- prefill paths (full / page-reused suffix / chunked) --------------

    def _row_digests(self, fset: str, toks, lengths, i: int, P: int):
        """Page-digest chain for row ``i``'s prefix span (None → row has
        no reusable prefix: too short or paging disabled)."""
        if not P or lengths[i] <= P:
            return None
        return page_digests(fset, toks[i, :P], self.pool.page_tokens)

    def _scatter_chain(self, caches, digests, row: int) -> tuple:
        """Commit a cached chain into ``row``: LRU-refresh + scatter each
        page and pin them all in a fresh block table.  Returns
        ``(caches, table)``."""
        pt = self.pool.page_tokens
        pids = self.prefix.lookup(digests)
        self.prefix.hits += 1
        table = BlockTable(self.pool)
        for j, pid in enumerate(pids):
            caches = self._scatter_page(caches, self.pool.payload(pid),
                                        jnp.int32(row), jnp.int32(j * pt))
            table.append_page(pid)
        return caches, table

    def _insert_chain_from_row(self, caches, digests, row: int) -> None:
        """Feed the cache: extract the pages of ``row``'s freshly computed
        prefix span and insert the chain (skipping already-resident
        pages)."""
        pt = self.pool.page_tokens
        self.prefix.insert_chain(
            digests,
            lambda j: self._extract_page(caches, jnp.int32(row),
                                         jnp.int32(j * pt), pt))

    def _prefill_rows(self, bucket, params, caches, toks, lengths, temps,
                      keys, idx, P: int, rows: list, refill: bool = False):
        """Prefill of the real rows ``idx`` (a new microbatch, or the
        refills of one retirement pass): chunked for long buckets;
        otherwise suffix-only when every real row covers its page chain,
        else full (which then feeds the page cache)."""
        key = bucket.key
        B, S = toks.shape
        idx = list(idx)
        if self._is_chunked(S):
            return self._prefill_chunked(bucket, params, caches, toks,
                                         lengths, temps, keys, idx, rows,
                                         refill)
        digs = [self._row_digests(key.fset, toks, lengths, i, P)
                for i in idx]
        use_sfx = bool(digs) and all(
            d is not None and self.prefix.covers(d) for d in digs)
        lengths_j, temps_j, keys_j = (self._dev(lengths),
                                      self._dev(temps), self._dev(keys))
        with obs.span("serve.prefill", "serve", bucket=str(key), batch=B,
                      pad_len=S, prefix_reuse=use_sfx, refill=refill):
            if use_sfx:
                for i, d in zip(idx, digs):
                    caches, rows[i].table = self._scatter_chain(
                        caches, d, i)
                cur, caches = self._prefill_sfx(
                    params, self._dev(toks[:, P:]), caches, lengths_j,
                    temps_j, keys_j, jnp.int32(P))
                self.metrics.counter("serve.prefix.reused_prefills").inc()
                bucket.padded_tokens += int(
                    B * (S - P) - np.maximum(lengths[idx] - P, 0).sum())
            else:
                # mixed hit/miss wave: rows whose chain IS cached still
                # count per-row hits (mirroring the per-row lookups of the
                # suffix path — the reuse just can't be exploited, since
                # suffix-only prefill is all-rows-or-none), and each
                # distinct uncovered chain counts ONE miss, matching the
                # single insert it triggers below
                missed: dict[tuple, int] = {}
                for i, d in zip(idx, digs):
                    if d is None:
                        continue
                    if self.prefix.covers(d):
                        self.prefix.hits += 1
                    else:
                        missed.setdefault(tuple(d), i)
                self.prefix.misses += len(missed)
                cur, caches = self._prefill(params, self._dev(toks),
                                            caches, lengths_j, temps_j,
                                            keys_j)
                bucket.padded_tokens += int(B * S - lengths[idx].sum())
                for d, i in missed.items():
                    self._insert_chain_from_row(caches, list(d), i)
        return cur, caches

    def _prefill_chunked(self, bucket, params, caches, toks, lengths,
                         temps, keys, idx: list, rows: list,
                         refill: bool):
        """Long-prompt prefill through the pre-warmed ``[B, C]`` chunk
        executable with a traced position offset.  Every row of a chunked
        bucket has its last real token in the final chunk (bucketing
        rounds L up to the next chunk multiple), so only the final call's
        sampled token is kept.  Leading whole chunks covered by every
        row's cached page chain are skipped — paged reuse at chunk scale;
        an uncovered wave feeds its full-page chains back to the cache."""
        key = bucket.key
        B, S = toks.shape
        C = self._chunk
        n_chunks = S // C
        pt = self.pool.page_tokens if self.prefix is not None else 0
        paged = bool(pt) and C % pt == 0
        digs = []
        n_skip = 0
        if paged:
            # full-page chains over each prompt minus its last token (the
            # first sampled token must come from a fresh computation)
            digs = [page_digests(key.fset, toks[i], pt,
                                 limit=int(lengths[i]) - 1)
                    for i in idx]
            covered = [len(self.prefix.chain(d)) * pt // C for d in digs]
            n_skip = min(min(c, n_chunks - 1) for c in covered)
        lengths_j, temps_j, keys_j = (self._dev(lengths),
                                      self._dev(temps), self._dev(keys))
        with obs.span("serve.prefill", "serve", bucket=str(key), batch=B,
                      pad_len=S, prefix_reuse=n_skip > 0, refill=refill,
                      chunks=n_chunks, chunks_skipped=n_skip):
            missed: dict[tuple, int] = {}
            if n_skip:
                npages = n_skip * C // pt
                for i, d in zip(idx, digs):
                    caches, rows[i].table = self._scatter_chain(
                        caches, d[:npages], i)
                self.metrics.counter("serve.prefix.reused_prefills").inc()
            elif paged:
                for i, d in zip(idx, digs):
                    if not d:
                        continue
                    if self.prefix.covers(d):
                        self.prefix.hits += 1
                    else:
                        missed.setdefault(tuple(d), i)
                self.prefix.misses += len(missed)
            cur = None
            for c in range(n_skip, n_chunks):
                cur, caches = self._prefill_sfx(
                    params, self._dev(toks[:, c * C:(c + 1) * C]), caches,
                    lengths_j, temps_j, keys_j, jnp.int32(c * C))
            self.metrics.counter("serve.chunked_prefills").inc()
            bucket.padded_tokens += int(
                B * (S - n_skip * C)
                - np.maximum(lengths[idx] - n_skip * C, 0).sum())
            for d, i in missed.items():
                self._insert_chain_from_row(caches, list(d), i)
        return cur, caches

    def _refill_rows(self, bucket, params, caches, cur, refills: list,
                     toks, lengths, temps, keys, slots, rows, hist,
                     P: int):
        """Pull pending requests into the slots one retirement pass freed
        (``refills`` = ``[(slot, request)]``): ONE prefill of a fresh
        cache through the microbatch's own executables (page-reused /
        chunked as the bucket demands; the rows not being refilled are
        recomputed and discarded), then copy the refilled rows into the
        batch cache.  Returns the decode input ``cur`` with each refilled
        row seeded by its sampled first token, and the caches."""
        B, S = toks.shape
        idx = [i for i, _ in refills]
        with obs.span("serve.refill", "serve", bucket=str(bucket.key),
                      slots=idx,
                      lengths=[len(nxt.prompt) for _, nxt in refills]):
            for i, nxt in refills:
                L2 = len(nxt.prompt)
                toks[i, :] = 0
                toks[i, :L2] = nxt.prompt
                lengths[i] = L2
                temps[i] = nxt.temperature
                keys[i] = self._req_key(nxt)
                slots[i] = S
                rows[i] = _Row(req=nxt, length=L2, emitted=1,
                               join=len(hist), active=True, cold=False)
            c1 = T.init_cache(self.cfg, B, self.max_seq)
            tk, c1 = self._prefill_rows(bucket, params, c1, toks, lengths,
                                        temps, keys, idx, P, rows,
                                        refill=True)
            mask = np.zeros((B,), bool)
            mask[idx] = True
            caches = self._copy_rows(caches, c1, self._dev(mask))
            tk = np.asarray(tk)
            # seed from the LIVE decode input, not hist[-1]: a substitution
            # made by an earlier iteration of this retirement pass (a
            # refill that itself retired at max_new_tokens == 1) exists
            # only in ``cur``
            cur_np = np.asarray(cur).copy()
            for i in idx:
                rows[i].first_tok = int(tk[i])
                cur_np[i] = tk[i]
            self.metrics.counter("serve.refills").inc(len(idx))
            return self._dev(cur_np), caches

    # -- equal mode: shared-position continuous decode --------------------

    def _serve_microbatch_equal(self, bucket, reqs: list[Request]) -> None:
        """Equal-length batching (state-carrying/windowed/MoE families):
        rows share a scalar position, so no refill or prefix reuse — but
        sampling still runs on device under per-request streams, requests
        still retire (and stamp latency) the step they finish, and the
        loop ends at the last real row's ``max_new``, not the slot max."""
        key = bucket.key
        params = self.variants[key.fset]
        S = key.pad_len
        B = bucket.batch
        n_real = len(reqs)
        was_warm = bucket.warmed
        if was_warm:
            bucket.hits += 1
        else:
            bucket.misses += 1
        m = self.metrics
        t0 = time.perf_counter()
        toks = np.zeros((B, S), np.int32)
        temps = np.zeros((B,), np.float32)
        keys = np.zeros((B, 2), np.uint32)
        rows: list[_Row] = []
        for i in range(B):
            r = reqs[min(i, n_real - 1)]
            toks[i, : len(r.prompt)] = r.prompt
            if i < n_real:
                temps[i] = r.temperature
                keys[i] = self._req_key(r)
                rows.append(_Row(req=r, length=len(r.prompt), emitted=1,
                                 join=0, active=True, cold=not was_warm))
            else:
                rows.append(_Row(req=None, length=len(r.prompt)))
        hist: list[np.ndarray] = []
        devbuf: list = []

        def process_retirements():
            ret = [i for i in range(B)
                   if rows[i].active and rows[i].req is not None
                   and rows[i].emitted >= rows[i].req.max_new_tokens]
            if not ret:
                return
            with obs.span("serve.retire_pass", "serve", retired=len(ret)):
                self._drain(devbuf, hist)
                for i in ret:
                    self._finalize(rows[i], i, bucket, hist, S, t0)

        with obs.span("serve.microbatch", "serve", bucket=str(key),
                      n_real=n_real, batch=B, pad_len=S, warm=was_warm):
            caches = T.init_cache(self.cfg, B, self.max_seq)
            lengths_j = jnp.full((B,), S, jnp.int32)
            temps_j, keys_j = jnp.asarray(temps), jnp.asarray(keys)
            with obs.span("serve.prefill", "serve", bucket=str(key),
                          batch=B, pad_len=S, prefix_reuse=False):
                cur, caches = self._prefill(params, self._dev(toks),
                                            caches, lengths_j, temps_j,
                                            keys_j)
            devbuf.append(cur)
            bucket.padded_tokens += int((B - n_real) * S)
            with obs.span("serve.decode", "serve", bucket=str(key)):
                process_retirements()
                t = 1
                while any(row.active for row in rows):
                    cur, caches = self._decode_sample(
                        params, cur[:, None], caches, jnp.int32(S + t - 1),
                        temps_j, keys_j, jnp.full((B,), t, jnp.int32))
                    devbuf.append(cur)
                    m.counter("serve.decode_steps").inc()
                    for row in rows:
                        if row.active:
                            row.emitted += 1
                    t += 1
                    process_retirements()
        bucket.warmed = True
        m.histogram("serve.microbatch.size").observe(n_real)
        if n_real > 1:
            m.counter("serve.microbatch.multi").inc()

    # ------------------------------------------------------------------
    # one-request-at-a-time reference (ground truth for parity tests)
    # ------------------------------------------------------------------

    def generate_reference(self, requests: list[Request],
                           width: Optional[int] = None) -> list[Request]:
        """Serve requests one at a time with no padding — the semantic
        baseline the scheduler path must match (masked/equal modes are
        bit-exact for greedy AND sampled decoding: the same fused sampler
        runs under the same per-request PRNG stream).  Each request fills
        every row of a ``width``-wide call.  The default, ``max_batch``,
        runs the serving path's matmul shapes (see the module docstring):
        that is the exactness oracle.  ``width=1`` is the plain
        one-request-at-a-time run that throughput benchmarks time as the
        unbatched baseline; its tokens may differ from the oracle's in
        the last bits of a logit.  Compiles are counted under
        ``reference_traces``, not as recompiles of the serving path."""
        self._ref_active = True
        try:
            return self._generate_reference(requests,
                                            width or self.max_batch)
        finally:
            self._ref_active = False

    def _generate_reference(self, requests: list[Request],
                            B: int) -> list[Request]:
        for r in requests:
            params = self.variants[r.fset]
            L = len(r.prompt)
            toks = jnp.asarray(np.tile(np.asarray(r.prompt, np.int32),
                                       (B, 1)))
            caches = T.init_cache(self.cfg, B, self.max_seq)
            temps = jnp.full((B,), float(r.temperature), jnp.float32)
            keys = jnp.asarray(np.tile(self._req_key(r), (B, 1)))
            tok, caches = self._prefill(params, toks, caches,
                                        jnp.full((B,), L, jnp.int32),
                                        temps, keys)
            out = [tok]
            for step in range(1, r.max_new_tokens):
                tok, caches = self._decode_sample(
                    params, tok[:, None], caches, jnp.int32(L + step - 1),
                    temps, keys, jnp.full((B,), step, jnp.int32))
                out.append(tok)
            r.out_tokens = [int(np.asarray(t)[0]) for t in out]
            r.done = True
        return requests

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Counters for benchmarks / CI assertions — a pure view over the
        engine's :class:`MetricsRegistry`, keeping the exact dict shape of
        the pre-registry implementation (tests assert on it)."""
        m = self.metrics
        totals = self.scheduler.totals()   # eviction-proof bucket counters
        hits, misses = totals["hits"], totals["misses"]
        real, padded = totals["real_tokens"], totals["padded_tokens"]
        mb = m.histogram("serve.microbatch.size")
        lat = m.histogram("serve.request.latency_s")
        return {
            "mode": self.mode,
            "requests": {"served": int(m.value("serve.requests_served")),
                         "rejected": self.scheduler.rejected},
            "tokens": {"prompt": real, "padded": padded,
                       "generated": int(m.value("serve.tokens_generated"))},
            "padding_waste": padded / (real + padded) if real + padded
            else 0.0,
            "microbatches": {
                "total": mb.count,
                "multi_request": int(m.value("serve.microbatch.multi")),
                "mean_size": mb.mean,
                "max_size": int(mb.max) if mb.count else 0,
                "refills": int(m.value("serve.refills")),
            },
            "bucket_hits": hits, "bucket_misses": misses,
            "bucket_hit_rate": hits / (hits + misses) if hits + misses
            else 0.0,
            "compile": {
                "warmup_traces": int(m.value("serve.traces",
                                             kind="warmup")),
                "steady_traces": int(m.value("serve.traces",
                                             kind="steady")),
                "reference_traces": int(m.value("serve.traces",
                                                kind="reference")),
                "post_warmup_recompiles": int(
                    m.value("serve.post_warmup_recompiles")),
            },
            "decode_steps": int(m.value("serve.decode_steps")),
            "chunked_prefills": int(m.value("serve.chunked_prefills")),
            "latency_s": {
                "mean": lat.mean,
                "max": lat.max if lat.count else 0.0,
            },
            "prefix_cache": (self.prefix.stats() if self.prefix is not None
                             else None),
            "kv_pages": (self.pool.stats() if self.pool is not None
                         else None),
            "scheduler": self.scheduler.stats(),
        }
