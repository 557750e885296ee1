"""Model builder: init / train-forward / prefill / decode for all 10
assigned architectures.

Layers are grouped into repeating pattern *segments* (configs.base.segments)
and scanned with ``jax.lax.scan``; parameters are stacked along a leading
repeat dim, which keeps the HLO compact (one block body per pattern, not per
layer) and lets XLA overlap each layer's collectives with the next layer's
compute.  Each scanned block body is rematerialized (``jax.checkpoint``) for
training.

All heavy matmuls are MPLinear / MoE*Split — the paper's tile-centric
mixed-precision GEMM is the matmul substrate of every architecture.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.formats import FormatSet
from repro.core.linear import init_mp_linear
from repro.models import common as C
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import xlstm as X
from repro.models.common import ACT_DTYPE


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, mixer: str, ffn: str) -> dict:
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": C.init_rms_norm(cfg.d_model)}
    dims = C.attn_dims(cfg.n_heads, cfg.n_kv_heads, cfg.d_model, cfg.tp,
                       cfg.head_dim, cfg.kv_dup_to_tp)
    fs = FormatSet.from_key(cfg.mp_formats)
    if mixer.startswith("attn"):
        p["attn"] = C.init_attention(km, cfg.d_model, dims, cfg.mp_policy,
                                     cfg.mp_tile, fset=fs)
    elif mixer == "mamba":
        p["mamba"] = M.init_mamba(km, cfg.d_model, cfg.mp_policy,
                                  expand=cfg.mamba_expand,
                                  d_state=cfg.mamba_d_state, tile=cfg.mp_tile)
    elif mixer == "mlstm":
        p["mlstm"] = X.init_mlstm(km, cfg.d_model, cfg.n_heads, cfg.mp_policy,
                                  tile=cfg.mp_tile)
    elif mixer == "slstm":
        p["slstm"] = X.init_slstm(km, cfg.d_model, cfg.n_heads, cfg.mp_policy,
                                  tile=cfg.mp_tile)
    if ffn == "mlp":
        p["norm2"] = C.init_rms_norm(cfg.d_model)
        p["mlp"] = C.init_mlp(kf, cfg.d_model, cfg.d_ff, cfg.mp_policy,
                              cfg.mp_tile, gated=cfg.gated_mlp, fset=fs)
    elif ffn == "moe":
        p["norm2"] = C.init_rms_norm(cfg.d_model)
        p["moe"] = MOE.init_moe(kf, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                cfg.top_k, cfg.mp_policy,
                                n_shared=cfg.n_shared,
                                shared_d_ff=cfg.shared_d_ff or None,
                                tile=cfg.mp_tile, ep=cfg.moe_ep)
    return p


def _apply_layer(params, x, cfg: ArchConfig, mixer: str, ffn: str, *,
                 positions, cache=None, position=None, slot=None,
                 kv_valid=None):
    """Pre-norm residual block.  Returns (x, aux_loss, new_cache)."""
    dims = C.attn_dims(cfg.n_heads, cfg.n_kv_heads, cfg.d_model, cfg.tp,
                       cfg.head_dim, cfg.kv_dup_to_tp)
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    h = C.rms_norm(x, params["norm1"], cfg.norm_eps)
    if mixer.startswith("attn"):
        window = cfg.local_window if mixer == "attn_local" else None
        if cache is None:
            att = C.attention_block(
                params["attn"], h, dims, positions=positions,
                causal=not cfg.encoder_only, window=window,
                rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
        else:
            att, ck, cv = C.decode_attention(
                params["attn"], h, dims, cache["k"], cache["v"],
                position=position, rope_theta=cfg.rope_theta, window=window,
                use_rope=cfg.use_rope, slot=slot, kv_valid=kv_valid)
            new_cache = {"k": ck, "v": cv}
        x = x + att
    elif mixer == "mamba":
        if cache is None:
            x = x + M.mamba_block(params["mamba"], h)
        else:
            out, new_cache = M.mamba_block(params["mamba"], h, state=cache)
            x = x + out
    elif mixer == "mlstm":
        if cache is None:
            x = x + X.mlstm_block(params["mlstm"], h, n_heads=cfg.n_heads)
        else:
            out, new_cache = X.mlstm_block(params["mlstm"], h,
                                           n_heads=cfg.n_heads, state=cache)
            x = x + out
    elif mixer == "slstm":
        if cache is None:
            x = x + X.slstm_block(params["slstm"], h, n_heads=cfg.n_heads)
        else:
            out, new_cache = X.slstm_block(params["slstm"], h,
                                           n_heads=cfg.n_heads, state=cache)
            x = x + out
    if ffn != "none":
        h2 = C.rms_norm(x, params["norm2"], cfg.norm_eps)
        if ffn == "mlp":
            x = x + C.mlp_block(params[ffn], h2)
        else:
            out, aux = MOE.moe_block(
                params["moe"], h2, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, return_aux=True)
            x = x + out
    return x.astype(ACT_DTYPE), aux, new_cache


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg: ArchConfig, mixer: str, batch: int, seq_len: int):
    dims = C.attn_dims(cfg.n_heads, cfg.n_kv_heads, cfg.d_model, cfg.tp,
                       cfg.head_dim, cfg.kv_dup_to_tp)
    if mixer == "attn_full":
        s = seq_len
        return {"k": jnp.zeros((batch, s, dims.n_kv, dims.head_dim),
                               ACT_DTYPE),
                "v": jnp.zeros((batch, s, dims.n_kv, dims.head_dim),
                               ACT_DTYPE)}
    if mixer == "attn_local":
        s = min(seq_len, cfg.local_window)
        return {"k": jnp.zeros((batch, s, dims.n_kv, dims.head_dim),
                               ACT_DTYPE),
                "v": jnp.zeros((batch, s, dims.n_kv, dims.head_dim),
                               ACT_DTYPE)}
    if mixer == "mamba":
        return M.init_mamba_state(batch, cfg.d_model,
                                  expand=cfg.mamba_expand,
                                  d_state=cfg.mamba_d_state)
    if mixer == "mlstm":
        return X.init_mlstm_state(batch, cfg.d_model, cfg.n_heads)
    if mixer == "slstm":
        return X.init_slstm_state(batch, cfg.d_model, cfg.n_heads)
    raise ValueError(mixer)


def init_cache(cfg: ArchConfig, batch: int, seq_len: int):
    """Stacked cache matching the segment schedule."""
    caches = []
    for pattern, repeats in cfg.segments():
        seg = {}
        for pi, (mixer, _) in enumerate(pattern):
            one = _init_layer_cache(cfg, mixer, batch, seq_len)
            seg[f"pos{pi}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape),
                one)
        caches.append(seg)
    return caches


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_model(key, cfg: ArchConfig) -> dict:
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": C.init_embedding(keys[0], cfg.vocab, cfg.d_model),
        "final_norm": C.init_rms_norm(cfg.d_model),
        "lm_head": init_mp_linear(keys[1], cfg.d_model, cfg.vocab,
                                  cfg.mp_policy, split="ksplit",
                                  tile=cfg.mp_tile,
                                  fset=FormatSet.from_key(cfg.mp_formats)),
    }
    if cfg.frontend == "audio":
        params["frontend_proj"] = init_mp_linear(
            keys[2], cfg.frontend_dim, cfg.d_model, cfg.mp_policy,
            split="ksplit", tile=None)
    elif cfg.frontend == "vision":
        params["frontend_proj"] = init_mp_linear(
            keys[2], cfg.frontend_dim, cfg.d_model, cfg.mp_policy,
            split="ksplit", tile=None)
    if cfg.encoder_only:
        params["pos_embed"] = (
            jax.random.normal(keys[3], (65536, cfg.d_model), jnp.float32)
            * 0.02).astype(ACT_DTYPE)

    segs = []
    lkey = keys[-1]
    # data-driven maps differ per layer and cannot stack under scan — the
    # scanned segments fall back to the ratio policy with the same HIGH
    # fraction (DESIGN.md §5); unscanned tails keep the data-driven maps.
    cfg_stack = cfg
    if cfg.mp_policy and cfg.mp_policy.kind in ("norm_topk",
                                                "outlier_aware"):
        cfg_stack = dataclasses.replace(
            cfg, mp_policy=dataclasses.replace(cfg.mp_policy, kind="ratio"))
    for pattern, repeats in cfg.segments():
        layer_cfg = cfg_stack if repeats > 1 else cfg
        stacked = []
        for r in range(repeats):
            row = []
            for pi, (mixer, ffn) in enumerate(pattern):
                lkey, sub = jax.random.split(lkey)
                row.append(_init_layer(sub, layer_cfg, mixer, ffn))
            stacked.append(row)
        # stack across repeats: tree of [repeats, ...] leaves per position.
        # One leaf at a time, dropping the per-layer copies as they are
        # stacked: stacking whole trees holds every layer twice, which at
        # full width is the device-memory peak of init.
        seg = {}
        for pi in range(len(pattern)):
            rows = []
            for r in range(repeats):
                leaves, treedef = jax.tree.flatten(stacked[r][pi])
                stacked[r][pi] = None
                rows.append(leaves)
            out = []
            for li in range(len(rows[0])):
                out.append(jnp.stack([row[li] for row in rows]))
                for row in rows:
                    row[li] = None
            seg[f"pos{pi}"] = jax.tree.unflatten(treedef, out)
        segs.append(seg)
    params["blocks"] = segs
    return params


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ArchConfig, batch: dict):
    """Token/frontend embedding.  Returns (x [B, S, d], positions [B, S])."""
    if cfg.frontend == "audio":
        x = params["frontend_proj"](batch["frames"].astype(ACT_DTYPE))
        x = x.astype(ACT_DTYPE)
    elif cfg.frontend == "vision":
        pe = params["frontend_proj"](
            batch["patch_embeds"].astype(ACT_DTYPE)).astype(ACT_DTYPE)
        te = C.embed(params["embed"], batch["tokens"])
        x = jnp.concatenate([pe, te], axis=1)
    else:
        x = C.embed(params["embed"], batch["tokens"])
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.encoder_only:
        x = x + params["pos_embed"][None, :S]
    return x, positions


def _run_segments(params, cfg: ArchConfig, x, positions, remat: bool):
    """Scan each segment.  Returns (x, total_aux)."""
    total_aux = jnp.zeros((), jnp.float32)
    for seg_idx, (pattern, repeats) in enumerate(cfg.segments()):
        seg_params = params["blocks"][seg_idx]

        def body(x, layer_params, pattern=pattern):
            aux_sum = jnp.zeros((), jnp.float32)
            for pi, (mixer, ffn) in enumerate(pattern):
                x, aux, _ = _apply_layer(layer_params[f"pos{pi}"], x, cfg,
                                         mixer, ffn, positions=positions)
                aux_sum = aux_sum + aux
            return x.astype(ACT_DTYPE), aux_sum

        # grouped remat: scan over groups of g pattern-repeats; each group
        # is one checkpoint region, so the saved residual stack shrinks by
        # g× (405B: 15.75 GB → 2.6 GB at g=6) at no extra recompute beyond
        # the standard one forward.
        g = cfg.remat_group if repeats % max(cfg.remat_group, 1) == 0 else 1

        def group_body(x, group_params, body=body, g=g):
            aux_sum = jnp.zeros((), jnp.float32)
            for i in range(g):
                one = jax.tree.map(lambda a: a[i], group_params)
                x, aux = body(x, one)
                aux_sum = aux_sum + aux
            return x, aux_sum

        if remat and cfg.remat:
            # prevent_cse=False is the scan-safe form (True inserts
            # optimization barriers that leave fp32 copies of the saved
            # residual stack alive — observed +31 GB on the 405B cell)
            group_body = jax.checkpoint(
                group_body, prevent_cse=False,
                policy=jax.checkpoint_policies.nothing_saveable)
        if repeats > 1:
            grouped = jax.tree.map(
                lambda a: a.reshape(repeats // g, g, *a.shape[1:]),
                seg_params)

            def scan_body(carry, group_params, group_body=group_body):
                x, aux = group_body(carry, group_params)
                return x, aux
            x, auxes = jax.lax.scan(scan_body, x, grouped)
            total_aux = total_aux + auxes.sum()
        else:
            # repeats == 1 → g == 1; leaves already carry the [1, ...] dim
            x, aux = group_body(x, seg_params)
            total_aux = total_aux + aux
    return x, total_aux


def forward_train(params, cfg: ArchConfig, batch: dict):
    """Full training forward: batch → (loss, metrics)."""
    x, positions = _embed_inputs(params, cfg, batch)
    x, aux = _run_segments(params, cfg, x, positions, remat=True)
    x = C.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = params["lm_head"](x)
    labels = batch["labels"]
    if cfg.frontend == "vision":
        # labels only cover the text positions
        logits = logits[:, -labels.shape[1]:]
    loss = C.cross_entropy(logits, labels)
    if cfg.n_experts:
        loss = loss + 0.01 * aux
    return loss, {"ce": loss, "aux": aux}


def forward_prefill(params, cfg: ArchConfig, batch: dict):
    """Prefill: run the prompt, return last-position logits.

    (Cache materialization for subsequent decode reuses forward compute in
    serve.engine.)"""
    x, positions = _embed_inputs(params, cfg, batch)
    x, _ = _run_segments(params, cfg, x, positions, remat=False)
    x = C.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return params["lm_head"](x)


def forward_decode(params, cfg: ArchConfig, tokens, caches, position, *,
                   slot=None, kv_valid=None):
    """One-token decode step.  tokens: [B, 1]; caches from init_cache.
    Returns (logits [B, 1, V], new_caches).

    ``position`` is normally a shared scalar.  The serve scheduler's
    right-padded microbatches pass a per-request [B] position vector (true
    token positions for RoPE) together with the cache ``slot`` — a shared
    scalar, or a [B] vector when continuous decode lets each row progress
    independently (retire-and-refill) — and a [B, S_max] ``kv_valid``
    visibility mask; full-attention layers then stay bit-exact with
    unbatched decoding despite padding."""
    x = C.embed(params["embed"], tokens)
    B = x.shape[0]
    if jnp.ndim(position) != 0:
        positions = jnp.reshape(position, (B, 1))
    else:
        positions = jnp.full((B, 1), position)
    if cfg.encoder_only:
        raise ValueError("encoder-only arch has no decode step")
    new_caches = []
    for seg_idx, (pattern, repeats) in enumerate(cfg.segments()):
        seg_params = params["blocks"][seg_idx]
        seg_cache = caches[seg_idx]

        def body(x, inputs, pattern=pattern):
            layer_params, layer_cache = inputs
            new_cache = {}
            for pi, (mixer, ffn) in enumerate(pattern):
                x, _, nc = _apply_layer(
                    layer_params[f"pos{pi}"], x, cfg, mixer, ffn,
                    positions=positions, cache=layer_cache[f"pos{pi}"],
                    position=position, slot=slot, kv_valid=kv_valid)
                new_cache[f"pos{pi}"] = nc
            return x.astype(ACT_DTYPE), new_cache

        if repeats > 1:
            x, nc = jax.lax.scan(body, x, (seg_params, seg_cache))
        else:
            one_p = jax.tree.map(lambda a: a[0], seg_params)
            one_c = jax.tree.map(lambda a: a[0], seg_cache)
            x, nc1 = body(x, (one_p, one_c))
            nc = jax.tree.map(lambda a: a[None], nc1)
        new_caches.append(nc)
    x = C.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return params["lm_head"](x), new_caches
