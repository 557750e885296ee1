"""Architecture configuration schema + registry.

One ``ArchConfig`` per assigned architecture (exact public-literature specs)
plus reduced smoke variants.  ``layer_kinds`` expands the repeating block
pattern; the model builder scans over whole pattern periods.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

from repro.core.precision import Policy

REGISTRY: dict[str, "ArchConfig"] = {}

#: default model-parallel width (sized for a 16×16 pod; the serve
#: launcher overrides it to 1)
DEFAULT_TP = 16


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    # --- attention pattern ---------------------------------------------
    attn_pattern: str = "full"   # full | local_global
    local_window: int = 1024
    global_every: int = 6        # 5 local : 1 global
    rope_theta: float = 500000.0
    use_rope: bool = True
    encoder_only: bool = False
    # --- modality frontend (stub per spec: precomputed embeddings) ------
    frontend: str = "none"       # none | audio | vision
    frontend_dim: int = 0        # raw embedding dim arriving from the stub
    n_patches: int = 0           # vision tokens in the prompt
    # --- MoE --------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    shared_d_ff: int = 0
    moe_every: int = 1       # apply MoE at layers i % moe_every == moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    # --- hybrid / ssm ------------------------------------------------------
    block_type: str = "attn"     # attn | mamba_hybrid | xlstm
    attn_every: int = 0          # hybrid: layer i % attn_every == attn_offset
    attn_offset: int = 0
    slstm_every: int = 8         # xlstm: i % slstm_every == 0 → sLSTM
    mamba_d_state: int = 16
    mamba_expand: int = 2
    # --- mixed-precision policy (the paper's technique) ---------------------
    mp_policy: Policy = Policy(kind="ratio", ratio_high=0.5)
    mp_tile: int = 128
    #: which registered precision formats play the D/S/Q roles
    #: (``repro.core.formats`` FormatSet key, e.g. "fp8_e5m2+fp16+fp32").
    #: Governs the dense stack (attention / MLP / lm_head); the batched
    #: MoE and Mamba split weights currently stay on the default set.
    mp_formats: str = "fp8_e4m3+bf16+fp32"
    #: optional (P, Q) device grid for the distributed SUMMA path: when set,
    #: the train launcher / serve engine run the launch-time SUMMA
    #: self-check at this config's tile/policy/format set and warm the
    #: distributed plan key (``--summa PxQ`` overrides from the CLI).
    summa_grid: Optional[tuple] = None
    #: padded-prompt-length shape buckets of the serve scheduler (None →
    #: ``serve.engine.DEFAULT_PAD_LENS``).  Every bucket is plan-warmed and
    #: pre-compiled by ``Engine.warmup()`` so steady-state serving never
    #: recompiles; prompts that fit no bucket within the waste cap are
    #: served through dynamically-created cold buckets (recorded misses).
    serve_buckets: Optional[tuple] = None
    # --- training ------------------------------------------------------------
    remat: bool = True
    norm_eps: float = 1e-6
    tp: int = DEFAULT_TP
    gated_mlp: bool = True
    fsdp: bool = False   # shard params over "data" too (ZeRO-3 / FSDP)
    remat_group: int = 1  # checkpoint every g scan steps (residual stack /g)
    kv_dup_to_tp: bool = False  # duplicate kv heads so the cache TP-shards
    # --- reduced smoke override -------------------------------------------
    notes: str = ""

    # ---------------------------------------------------------------------
    def layer_kinds(self) -> list[tuple[str, str]]:
        """[(mixer, ffn)] per layer.  mixer ∈ {attn_full, attn_local, mamba,
        mlstm, slstm}; ffn ∈ {mlp, moe, none}."""
        kinds = []
        for i in range(self.n_layers):
            if self.block_type == "xlstm":
                mixer = "slstm" if (self.slstm_every
                                    and i % self.slstm_every == 0) else "mlstm"
                ffn = "none"   # cells carry their own FFN/projections
            elif self.block_type == "mamba_hybrid":
                mixer = ("attn_full" if self.attn_every
                         and i % self.attn_every == self.attn_offset
                         else "mamba")
                ffn = ("moe" if self.n_experts
                       and i % self.moe_every == self.moe_offset else "mlp")
            else:
                if self.attn_pattern == "local_global":
                    mixer = ("attn_full"
                             if i % self.global_every == self.global_every - 1
                             else "attn_local")
                else:
                    mixer = "attn_full"
                ffn = "moe" if self.n_experts else "mlp"
            kinds.append((mixer, ffn))
        return kinds

    def pattern_period(self) -> int:
        kinds = self.layer_kinds()
        for p in range(1, len(kinds) + 1):
            if all(kinds[i] == kinds[i % p] for i in range(len(kinds))):
                return p
        return len(kinds)

    def segments(self) -> list[tuple[list[tuple[str, str]], int]]:
        """[(pattern, repeats)] — the scan schedule.  Layers split into a
        main scanned segment of whole pattern periods plus an unrolled
        tail."""
        kinds = self.layer_kinds()
        p = self.pattern_period()
        main = len(kinds) // p
        segs = []
        if main:
            segs.append((kinds[:p], main))
        tail = kinds[main * p:]
        if tail:
            segs.append((tail, 1))
        return segs

    @property
    def moe_ep(self) -> bool:
        """Expert parallelism (shard E over model) when divisible; otherwise
        experts replicated with d_ff TP-sharded."""
        return self.n_experts > 0 and self.n_experts % self.tp == 0

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        dh = self.head_dim or d // self.n_heads
        total = v * d * 2  # embed + head
        for mixer, ffn in self.layer_kinds():
            if mixer.startswith("attn"):
                total += d * dh * (self.n_heads * 2
                                   + self.n_kv_heads * 2)
            elif mixer == "mamba":
                din = self.mamba_expand * d
                total += d * 2 * din + din * d + din * (
                    d // 16 + 2 * self.mamba_d_state)
            elif mixer == "mlstm":
                din = 2 * d
                total += (d * 2 * din + 3 * din * din // self.n_heads
                          + din * d)
            elif mixer == "slstm":
                total += 4 * d * d + int(4 / 3 * d) * d * 2
            if ffn == "mlp":
                total += 3 * d * f
            elif ffn == "moe":
                total += self.n_experts * 3 * d * f
                if self.n_shared:
                    total += 3 * d * self.shared_d_ff
        return total


def register(cfg: ArchConfig) -> ArchConfig:
    REGISTRY[cfg.name] = cfg
    return cfg


_ARCH_MODULES = [
    "jamba_v01_52b", "hubert_xlarge", "llama3_8b", "internlm2_1_8b",
    "gemma3_4b", "llama3_405b", "qwen2_moe_a2_7b", "phi35_moe",
    "llava_next_34b", "xlstm_1_3b",
]


def load_all() -> dict[str, ArchConfig]:
    for m in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{m}")
    return REGISTRY


def get(name: str) -> ArchConfig:
    if name not in REGISTRY:
        load_all()
    return REGISTRY[name]


def reduced(cfg: ArchConfig, tp: int = 2) -> ArchConfig:
    """Tiny same-family variant for CPU smoke tests: keeps the block
    pattern/family structure, shrinks every dimension."""
    period = cfg.pattern_period()
    n_layers = max(2, min(2 * period, 8))
    kw = dict(
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=64,
        n_heads=4,
        n_kv_heads=max(1, min(4, cfg.n_kv_heads)),
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab=128,
        head_dim=16,
        local_window=8,
        n_experts=min(4, cfg.n_experts) if cfg.n_experts else 0,
        top_k=min(2, cfg.top_k) if cfg.top_k else 0,
        n_shared=min(1, cfg.n_shared),
        shared_d_ff=64 if cfg.n_shared else 0,
        frontend_dim=32 if cfg.frontend != "none" else 0,
        n_patches=8 if cfg.frontend == "vision" else 0,
        mp_tile=16,
        tp=tp,
        mamba_d_state=4,
        serve_buckets=(4, 8, 16, 32),
    )
    return dataclasses.replace(cfg, **kw)
