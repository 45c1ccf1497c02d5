"""Config system: typed, frozen dataclasses + CLI override support.

Every assigned architecture is a `ModelConfig` in `configs/<id>.py`; shapes
are the four assigned input-shape cells; `ParallelConfig` carries the mesh /
sharding / remat / pipeline knobs.  `configs.registry` resolves ``--arch`` /
``--shape`` strings.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Attention / block-level configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class YarnConfig:
    """YaRN RoPE scaling (HF ``rope_type="yarn"``): frequency pairs past
    the ``beta_fast``/``beta_slow`` correction range are interpolated by
    ``factor``, a linear ramp blends the ones inside it, and cos/sin are
    scaled by ``attention_factor``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class AttentionConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    # ann — softmax; ssa — stochastic spiking (paper eq. 5/6); spikformer —
    # Spikformer baseline; sdsa — addition-only spike-driven (k AND v)
    # column sum; qksum — addition-only token-sum QK scoring
    impl: str = "ann"                 # ann | ssa | spikformer | sdsa | qksum
    rope_theta: float = 10_000.0
    rope_type: str = "rope"           # rope | mrope | none
    # YaRN scaling of the global ("G") layers' RoPE; window ("L") layers
    # keep plain RoPE at the same theta (mellum2's per-kind rope_parameters)
    global_yarn: Optional[YarnConfig] = None
    softcap: Optional[float] = None   # gemma2 attn logit soft-capping (ANN only)
    sliding_window: Optional[int] = None
    # layer i is local (sliding-window) iff pattern[i % len(pattern)] == "L"
    layer_pattern: str = "G"          # e.g. "LG" = gemma2 alternating
    ssa_time_steps: int = 4           # T for ssa/spikformer impls
    # KV-cache representation for spiking decode ("ssa"/"sdsa" impls):
    #   dense  — real-valued K/V cached, spike trains re-encoded every step
    #   packed — K/V spike trains cached as uint32 bit-planes (1 bit/spike,
    #            repro.bitpack); decode reads packed words, bit-identical
    #            outputs to dense for the same seed
    spike_storage: str = "dense"      # dense | packed
    # Serving-side KV-cache layout (consumed by ``serving.ServingEngine``):
    #   slab  — one contiguous max_seq region per decode slot (B, S, ...)
    #   paged — slots share a page pool ((num_pages, page_size, ...) leaves,
    #           repro.serving.paging); per-request block tables map logical
    #           rows to pages, decode gathers pages back into the slab
    #           layout per tick, so every attention backend is unchanged and
    #           token streams stay bit-identical to the slab engine
    cache_layout: str = "slab"        # slab | paged
    # Attention-backend dispatch (repro.attention registry):
    #   auto  — fused Pallas kernels on TPU, XLA reference elsewhere
    #   xla   — force the XLA implementations (ann-xla / ssa-xla /
    #           spikformer-xla); ssa-xla shares the fused kernel's counter
    #           RNG, so xla vs fused is bit-identical for the same rng
    #   fused — force the Pallas kernels (impl="ssa" or "sdsa"; interpret
    #           mode off-TPU); with spike_storage="packed", decode consumes
    #           the uint32 KV bit-planes directly (ssa-/sdsa-fused-packed;
    #           sdsa falls back to sdsa-xla where no fused kernel exists)
    backend: str = "auto"             # auto | xla | fused
    causal: bool = True
    # --- perf knobs (hillclimb levers; defaults = paper-faithful baseline) --
    # pad query heads up to this count with zero-weight heads: exact same
    # function, but a TP-divisible head axis (e.g. yi-34b 56 -> 64 on a
    # 16-way model axis avoids replicated attention + full-size grad ARs)
    pad_heads_to: int = 0
    # blockwise online-softmax attention (never materialise the S x S score
    # matrix — the SAU-dataflow insight applied to the ANN path); chunk size
    # in kv tokens, None = vanilla sdpa
    flash_chunk: Optional[int] = None


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_ffn_dim: int
    num_shared_experts: int = 0       # deepseek-moe shared experts
    shared_ffn_dim: int = 0
    router_aux_coef: float = 0.01


@dataclass(frozen=True)
class MambaConfig:                    # Mamba2 (SSD) block
    state_dim: int = 64
    head_dim: int = 64
    expand: int = 2
    chunk: int = 256
    conv_width: int = 4


@dataclass(frozen=True)
class XLSTMConfig:
    # block i is sLSTM iff i in slstm_layers, else mLSTM
    slstm_layers: Tuple[int, ...] = ()
    mlstm_head_dim: int = 64
    proj_factor: float = 2.0
    chunk: int = 256


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio | spiking_vit
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attention: AttentionConfig
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    # hybrid (zamba2): 1 shared attention block applied every k mamba blocks
    hybrid_attn_every: int = 0
    # enc-dec (whisper): decoder layers; num_layers = encoder layers
    decoder_layers: int = 0
    max_target_len: int = 448
    act: str = "swiglu"               # swiglu | geglu | gelu
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    final_softcap: Optional[float] = None   # gemma2 final-logit capping
    post_norms: bool = False          # gemma2 post-attn/post-mlp norms
    dtype: str = "bfloat16"
    # scan layer stacks (True) or python-unroll them (False; used by the
    # dry-run's depth-calibration compiles where scan hides per-layer cost)
    scan_layers: bool = True
    # vision stub (qwen2-vl / spiking ViT): inputs are precomputed embeddings
    frontend: str = "tokens"          # tokens | embeddings (stubbed frontend)
    sub_quadratic: bool = False       # eligible for long_500k cells
    long_context_note: str = ""

    @property
    def num_heads(self) -> int:
        return self.attention.num_heads

    @property
    def head_dim(self) -> int:
        return self.attention.head_dim

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks), for 6ND roofline."""
        from repro.models.counting import count_params

        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.counting import count_params

        return count_params(self, active_only=True)


# ---------------------------------------------------------------------------
# Input-shape cells (assigned per architecture)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Parallelism / runtime configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    multi_pod: bool = False
    pipeline_stages: int = 1          # >1: the pod axis becomes a PP axis
    microbatches: int = 4             # PP microbatches
    remat: str = "dots"               # none | dots | full
    zero1: bool = True                # shard optimizer state over data axis
    scan_layers: bool = True
    grad_compression: str = "none"    # none | int8_ef
    # decode-cache layout when kv_heads < model axis: "seq" shards the cache
    # sequence dim (flash-decode combine), "replicate" keeps kv replicated
    decode_cache_shard: str = "seq"
    seq_shard_activations: bool = True  # sequence-parallel norm/mlp activations


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    seed: int = 0
    checkpoint_every: int = 200
    keep_checkpoints: int = 3


def with_overrides(cfg, **kv):
    """Functional config override helper (nested via ``__`` paths).

    Nested keys sharing a prefix are merged (``attention__impl=...,
    attention__backend=...`` both apply) instead of the last one silently
    replacing the rest.
    """
    updates: dict = {}
    nested: dict[str, dict] = {}
    for key, val in kv.items():
        if "__" in key:
            head, rest = key.split("__", 1)
            nested.setdefault(head, {})[rest] = val
        else:
            updates[key] = val
    for head, sub_kv in nested.items():
        updates[head] = with_overrides(getattr(cfg, head), **sub_kv)
    return dataclasses.replace(cfg, **updates)
