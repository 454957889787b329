"""Config system: model, parallelism, and input-shape descriptions.

Every assigned architecture gets one ``<arch>.py`` module in this package that
builds a :class:`ModelConfig` with the exact published dimensions (source cited
in the module docstring).  The registry in ``__init__.py`` exposes them for
``--arch <id>`` selection, and :func:`smoke_variant` derives the reduced
CPU-runnable variant used by the per-arch smoke tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""
    n_experts: int                    # routed experts
    top_k: int
    d_ff_expert: int                  # hidden dim of each routed expert
    n_shared_experts: int = 0         # always-on shared experts (DeepSeek-style)
    d_ff_shared: int = 0              # hidden dim of the shared expert(s)
    capacity_factor: float = 1.25
    dispatch: str = "dense_onehot"    # "dense_onehot" | "sort_scatter"
    router_dtype: str = "float32"
    router_noise: float = 0.0
    aux_loss_weight: float = 0.01     # load-balance loss


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2/V3) dims. [arXiv:2412.19437]"""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Selective-SSM (Mamba-style) head configuration."""
    state_dim: int = 16               # N: per-channel state size
    conv_width: int = 4
    expand: int = 2                   # inner dim = expand * d_model
    dt_rank: int = 0                  # 0 -> ceil(d_model / 16)
    chunk: int = 128                  # chunked-scan block length


@dataclass(frozen=True)
class ParallelConfig:
    """How a config shards on the production mesh (see sharding.py)."""
    fsdp: bool = False                # shard params over ('pod','data') too
    # §Perf X3: small models should not tensor-parallel at all — per-chip
    # compute is trivial and every TP all-reduce is pure overhead. False
    # drops the 'model' axis from all param rules (pure data parallelism;
    # the model axis stays idle for them on the shared production mesh).
    tensor_parallel: bool = True
    # §Perf Q1: train-shape-only ZeRO-style policy — train_4k's global
    # batch (256) can fill all 256 chips with pure DP + FSDP-sharded
    # params, dropping the TP all-reduces (qwen3 1.36->0.54s, olmo
    # 2.06->0.33s, granite 4.3->2.1s). Prefill/decode batches cannot, so
    # this applies to train_step only (see launch.steps.make_step).
    train_dp_only: bool = False
    seq_parallel: bool = True         # shard residual stream seq dim over 'model'
    # §Perf G1: shard the decode KV cache's seq dim over 'model' (XLA
    # inserts the flash-decode partial-softmax combine). GQA archs have
    # kv_heads < |model| so the head axis cannot use the mesh; without
    # this the cache replicates 16x and decode_32k blows HBM (granite:
    # 45 GB/chip -> 4.7 GB/chip). Default ON; resolve() drops the axis
    # whenever it does not divide.
    context_parallel_decode: bool = True
    microbatch: int = 1               # gradient-accumulation steps
    remat: str = "block"              # "none" | "block" (checkpoint each layer)
    optimizer_moment_dtype: str = "float32"
    expert_parallel: bool = True      # MoE expert axis over 'model'


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    # attention options
    qk_norm: bool = False             # Qwen3-style per-head RMSNorm on q,k
    attn_window: int = 0              # 0 = full attention; >0 = sliding window
    rope_theta: float = 500_000.0
    attn_logit_softcap: float = 0.0
    # norm / embedding options
    nonparametric_norm: bool = False  # OLMo: LayerNorm without learnable params
    tie_embeddings: bool = False
    # family-specific sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    first_k_dense: int = 0            # DeepSeek: first k layers use dense FFN
    mtp_depth: int = 0                # DeepSeek: multi-token-prediction heads
    block_pattern: Tuple[str, ...] = ()   # xLSTM: e.g. ('slstm','mlstm')*12
    hybrid_parallel_heads: bool = False   # Hymba: attn & SSM heads in parallel
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    n_audio_frames: int = 1500        # stub frontend output length
    # vlm (llava)
    n_vision_patches: int = 0         # patch embeddings per request (anyres tiles)
    # numerics
    dtype: str = "bfloat16"
    # §Perf G5: store the decode KV cache quantised (e.g. "int8", with
    # per-(b,t,head) f16 scales) — halves the dominant decode memory term.
    # "" = cache in model dtype.
    kv_cache_dtype: str = ""
    norm_eps: float = 1e-5
    # scalar multipliers of the residual and attention paths (Granite);
    # the defaults leave the block as it is, and only the dense block
    # applies the others
    embedding_multiplier: float = 1.0   # h0 = embed[ids] * m
    residual_multiplier: float = 1.0    # h = h + m * branch(norm(h)), both
    attention_multiplier: float = 0.0   # softmax scale; 0 -> 1/sqrt(head_dim)
    logits_scaling: float = 1.0         # logits = head(norm(h)) / s
    # parallelism defaults for this arch
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    source: str = ""                  # citation

    def __post_init__(self):
        scaled = {k: v for k, v, default in (
            ("embedding_multiplier", self.embedding_multiplier, 1.0),
            ("residual_multiplier", self.residual_multiplier, 1.0),
            ("attention_multiplier", self.attention_multiplier, 0.0),
            ("logits_scaling", self.logits_scaling, 1.0)) if v != default}
        if scaled and self.family != "dense":
            raise ValueError(f"{self.arch_id}: {sorted(scaled)} are applied "
                             f"by the dense block only, not {self.family!r}")

    # ---- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    def n_params(self) -> int:
        """Approximate total parameter count (for roofline MODEL_FLOPS)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        hd = self.resolved_head_dim
        emb = V * D * (1 if self.tie_embeddings else 2)
        per_layer = 0          # common (attention / ssm) per-layer params
        if self.mla is not None:
            m = self.mla
            qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
            per_layer += D * m.q_lora_rank + m.q_lora_rank * self.n_heads * qk_hd
            per_layer += D * (m.kv_lora_rank + m.qk_rope_head_dim)
            per_layer += m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
            per_layer += self.n_heads * m.v_head_dim * D
        elif self.family != "ssm":
            per_layer += D * self.n_heads * hd          # Wq
            per_layer += 2 * D * self.n_kv_heads * hd   # Wk, Wv
            per_layer += self.n_heads * hd * D          # Wo
        if self.ssm is not None:
            inner = self.ssm.expand * D
            per_layer += 2 * D * inner + inner * D      # in/gate/out proj
            per_layer += inner * self.ssm.state_dim * 2  # B,C proj (approx)
        total = emb + self.n_layers * per_layer
        if self.moe is not None:
            e = self.moe
            routed = e.n_experts * 3 * D * e.d_ff_expert
            shared = e.n_shared_experts * 3 * D * (e.d_ff_shared or e.d_ff_expert)
            n_moe_layers = self.n_layers - self.first_k_dense
            total += n_moe_layers * (routed + shared + D * e.n_experts)
            total += self.first_k_dense * 3 * D * F     # dense-FFN head layers
        elif F > 0:
            total += self.n_layers * 3 * D * F          # SwiGLU
        if self.encoder_layers:
            enc_layer = 4 * D * D + 3 * D * F
            total += self.encoder_layers * enc_layer
            total += self.n_layers * 4 * D * D          # cross-attention
        return total

    def n_active_params(self) -> int:
        """Active (per-token) parameters — differs for MoE."""
        if self.moe is None:
            return self.n_params()
        e = self.moe
        D = self.d_model
        per_layer_routed_all = e.n_experts * 3 * D * e.d_ff_expert
        per_layer_routed_act = e.top_k * 3 * D * e.d_ff_expert
        n_moe_layers = self.n_layers - self.first_k_dense
        return self.n_params() - n_moe_layers * (per_layer_routed_all - per_layer_routed_act)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                         # "train" | "prefill" | "decode"


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced variant of the same family: 2 layers, d_model<=512, <=4 experts."""
    d_model = min(cfg.d_model, 256)
    n_heads = min(cfg.n_heads, 4)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    # keep the GQA ratio flavour: at least 2:1 when original had grouping
    if cfg.n_kv_heads < cfg.n_heads and n_kv == n_heads:
        n_kv = max(1, n_heads // 2)
    head_dim = min(cfg.resolved_head_dim, 64)
    kw = dict(
        n_layers=2,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        parallel=ParallelConfig(remat="none"),
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            n_experts=min(cfg.moe.n_experts, 4),
            top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=min(cfg.moe.d_ff_expert, 256),
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            d_ff_shared=min(cfg.moe.d_ff_shared, 256) if cfg.moe.d_ff_shared else 0,
            # effectively dropless at smoke scale so prefill+decode is
            # bit-consistent with the full forward (capacity bucketing
            # depends on which tokens are co-batched)
            capacity_factor=8.0,
        )
        kw["first_k_dense"] = min(cfg.first_k_dense, 1)
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                              qk_nope_head_dim=32, qk_rope_head_dim=16,
                              v_head_dim=32)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, state_dim=min(cfg.ssm.state_dim, 8),
                                        chunk=32)
    if cfg.block_pattern:
        kw["block_pattern"] = cfg.block_pattern[:2]
    if cfg.encoder_layers:
        kw["encoder_layers"] = 2
        kw["n_audio_frames"] = 32
    if cfg.n_vision_patches:
        kw["n_vision_patches"] = 16
    if cfg.mtp_depth:
        kw["mtp_depth"] = 1
    return cfg.with_(**kw)
