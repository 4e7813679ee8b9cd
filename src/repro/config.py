"""Configuration schema for the repro framework.

Every architecture (the paper's own DiT families and the 10 assigned
backbones) is described by a `ModelConfig` built from small frozen spec
dataclasses.  The stack is a sequence of *stages*; each stage is a repeated
*unit* of block specs.  Repetition maps onto `jax.lax.scan` with stacked
params, which keeps the lowered HLO compact enough that 512-device GSPMD
compiles finish on a single host core.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple, Union


# ---------------------------------------------------------------------------
# Mixer specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AttentionSpec:
    """Multi-head attention: GQA/MQA/MHA or MLA (DeepSeek-style latent KV)."""
    kind: str = "gqa"                    # "gqa" | "mla"
    num_heads: int = 8
    num_kv_heads: int = 8                # ignored for MLA
    head_dim: int = 64
    window: Optional[int] = None         # sliding-window size; None = full
    causal: bool = True
    cross: bool = False                  # cross-attention (memory from cond)
    qk_norm: bool = False                # per-head RMSNorm on q,k (qwen3)
    qkv_bias: bool = False               # qwen2.5
    logit_softcap: Optional[float] = None  # gemma2: 50.0
    pos_emb: str = "rope"                # "rope" | "none"
    rope_theta: float = 10000.0
    # factorized video attention (OpenSora STDiT-style): None|"spatial"|"temporal"
    pattern: Optional[str] = None
    # --- MLA only ---
    q_lora_rank: Optional[int] = None    # None: full-rank q projection
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128

    @property
    def q_dim(self) -> int:
        if self.kind == "mla":
            return self.num_heads * (self.nope_head_dim + self.rope_head_dim)
        return self.num_heads * self.head_dim

    @property
    def o_in_dim(self) -> int:
        if self.kind == "mla":
            return self.num_heads * self.v_head_dim
        return self.num_heads * self.head_dim


@dataclass(frozen=True)
class SSMSpec:
    """Mamba-2 SSD mixer [arXiv:2405.21060]."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128                     # SSD chunk length
    a_init_range: Tuple[float, float] = (1.0, 16.0)


@dataclass(frozen=True)
class RGLRUSpec:
    """RG-LRU recurrent mixer from Griffin/RecurrentGemma [arXiv:2402.19427]."""
    num_heads: int = 8                   # block-diagonal gate projections
    conv_width: int = 4
    expand: int = 1                      # lru width = expand * d_model (RG uses 1x on 2b? actually 2560->lru 2560)
    c_constant: float = 8.0


# ---------------------------------------------------------------------------
# Feed-forward specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MLPSpec:
    d_ff: int = 2048
    activation: str = "silu"             # "silu" | "gelu" | "gelu_tanh"
    gated: bool = True                   # GLU variant (SwiGLU/GeGLU)


@dataclass(frozen=True)
class MoESpec:
    """Routed mixture-of-experts FFN with optional shared experts."""
    num_experts: int = 8
    top_k: int = 2
    d_ff: int = 2048                     # per routed expert
    num_shared: int = 0
    d_ff_shared: int = 0
    activation: str = "silu"
    gated: bool = True
    router: str = "softmax"              # "softmax" | "sigmoid" (dsv3)
    router_scale: float = 1.0            # dsv3 routed_scaling_factor 2.5
    aux_loss_weight: float = 0.0
    norm_topk: bool = True               # renormalize top-k weights
    capacity_factor: float = 0.0         # 0 => dense dispatch (einsum over experts)


FFNSpec = Union[MLPSpec, MoESpec]
MixerSpec = Union[AttentionSpec, SSMSpec, RGLRUSpec]


# ---------------------------------------------------------------------------
# Block / stage / model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockSpec:
    """One residual block: (norm → mixer → +res) [→ (norm → xattn → +res)]
    [→ (norm → ffn → +res)].

    `mixer=None` is allowed (FFN-only block).  `ffn=None` is used for Mamba-2
    blocks, which fold the FFN into the mixer.
    """
    mixer: Optional[MixerSpec] = None
    cross: Optional[AttentionSpec] = None
    ffn: Optional[FFNSpec] = None
    norm: str = "rmsnorm"                # "rmsnorm" | "layernorm"
    post_norm: bool = False              # gemma2: extra norm after branch
    # adaLN conditioning: None; "zero" (DiT: the block projects the
    # condition to its shift/scale/gate); "single" (PixArt / STDiT3: a
    # learned (6, d) table plus one projection shared by every block)
    adaln: Optional[str] = None
    cross_norm: bool = True              # norm before cross-attention
    type_tag: str = ""                   # SmoothCache type prefix ("s_"/"t_")

    def branch_names(self) -> Tuple[str, ...]:
        out = []
        if self.mixer is not None:
            out.append("mixer")
        if self.cross is not None:
            out.append("cross")
        if self.ffn is not None:
            out.append("ffn")
        return tuple(out)

    def branch_types(self) -> Tuple[str, ...]:
        """SmoothCache layer *types* for each branch (paper's set S)."""
        out = []
        if self.mixer is not None:
            if isinstance(self.mixer, AttentionSpec):
                out.append(self.type_tag + "attn")
            elif isinstance(self.mixer, SSMSpec):
                out.append(self.type_tag + "ssm")
            else:
                out.append(self.type_tag + "rglru")
        if self.cross is not None:
            out.append(self.type_tag + "xattn")
        if self.ffn is not None:
            out.append(self.type_tag + "ffn")
        return tuple(out)


@dataclass(frozen=True)
class Stage:
    """`repeat` copies of `unit` (a tuple of BlockSpecs), scanned when >1."""
    unit: Tuple[BlockSpec, ...]
    repeat: int = 1

    @property
    def num_layers(self) -> int:
        return len(self.unit) * self.repeat


@dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    vocab_size: int
    stages: Tuple[Stage, ...] = ()
    norm: str = "rmsnorm"
    tie_embeddings: bool = False
    # additive absolute pos emb: "none" | "sinusoidal"; a diffusion model
    # takes a 1-D sin-cos table over its tokens unless "sincos_2d" (a 2-D
    # table over each frame's patch grid)
    pos_emb: str = "none"
    max_seq_len: int = 8192
    logit_softcap: Optional[float] = None   # gemma2 final softcap 30.0
    embed_scale: bool = False            # gemma: scale embeddings by sqrt(d)
    # multi-codebook token IO (musicgen): K codebooks share the embedding sum
    num_codebooks: int = 1
    # prepended continuous embeddings (VLM patches / audio frames); 0 = none
    num_prefix_embeds: int = 0
    # DeepSeek-style multi-token prediction depth (extra training head)
    mtp_depth: int = 0
    # diffusion-task configs: latent input instead of tokens
    task: str = "lm"                     # "lm" | "diffusion"
    latent_shape: Tuple[int, ...] = ()   # diffusion: per-sample latent shape
    patch: int = 1                       # diffusion image patch size
    cond_dim: int = 0                    # cross-attention memory width
    # text conditioning (Open-Sora): the caller's memory is ``text_dim``
    # wide (a T5 output); a caption MLP maps it to ``cond_dim``, and CFG's
    # unconditional rows take a learned null caption of ``text_len`` rows
    text_dim: int = 0
    text_len: int = 0
    fps: int = 0                         # frame rate embedded into t; 0 = none
    # a diffusion denoiser's matrix products: a jax.default_matmul_precision
    # name ("high": three bfloat16 passes on the TPU), None = the default
    matmul_precision: Optional[str] = None
    num_classes: int = 0                 # label conditioning (DiT-XL)
    # long-context policy for long_500k: "native" (ssm/hybrid) | "swa" | None
    long_context: Optional[str] = None
    swa_window: int = 8192
    dtype: str = "bfloat16"
    citation: str = ""

    @property
    def num_layers(self) -> int:
        return sum(s.num_layers for s in self.stages)

    @property
    def input_memory_dim(self) -> int:
        """The width of the memory a caller passes: the text encoder's
        where the model has a caption MLP, else the cross-attention's."""
        return self.text_dim or self.cond_dim

    def blocks(self):
        """Iterate (stage_idx, rep_idx, block_idx_in_unit, BlockSpec) in order."""
        for si, st in enumerate(self.stages):
            for r in range(st.repeat):
                for bi, b in enumerate(st.unit):
                    yield si, r, bi, b

    def layer_types(self) -> Tuple[str, ...]:
        """All SmoothCache-eligible layer types present in the model."""
        types = []
        for st in self.stages:
            for b in st.unit:
                for t in b.branch_types():
                    if t not in types:
                        types.append(t)
        return tuple(types)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input-shape presets (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapePreset:
    name: str
    seq_len: int
    global_batch: int
    program: str                         # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k":    ShapePreset("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapePreset("prefill_32k", 32_768,   32, "prefill"),
    "decode_32k":  ShapePreset("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapePreset("long_500k",  524_288,    1, "decode"),
}
