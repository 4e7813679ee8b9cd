"""Open-Sora v1.2's STDiT3-XL/2, the paper's text-to-video model
[github.com/hpcaitech/Open-Sora, opensora/models/stdit/stdit3.py;
arXiv:2412.20404; SmoothCache §3.1].

28 spatial and 28 temporal blocks, interleaved; d_model=1152, 16 heads of
72, GELU-tanh MLP 4608.  A block: adaLN-single modulation (one ``t_block``
projection shared by all blocks plus a per-block (6, d) table), self-
attention with qk RMSNorm (spatial: within a frame, no RoPE; temporal:
across frames, RoPE over time), cross-attention to the caption with no
norm and no gate, and the MLP: the paper's 6 SmoothCache types
{s_attn, s_xattn, s_ffn, t_attn, t_xattn, t_ffn}.  Around the blocks: a
caption MLP 4096 → 1152 → 1152 over a T5-XXL output of 300 rows with a
learned null caption for CFG, an fps embedding added to t, 2-D sin-cos
positions over each frame's patch grid and the T2I final layer.

Latent: 240p 16:9 "2s" (51 frames): 15 × 30 × 53 after the VAE, the width
padded to 54 for the (1, 2, 2) patch → 15 frames × 405 tokens.
"""
from repro.config import AttentionSpec, BlockSpec, MLPSpec, ModelConfig, Stage
from repro.configs.common import smoke_variant

D = 1152


def _block(pattern, pos_emb, tag):
    return BlockSpec(
        mixer=AttentionSpec(num_heads=16, num_kv_heads=16, head_dim=72,
                            causal=False, pattern=pattern, qk_norm=True,
                            qkv_bias=True, pos_emb=pos_emb),
        cross=AttentionSpec(num_heads=16, num_kv_heads=16, head_dim=72,
                            cross=True, causal=False, qkv_bias=True,
                            pos_emb="none"),
        ffn=MLPSpec(d_ff=4608, activation="gelu_tanh", gated=False),
        norm="layernorm", adaln="single", cross_norm=False,
        type_tag=tag)


def full() -> ModelConfig:
    return ModelConfig(
        name="opensora-v12",
        d_model=D, vocab_size=0, task="diffusion",
        stages=(Stage(unit=(_block("spatial", "none", "s_"),
                            _block("temporal", "rope", "t_")),
                      repeat=28),),
        norm="layernorm", pos_emb="sincos_2d",
        latent_shape=(15, 30, 54, 4), patch=2, cond_dim=D,
        text_dim=4096, text_len=300, fps=24,
        # guidance at 7.0 amplifies rounding: with one bfloat16 pass per
        # matrix product (the TPU's default) the served latents sit only
        # 2-3x closer to float32's than bfloat16's do, mostly from rounding
        # the weights; three passes (bfloat16_3x) bring them to float32's
        matmul_precision="high",
        citation="SmoothCache §3.1; Open-Sora v1.2 (STDiT3-XL/2)")


def smoke() -> ModelConfig:
    return smoke_variant(full(), d_model=128)
