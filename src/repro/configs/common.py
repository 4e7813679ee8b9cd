"""Shared helpers for architecture configs, incl. the smoke-test reducer."""
from __future__ import annotations

import dataclasses

from repro.config import (AttentionSpec, BlockSpec, MLPSpec, ModelConfig,
                          MoESpec, RGLRUSpec, SSMSpec, Stage)


def _shrink_mixer(m, d_model):
    if m is None:
        return None
    if isinstance(m, AttentionSpec):
        heads = 4 if m.num_heads >= 4 else m.num_heads
        kv = max(1, heads * m.num_kv_heads // m.num_heads)
        kw = dict(num_heads=heads, num_kv_heads=kv, head_dim=d_model // heads)
        if m.kind == "mla":
            kw.update(q_lora_rank=(64 if m.q_lora_rank else None),
                      kv_lora_rank=64, rope_head_dim=16, nope_head_dim=32,
                      v_head_dim=32)
        if m.window is not None:
            kw["window"] = min(m.window, 16)
        return dataclasses.replace(m, **kw)
    if isinstance(m, SSMSpec):
        return dataclasses.replace(m, d_state=16, head_dim=16, chunk=8)
    return dataclasses.replace(m, num_heads=2)


def _shrink_ffn(f, d_model):
    if f is None:
        return None
    if isinstance(f, MoESpec):
        return dataclasses.replace(
            f, num_experts=min(4, f.num_experts), top_k=min(2, f.top_k),
            d_ff=max(32, d_model), num_shared=min(1, f.num_shared),
            d_ff_shared=(max(32, d_model) if f.num_shared else 0))
    return dataclasses.replace(f, d_ff=2 * d_model)


def smoke_variant(cfg: ModelConfig, d_model: int = 128,
                  unit_repeats: int = 1) -> ModelConfig:
    """Reduced same-family variant: ≤2-ish layers (one unit per stage),
    d_model ≤ 512, ≤4 experts — runs a CPU forward/train step fast."""
    assert d_model <= 512
    stages = []
    for st in cfg.stages:
        unit = tuple(
            dataclasses.replace(
                b, mixer=_shrink_mixer(b.mixer, d_model),
                cross=_shrink_mixer(b.cross, d_model),
                ffn=_shrink_ffn(b.ffn, d_model))
            for b in st.unit)
        stages.append(Stage(unit=unit, repeat=min(unit_repeats, st.repeat)))
    return cfg.replace(
        name=cfg.name + "-smoke", d_model=d_model,
        vocab_size=min(cfg.vocab_size, 512) if cfg.vocab_size else cfg.vocab_size,
        stages=tuple(stages), max_seq_len=min(cfg.max_seq_len, 256),
        cond_dim=min(cfg.cond_dim, 64) if cfg.cond_dim else 0,
        text_dim=min(cfg.text_dim, 64), text_len=min(cfg.text_len, 8),
        num_prefix_embeds=min(cfg.num_prefix_embeds, 8),
        latent_shape=_shrink_latent(cfg.latent_shape),
        swa_window=16, dtype="float32")


def _shrink_latent(shape):
    if not shape:
        return ()
    if len(shape) == 3:         # (H, W, C) image latents
        return (8, 8, shape[-1])
    if len(shape) == 4:         # (T, H, W, C) video latents
        return (4, 8, 8, shape[-1])
    return (16, shape[-1])      # (L, C) audio latents
