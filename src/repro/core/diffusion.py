"""Diffusion wrapper: turns any repro backbone into a DiT denoiser.

Adds patchify/unpatchify, sinusoidal timestep embedding → MLP, optional
class-label embedding (with a CFG null class), and adaLN-zero conditioning
(the backbone's blocks carry ``adaln="zero"``).  Works for image latents
(H, W, C), video latents (T, H, W, C — spatial patchify, factorized
attention) and audio latents (L, C).

Open-Sora's STDiT3 options: adaLN-single blocks (one ``t_block``
projection of t for all blocks, and the T2I final layer modulated by t),
an fps embedding added to t (``cfg.fps``), 2-D sin-cos positions per frame
(``cfg.pos_emb == "sincos_2d"``), and a text memory read through a caption
MLP with a learned null caption for CFG (``cfg.text_dim``).

Prediction types: "eps" (DDPM/DDIM/DPM++) and "v_rf" (rectified flow).
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L, transformer as T

TIME_EMB_DIM = 256


# ---------------------------------------------------------------------------
# Patchify
# ---------------------------------------------------------------------------

def token_shape(cfg: ModelConfig):
    """Returns (num_tokens, token_dim, video_shape or None)."""
    ls = cfg.latent_shape
    p = cfg.patch
    if len(ls) == 3:    # (H, W, C) image
        h, w, c = ls
        return (h // p) * (w // p), p * p * c, None
    if len(ls) == 4:    # (T, H, W, C) video — spatial patchify only
        t, h, w, c = ls
        s = (h // p) * (w // p)
        return t * s, p * p * c, (t, s)
    ll, c = ls          # (L, C) audio
    assert p == 1
    return ll, c, None


def patchify(cfg: ModelConfig, x):
    """x: (B, *latent_shape) → (B, N, token_dim)."""
    p = cfg.patch
    ls = cfg.latent_shape
    b = x.shape[0]
    if len(ls) == 3:
        h, w, c = ls
        x = x.reshape(b, h // p, p, w // p, p, c)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p), p * p * c)
    if len(ls) == 4:
        t, h, w, c = ls
        x = x.reshape(b, t, h // p, p, w // p, p, c)
        return x.transpose(0, 1, 2, 4, 3, 5, 6).reshape(
            b, t * (h // p) * (w // p), p * p * c)
    return x


def unpatchify(cfg: ModelConfig, tok):
    p = cfg.patch
    ls = cfg.latent_shape
    b = tok.shape[0]
    if len(ls) == 3:
        h, w, c = ls
        x = tok.reshape(b, h // p, w // p, p, p, c)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, w, c)
    if len(ls) == 4:
        t, h, w, c = ls
        x = tok.reshape(b, t, h // p, w // p, p, p, c)
        return x.transpose(0, 1, 2, 4, 3, 5, 6).reshape(b, t, h, w, c)
    return tok


# ---------------------------------------------------------------------------
# Wrapper params
# ---------------------------------------------------------------------------

def _mlp_init(key, d_in, d, dtype):
    k1, k2 = jax.random.split(key)
    return {"w1": L.dense_init(k1, d_in, d, dtype), "b1": L.zeros((d,), dtype),
            "w2": L.dense_init(k2, d, d, dtype), "b2": L.zeros((d,), dtype)}


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    assert cfg.task == "diffusion"
    n_tok, tok_dim, _ = token_shape(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "backbone": T.init_params(ks[0], cfg, dtype, adaln_dim=d),
        "patch_in": {"w": L.dense_init(ks[1], tok_dim, d, dtype),
                     "b": L.zeros((d,), dtype)},
        "t_mlp": {"w1": L.dense_init(ks[2], TIME_EMB_DIM, d, dtype),
                  "b1": L.zeros((d,), dtype),
                  "w2": L.dense_init(ks[3], d, d, dtype),
                  "b2": L.zeros((d,), dtype)},
        # adaLN-zero final layer: cond → (shift, scale); zero-init out proj
        "final_mod": {"w": L.zeros((d, 2 * d), dtype),
                      "b": L.zeros((2 * d,), dtype)},
        "out": {"w": L.zeros((d, tok_dim), dtype),
                "b": L.zeros((tok_dim,), dtype)},
    }
    if any(b.adaln == "single" for st in cfg.stages for b in st.unit):
        # one t → 6·d projection for every block; the T2I final layer adds
        # t to a (2, d) shift/scale table
        p["t_block"] = {"w": L.zeros((d, 6 * d), dtype),
                        "b": L.zeros((6 * d,), dtype)}
        p["final_mod"] = {"table": L.zeros((2, d), dtype)}
    if cfg.num_classes:
        # +1 slot = CFG null label
        p["label_embed"] = L.embed_init(ks[4], cfg.num_classes + 1, d, dtype)
    if cfg.fps:
        p["fps_mlp"] = _mlp_init(ks[5], TIME_EMB_DIM, d, dtype)
    if cfg.text_dim:
        # caption MLP text_dim → cond_dim → cond_dim; the null caption is
        # in the text encoder's space and goes through it too
        p["y_proj"] = _mlp_init(ks[6], cfg.text_dim, cfg.cond_dim, dtype)
        p["y_null"] = L.dense_init(ks[7], cfg.text_len, cfg.text_dim, dtype,
                                   scale=cfg.text_dim ** -0.5)
    return p


def _time_mlp(p, emb):
    return jax.nn.silu(emb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def _cond_vector(cfg: ModelConfig, params, t, label=None):
    """t: (B,) diffusion time in [0, 1000) or [0,1]; label: (B,) int."""
    te = L.sinusoidal_embedding(t.astype(jnp.float32), TIME_EMB_DIM)
    te = _time_mlp(params["t_mlp"], te)
    if label is not None and "label_embed" in params:
        te = te + jnp.take(params["label_embed"], label, axis=0)
    if "fps_mlp" in params:
        fps = jnp.full(t.shape, float(cfg.fps), jnp.float32)
        te = te + _time_mlp(params["fps_mlp"],
                            L.sinusoidal_embedding(fps, TIME_EMB_DIM))
    return te


def positions(cfg: ModelConfig, n_tokens: int):
    """(N, d) additive positions: 1-D sin-cos over the flattened tokens
    (DiT-style), or with ``pos_emb == "sincos_2d"`` each frame's 2-D table
    over its patch grid, the first half of the width embedding the column
    and the second the row (Open-Sora's ``PositionEmbedding2D``)."""
    if cfg.pos_emb != "sincos_2d":
        return L.sinusoidal_embedding(jnp.arange(n_tokens), cfg.d_model)
    h = cfg.latent_shape[-3] // cfg.patch
    w = cfg.latent_shape[-2] // cfg.patch
    rows, cols = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
    half = cfg.d_model // 2
    emb = jnp.concatenate([L.sinusoidal_embedding(cols.reshape(-1), half),
                           L.sinusoidal_embedding(rows.reshape(-1), half)],
                          axis=-1)
    return jnp.tile(emb, (n_tokens // (h * w), 1))


def _memory(params, memory):
    """Cross-attention's memory and padding mask from the caller's: a text
    memory ``{"text": (B, M, text_dim), "mask": (B, M) bool}`` or a plain
    (B, M, width) array, every row read; a model with a caption MLP maps
    the text through it."""
    mask = None
    if isinstance(memory, dict):
        memory, mask = memory["text"], memory["mask"]
    if memory is not None and "y_proj" in params:
        p = params["y_proj"]
        memory = L.activation("gelu_tanh")(memory @ p["w1"] + p["b1"])
        memory = memory @ p["w2"] + p["b2"]
    return memory, mask


def null_cond(cfg: ModelConfig, params, label=None, memory=None):
    """Classifier-free guidance's unconditional ``(label, memory)`` for a
    batch conditioned on ``label`` and ``memory``: the null class, and the
    model's learned null caption under each row's own padding mask
    (Open-Sora), or zeros where the model has none."""
    if label is not None:
        label = jnp.full_like(label, cfg.num_classes)
    if memory is None:
        return label, None
    text = memory["text"] if isinstance(memory, dict) else memory
    if "y_null" in params:
        null = jnp.broadcast_to(params["y_null"].astype(text.dtype),
                                text.shape)
    else:
        null = jnp.zeros_like(text)
    if isinstance(memory, dict):
        return label, {"text": null, "mask": memory["mask"]}
    return label, null


def apply(cfg: ModelConfig, params, x, t, **kw):
    """Denoiser: x (B, *latent_shape), t (B,) → prediction (B, *latent_shape).
    ``memory``: a (B, M, width) array or a text memory (:func:`_memory`).
    Its parts run under ``jax.named_scope``s: ``embed`` and ``final`` here,
    and in each block ``adaln`` and the branch's SmoothCache type
    (``attn``, ``xattn``, ``ffn``), so a profiler trace names each op's
    branch in the cache's own vocabulary.

    Returns (pred, aux) with aux["branch"] holding per-layer pre-residual
    branch outputs (the SmoothCache payload) when requested.
    ``collect_branches`` may be a bool or a collection of layer types — the
    executor's liveness analysis passes the exact set of types whose fresh
    outputs a later step will read, so dead branches are never stacked.
    Its matrix products run at ``cfg.matmul_precision`` where the model
    names one."""
    with (jax.default_matmul_precision(cfg.matmul_precision)
          if cfg.matmul_precision else contextlib.nullcontext()):
        return _apply(cfg, params, x, t, **kw)


def _apply(cfg: ModelConfig, params, x, t, *, label=None, memory=None,
           skip=None, branch_caches=None, collect_branches=False,
           use_flash=False):
    _, _, video_shape = token_shape(cfg)
    with jax.named_scope("embed"):
        tok = patchify(cfg, x)
        h = tok @ params["patch_in"]["w"] + params["patch_in"]["b"]
        # fixed sin-cos positional embedding
        h = h + positions(cfg, h.shape[1])[None].astype(h.dtype)
        cond = _cond_vector(cfg, params, t, label)
        block_cond = cond
        if "t_block" in params:
            block_cond = (jax.nn.silu(cond) @ params["t_block"]["w"]
                          + params["t_block"]["b"])
        memory, memory_mask = _memory(params, memory)
    out, aux = T.forward(
        cfg, params["backbone"], embeds=h, memory=memory, cond=block_cond,
        skip=skip, branch_caches=branch_caches,
        collect_branches=collect_branches,
        use_flash=use_flash, video_shape=video_shape,
        memory_mask=memory_mask)
    with jax.named_scope("final"):
        fm = params["final_mod"]
        if "table" in fm:           # T2I final layer: t + (2, d) table
            shift, scale = jnp.split(fm["table"] + cond[:, None, :], 2,
                                     axis=1)
        else:
            mod = jax.nn.silu(cond) @ fm["w"] + fm["b"]
            shift, scale = jnp.split(mod[:, None, :], 2, axis=-1)
        out = out * (1.0 + scale) + shift
        out = out @ params["out"]["w"] + params["out"]["b"]
        return unpatchify(cfg, out), aux


# ---------------------------------------------------------------------------
# VP forward process + training losses
# ---------------------------------------------------------------------------

def vp_schedule(num_train_steps: int = 1000, beta_start: float = 1e-4,
                beta_end: float = 2e-2):
    betas = jnp.linspace(beta_start, beta_end, num_train_steps, dtype=jnp.float32)
    alphas = 1.0 - betas
    alpha_bar = jnp.cumprod(alphas)
    return {"betas": betas, "alphas": alphas, "alpha_bar": alpha_bar}


def q_sample(sched, x0, t, noise):
    """VP forward: x_t = sqrt(ᾱ_t) x₀ + sqrt(1-ᾱ_t) ε.  t: (B,) int."""
    ab = sched["alpha_bar"][t]
    shape = (-1,) + (1,) * (x0.ndim - 1)
    return (jnp.sqrt(ab).reshape(shape) * x0
            + jnp.sqrt(1.0 - ab).reshape(shape) * noise)


def eps_loss(cfg, params, key, x0, *, sched, label=None, memory=None):
    """DDPM ε-prediction loss."""
    kt, kn = jax.random.split(key)
    b = x0.shape[0]
    t = jax.random.randint(kt, (b,), 0, sched["betas"].shape[0])
    noise = jax.random.normal(kn, x0.shape, x0.dtype)
    xt = q_sample(sched, x0, t, noise)
    pred, _ = apply(cfg, params, xt, t, label=label, memory=memory)
    return jnp.mean(jnp.square(pred - noise))


def rf_loss(cfg, params, key, x0, *, label=None, memory=None):
    """Rectified-flow velocity loss: x_t = (1-t)x₀ + t·ε, v* = ε − x₀."""
    kt, kn = jax.random.split(key)
    b = x0.shape[0]
    t = jax.random.uniform(kt, (b,))
    noise = jax.random.normal(kn, x0.shape, x0.dtype)
    shape = (-1,) + (1,) * (x0.ndim - 1)
    xt = (1.0 - t).reshape(shape) * x0 + t.reshape(shape) * noise
    pred, _ = apply(cfg, params, xt, t * 1000.0, label=label, memory=memory)
    return jnp.mean(jnp.square(pred - (noise - x0)))
