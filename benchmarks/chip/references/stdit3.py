"""Plain reference of Open-Sora v1.2 text-to-video sampling (STDiT3-XL/2;
github.com/hpcaitech/Open-Sora, ``opensora/models/stdit/stdit3.py`` and
``opensora/schedulers/rf``; arXiv:2412.20404).

The benchmark's yardstick for the Open-Sora configuration: the weights it
serves, the noise a served batch starts from, each request's text memory
(its conditioning record), and a straightforward ``jax.numpy`` sampler:
rectified-flow Euler with Open-Sora's timestep transform and classifier-
free guidance against the learned null caption.  It imports nothing of the
program under test.  One denoiser evaluation, with ``t6 = t_block(t)``:

    t = t_mlp(sincos(t)) + fps_mlp(sincos(fps))
    y = caption_mlp(text)                     # 4096 → d → d, GELU-tanh
    x = patch_embed(x) + pos2d
    per block, spatial then temporal, 28 times:
      shift/scale/gate ×2 = table[6, d] + t6
      x += gate1 · Attn(LN(x)·(1 + scale1) + shift1)   # qk RMSNorm;
            # within a frame (spatial) or across frames with RoPE (temporal)
      x += CrossAttn(x, y, mask)                       # no norm, no gate
      x += gate2 · MLP(LN(x)·(1 + scale2) + shift2)
    out = Linear(LN(x)·(1 + scale) + shift), shift/scale = table[2, d] + t

It follows the configuration file's ``assumed`` departures: RoPE rotates
the two halves of a head (``rotary_embedding_torch`` pairs neighbours);
the attention output projections and the MLPs carry no bias; the
positions are the integer patch grid, not rescaled; the normalisations
carry a scale and a bias (held at 1 and 0, so they act as STDiT3's
affine-free LayerNorm), and the qk RMSNorm's weight is stored as its offset
from 1.

A request's record is ``{"text_seed", "caption_tokens"}``: its memory is a
seeded N(0, 1) stand-in for a T5-XXL output of :data:`TEXT_LEN` ×
:data:`TEXT_DIM`, whose rows from ``caption_tokens`` on are zero and
masked out, as Open-Sora pads a caption.

Everything runs in ``dtype`` with matrix products at ``precision``: the
reference is float32 at ``highest``; the control of the correctness check
is the same code in bfloat16 at the default precision.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_dit_of_stdit3", os.path.join(os.path.dirname(__file__), "dit.py"))
_dit = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dit)

#: the served batch's noise and the seed's key, as for the DiT cells
seed_key = _dit.seed_key
key_noise = _dit.key_noise
batch_noise = _dit.batch_noise

#: the seed of the served model: one model per configuration; a run's seed
#: only reorders its FFN units and attention heads (:func:`make_weights`)
MODEL_SEED = 0
#: standard deviation of the seeded adaLN leaves (the blocks' tables,
#: ``t_block``, the final table and ``out``): at their zero init the model
#: outputs exactly 0
ADALN_STD = 0.02
#: the T5-XXL output Open-Sora conditions on: 300 rows of 4096
TEXT_LEN, TEXT_DIM = 300, 4096


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def layout(m):
    """The parameter tree as ``{path: (shape, rule)}``; the rule is
    ``("normal", std)``, ``"fan_in"``, ``"ones"`` or ``"zeros"``.  Paths
    are tuples of keys; ``stages`` holds one scanned stage of the
    (spatial, temporal) pair, stacked over the pairs."""
    d, n = m["hidden_size"], m["depth"] // 2
    dff, hd = m["mlp_hidden"], m["head_dim"]
    inner = m["num_heads"] * hd
    c, temb = m["cond_dim"], m["time_embed_dim"]
    tok = m["patch_size"] ** 2 * m["latent_shape"][-1]
    out = {
        ("backbone", "final_norm", "scale"): ((d,), "ones"),
        ("backbone", "final_norm", "bias"): ((d,), "zeros"),
        ("final_mod", "table"): ((2, d), ("normal", ADALN_STD)),
        ("out", "w"): ((d, tok), ("normal", ADALN_STD)),
        ("out", "b"): ((tok,), ("normal", ADALN_STD)),
        ("patch_in", "w"): ((tok, d), "fan_in"),
        ("patch_in", "b"): ((d,), "zeros"),
        ("t_block", "w"): ((d, 6 * d), ("normal", ADALN_STD)),
        ("t_block", "b"): ((6 * d,), ("normal", ADALN_STD)),
        ("y_null",): ((m["text_len"], m["text_dim"]),
                      ("normal", m["text_dim"] ** -0.5)),
        ("y_proj", "w1"): ((m["text_dim"], c), "fan_in"),
        ("y_proj", "b1"): ((c,), "zeros"),
        ("y_proj", "w2"): ((c, c), "fan_in"),
        ("y_proj", "b2"): ((c,), "zeros"),
    }
    for mlp in ("t_mlp", "fps_mlp"):
        out[(mlp, "w1")] = ((temb, d), "fan_in")
        out[(mlp, "b1")] = ((d,), "zeros")
        out[(mlp, "w2")] = ((d, d), "fan_in")
        out[(mlp, "b2")] = ((d,), "zeros")
    for i in range(2):
        blk = ("backbone", "stages", 0, i)
        out[blk + ("mod", "table")] = ((n, 6, d), ("normal", ADALN_STD))
        for norm in ("norm1", "norm2"):
            out[blk + (norm, "scale")] = ((n, d), "ones")
            out[blk + (norm, "bias")] = ((n, d), "zeros")
        out[blk + ("ffn", "w_up")] = ((n, d, dff), "fan_in")
        out[blk + ("ffn", "w_down")] = ((n, dff, d), "fan_in")
        for attn, kv_in in (("mixer", d), ("cross", c)):
            out[blk + (attn, "wq")] = ((n, d, inner), "fan_in")
            out[blk + (attn, "wk")] = ((n, kv_in, inner), "fan_in")
            out[blk + (attn, "wv")] = ((n, kv_in, inner), "fan_in")
            out[blk + (attn, "wo")] = ((n, inner, d), "fan_in")
            for b in ("bq", "bk", "bv"):
                out[blk + (attn, b)] = ((n, inner), ("normal", ADALN_STD))
        out[blk + ("mixer", "q_norm", "scale")] = ((n, hd), "zeros")
        out[blk + ("mixer", "k_norm", "scale")] = ((n, hd), "zeros")
    return out


def _nest(flat):
    """``{path: leaf}`` → the nested tree (``stages`` is a list holding the
    (spatial, temporal) tuple, as the program's scanned stage is)."""
    tree = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    bb = tree["backbone"]
    bb["stages"] = [tuple(bb["stages"][0][i] for i in sorted(bb["stages"][0]))]
    return tree


@functools.partial(jax.jit, static_argnums=(0, 3))
def _make(mkey, key, order_key, dtype):
    m = dict(mkey)
    flat = {}
    for i, (path, (shape, rule)) in enumerate(sorted(layout(m).items(),
                                                      key=lambda kv: str(kv[0]))):
        if rule == "ones":
            a = jnp.ones(shape, jnp.float32)
        elif rule == "zeros":
            a = jnp.zeros(shape, jnp.float32)
        else:
            std = 1.0 / math.sqrt(shape[-2]) if rule == "fan_in" else rule[1]
            a = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        flat[path] = a
    _reorder(m, flat, order_key)
    return _nest({path: a.astype(dtype) for path, a in flat.items()})


def _reorder(m, flat, key) -> None:
    """Reorders each block's FFN hidden units and the heads of its two
    attentions by permutations drawn from ``key``: other arrays, the same
    function."""
    n, heads, hd = m["depth"] // 2, m["num_heads"], m["head_dim"]

    def perms(k, size):
        return jax.vmap(lambda kk: jax.random.permutation(kk, size))(
            jax.random.split(k, n))

    for i, k in enumerate(jax.random.split(key, 6)):
        blk = ("backbone", "stages", 0, i // 3)
        part = ("ffn", "mixer", "cross")[i % 3]
        if part == "ffn":
            p = perms(k, m["mlp_hidden"])
            up, down = blk + ("ffn", "w_up"), blk + ("ffn", "w_down")
            flat[up] = jnp.take_along_axis(flat[up], p[:, None, :], axis=2)
            flat[down] = jnp.take_along_axis(flat[down], p[:, :, None],
                                             axis=1)
            continue
        p = perms(k, heads)
        for name in ("wq", "wk", "wv"):
            path = blk + (part, name)
            w = flat[path].reshape(n, -1, heads, hd)
            flat[path] = jnp.take_along_axis(
                w, p[:, None, :, None], axis=2).reshape(flat[path].shape)
        for name in ("bq", "bk", "bv"):
            path = blk + (part, name)
            w = flat[path].reshape(n, heads, hd)
            flat[path] = jnp.take_along_axis(
                w, p[:, :, None], axis=1).reshape(flat[path].shape)
        path = blk + (part, "wo")
        w = flat[path].reshape(n, heads, hd, -1)
        flat[path] = jnp.take_along_axis(
            w, p[:, :, None, None], axis=1).reshape(flat[path].shape)


def make_weights(m, seed: int, dtype=jnp.float32):
    """The weights of the served model (:data:`MODEL_SEED`) with each
    block's FFN units and attention heads in an order drawn from the seed,
    on the device, in one jitted call."""
    return _make(_dit._model_key(m),
                 jax.random.fold_in(seed_key(MODEL_SEED), 7),
                 jax.random.fold_in(seed_key(seed), 8),
                 jnp.dtype(dtype).name)


# ---------------------------------------------------------------------------
# Conditioning: one caption per request
# ---------------------------------------------------------------------------

def condition(m, mix, rng) -> dict:
    """One request's record drawn from ``rng``: the seed of its text
    memory, and its caption's token count, uniform over the mix's
    ``caption_tokens`` range (inclusive)."""
    lo, hi = mix["caption_tokens"]
    return {"text_seed": int(rng.integers(0, 1 << 31)),
            "caption_tokens": int(rng.integers(lo, hi + 1))}


def text_memory(record) -> np.ndarray:
    """The record's (TEXT_LEN, TEXT_DIM) float32 memory: N(0, 1) rows from
    its seed, zero from ``caption_tokens`` on."""
    rng = np.random.default_rng(record["text_seed"])
    text = rng.standard_normal((TEXT_LEN, TEXT_DIM), dtype=np.float32)
    text[record["caption_tokens"]:] = 0.0
    return text


def request_args(record) -> dict:
    """The ``serve.Request`` keywords of a record."""
    return {"memory": text_memory(record),
            "memory_len": record["caption_tokens"]}


def calibration_records(m, mix, rng, n: int) -> list:
    """The records of a calibration batch of ``n`` samples."""
    return [condition(m, mix, rng) for _ in range(n)]


def _text_and_mask(records):
    text = np.stack([text_memory(r) for r in records])
    mask = (np.arange(TEXT_LEN)[None]
            < np.asarray([r["caption_tokens"] for r in records])[:, None])
    return text, mask


def cond_args(records) -> dict:
    """The program's ``cond_args`` for a batch of records."""
    text, mask = _text_and_mask(records)
    return {"memory": {"text": jnp.asarray(text), "mask": jnp.asarray(mask)}}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _sincos(pos, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _pos2d(h, w, d):
    """(h·w, d): the column's sin-cos table in the first half of the width,
    the row's in the second, rows major."""
    rows, cols = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    return jnp.concatenate([_sincos(jnp.asarray(cols.reshape(-1)), d // 2),
                            _sincos(jnp.asarray(rows.reshape(-1)), d // 2)],
                           axis=-1)


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mu).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm(x, offset, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + offset.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """Rotates (n, L, heads, hd) by position along L, in two halves."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attend(q, k, v, prec, valid=None):
    """Multi-head attention of (n, Lq, heads, hd) queries over (n, Lk,
    heads, hd) keys; ``valid`` (n, Lk) masks keys out."""
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=prec)
    s = s.astype(jnp.float32) / math.sqrt(q.shape[-1])
    if valid is not None:
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("nhqk,nkhd->nqhd", w, v, precision=prec)


def _forward(m, prec, params, x, t, text, valid):
    """One denoiser evaluation of rows ``x`` (R, frames, H, W, C), at
    times ``t`` (R,), on text memories ``text`` (R, TEXT_LEN, TEXT_DIM)
    whose ``valid`` rows (R, TEXT_LEN) are read.  Returns the velocity."""
    dt = x.dtype
    mm = functools.partial(jnp.matmul, precision=prec)
    d, heads, hd = m["hidden_size"], m["num_heads"], m["head_dim"]
    eps = m["layernorm_eps"]
    p = m["patch_size"]
    rows, frames, hh, ww, ch = x.shape
    gh, gw = hh // p, ww // p
    fs = gh * gw
    tok = x.reshape(rows, frames, gh, p, gw, p, ch).transpose(
        0, 1, 2, 4, 3, 5, 6).reshape(rows, frames * fs, p * p * ch)
    h = mm(tok, params["patch_in"]["w"]) + params["patch_in"]["b"]
    h = h + jnp.tile(_pos2d(gh, gw, d), (frames, 1))[None].astype(dt)

    def time_mlp(mp, e):
        return mm(jax.nn.silu(mm(e.astype(dt), mp["w1"]) + mp["b1"]),
                  mp["w2"]) + mp["b2"]
    temb = m["time_embed_dim"]
    te = (time_mlp(params["t_mlp"], _sincos(t, temb))
          + time_mlp(params["fps_mlp"],
                     _sincos(jnp.full(t.shape, m["fps"], jnp.float32), temb)))
    t6 = (mm(jax.nn.silu(te), params["t_block"]["w"])
          + params["t_block"]["b"]).reshape(rows, 6, d)
    yp = params["y_proj"]
    y = mm(jax.nn.gelu(mm(text.astype(dt), yp["w1"]) + yp["b1"],
                       approximate=True), yp["w2"]) + yp["b2"]

    def proj(a, lp, name):
        return mm(a, lp["w" + name]) + lp["b" + name]

    def block(h, lp, temporal):
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(lp["mod"]["table"] + t6, 6,
                                               axis=1)
        a_in = _layernorm(h, lp["norm1"]["scale"], lp["norm1"]["bias"],
                          eps) * (1 + sc1) + sh1
        at = lp["mixer"]
        q, k, v = (proj(a_in, at, n).reshape(rows, frames, fs, heads, hd)
                   for n in "qkv")
        q = _rmsnorm(q, at["q_norm"]["scale"], m["rmsnorm_eps"])
        k = _rmsnorm(k, at["k_norm"]["scale"], m["rmsnorm_eps"])
        if temporal:      # (rows·fs) sequences of the frames, RoPE in time
            q, k, v = (a.transpose(0, 2, 1, 3, 4).reshape(
                rows * fs, frames, heads, hd) for a in (q, k, v))
            q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
            o = _attend(q, k, v, prec).reshape(
                rows, fs, frames, heads * hd).transpose(0, 2, 1, 3)
        else:             # (rows·frames) sequences of one frame's tokens
            q, k, v = (a.reshape(rows * frames, fs, heads, hd)
                       for a in (q, k, v))
            o = _attend(q, k, v, prec)
        a = mm(o.reshape(rows, frames * fs, heads * hd), at["wo"])
        h = h + g1 * a
        ca = lp["cross"]
        q = proj(h, ca, "q").reshape(rows, frames * fs, heads, hd)
        k = proj(y, ca, "k").reshape(rows, -1, heads, hd)
        v = proj(y, ca, "v").reshape(rows, -1, heads, hd)
        o = _attend(q, k, v, prec, valid)
        h = h + mm(o.reshape(rows, frames * fs, heads * hd), ca["wo"])
        f_in = _layernorm(h, lp["norm2"]["scale"], lp["norm2"]["bias"],
                          eps) * (1 + sc2) + sh2
        f = mm(jax.nn.gelu(mm(f_in, lp["ffn"]["w_up"]), approximate=True),
               lp["ffn"]["w_down"])
        return h + g2 * f

    def pair(h, lp):
        return block(block(h, lp[0], False), lp[1], True), None

    h, _ = jax.lax.scan(pair, h, params["backbone"]["stages"][0])
    fn = params["backbone"]["final_norm"]
    shift, scale = jnp.split(params["final_mod"]["table"] + te[:, None, :],
                             2, axis=1)
    h = _layernorm(h, fn["scale"], fn["bias"], eps) * (1 + scale) + shift
    out = mm(h, params["out"]["w"]) + params["out"]["b"]
    return out.reshape(rows, frames, gh, gw, p, p, ch).transpose(
        0, 1, 2, 4, 3, 5, 6).reshape(x.shape)


def time_grid(sampler) -> np.ndarray:
    """Open-Sora's grid t = 1 − i/steps, i = 0..steps, moved by its
    timestep transform t' = shift·t / (1 + (shift − 1)·t), float32."""
    g = np.linspace(1.0, 0.0, sampler["steps"] + 1, dtype=np.float32)
    shift = np.float32(sampler.get("shift", 1.0))
    return (shift * g / (np.float32(1.0) + (shift - np.float32(1.0)) * g)
            ).astype(np.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _step(mkey, prec, cfg_scale, params, x, t, dt, text, valid):
    """One guided Euler step: the conditioned rows read their caption, the
    unconditioned ones the null caption, both under the request's mask."""
    m = dict(mkey)
    null = jnp.broadcast_to(params["y_null"].astype(text.dtype), text.shape)
    x2 = jnp.concatenate([x, x], axis=0)
    t2 = jnp.full((x2.shape[0],), t, jnp.float32)
    pred = _forward(m, prec, params, x2, t2,
                    jnp.concatenate([text, null], axis=0),
                    jnp.concatenate([valid, valid], axis=0))
    c, u = jnp.split(pred.astype(jnp.float32), 2, axis=0)
    v = u + cfg_scale * (c - u)
    return (x.astype(jnp.float32) + dt * v).astype(x.dtype)


def sample(m, sampler, params, noise, records, skip=None, *,
           dtype=jnp.float32, precision="highest"):
    """Rectified-flow Euler with classifier-free guidance from ``noise``
    (R, *latent) for the R ``records``, every branch computed.  Returns
    the final latents as a float32 numpy array."""
    if skip and any(np.any(v) for v in skip.values()):
        raise ValueError("the STDiT3 reference computes every branch")
    mkey = _dit._model_key(m)
    grid = time_grid(sampler)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jnp.asarray(noise, dtype)
    text, valid = _text_and_mask(records)
    text, valid = jnp.asarray(text, dtype), jnp.asarray(valid)
    for s in range(sampler["steps"]):
        x = _step(mkey, precision, float(sampler["cfg_scale"]), params, x,
                  grid[s] * np.float32(1000.0), grid[s + 1] - grid[s], text,
                  valid)
    return np.asarray(x, np.float32)
