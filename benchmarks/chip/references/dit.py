"""Plain reference of class-conditional DiT sampling (arXiv:2212.09748).

The benchmark's yardstick for the DiT configurations: the weights it
serves, the noise a served batch starts from, and a straightforward
``jax.numpy`` DDIM sampler with classifier-free guidance and an optional
static SmoothCache skip mask.  It imports nothing of the program under
test; it follows the published DiT block with the departures that the
configuration files list under ``assumed``:

* positions are a 1-D sin-cos embedding over the flattened patch grid;
* the output projection emits the latent channels only (no learned
  sigma);
* attention and MLP projections carry no bias;
* the normalisations carry a scale and a bias (held at 1 and 0 here, so
  they act as DiT's affine-free LayerNorm).

Where the served policy is SmoothCache, the reference calibrates on its
own: it samples the calibration batch with every branch computed, builds
the per-type relative L1 error curves (paper Eq. 4, Fig. 2) and derives
the skip mask from them with the greedy rule.  Nothing of the program's
calibration enters it.

Everything runs in ``dtype`` with matrix products at ``precision``: the
reference is float32 at ``highest``; the control of the correctness check
is the same code in bfloat16 at the default precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: standard deviation of the seeded adaLN-zero leaves (every block's
#: ``mod``, ``final_mod`` and ``out``): at their zero init the model
#: outputs exactly 0, and caching would be trivially exact
ADALN_STD = 0.02
EMBED_STD = 0.02


def _model_key(m):
    """The hashable model numbers the jitted functions specialise on."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def layout(m):
    """The parameter tree as ``{path: (shape, rule)}``; the rule is
    ``("normal", std)``, ``"fan_in"``, ``"ones"`` or ``"zeros"``.  Paths
    are tuples of keys; ``stages`` holds one scanned stage of one block,
    stacked over the depth."""
    d, depth = m["hidden_size"], m["depth"]
    dff, heads, hd = m["mlp_hidden"], m["num_heads"], m["head_dim"]
    p = m["patch_size"]
    tok = p * p * m["latent_shape"][-1]
    temb = m["time_embed_dim"]
    blk = ("backbone", "stages", 0, 0)
    out = {
        ("backbone", "final_norm", "scale"): ((d,), "ones"),
        ("backbone", "final_norm", "bias"): ((d,), "zeros"),
        blk + ("ffn", "w_up"): ((depth, d, dff), "fan_in"),
        blk + ("ffn", "w_down"): ((depth, dff, d), "fan_in"),
        blk + ("mod", "w"): ((depth, d, 6 * d), ("normal", ADALN_STD)),
        blk + ("mod", "b"): ((depth, 6 * d), ("normal", ADALN_STD)),
        blk + ("norm1", "scale"): ((depth, d), "ones"),
        blk + ("norm1", "bias"): ((depth, d), "zeros"),
        blk + ("norm2", "scale"): ((depth, d), "ones"),
        blk + ("norm2", "bias"): ((depth, d), "zeros"),
        ("final_mod", "w"): ((d, 2 * d), ("normal", ADALN_STD)),
        ("final_mod", "b"): ((2 * d,), ("normal", ADALN_STD)),
        ("label_embed",): ((m["num_classes"] + 1, d), ("normal", EMBED_STD)),
        ("out", "w"): ((d, tok), ("normal", ADALN_STD)),
        ("out", "b"): ((tok,), ("normal", ADALN_STD)),
        ("patch_in", "w"): ((tok, d), "fan_in"),
        ("patch_in", "b"): ((d,), "zeros"),
        ("t_mlp", "w1"): ((temb, d), "fan_in"),
        ("t_mlp", "b1"): ((d,), "zeros"),
        ("t_mlp", "w2"): ((d, d), "fan_in"),
        ("t_mlp", "b2"): ((d,), "zeros"),
    }
    for name in ("wq", "wk", "wv"):
        out[blk + ("mixer", name)] = ((depth, d, heads * hd), "fan_in")
    out[blk + ("mixer", "wo")] = ((depth, heads * hd, d), "fan_in")
    return out


def _nest(flat):
    """``{path: leaf}`` → the nested tree (``stages`` is a list holding a
    one-block tuple, as the program's scanned stage is)."""
    tree = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    bb = tree["backbone"]
    bb["stages"] = [tuple(bb["stages"][0][i]
                          for i in sorted(bb["stages"][0]))]
    return tree


def seed_key(seed: int):
    """A PRNG key from any whole number (more bits than 32 are folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


#: the seed of the served model: one model per configuration, as a
#: deployment serves one; a run's seed only reorders its FFN units and
#: attention heads (:func:`make_weights`)
MODEL_SEED = 0


@functools.partial(jax.jit, static_argnums=(0, 3))
def _make(mkey, key, order_key, dtype):
    m = dict(mkey)
    flat = {}
    for i, (path, (shape, rule)) in enumerate(sorted(layout(m).items(),
                                                      key=lambda kv: str(kv[0]))):
        k = jax.random.fold_in(key, i)
        if rule == "ones":
            a = jnp.ones(shape, jnp.float32)
        elif rule == "zeros":
            a = jnp.zeros(shape, jnp.float32)
        else:
            std = (1.0 / math.sqrt(shape[-2]) if rule == "fan_in"
                   else rule[1])
            a = std * jax.random.normal(k, shape, jnp.float32)
        flat[path] = a
    _reorder(m, flat, order_key)
    return _nest({path: a.astype(dtype) for path, a in flat.items()})


def _reorder(m, flat, key) -> None:
    """Reorders each block's FFN hidden units and attention heads by
    permutations drawn from ``key``: other arrays, the same function."""
    depth, heads, hd = m["depth"], m["num_heads"], m["head_dim"]
    blk = ("backbone", "stages", 0, 0)
    kf, kh = jax.random.split(key)

    def perms(k, n):
        return jax.vmap(lambda kk: jax.random.permutation(kk, n))(
            jax.random.split(k, depth))
    pf, ph = perms(kf, m["mlp_hidden"]), perms(kh, heads)
    up, down = blk + ("ffn", "w_up"), blk + ("ffn", "w_down")
    flat[up] = jnp.take_along_axis(flat[up], pf[:, None, :], axis=2)
    flat[down] = jnp.take_along_axis(flat[down], pf[:, :, None], axis=1)
    for name in ("wq", "wk", "wv"):
        path = blk + ("mixer", name)
        w = flat[path].reshape(depth, -1, heads, hd)
        flat[path] = jnp.take_along_axis(
            w, ph[:, None, :, None], axis=2).reshape(flat[path].shape)
    path = blk + ("mixer", "wo")
    w = flat[path].reshape(depth, heads, hd, -1)
    flat[path] = jnp.take_along_axis(
        w, ph[:, :, None, None], axis=1).reshape(flat[path].shape)


def make_weights(m, seed: int, dtype=jnp.float32):
    """The weights of the served model (:data:`MODEL_SEED`) with each
    block's FFN units and attention heads in an order drawn from the seed,
    on the device, in one jitted call.  Every seed serves the same function,
    so the calibrated skip mask, and with it the work of a request, does not
    change with the seed; the arrays do."""
    return _make(_model_key(m), jax.random.fold_in(seed_key(MODEL_SEED), 7),
                 jax.random.fold_in(seed_key(seed), 8),
                 jnp.dtype(dtype).name)


# ---------------------------------------------------------------------------
# Noise of a served batch
# ---------------------------------------------------------------------------

def batch_key(seeds):
    """The documented key of a served batch: ``PRNGKey(len(seeds))`` with
    each member's seed folded in as 32 bits, in row order."""
    key = jax.random.PRNGKey(len(seeds))
    for s in seeds:
        key = jax.random.fold_in(key, np.uint32(int(s) & 0xFFFFFFFF))
    return key


def key_noise(key, rows, latent_shape):
    """The initial latent of a batch sampled from ``key``: the first half
    of one key split, drawn for all rows at once."""
    knoise, _ = jax.random.split(key)
    return jax.random.normal(knoise, (rows,) + tuple(latent_shape))


def batch_noise(seeds, latent_shape):
    """The initial latent of a served batch."""
    return key_noise(batch_key(seeds), len(seeds), latent_shape)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _sincos(pos, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mu).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _patchify(x, p):
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def _unpatchify(tok, p, shape):
    h, w, c = shape
    b = tok.shape[0]
    x = tok.reshape(b, h // p, w // p, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, w, c)


def _forward(m, prec, params, x, t, labels, cache_attn, cache_ffn,
             skip_attn, skip_ffn):
    """One denoiser evaluation of rows ``x`` (already CFG-doubled).
    Every branch is computed; where a type is skipped its cached output
    (the pre-gate branch output of the last computed step) is used in its
    place and kept as the cache.  Returns (eps, cache_attn, cache_ffn)."""
    dt = x.dtype
    mm = functools.partial(jnp.matmul, precision=prec)
    d, heads, hd = m["hidden_size"], m["num_heads"], m["head_dim"]
    eps_ln = m["layernorm_eps"]
    p = m["patch_size"]
    tok = _patchify(x, p)
    h = mm(tok, params["patch_in"]["w"]) + params["patch_in"]["b"]
    h = h + _sincos(jnp.arange(h.shape[1]), d)[None].astype(dt)
    tm = params["t_mlp"]
    te = _sincos(t, m["time_embed_dim"]).astype(dt)
    te = jax.nn.silu(mm(te, tm["w1"]) + tm["b1"])
    cond = mm(te, tm["w2"]) + tm["b2"] + params["label_embed"][labels]
    c_act = jax.nn.silu(cond)
    rows, n = h.shape[0], h.shape[1]

    def block(h, xs):
        lp, ca, cf = xs
        mod = (mm(c_act, lp["mod"]["w"]) + lp["mod"]["b"])[:, None, :]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        a_in = _layernorm(h, lp["norm1"]["scale"], lp["norm1"]["bias"],
                          eps_ln) * (1 + sc1) + sh1
        at = lp["mixer"]
        q = mm(a_in, at["wq"]).reshape(rows, n, heads, hd)
        k = mm(a_in, at["wk"]).reshape(rows, n, heads, hd)
        v = mm(a_in, at["wv"]).reshape(rows, n, heads, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec)
        s = s.astype(jnp.float32) / math.sqrt(hd)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=prec)
        a = mm(o.reshape(rows, n, heads * hd), at["wo"])
        a = jnp.where(skip_attn, ca, a)
        h = h + a * g1
        f_in = _layernorm(h, lp["norm2"]["scale"], lp["norm2"]["bias"],
                          eps_ln) * (1 + sc2) + sh2
        f = mm(jax.nn.gelu(mm(f_in, lp["ffn"]["w_up"]), approximate=True),
               lp["ffn"]["w_down"])
        f = jnp.where(skip_ffn, cf, f)
        h = h + f * g2
        return h, (a, f)

    stage = params["backbone"]["stages"][0][0]
    h, (cache_attn, cache_ffn) = jax.lax.scan(
        block, h, (stage, cache_attn, cache_ffn))
    fn = params["backbone"]["final_norm"]
    h = _layernorm(h, fn["scale"], fn["bias"], eps_ln)
    fm = mm(c_act, params["final_mod"]["w"]) + params["final_mod"]["b"]
    shift, scale = jnp.split(fm[:, None, :], 2, axis=-1)
    h = h * (1 + scale) + shift
    out = mm(h, params["out"]["w"]) + params["out"]["b"]
    return (_unpatchify(out, p, m["latent_shape"]), cache_attn, cache_ffn)


def ddim_tables(sampler):
    """Model times and ᾱ at each step and the next, as numpy float32."""
    n_train = sampler["num_train_steps"]
    betas = np.linspace(sampler["beta_start"], sampler["beta_end"], n_train,
                        dtype=np.float32)
    alpha_bar = np.cumprod(1.0 - betas, dtype=np.float32)
    ts = np.round(np.linspace(n_train - 1, 0, sampler["steps"])).astype(
        np.int32)
    ab = alpha_bar[ts]
    ab_next = np.concatenate([alpha_bar[ts[1:]], np.ones(1, np.float32)])
    return ts.astype(np.float32), ab, ab_next


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _step(mkey, prec, cfg_scale, params, x, t, a, an, labels, null,
          cache_attn, cache_ffn, skip_attn, skip_ffn):
    m = dict(mkey)
    dt = x.dtype
    x2 = jnp.concatenate([x, x], axis=0)
    lab2 = jnp.concatenate([labels, jnp.full_like(labels, null)], axis=0)
    t2 = jnp.full((x2.shape[0],), t, jnp.float32)
    pred, cache_attn, cache_ffn = _forward(
        m, prec, params, x2, t2, lab2, cache_attn, cache_ffn, skip_attn,
        skip_ffn)
    c, u = jnp.split(pred.astype(jnp.float32), 2, axis=0)
    eps = u + cfg_scale * (c - u)
    x32 = x.astype(jnp.float32)
    x0 = (x32 - jnp.sqrt(1 - a) * eps) / jnp.sqrt(a)
    x = (jnp.sqrt(an) * x0 + jnp.sqrt(1 - an) * eps).astype(dt)
    return x, cache_attn, cache_ffn


def _branch_shape(m, rows):
    n = (m["latent_shape"][0] // m["patch_size"]) * (
        m["latent_shape"][1] // m["patch_size"])
    return (m["depth"], rows, n, m["hidden_size"])


def sample(m, sampler, params, noise, labels, skip=None, *,
           dtype=jnp.float32, precision="highest"):
    """DDIM (η = 0) with classifier-free guidance from ``noise``
    (R, *latent) for ``labels`` (R,); ``skip`` maps ``"attn"``/``"ffn"`` to
    a boolean per step (True = reuse the cached branch output).  Returns
    the final latents as a float32 numpy array."""
    mkey = _model_key(m)
    ts, ab, ab_next = ddim_tables(sampler)
    steps = len(ts)
    skip = skip or {}
    s_attn = np.asarray(skip.get("attn", np.zeros(steps, bool)), bool)
    s_ffn = np.asarray(skip.get("ffn", np.zeros(steps, bool)), bool)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jnp.asarray(noise, dtype)
    shape = _branch_shape(m, 2 * x.shape[0])
    cache_attn = jnp.zeros(shape, dtype)
    cache_ffn = jnp.zeros(shape, dtype)
    labels = jnp.asarray(labels, jnp.int32)
    for s in range(steps):
        x, cache_attn, cache_ffn = _step(
            mkey, precision, float(sampler["cfg_scale"]), params, x,
            ts[s], ab[s], ab_next[s], labels, m["num_classes"], cache_attn,
            cache_ffn, bool(s_attn[s]), bool(s_ffn[s]))
    return np.asarray(x, np.float32)


# ---------------------------------------------------------------------------
# SmoothCache calibration and schedule (paper Eq. 4)
# ---------------------------------------------------------------------------

#: the branch types, in the order the forward returns their outputs
TYPES = ("attn", "ffn")


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(10,))
def _calib_step(mkey, prec, cfg_scale, params, x, t, a, an, labels, null,
                window):
    """One uncached step.  ``window`` holds, per type, the conditioned
    rows' branch outputs of the last K steps, newest first, as (K, depth,
    R, N, d).  Returns the next latent, the window moved on by this step,
    and per type the relative L1 change ``||cur − prev_k||₁ / ||cur||₁``
    of every layer and row against each of the K steps, (K, depth, R)."""
    m = dict(mkey)
    rows = x.shape[0]
    x2 = jnp.concatenate([x, x], axis=0)
    lab2 = jnp.concatenate([labels, jnp.full_like(labels, null)], axis=0)
    t2 = jnp.full((x2.shape[0],), t, jnp.float32)
    zeros = jnp.zeros(_branch_shape(m, 2 * rows), x.dtype)
    pred, out_attn, out_ffn = _forward(m, prec, params, x2, t2, lab2, zeros,
                                       zeros, False, False)
    c, u = jnp.split(pred.astype(jnp.float32), 2, axis=0)
    eps = u + cfg_scale * (c - u)
    x32 = x.astype(jnp.float32)
    x0 = (x32 - jnp.sqrt(1 - a) * eps) / jnp.sqrt(a)
    x = (jnp.sqrt(an) * x0 + jnp.sqrt(1 - an) * eps).astype(x.dtype)
    new_window, errs = [], []
    for cur, win in zip((out_attn, out_ffn), window):
        cur = cur[:, :rows].astype(jnp.float32)    # the conditioned half
        num = jnp.sum(jnp.abs(cur[None] - win), axis=(3, 4))
        den = jnp.sum(jnp.abs(cur), axis=(2, 3)) + 1e-12
        errs.append(num / den[None])
        new_window.append(jnp.concatenate([cur[None], win[:-1]], axis=0))
    return x, tuple(new_window), tuple(errs)


def error_curves(m, sampler, params, noise, labels, k_max, *,
                 dtype=jnp.float32, precision="highest"):
    """The per-type error curves of an uncached guided sampling pass from
    ``noise`` (R, *latent) for ``labels`` (R,): ``{type: (S, K+1)}`` with
    entry [s, k] the layer mean, then the row mean, of the relative L1
    change of the branch output at step s against step s−k (conditioned
    rows only); NaN where k > s, 0 in column 0."""
    mkey = _model_key(m)
    ts, ab, ab_next = ddim_tables(sampler)
    steps = len(ts)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jnp.asarray(noise, dtype)
    labels = jnp.asarray(labels, jnp.int32)
    shape = (k_max,) + _branch_shape(m, x.shape[0])
    window = tuple(jnp.zeros(shape, jnp.float32) for _ in TYPES)
    curves = {t: np.full((steps, k_max + 1), np.nan) for t in TYPES}
    for s in range(steps):
        x, window, errs = _calib_step(
            mkey, precision, float(sampler["cfg_scale"]), params, x, ts[s],
            ab[s], ab_next[s], labels, m["num_classes"], window)
        for t, e in zip(TYPES, errs):
            e = np.asarray(e, np.float64)
            curves[t][s, 0] = 0.0
            for k in range(1, min(k_max, s) + 1):
                curves[t][s, k] = e[k - 1].mean(axis=0).mean()
    return curves


def policy_rule(policy: str):
    """``(alpha, k_max)`` of a flat ``smoothcache:alpha=a,k_max=k`` spec."""
    name, _, args = policy.partition(":")
    if name != "smoothcache":
        raise ValueError(f"the reference has no schedule rule for {name!r}")
    kw = dict(kv.split("=") for kv in args.split(",") if kv)
    if set(kw) != {"alpha", "k_max"}:
        raise ValueError(f"{policy!r} must name alpha and k_max")
    return float(kw["alpha"]), int(kw["k_max"])


def smoothcache_schedule(curves, alpha, k_max):
    """The greedy rule of paper Eq. 4: step s reuses a type's cached
    output iff its error to the step that filled the cache, k = s − last
    computed step, is below ``alpha`` and k ≤ ``k_max``.  Step 0 computes.
    Returns ``{type: bool array}`` (True = skip)."""
    out = {}
    for t, err in curves.items():
        skip = np.zeros(err.shape[0], bool)
        last = 0
        for s in range(1, err.shape[0]):
            k = s - last
            skip[s] = k <= k_max and bool(err[s, k] < alpha)
            if not skip[s]:
                last = s
        out[t] = skip
    return out


def schedule_violation(curves, skip, alpha, k_max) -> float:
    """How far a served mask ``skip`` breaks the rule under ``curves``,
    in units of the error curve, walked along the mask's own cache lags:
    a skipped step whose error to the step filling the cache is not below
    ``alpha`` reads ``err − alpha`` (infinite past ``k_max`` or at step
    0); a computed step that the rule would have skipped reads
    ``alpha − err``.  0 where the mask is the rule's."""
    worst = 0.0
    for t, err in curves.items():
        v = np.asarray(skip.get(t, np.zeros(err.shape[0], bool)), bool)
        if v.shape != (err.shape[0],):
            return math.inf
        last = 0
        for s in range(err.shape[0]):
            k = s - last
            if v[s]:
                if s == 0 or k > k_max:
                    return math.inf
                worst = max(worst, float(err[s, k]) - alpha)
            else:
                if s > 0 and k <= k_max:
                    worst = max(worst, alpha - float(err[s, k]))
                last = s
    return worst
