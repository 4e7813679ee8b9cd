"""Operations of one denoiser forward, from the ``model`` block of a
configuration file.

A copy of the multiply-accumulate arithmetic of the program's
``repro/utils/flops.py`` (``attn_macs``, ``ffn_macs``, ``non_block_macs``
and the factorised video attention of ``model_macs_by_type``), for dense
multi-head attention and ungated MLPs.  It reads only the configuration
file, so it stays fixed when the program changes.  FLOPs = 2 · MACs.  Like
the original it leaves out the per-row adaLN modulation and the norms
(about 0.2% of a DiT-XL forward), so a share of a peak built on it errs
low, never high.

The model block's ``branches`` list the cacheable branches of one forward,
each with its SmoothCache ``type`` as the program names it, its ``op``
and how many of it the forward runs (``count``, by default ``depth``):

* ``self_attn`` over ``over``: ``all`` tokens (the default), each
  ``frame``'s tokens (spatial attention: ``frames`` sequences of the
  tokens per frame), or each token position across ``time`` (temporal
  attention: that many sequences of ``frames``);
* ``cross_attn``: every token attends to a memory of ``memory_len`` rows
  of width ``memory_dim``, both keys of the model block;
* ``mlp``: ``mlp_hidden`` wide, ungated.

A 4-D ``latent_shape`` (frames, h, w, c) holds ``frames`` frames of
``(h / patch_size) · (w / patch_size)`` tokens; a 3-D one (h, w, c), one.
"""
from __future__ import annotations


def frames(m) -> int:
    return m["latent_shape"][0] if len(m["latent_shape"]) == 4 else 1


def frame_tokens(m) -> int:
    h, w = m["latent_shape"][-3], m["latent_shape"][-2]
    return (h // m["patch_size"]) * (w // m["patch_size"])


def tokens(m) -> int:
    return frames(m) * frame_tokens(m)


def attn_macs(m, lq: int, lk: int, kv_in: int) -> int:
    """One attention layer over one sequence of ``lq`` queries and ``lk``
    keys of width ``kv_in``: projections, scores, AV."""
    d, hd, heads = m["hidden_size"], m["head_dim"], m["num_heads"]
    macs = lq * d * heads * hd               # q
    macs += 2 * lk * kv_in * heads * hd      # k, v
    macs += heads * lq * lk * hd * 2         # scores + AV
    macs += lq * heads * hd * d              # out
    return macs


def branch_macs(m, b) -> int:
    """One branch of one row's forward."""
    op, d = b["op"], m["hidden_size"]
    if op == "mlp":
        return tokens(m) * d * m["mlp_hidden"] * 2
    if op == "cross_attn":
        return attn_macs(m, tokens(m), m["memory_len"], m["memory_dim"])
    if op != "self_attn":
        raise ValueError(f"no operation count for {op!r}")
    seqs, length = {"all": (1, tokens(m)),
                    "frame": (frames(m), frame_tokens(m)),
                    "time": (frame_tokens(m), frames(m))}[b.get("over", "all")]
    return seqs * attn_macs(m, length, length, d)


def non_block_macs(m) -> int:
    """Patch embedding and output projection, plus the time-embedding MLP
    as the original counts it."""
    tok_dim = m["latent_shape"][-1] * m["patch_size"] ** 2
    d = m["hidden_size"]
    return 2 * tokens(m) * d * tok_dim + d * d * 2


def types(m) -> list:
    """The branch types, in the file's order."""
    return list(dict.fromkeys(b["type"] for b in m["branches"]))


def macs_by_type(m) -> dict:
    """MACs of one forward of one row, per SmoothCache layer type."""
    out = dict.fromkeys(types(m), 0)
    for b in m["branches"]:
        out[b["type"]] += b.get("count", m["depth"]) * branch_macs(m, b)
    return out


def row_step_flops(m, computed=None) -> float:
    """FLOPs of one denoiser evaluation of one row in which the layer types
    in ``computed`` (by default all) ran; the others were reused from the
    cache."""
    macs = sum(v for t, v in macs_by_type(m).items()
               if computed is None or t in computed)
    return 2.0 * (macs + non_block_macs(m))


def sample_flops(m, skip, steps: int, cfg_scale) -> float:
    """FLOPs of one whole sample: every step's evaluation, over both CFG
    rows when guidance is on; ``skip`` maps a type to its per-step reuse
    flags."""
    rows = 2 if cfg_scale is not None else 1
    total = 0.0
    for s in range(steps):
        computed = [t for t in types(m)
                    if not (skip and t in skip and bool(skip[t][s]))]
        total += row_step_flops(m, computed)
    return rows * total
