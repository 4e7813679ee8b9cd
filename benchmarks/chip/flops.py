"""Operations of one DiT forward, from its shapes.

A copy of the multiply-accumulate arithmetic of the program's
``repro/utils/flops.py`` (``attn_macs``, ``ffn_macs``,
``non_block_macs``), restricted to what the DiT configurations use: dense
multi-head self-attention and an ungated MLP.  It reads the model numbers
of a configuration file, so it stays fixed when the program changes.
FLOPs = 2 · MACs.  Like the original it leaves out the per-row adaLN
modulation and the norms (about 0.2% of a DiT-XL forward), so a share of
a peak built on it errs low, never high.
"""
from __future__ import annotations


def tokens(m) -> int:
    h, w = m["latent_shape"][0], m["latent_shape"][1]
    return (h // m["patch_size"]) * (w // m["patch_size"])


def attn_macs(m, seq: int) -> float:
    """One attention layer over one sequence: projections, scores, AV."""
    d, hd, heads = m["hidden_size"], m["head_dim"], m["num_heads"]
    macs = seq * d * heads * hd              # q
    macs += 2 * seq * d * heads * hd         # k, v
    macs += heads * seq * seq * hd * 2       # scores + AV
    macs += seq * heads * hd * d             # out
    return float(macs)


def ffn_macs(m, seq: int) -> float:
    return float(seq * m["hidden_size"] * m["mlp_hidden"] * 2)


def non_block_macs(m, seq: int) -> float:
    """Patch embedding and output projection, plus the time-embedding MLP
    as the original counts it."""
    tok_dim = m["latent_shape"][-1] * m["patch_size"] ** 2
    d = m["hidden_size"]
    return float(2 * seq * d * tok_dim + d * d * 2)


def macs_by_type(m) -> dict:
    """MACs of one forward of one row, per SmoothCache layer type."""
    seq = tokens(m)
    return {"attn": m["depth"] * attn_macs(m, seq),
            "ffn": m["depth"] * ffn_macs(m, seq)}


def row_step_flops(m, computed=("attn", "ffn")) -> float:
    """FLOPs of one denoiser evaluation of one row in which the layer types
    in ``computed`` ran (the others were reused from the cache)."""
    by_type = macs_by_type(m)
    macs = sum(v for t, v in by_type.items() if t in computed)
    return 2.0 * (macs + non_block_macs(m, tokens(m)))


def sample_flops(m, skip, steps: int, cfg_scale) -> float:
    """FLOPs of one whole sample: every step's evaluation, over both CFG
    rows when guidance is on; ``skip`` maps a type to its per-step reuse
    flags."""
    rows = 2 if cfg_scale is not None else 1
    total = 0.0
    for s in range(steps):
        computed = [t for t in ("attn", "ffn")
                    if not (skip and t in skip and bool(skip[t][s]))]
        total += row_step_flops(m, computed)
    return rows * total
