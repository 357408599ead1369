"""The program under test, as a configuration file describes it."""
from __future__ import annotations

from bench import harness as H


def program_config(cfg: dict):
    """The program's configuration for ``cfg``, checked against the
    widths the configuration file states."""
    from repro.configs import get_arch, smoke_dual_variant
    pc = get_arch(cfg["arch"])
    if cfg.get("smoke"):
        pc = smoke_dual_variant(pc)
    for side in ("image_tower", "text_tower"):
        t, p = cfg[side], getattr(pc, side)
        have = {"n_layers": p.n_layers, "d_model": p.d_model,
                "n_heads": p.n_heads, "head_dim": p.resolved_head_dim,
                "d_ff": p.d_ff, "rope_theta": p.rope_theta,
                "norm_eps": p.norm_eps}
        if t.get("image_size"):
            have.update(image_size=p.image_size, patch_size=p.patch_size,
                        channels=p.channels)
        else:
            have["vocab"] = p.vocab
        diff = {k: (t.get(k), v) for k, v in have.items() if t.get(k) != v}
        if diff:
            raise H.BenchError(f"{cfg['name']} {side}: configuration file "
                               f"and program differ (file, program): {diff}")
    if pc.embed_dim != cfg["embed_dim"]:
        raise H.BenchError(f"embed_dim: file {cfg['embed_dim']}, program "
                           f"{pc.embed_dim}")
    return pc
