"""Operations and bytes, counted from a configuration's widths and a
call's shapes (multiply-add = 2 operations; matrix products only).

``tower_forward_flops`` is one example through one tower: patchify or
embedding lookup, per block the q/k/v/o projections, the two attention
products and the three SwiGLU products, and nothing for norms, softmax or
rotary positions. ``pair_forward_flops`` adds both projections onto the
embedding. A training step needs three forwards' worth per pair (forward,
and a backward twice as large); the second forward of Algorithm 1 (pass 2
recomputes every tower forward) is recompute and is not counted.
"""
from __future__ import annotations


def tower_forward_flops(t: dict, seq: int) -> int:
    d, dff, L = t["d_model"], t["d_ff"], t["n_layers"]
    q = t["n_heads"] * t["head_dim"]
    per_block = (2 * seq * d * q * 3          # q, k, v
                 + 2 * seq * seq * q * 2      # scores, weights x values
                 + 2 * seq * q * d            # output projection
                 + 2 * seq * d * dff * 3)     # SwiGLU: in, gate, out
    front = 0
    if t.get("image_size"):
        front = 2 * seq * t["patch_size"] ** 2 * t["channels"] * d
    return L * per_block + front


def image_seq(t: dict) -> int:
    return (t["image_size"] // t["patch_size"]) ** 2


def image_forward_flops(cfg: dict) -> int:
    """One image through the image tower and its projection."""
    it = cfg["image_tower"]
    return tower_forward_flops(it, image_seq(it)) \
        + 2 * it["d_model"] * cfg["embed_dim"]


def text_forward_flops(cfg: dict, text_len: int) -> int:
    """One caption of ``text_len`` tokens through the text tower and its
    projection."""
    tt = cfg["text_tower"]
    return tower_forward_flops(tt, text_len) \
        + 2 * tt["d_model"] * cfg["embed_dim"]


def pair_forward_flops(cfg: dict, text_len: int) -> dict:
    img, txt = image_forward_flops(cfg), text_forward_flops(cfg, text_len)
    return {"image": img, "text": txt, "total": img + txt}


def train_flops_per_pair(cfg: dict, text_len: int) -> int:
    """Model operations a training step needs per pair (no recompute)."""
    return 3 * pair_forward_flops(cfg, text_len)["total"]


# ---------------------------------------------------------------------------
# kernels: (operations, bytes) one call needs, from its shapes
# ---------------------------------------------------------------------------


def contrastive_fwd_cost(bx: int, by: int, d: int, itemsize: int = 4):
    """Fused forward: the (bx, by) similarity tile sweep; reads X and Y,
    writes a row and a column log-sum-exp."""
    return 2 * bx * by * d, (bx + by) * d * itemsize + (bx + by) * 4


def contrastive_bwd_cost(bx: int, by: int, d: int, itemsize: int = 4):
    """Fused backward: the similarity again (the forward keeps no
    (bx, by) matrix), dX = dA·Y and dY = dAᵀ·X; reads X, Y and both
    log-sum-exps, writes dX and dY."""
    return (6 * bx * by * d,
            2 * (bx + by) * d * itemsize + (bx + by) * 4)


def topk_cost(b: int, n: int, d: int, k: int, itemsize: int = 4):
    """Similarity then top-k of ``b`` queries over ``n`` classes: reads
    the queries and the class matrix once, writes (b, k) values and
    indices."""
    return 2 * b * n * d, (b + n) * d * itemsize + b * k * 8
