"""Reference zero-shot classification: the prompt-ensembled class matrix
(the normalised mean of each class's prompt embeddings, CLIP-style as the
paper evaluates) and the image embeddings, both through the plain
reference towers, and their logits A = X·Cᵀ / tau (paper §3)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model as M
from bench.reference.train import init_params_device


def _blocks(fn, arrays: dict, rows: int):
    n = len(next(iter(arrays.values())))
    return np.concatenate([np.asarray(fn({k: v[lo:lo + rows]
                                          for k, v in arrays.items()}))
                           for lo in range(0, n, rows)])


def embeddings(cfg: dict, seed: int, images, tokens, mask,
               n_templates: int, *, num: M.Numerics = M.REFERENCE,
               block_rows: int = 64):
    """(image embeddings (n_images, d) float32, class matrix (n_classes,
    d) float64 unit rows, 1/tau) of the seed's weights."""
    params = init_params_device(cfg, seed)
    img = jax.jit(lambda p, b: M.embed(cfg, p, "image", b, num))
    txt = jax.jit(lambda p, b: M.embed(cfg, p, "text", b, num))
    x = _blocks(lambda b: img(params, b),
                {"image": np.asarray(images, np.float32)}, block_rows)
    t = _blocks(lambda b: txt(params, b),
                {"tokens": tokens, "attn_mask": mask}, 512)
    c = t.reshape(-1, n_templates, t.shape[-1]).astype(np.float64).mean(1)
    c /= np.maximum(np.linalg.norm(c, axis=1, keepdims=True), 1e-6)
    inv_tau = float(np.exp(-np.float64(params["log_tau"])))
    return x, c, inv_tau


def logits(cfg: dict, seed: int, images, tokens, mask, n_templates: int,
           *, num: M.Numerics = M.REFERENCE, block_rows: int = 64):
    """(n_images, n_classes) logits of the seed's weights, the similarity
    in the same numerics as the towers."""
    x, c, inv_tau = embeddings(cfg, seed, images, tokens, mask, n_templates,
                               num=num, block_rows=block_rows)
    return similarity(x, c, inv_tau, num)


def similarity(x, c, inv_tau, num: M.Numerics):
    """A = X·Cᵀ / tau: exact (float64) at ``highest`` precision, else one
    float32 matrix product at the stated precision."""
    if num.precision == M.HIGHEST:
        return (x.astype(np.float64) @ c.T) * inv_tau
    return np.asarray(jnp.einsum("id,jd->ij", jnp.asarray(x, jnp.float32),
                                 jnp.asarray(c, jnp.float32),
                                 precision=num.precision),
                      np.float64) * inv_tau


def answer_gaps(ref_logits, values, indices) -> tuple[float, float]:
    """(rank gap, value gap) of served top-k answers against the
    reference's logits, in logit units: how far the reference logit of the
    class served at rank j lies below the reference's own j-th best, and
    how far each served value lies from the reference logit of its class
    (worst over every row and rank)."""
    if len(indices) == 0:
        return float("nan"), float("nan")
    k = indices.shape[1]
    best = -np.sort(-ref_logits, axis=1)[:, :k]
    served = np.take_along_axis(ref_logits, indices.astype(np.int64), 1)
    rank_gap = float(np.max(best - served))
    value_gap = float(np.max(np.abs(values - served)))
    return max(rank_gap, 0.0), value_gap


def readings(x, c, inv_tau, values, indices, class_matrix,
             num: M.Numerics) -> dict:
    """The numbers the served answers and the served class matrix are held
    to, against the reference's embeddings ``x``, class matrix ``c`` and
    1/tau computed in ``num``: the rank and value gaps, and ``class_gap``,
    the widest distance between a served class row and the reference's
    (rows are unit vectors)."""
    rank_gap, value_gap = answer_gaps(similarity(x, c, inv_tau, num),
                                      values, indices)
    return {"rank_gap": rank_gap, "value_gap": value_gap,
            "class_gap": float(np.max(np.linalg.norm(
                np.asarray(class_matrix, np.float64) - c, axis=1)))}
