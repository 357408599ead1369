"""BASIC dual encoder: image tower F and text tower G mapping into S^D.

Paper §3: F(x), G(y) live on the D-dimensional unit sphere; similarity
A = (X^T Y)/tau with learnable temperature tau (stored as log_tau).
Text pooling is mean-over-positions (paper §7.2, unlike ALIGN's [CLS]).

Each tower's ops run under ``jax.named_scope`` ("image_tower" /
"text_tower", with "attention" / "mlp" inside the blocks): the compiled
step's op metadata, which the profiler's op views read, then names the
tower and the part of the block each op belongs to.

Both encoders take a ``precision`` policy (models.precision): the towers
run in its compute dtype while the embedding projections and the unit-norm
always land in fp32 under the default policies — the contrastive loss (and
its Pallas kernels) see fp32 embeddings regardless of tower precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.dual import DualEncoderConfig
from repro.models import layers as L
from repro.models import precision as prec_lib
from repro.models import transformer as tf


def init_params(cfg: DualEncoderConfig, rng):
    """Parameter pytree: per-tower transformer params (incl. the image
    tower's patchify frontend) + embedding projections + log_tau."""
    ki, kt, kpi, kpt = jax.random.split(rng, 4)
    return {
        "image": {
            "tower": tf.init_params(cfg.image_tower, ki),
            "proj": L.dense_init(kpi, cfg.image_tower.d_model, cfg.embed_dim),
        },
        "text": {
            "tower": tf.init_params(cfg.text_tower, kt),
            "proj": L.dense_init(kpt, cfg.text_tower.d_model, cfg.embed_dim),
        },
        "log_tau": jnp.asarray(jnp.log(cfg.init_temperature), jnp.float32),
    }


def _norm(z):
    return z / jnp.linalg.norm(z, axis=-1, keepdims=True).clip(1e-6)


def encode_image(cfg: DualEncoderConfig, params, images, *, precision=None,
                 remat_policy=None):
    """images: dict with 'image' (b, H, W, C) raw pixels (the tower's
    patchify frontend embeds them). Returns (b, D) on S^D, fp32."""
    pol = prec_lib.resolve(precision)
    with jax.named_scope("image_tower"):
        h = tf.encode(cfg.image_tower, params["image"]["tower"], images,
                      precision=pol, remat_policy=remat_policy)
        return _norm(L.dense(pol.project(h),
                             params["image"]["proj"]).astype(jnp.float32))


def encode_text(cfg: DualEncoderConfig, params, texts, *, precision=None,
                remat_policy=None):
    """texts: dict with 'tokens' (b, s) (+ optional 'attn_mask', which masks
    padding inside attention and pooling)."""
    pol = prec_lib.resolve(precision)
    with jax.named_scope("text_tower"):
        h = tf.encode(cfg.text_tower, params["text"]["tower"], texts,
                      precision=pol, remat_policy=remat_policy)
        return _norm(L.dense(pol.project(h),
                             params["text"]["proj"]).astype(jnp.float32))


def temperature(params):
    """tau = exp(log_tau) — the learnable similarity temperature (paper §3)."""
    return jnp.exp(params["log_tau"])
