"""Paper Algorithm 1: GradAccum for the contrastive loss.

The contrastive loss needs the entire B×B similarity matrix, so per-microbatch
losses cannot be formed independently. Algorithm 1 instead:

  pass 1  (lines 2-5):  forward each microbatch through F, G keeping ONLY the
                        embeddings X, Y (activations discarded),
  lines 6-12:           full-batch loss on (X, Y) and its gradient (dX, dY),
  pass 2  (lines 13-16): re-run each microbatch forward, back-prop the dX/dY
                        slice into the weights, accumulate.

In JAX both passes are ``lax.scan`` over microbatches; pass 2 uses ``jax.vjp``
of the tower forward. The result is the EXACT full-batch gradient (asserted in
tests/test_gradaccum.py), with peak memory Θ(M·Mem(tower)) instead of
Θ(B·Mem(F+G)).

``microbatch_grads`` is the streaming form (paper "Yields" line): it emits the
per-microbatch gradient stream c_1..c_K consumed by core/moment_accum.py.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.contrastive import contrastive_loss


def _split(tree, k):
    """Reshape every leaf (B, ...) -> (k, B//k, ...), microbatch j taking
    rows j, j+k, j+2k, ... . A batch sharded in contiguous per-device
    blocks then gives every microbatch an equal share of every device's
    rows, so the microbatch scan stays data-parallel (a contiguous split
    would put whole microbatches on single devices)."""
    return jax.tree.map(
        lambda x: jnp.swapaxes(
            x.reshape(x.shape[0] // k, k, *x.shape[1:]), 0, 1), tree)


def _merge(z):
    """Inverse of ``_split`` for a stacked (k, B//k, D) leaf -> (B, D) in
    batch order."""
    return jnp.swapaxes(z, 0, 1).reshape(-1, z.shape[-1])


def contrastive_step(encode_image: Callable, encode_text: Callable,
                     params, batch, num_micro: int,
                     loss_fn: Callable = contrastive_loss,
                     loss_opts: dict | None = None,
                     emb_sharding=None):
    """Exact full-batch contrastive gradient via Algorithm 1.

    encode_image(params, images_mb) -> (M, D) embeddings (unit-norm)
    encode_text(params, texts_mb)   -> (M, D)
    params must contain 'log_tau'. batch = {'images': ..., 'texts': ...} with
    leading batch dim B on every leaf; num_micro must divide B.

    ``loss_opts`` is forwarded to ``loss_fn`` as keyword arguments — e.g.
    ``loss_fn=fused_kernel_loss, loss_opts={"interpret": True, "bm": 256}``
    plumbs explicit interpret/block overrides down to the Pallas kernels.

    ``loss_fn`` may also be a cross-shard GLOBAL-batch loss
    (``core.distributed_loss.make_global_loss_fn(mesh, ...)``); pass
    ``emb_sharding=distributed_loss.emb_sharding(mesh)`` with it, so the
    (B, D) embedding block and its dX/dY cotangents are pinned
    batch-sharded over the data axes between the tower scans and the
    shard_map'd loss — accumulation × data-parallel × tensor-parallel
    then compose under one jit (launch/train_distributed.py).

    Returns (loss, metrics, grads) with grads exactly equal to
    jax.grad of the monolithic loss (same contraction order).
    """
    images = _split(batch["images"], num_micro)
    texts = _split(batch["texts"], num_micro)

    def _pin(z):
        if emb_sharding is None:
            return z
        return jax.lax.with_sharding_constraint(z, emb_sharding)

    # ---- pass 1: embeddings only (lines 2-5) ----
    def fwd(_, mb):
        img, txt = mb
        return None, (encode_image(params, img), encode_text(params, txt))

    _, (X, Y) = jax.lax.scan(fwd, None, (images, texts))
    X = _pin(_merge(X))
    Y = _pin(_merge(Y))

    # ---- lines 6-12: loss on embeddings + d(loss)/d(X, Y, log_tau) ----
    def loss_on_emb(x, y, log_tau):
        tau = jnp.exp(log_tau)
        return loss_fn(x, y, tau, **(loss_opts or {}))

    (loss, metrics), (dX, dY, dlog_tau) = jax.value_and_grad(
        loss_on_emb, argnums=(0, 1, 2), has_aux=True)(
            X, Y, params["log_tau"])

    dXm = _split(_pin(dX), num_micro)
    dYm = _split(_pin(dY), num_micro)

    # ---- pass 2: rematerialize per microbatch, VJP into weights ----
    zero = jax.tree.map(jnp.zeros_like, params)

    def bwd(g, mb):
        img, txt, dx, dy = mb
        _, vjp_i = jax.vjp(lambda p: encode_image(p, img), params)
        _, vjp_t = jax.vjp(lambda p: encode_text(p, txt), params)
        gi, = vjp_i(dx)
        gt, = vjp_t(dy)
        g = jax.tree.map(lambda a, b, c: a + b + c, g, gi, gt)
        return g, None

    grads, _ = jax.lax.scan(bwd, zero, (images, texts, dXm, dYm))
    # the embedding VJPs contribute nothing to log_tau; add the direct term
    grads["log_tau"] = grads["log_tau"] + dlog_tau
    return loss, metrics, grads


def microbatch_grads(encode_image: Callable, encode_text: Callable,
                     params, batch, num_micro: int,
                     loss_fn: Callable = contrastive_loss,
                     loss_opts: dict | None = None):
    """Streaming form: returns (loss, metrics, c) where c is the stacked
    per-microbatch gradient stream, leaves (K, ...); mean over K equals the
    exact full-batch gradient (up to the 1/K normalization, paper §4.1)."""
    images = _split(batch["images"], num_micro)
    texts = _split(batch["texts"], num_micro)

    def fwd(_, mb):
        img, txt = mb
        return None, (encode_image(params, img), encode_text(params, txt))

    _, (X, Y) = jax.lax.scan(fwd, None, (images, texts))
    Xf, Yf = _merge(X), _merge(Y)

    def loss_on_emb(x, y, log_tau):
        tau = jnp.exp(log_tau)
        return loss_fn(x, y, tau, **(loss_opts or {}))

    (loss, metrics), (dX, dY, dlog_tau) = jax.value_and_grad(
        loss_on_emb, argnums=(0, 1, 2), has_aux=True)(
            Xf, Yf, params["log_tau"])
    dXm = _split(dX, num_micro)
    dYm = _split(dY, num_micro)

    def one(mb):
        img, txt, dx, dy = mb
        _, vjp_i = jax.vjp(lambda p: encode_image(p, img), params)
        _, vjp_t = jax.vjp(lambda p: encode_text(p, txt), params)
        gi, = vjp_i(dx)
        gt, = vjp_t(dy)
        g = jax.tree.map(lambda a, b: a + b, gi, gt)
        # K * grad-share so that mean_K(c_i) == exact full gradient
        g = jax.tree.map(lambda a: a * num_micro, g)
        g["log_tau"] = g["log_tau"] + dlog_tau
        return g

    _, c = jax.lax.scan(lambda _, mb: (None, one(mb)), None,
                        (images, texts, dXm, dYm))
    return loss, metrics, c
