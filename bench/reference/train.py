"""Reference training steps: the exact full-batch contrastive gradient by
the paper's Algorithm 1, computed in blocks of rows, and the AdaFactorW
update (paper App. B: AdaFactor's factored second moment, AdamW's
decoupled weight decay, a first moment stored in bfloat16).

Nothing here imports the program. ``faults`` plants the mistakes a
training step can make, so that each can be read against the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.reference import model as M


class Reference:
    """Block-wise forward/backward of the dual encoder for one
    configuration. ``mesh`` (optional, one axis) spreads each block's rows
    over several chips; the weights are replicated."""

    def __init__(self, cfg: dict, num: M.Numerics = M.REFERENCE,
                 block_rows: int = 64, mesh=None):
        self.cfg, self.num, self.block = cfg, num, int(block_rows)
        self.mesh = mesh
        num_ = num

        def fwd(params, images, texts):
            return (M.embed(cfg, params, "image", images, num_),
                    M.embed(cfg, params, "text", texts, num_))

        def bwd(params, images, texts, dx, dy):
            _, vjp = jax.vjp(lambda p: fwd(p, images, texts), params)
            return vjp((dx, dy))[0]

        def loss(x, y, log_tau):
            return jax.value_and_grad(M.contrastive_loss, (0, 1, 2))(
                x, y, log_tau)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._loss = jax.jit(loss)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=0)

    def _rows(self, tree, lo, hi):
        part = jax.tree.map(lambda a: np.asarray(a[lo:hi]), tree)
        if self.mesh is None:
            return part
        shd = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        return jax.tree.map(lambda a: jax.device_put(a, shd), part)

    def place(self, params):
        if self.mesh is None:
            return params
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def loss_and_grad(self, params, batch, *, rows=None, local_shards=1):
        """(loss, grads) of the full batch, or of its first ``rows`` rows.
        ``local_shards > 1`` computes the loss of each of that many equal
        slices alone and averages them: the global batch with the exchange
        between chips left out."""
        images, texts = batch["images"], batch["texts"]
        n = len(texts["tokens"]) if rows is None else int(rows)
        xs, ys = [], []
        for lo in range(0, n, self.block):
            hi = min(n, lo + self.block)
            x, y = self._fwd(params, self._rows(images, lo, hi),
                             self._rows(texts, lo, hi))
            xs.append(np.asarray(x))
            ys.append(np.asarray(y))
        X, Y = np.concatenate(xs), np.concatenate(ys)
        per = n // local_shards
        loss, dX, dY, dtau = 0.0, [], [], 0.0
        for s in range(local_shards):
            sl = slice(s * per, (s + 1) * per)
            l_s, (dx, dy, dt) = self._loss(X[sl], Y[sl], params["log_tau"])
            loss += float(l_s) / local_shards
            dX.append(np.asarray(dx) / local_shards)
            dY.append(np.asarray(dy) / local_shards)
            dtau = dtau + dt / local_shards
        dX, dY = np.concatenate(dX), np.concatenate(dY)
        grads = None
        for lo in range(0, n, self.block):
            hi = min(n, lo + self.block)
            g = self._bwd(params, self._rows(images, lo, hi),
                          self._rows(texts, lo, hi),
                          self._rows(dX, lo, hi), self._rows(dY, lo, hi))
            grads = g if grads is None else self._add(grads, g)
        grads["log_tau"] = grads["log_tau"] + dtau
        return loss, grads


def factored(shape, threshold):
    return len(shape) >= 2 and shape[-1] >= threshold \
        and shape[-2] >= threshold


def adafactorw_init(params, thr):
    """Zero moments: a factored (rows, cols) pair for large matrices, a
    full second moment for the rest."""
    def v(p):
        if factored(p.shape, thr):
            return (jnp.zeros(p.shape[:-1], jnp.float32),
                    jnp.zeros((*p.shape[:-2], p.shape[-1]), jnp.float32))
        return (jnp.zeros(p.shape, jnp.float32), jnp.zeros((), jnp.float32))
    return {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16),
                              params),
            "v": jax.tree.map(v, params)}


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "clip", "thr"))
def adafactorw_update(params, grads, state, lr, *, b1, b2, eps, wd, clip,
                      thr):
    """One AdaFactorW step: returns (new params, new state)."""
    def one(p, g, m, v):
        g = g.astype(jnp.float32)
        g2 = g * g + eps
        if factored(p.shape, thr):
            r = b2 * v[0] + (1 - b2) * jnp.mean(g2, -1)
            c = b2 * v[1] + (1 - b2) * jnp.mean(g2, -2)
            nv = (r, c)
        else:
            nv = (b2 * v[0] + (1 - b2) * g2, v[1])
        nm = b1 * m.astype(jnp.float32) + (1 - b1) * g
        if factored(p.shape, thr):
            r, c = nv
            denom = jnp.sqrt(r[..., :, None] * c[..., None, :] / jnp.maximum(
                jnp.mean(r, -1)[..., None, None], eps))
            u = nm / jnp.maximum(denom, jnp.sqrt(eps))
        else:
            u = nm / jnp.sqrt(nv[0] + eps)
        rms = jnp.sqrt(jnp.mean(u * u) + 1e-30)
        u = u / jnp.maximum(1.0, rms / clip) + wd * p
        return p - lr * u, nm.astype(jnp.bfloat16), nv

    out = jax.tree.map(one, params, grads, state["m"], state["v"],
                       is_leaf=lambda x: isinstance(x, tuple))
    is_out = lambda x: isinstance(x, tuple) and len(x) == 3  # noqa: E731
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=is_out)
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=is_out)
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=is_out)
    return new_p, {"m": new_m, "v": new_v}


def leaf_norms(tree) -> dict:
    """{leaf path: float64 L2 norm} of a pytree of arrays."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.sqrt(np.sum(
        np.square(np.asarray(v, np.float64))))) for k, v in flat}


def run_steps(cfg: dict, seed: int, batches, *, num=M.REFERENCE,
              block_rows=64, mesh=None, fault=None):
    """Drive the reference through ``len(batches)`` steps from the seed's
    weights. Returns {'loss': [...], 'grad0': {leaf: norm},
    'change': {leaf: norm}} — the per-step losses, the first gradient's
    per-leaf norms, and the per-leaf norms of the weights' change over all
    the steps.

    ``fault``: None, 'half_batch' (the loss over half the rows),
    'no_exchange:<n>' (the loss of n slices alone, averaged)."""
    opt = cfg["optimizer"]
    ref = Reference(cfg, num, block_rows, mesh)
    params = ref.place(init_params_device(cfg, seed))
    p0 = jax.tree.map(np.asarray, params)
    state = adafactorw_init(params, opt["factored_threshold"])
    losses, grad0 = [], None
    for batch in batches:
        rows, shards = None, 1
        if fault == "half_batch":
            rows = len(batch["texts"]["tokens"]) // 2
        elif fault and fault.startswith("no_exchange:"):
            shards = int(fault.split(":")[1])
        loss, grads = ref.loss_and_grad(params, batch, rows=rows,
                                        local_shards=shards)
        losses.append(loss)
        if grad0 is None:
            grad0 = leaf_norms(grads)
        params, state = adafactorw_update(
            params, grads, state, opt["lr"], b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["eps"], wd=opt["weight_decay"],
            clip=opt["clip_threshold"], thr=opt["factored_threshold"])
    change = leaf_norms(jax.tree.map(lambda a, b: np.asarray(a) - b,
                                     params, p0))
    return {"loss": losses, "grad0": grad0, "change": change}


def init_params_device(cfg, seed):
    """The seed's weights, made on the device in one call."""
    return jax.jit(functools.partial(M.init_params, cfg))(
        jax.random.key(int(seed)))
