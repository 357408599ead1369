"""Cross-shard global-batch contrastive loss (DESIGN.md §7).

The paper's quality driver is the GLOBAL contrastive batch (B = 65536):
every example must see every other example in the batch as a negative,
across all data-parallel shards. This module computes exactly that from
per-shard embedding blocks, two ways:

``all_gather_loss``
    Gather X and Y over the data axis, run the single-pass fused Pallas
    loss (kernels/contrastive_loss) on the full (B_global, D) arrays on
    every device, pmean. Simple and exact — autodiff through the
    collectives yields the correct per-shard dX/dY (transpose of the
    tiled all-gather is a psum-scatter) — but every device does the full
    O(B_global²·D) similarity work, redundantly R times.

``chunked_loss``
    The per-shard scheme: each shard keeps only its local X block and
    streams the R gathered Y chunks through the fused kernel, one square
    (B_local, B_local) launch at a time. Each shard therefore computes
    only its row block (local rows × all columns) and the matching
    column partials; partial column log-sum-exps are psum-combined
    across shards. Per-device similarity work drops to
    O(B_local·B_global·D) — an R/2× saving over ``all_gather_loss`` at
    the same answer — and no device ever holds a (B_global, B_global)
    logit matrix, not even blockwise: the largest live tile is
    (bm, bn) ⊂ (B_local, B_local) in VMEM. The backward is a custom VJP
    that streams the same chunks through the no-diagonal fused backward
    (ops.chunk_grads) and psum-scatters the dY partials back to their
    owning shards (gradient-reduction correctness argument: DESIGN.md
    §7.3).

Both are shard-level functions: call them inside ``shard_map`` (or any
context where ``axis`` is a bound mesh axis name). ``make_global_loss_fn``
wraps either into a jit-level ``loss_fn(x, y, tau) -> (loss, metrics)``
drop-in for ``core.gradaccum.contrastive_step``, so Algorithm-1 gradient
accumulation, data parallelism, and tensor-parallel towers compose under
one jit (launch/train_distributed.py --objective contrastive).

shard_map runs with ``check_vma=False`` (Pallas calls have no replication
rule), which fixes the AD boundary convention this module compensates
for: the cotangent of the replicated P() loss arrives at each shard
scaled by 1/R, per-shard cotangents returned for P(data) inputs are used
as the local blocks directly, and cotangents returned for replicated P()
inputs are psum'd by the unmapping. ``_chunked_bwd`` therefore scales
dX/dY/dτ by R and does NOT psum dτ itself. Pinned by
tests/test_distributed_loss.py against the single-device oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sharding as shd
from repro.kernels.contrastive_loss import ops


def _linear_axis_index(axis):
    """Shard's linear position over ``axis`` (name or tuple of names),
    major-to-minor in tuple order — matches the concatenation order of
    ``all_gather``/``psum_scatter`` over the same tuple."""
    if not isinstance(axis, tuple):
        return jax.lax.axis_index(axis)
    idx = jnp.zeros((), jnp.int32)
    for name in axis:
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def _zero_metrics():
    zero = jnp.zeros((), jnp.float32)
    return {"row_loss": zero, "col_loss": zero, "i2t_top1": zero}


# ---------------------------------------------------------------------------
# all-gather variant
# ---------------------------------------------------------------------------


def all_gather_loss(x_l, y_l, log_tau, *, axis, interpret=None,
                    bm=None, bn=None):
    """Global-batch contrastive loss from per-shard embedding blocks by
    gathering both sides (shard-level; call inside shard_map).

    x_l, y_l: (B_local, D) fp32/bf16 unit-norm local blocks, row i of
    each being the two views of the same pair; log_tau: scalar fp32;
    axis: mesh axis name (or tuple) the batch is sharded over. Returns
    the replicated scalar fp32 loss of the full (B_global, B_global)
    problem. Differentiable: gradients flow through the collectives
    (all-gather transposes to psum-scatter), so jax.grad inside the
    enclosing jit returns per-shard dX/dY blocks and the psum'd dτ."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    x_g = jax.lax.all_gather(x_l, axis, tiled=True)
    y_g = jax.lax.all_gather(y_l, axis, tiled=True)
    loss = ops.fused_contrastive_loss(x_g, y_g, log_tau, interpret, bm, bn)
    return jax.lax.pmean(loss, axis)


# ---------------------------------------------------------------------------
# chunked-negatives variant
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def chunked_loss(x_l, y_l, log_tau, axis, interpret=None, bm=None, bn=None):
    """Global-batch contrastive loss, per-shard chunked-negatives scheme
    (shard-level; call inside shard_map — see module docstring).

    x_l, y_l: (B_local, D) fp32/bf16 unit-norm local blocks; log_tau:
    scalar fp32; axis: mesh axis name (or tuple). Each shard computes
    its row block of the global similarity structure by streaming the R
    gathered Y chunks through the single-pass fused kernel; column LSEs
    are psum-combined. Returns the replicated scalar fp32 loss; value
    and gradients match ``all_gather_loss`` (and the single-device fused
    loss at the same global batch) to fp32 tolerance, with per-device
    similarity work reduced R/2× and no (B_global, B_global) residency."""
    loss, _ = _chunked_fwd(x_l, y_l, log_tau, axis, interpret, bm, bn)
    return loss


def _chunked_fwd(x_l, y_l, log_tau, axis, interpret, bm, bn):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b_l = x_l.shape[0]
    inv_tau = jnp.exp(-log_tau)
    y_all = jax.lax.all_gather(y_l, axis, tiled=False)   # (R, B_local, D)
    if isinstance(axis, tuple):                          # (R1, R2, ...) -> (R,)
        y_all = y_all.reshape((-1,) + y_l.shape)

    def chunk(row_lse, y_r):
        rl_r, cl_r = ops.chunk_row_col_lse(x_l, y_r, inv_tau,
                                           interpret=interpret, bm=bm, bn=bn)
        return jnp.logaddexp(row_lse, rl_r), cl_r

    row_lse0 = jnp.full((b_l,), -jnp.inf, jnp.float32)
    row_lse, col_parts = jax.lax.scan(chunk, row_lse0, y_all)

    # combine partial col LSEs across shards: col_parts[r] holds, for the
    # columns of chunk r, log sum over THIS shard's rows; the global col
    # LSE is the stable log-psum-exp over shards
    m = jax.lax.pmax(col_parts, axis)
    col_lse = m + jnp.log(jax.lax.psum(jnp.exp(col_parts - m), axis))

    r_own = _linear_axis_index(axis)
    diag = jnp.sum(x_l.astype(jnp.float32) * y_l.astype(jnp.float32),
                   axis=1) * inv_tau
    col_own = jax.lax.dynamic_index_in_dim(col_lse, r_own, 0, keepdims=False)
    row_term = jax.lax.pmean(jnp.mean(row_lse - diag), axis)
    col_term = jax.lax.pmean(jnp.mean(col_own - diag), axis)
    loss = 0.5 * (row_term + col_term)
    return loss, (x_l, y_l, log_tau, row_lse, col_lse)


def _chunked_bwd(axis, interpret, bm, bn, res, g):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    x_l, y_l, log_tau, row_lse, col_lse = res
    b_l, d = x_l.shape
    inv_tau = jnp.exp(-log_tau)
    r_own = _linear_axis_index(axis)
    y_all = jax.lax.all_gather(y_l, axis, tiled=False)
    if isinstance(axis, tuple):
        y_all = y_all.reshape((-1,) + y_l.shape)
    n_shards = y_all.shape[0]                 # static: from the gathered shape
    b_g = n_shards * b_l

    def chunk(_, inp):
        y_r, cl_r = inp
        dx_r, dy_r, dtau_r = ops.chunk_grads(
            x_l, y_r, inv_tau, row_lse, cl_r, b_norm=b_g, with_diag=False,
            interpret=interpret, bm=bm, bn=bn)
        return None, (dx_r, dy_r, dtau_r)

    _, (dx_parts, dy_parts, dtau_parts) = jax.lax.scan(
        chunk, None, (y_all, col_lse))
    dx = jnp.sum(dx_parts, axis=0)
    dtau = jnp.sum(dtau_parts)

    # positive-pair (shard-diagonal) correction, fully local: the kernels
    # ran with with_diag=False, so add the -δ_ij/B_global term for the own
    # chunk: dA_ii -= 1/B_g  =>  dX_i -= y_i·τ⁻¹/B_g, dY_i -= x_i·τ⁻¹/B_g,
    # dτ_log += Σ_i a_ii/B_g
    xf = x_l.astype(jnp.float32)
    yf = y_l.astype(jnp.float32)
    diag = jnp.sum(xf * yf, axis=1) * inv_tau
    dx = dx - (inv_tau / b_g) * yf
    dy_parts = dy_parts.at[r_own].add(-(inv_tau / b_g) * xf)
    dtau = dtau + jnp.sum(diag) / b_g

    # each shard holds dY partials for ALL columns (from its rows);
    # psum-scatter sums across shards and hands each shard its own block
    dy = jax.lax.psum_scatter(dy_parts.reshape(b_g, d), axis, tiled=True)

    # check_vma=False boundary compensation (module docstring): the
    # incoming replicated-loss cotangent g is scaled 1/R per shard, and
    # the replicated log_tau's cotangent is psum'd by the unmapping — so
    # scale everything by R and return the LOCAL dτ contribution unpsum'd
    r = n_shards
    return ((r * g * dx).astype(x_l.dtype), (r * g * dy).astype(y_l.dtype),
            r * g * dtau)


chunked_loss.defvjp(_chunked_fwd, _chunked_bwd)


# ---------------------------------------------------------------------------
# jit-level drop-in for core.gradaccum
# ---------------------------------------------------------------------------

METHODS = ("allgather", "chunked")


def make_global_loss_fn(mesh, method: str = "chunked", *, data_axes=None,
                        interpret=None, bm=None, bn=None):
    """Build a ``loss_fn(x, y, tau) -> (loss, metrics)`` computing the
    cross-shard GLOBAL-batch contrastive loss, drop-in for
    ``core.gradaccum.contrastive_step(loss_fn=...)``.

    mesh: the jax Mesh the step runs under; method: 'allgather' or
    'chunked' (see module docstring); data_axes: mesh axis names the
    batch dim is sharded over (default: sharding.data_axes(mesh),
    restricted to axes present in the mesh). x, y are the logical
    (B_global, D) embedding arrays — GSPMD keeps them sharded over the
    data axes, shard_map hands each device its local block, and the
    collectives above do the rest. When the data extent is 1 the
    shard_map is skipped entirely and the single-device fused loss is
    returned (identical value/gradients — the distributed paths reduce
    to it). Metrics are zeros (same contract as fused_kernel_loss: the
    full-matrix argmax metric has no blockwise form).
    """
    if method not in METHODS:
        raise ValueError(f"method must be one of {METHODS}, got {method!r}")
    if data_axes is None:
        data_axes = tuple(a for a in shd.data_axes(mesh) if a in mesh.shape)
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]

    if n_shards == 1:
        from repro.core.contrastive import fused_kernel_loss

        def loss_fn_single(x, y, tau):
            return fused_kernel_loss(x, y, tau, interpret=interpret,
                                     bm=bm, bn=bn)
        return loss_fn_single

    axis = data_axes if len(data_axes) > 1 else data_axes[0]

    def local_fn(x_l, y_l, log_tau):
        if method == "allgather":
            return all_gather_loss(x_l, y_l, log_tau, axis=axis,
                                   interpret=interpret, bm=bm, bn=bn)
        return chunked_loss(x_l, y_l, log_tau, axis, interpret, bm, bn)

    mapped = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(data_axes), P(data_axes), P()),
                           out_specs=P(), check_vma=False)

    def loss_fn(x, y, tau):
        loss = mapped(x, y, jnp.log(tau))
        return loss, _zero_metrics()

    return loss_fn


def emb_sharding(mesh, data_axes=None):
    """NamedSharding for (B, D) embedding blocks: batch over the data
    axes, D replicated — the layout ``make_global_loss_fn`` expects and
    ``gradaccum.contrastive_step(emb_sharding=...)`` pins between the
    tower pass and the loss so GSPMD cannot re-gather the embeddings."""
    if data_axes is None:
        data_axes = tuple(a for a in shd.data_axes(mesh) if a in mesh.shape)
    return jax.sharding.NamedSharding(mesh, P(data_axes, None))
