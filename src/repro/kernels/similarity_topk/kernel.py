"""Pallas TPU kernel: blockwise similarity→top-k — (b, n) logits never hit HBM.

Open-vocabulary classification at serving time is one matmul against the
class-embedding matrix followed by a top-k (DESIGN.md §6.3). At the label
spaces this repo targets (10⁵ classes, reproducible-scaling-laws regime) the
(b, n_classes) logit matrix is the memory hot-spot — 4·b·n bytes that are
reduced to k numbers per row immediately after being written. This kernel
fuses the two: logits are computed tile-by-tile in VMEM and a RUNNING top-k
per image row is carried in VMEM scratch across the class axis, so HBM
traffic is Θ(b·d + n·d + b·k).

Grid (nI, nJ), j (class blocks) innermost, TPU grids execute sequentially
row-major:

  - per tile: A_ij = X_i · C_jᵀ · inv_tau (MXU, fp32 accumulation; bf16
    inputs stay bf16 on the wires),
  - the (bm, k) running top-k (values + global class indices) lives in VMEM
    scratch, re-initialized at j==0 and merged with each tile via k rounds
    of select-max-then-retire over the (bm, k+bc) candidate pool,
  - at j==nJ−1 the scratch is flushed to the streamed (bm, k) outputs.

Ordering contract (matches ref.py exactly): descending by value, ties broken
by LOWER class index — each select round picks the smallest index among the
columns achieving the row max, then retires that single candidate by index.
Padded class columns (n not divisible by bc) carry value NEG and are never
selected while ≥ k real candidates remain, which ``ops.similarity_topk``
guarantees by requiring k ≤ min(n_classes, bc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30          # sentinel: below any real similarity (unit-ish inputs)
IDX_PAD = 2 ** 30    # sentinel index: above any real class id


def _tile(x_ref, c_ref, inv_tau):
    """X_i · C_jᵀ tile with fp32 MXU accumulation (bf16 inputs stay bf16)."""
    return jax.lax.dot_general(x_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * inv_tau


def _merge_topk(vals, idx, cand_v, cand_i, k):
    """Top-k of the candidate pool [running top-k | new tile], ties to the
    lower index. k static → the select/retire rounds unroll."""
    cand_v = jnp.concatenate([vals, cand_v], axis=1)
    cand_i = jnp.concatenate([idx, cand_i], axis=1)
    out_v, out_i = [], []
    for _ in range(k):
        m = jnp.max(cand_v, axis=1)                            # (bm,)
        at_max = cand_v == m[:, None]
        sel = jnp.min(jnp.where(at_max, cand_i, IDX_PAD), axis=1)
        out_v.append(m)
        out_i.append(sel)
        cand_v = jnp.where(cand_i == sel[:, None], NEG, cand_v)
    return jnp.stack(out_v, axis=1), jnp.stack(out_i, axis=1)


def _topk_kernel(x_ref, c_ref, inv_tau_ref, n_valid_ref, vals_ref, idx_ref,
                 vscr, iscr, *, bc, k, nj):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vscr[...] = jnp.full_like(vscr, NEG)
        iscr[...] = jnp.full_like(iscr, IDX_PAD)

    a = _tile(x_ref, c_ref, inv_tau_ref[0])                    # (bm, bc)
    col = j * bc + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    a = jnp.where(col < n_valid_ref[0], a, NEG)                # mask padding

    vscr[...], iscr[...] = _merge_topk(vscr[...], iscr[...], a, col, k)

    @pl.when(j == nj - 1)
    def _emit():
        vals_ref[...] = vscr[...]
        idx_ref[...] = iscr[...]


def topk_fused(x, c, inv_tau, *, k, bm, bc, n_classes, n_valid=None,
               interpret=False):
    """One grid sweep -> (values (b, k) fp32, indices (b, k) int32).

    x: (b, d) with b % bm == 0; c: (n_pad, d) with n_pad % bc == 0 and
    rows ≥ n_classes zero-padded (masked by index inside the kernel).
    ``n_valid`` optionally overrides the static ``n_classes`` mask with a
    TRACED scalar (the sharded serving path masks each shard's padded tail
    with a value computed from the shard index at run time); columns ≥ the
    mask carry value NEG, so when fewer than k valid columns exist the tail
    of the output is (NEG, <masked col id>) — callers that shard must
    retire those by value (see serving/retrieval/sharded.py).
    """
    b, d = x.shape
    n_pad = c.shape[0]
    assert b % bm == 0 and n_pad % bc == 0, (b, bm, n_pad, bc)
    ni, nj = b // bm, n_pad // bc
    inv_tau = jnp.asarray([inv_tau], jnp.float32)
    n_valid = jnp.asarray(n_classes if n_valid is None else n_valid,
                          jnp.int32).reshape((1,))

    return pl.pallas_call(
        functools.partial(_topk_kernel, bc=bc, k=k, nj=nj),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((bm, k), jnp.float32),   # running top-k values
            pltpu.VMEM((bm, k), jnp.int32),     # running top-k class ids
        ],
        name="similarity_topk",
        interpret=interpret,
    )(x, c, inv_tau, n_valid)
