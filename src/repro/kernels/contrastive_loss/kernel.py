"""Pallas TPU kernels: blockwise contrastive loss — B×B never hits HBM.

TPU adaptation of the paper's memory insight (DESIGN.md §2): Algorithm 1
stores the full similarity matrix (Θ(B²) = 16 GB at B=65536); here tiles of
X·Yᵀ live only in VMEM and row/column log-sum-exps are accumulated online
(flash-attention-style running max/sum), so HBM traffic is Θ(B·D).

Single-pass kernels (DESIGN.md §2.3) — the default path, 2 launches total:
  _fused_fwd_kernel : grid (nI, nJ) -> row LSE and col LSE in ONE sweep.
      Row LSE runs the usual online rescale over the inner j axis (row
      running max/sum live in VMEM scratch, finalized at j == nJ-1).
      Col LSE is carried across the OUTER i axis in VMEM scratch of
      shape (nJ, 1, bn): tile j updates slab j (a leading-dim index,
      which Mosaic addresses without any alignment proof), finalized
      into the resident output at i == nI-1.
  _fused_bwd_kernel : grid (nI, nJ) -> dX, dY, dlog_tau in ONE sweep.
      Each X·Yᵀ tile is computed once and contracted both ways: dX_i
      accumulates in its streamed output block over the inner j axis; dY
      accumulates slice-wise into a VMEM-resident (B, D) fp32 output
      (constant index map) across the outer i axis; dτ is a resident
      SMEM scalar. Versus the legacy 4-pass path this halves X·Yᵀ matmul
      FLOPs and roughly halves HBM reads of X/Y.

Legacy 4-pass kernels (kept for the perf-regression baseline in
benchmarks/kernel_bench.py; each a clean single-reduction grid):
  _row_lse_kernel : grid (nI, nJ) -> row LSE          (J inner, online LSE)
  _col_lse_kernel : grid (nJ, nI) -> col LSE          (I inner, online LSE)
  _dx_kernel      : grid (nI, nJ) -> dX rows + dlog_tau partials
  _dy_kernel      : grid (nJ, nI) -> dY rows

Backward recomputes each tile from (row_lse, col_lse):
  dA_ij = (exp(A_ij - row_lse_i) + exp(A_ij - col_lse_j) - 2·δ_ij) / (2B)

Inputs may be bf16 (fed straight to the MXU with fp32 accumulation via
``preferred_element_type``) or fp32.

Layouts (what Mosaic accepts on a real TPU): no block is 1-D. Row
statistics travel as (B, 1) columns in (bm, 1) blocks and column
statistics as (1, B) rows in (1, bn) blocks — the shapes a keepdims
reduction over the tile's lanes / sublanes produces, so no in-kernel
relayout. The scalar 1/τ is a (1, 1) SMEM input and dτ a (1, 1) SMEM
output. The public wrappers take and return (B,) vectors.

Block sizes are multiples of (8, 128) sublane×lane tiling; D is kept
whole in VMEM (embedding dims here are
≤ 2048 ⇒ X/Y tiles of bm×D ≤ 1 MB each). The VMEM footprint model behind
block selection is in ops.pick_blocks (DESIGN.md §2.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30

_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)     # (1, 1) fp32 scalar


def _scalar(v):
    return jnp.reshape(jnp.asarray(v, jnp.float32), (1, 1))


def _rows(bm, order="ij"):
    """(bm, 1) block of a (B, 1) row-statistic column, for grid order
    ``ij`` (i outer) or ``ji`` (j outer)."""
    if order == "ij":
        return pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    return pl.BlockSpec((bm, 1), lambda j, i: (i, 0))


def _cols(bn, order="ij"):
    """(1, bn) block of a (1, B) column-statistic row."""
    if order == "ij":
        return pl.BlockSpec((1, bn), lambda i, j: (0, j))
    return pl.BlockSpec((1, bn), lambda j, i: (0, j))


def _tile(x_ref, y_ref, inv_tau):
    """X_i · Y_jᵀ tile with fp32 MXU accumulation (bf16 inputs stay bf16)."""
    return jax.lax.dot_general(x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * inv_tau


def _contract(da, v_ref):
    """da · V tile; da is cast to the operand dtype so bf16 uses the MXU."""
    return jax.lax.dot_general(da.astype(v_ref.dtype), v_ref[...],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _online_update(m, s, a, axis):
    """One online-LSE step over ``axis`` of a; m/s keep their 2-D shape
    ((bm, 1) for axis=1, (1, bn) for axis=0)."""
    m_new = jnp.maximum(m, jnp.max(a, axis=axis, keepdims=True))
    s_new = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(a - m_new), axis=axis,
                                             keepdims=True)
    return m_new, s_new


# ---------------------------------------------------------------------------
# single-pass forward: row LSE + col LSE in one sweep
# ---------------------------------------------------------------------------


def _fused_fwd_kernel(x_ref, y_ref, inv_tau_ref, rlse_ref, clse_ref,
                      rm, rs, cm, cs, *, ni, nj):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init_row():
        rm[...] = jnp.full_like(rm, NEG)
        rs[...] = jnp.zeros_like(rs)

    @pl.when(i == 0)
    def _init_col():
        cm[j] = jnp.full(cm.shape[1:], NEG, jnp.float32)
        cs[j] = jnp.zeros(cs.shape[1:], jnp.float32)

    a = _tile(x_ref, y_ref, inv_tau_ref[0, 0])         # (bm, bn)

    rm[...], rs[...] = _online_update(rm[...], rs[...], a, axis=1)
    cm[j], cs[j] = _online_update(cm[j], cs[j], a, axis=0)

    @pl.when(j == nj - 1)
    def _finalize_row():
        rlse_ref[...] = rm[...] + jnp.log(rs[...])

    @pl.when(i == ni - 1)
    def _finalize_col():
        clse_ref[j] = cm[j] + jnp.log(cs[j])


def fwd_fused(x, y, inv_tau, *, bm=128, bn=128, interpret=False):
    """Single grid sweep -> (row_lse, col_lse), each (B,) fp32."""
    b, d = x.shape
    assert b % bm == 0 and b % bn == 0, (b, bm, bn)
    ni, nj = b // bm, b // bn

    rlse, clse = pl.pallas_call(
        functools.partial(_fused_fwd_kernel, ni=ni, nj=nj),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            _SCALAR,
        ],
        out_specs=[
            _rows(bm),
            pl.BlockSpec((nj, 1, bn), lambda i, j: (0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nj, 1, bn), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bm, 1), jnp.float32),       # row running max
            pltpu.VMEM((bm, 1), jnp.float32),       # row running sum
            pltpu.VMEM((nj, 1, bn), jnp.float32),   # col running max
            pltpu.VMEM((nj, 1, bn), jnp.float32),   # col running sum
        ],
        name="contrastive_fused_fwd",
        interpret=interpret,
    )(x, y, _scalar(inv_tau))
    return rlse.reshape(b), clse.reshape(b)


# ---------------------------------------------------------------------------
# single-pass backward: dX, dY, dlog_tau in one sweep
# ---------------------------------------------------------------------------


def _diag_mask(i, j, bm, bn):
    """2·δ_ij contribution for the (i, j) tile (global diagonal)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
    return (rows == cols).astype(jnp.float32)


def _dlogits(a, rlse_ref, clse_ref, i, j, *, bm, bn, b_norm, with_diag):
    """dLoss/dA for the (i, j) tile from the (bm, 1) row and (1, bn)
    column LSE blocks."""
    da = jnp.exp(a - rlse_ref[...]) + jnp.exp(a - clse_ref[...])
    if with_diag:
        da = da - 2.0 * _diag_mask(i, j, bm, bn)
    return da / (2.0 * b_norm)


def _fused_bwd_kernel(x_ref, y_ref, inv_tau_ref, rlse_ref, clse_ref,
                      dx_ref, dy_ref, dtau_ref, *, bm, bn, b_norm, with_diag):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init_dx():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    @pl.when((i == 0) & (j == 0))
    def _init_dtau():
        dtau_ref[0, 0] = 0.0

    inv_tau = inv_tau_ref[0, 0]
    a = _tile(x_ref, y_ref, inv_tau)
    da = _dlogits(a, rlse_ref, clse_ref, i, j, bm=bm, bn=bn, b_norm=b_norm,
                  with_diag=with_diag)

    dx_ref[...] += _contract(da, y_ref) * inv_tau
    dy_contrib = _contract(da.T, x_ref) * inv_tau
    sl = pl.ds(pl.multiple_of(j * bn, bn), bn)

    @pl.when(i == 0)
    def _dy_first():
        dy_ref[sl, :] = dy_contrib

    @pl.when(i > 0)
    def _dy_accum():
        dy_ref[sl, :] += dy_contrib

    dtau_ref[0, 0] += -jnp.sum(da * a)


def bwd_fused(x, y, inv_tau, row_lse, col_lse, *, bm=128, bn=128,
              interpret=False, b_norm=None, with_diag=True):
    """Single grid sweep -> (dX, dY, dlog_tau), gradients in fp32.

    ``b_norm`` overrides the 1/(2B) normalization batch (the GLOBAL batch
    when this kernel computes one remote-negative chunk of a cross-shard
    loss — core/distributed_loss.py); ``with_diag=False`` drops the
    -2·δ_ij positive-pair term, which only lives in the shard-diagonal
    chunk of the global matrix (DESIGN.md §7.2)."""
    b, d = x.shape
    assert b % bm == 0 and b % bn == 0, (b, bm, bn)
    ni, nj = b // bm, b // bn

    dx, dy, dtau = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, bm=bm, bn=bn,
                          b_norm=b if b_norm is None else b_norm,
                          with_diag=with_diag),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            _SCALAR,
            _rows(bm),
            _cols(bn),
        ],
        out_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((b, d), lambda i, j: (0, 0)),
            _SCALAR,
        ],
        out_shape=[jax.ShapeDtypeStruct((b, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        name="contrastive_fused_bwd",
        interpret=interpret,
    )(x, y, _scalar(inv_tau), row_lse.reshape(b, 1), col_lse.reshape(1, b))
    return dx, dy, dtau[0, 0]


# ---------------------------------------------------------------------------
# legacy 4-pass kernels (perf-regression baseline; see DESIGN.md §2.2)
# ---------------------------------------------------------------------------


def _row_lse_kernel(x_ref, y_ref, inv_tau_ref, m_ref, s_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    a = _tile(x_ref, y_ref, inv_tau_ref[0, 0])         # (bm, bn)
    m_ref[...], s_ref[...] = _online_update(m_ref[...], s_ref[...], a, axis=1)


def _col_lse_kernel(x_ref, y_ref, inv_tau_ref, m_ref, s_ref):
    i = pl.program_id(1)                              # grid = (nJ, nI)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    a = _tile(x_ref, y_ref, inv_tau_ref[0, 0])         # (bm, bn)
    m_ref[...], s_ref[...] = _online_update(m_ref[...], s_ref[...], a, axis=0)


def _dx_kernel(x_ref, y_ref, inv_tau_ref, rlse_ref, clse_ref,
               dx_ref, dtau_ref, *, bm, bn, b_norm, with_diag):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    @pl.when((i == 0) & (j == 0))
    def _init2():
        dtau_ref[0, 0] = 0.0

    inv_tau = inv_tau_ref[0, 0]
    a = _tile(x_ref, y_ref, inv_tau)
    da = _dlogits(a, rlse_ref, clse_ref, i, j, bm=bm, bn=bn, b_norm=b_norm,
                  with_diag=with_diag)
    dx_ref[...] += _contract(da, y_ref) * inv_tau
    dtau_ref[0, 0] += -jnp.sum(da * a)


def _dy_kernel(x_ref, y_ref, inv_tau_ref, rlse_ref, clse_ref, dy_ref,
               *, bm, bn, b_norm, with_diag):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dy_ref[...] = jnp.zeros_like(dy_ref)

    inv_tau = inv_tau_ref[0, 0]
    a = _tile(x_ref, y_ref, inv_tau)                   # (bm, bn)
    da = _dlogits(a, rlse_ref, clse_ref, i, j, bm=bm, bn=bn, b_norm=b_norm,
                  with_diag=with_diag)
    dy_ref[...] += _contract(da.T, x_ref) * inv_tau


def row_col_lse(x, y, inv_tau, *, bm=128, bn=128, interpret=False):
    b, d = x.shape
    assert b % bm == 0 and b % bn == 0, (b, bm, bn)
    ni, nj = b // bm, b // bn
    inv_tau = _scalar(inv_tau)

    rm, rs = pl.pallas_call(
        _row_lse_kernel,
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            _SCALAR,
        ],
        out_specs=[_rows(bm), _rows(bm)],
        out_shape=[jax.ShapeDtypeStruct((b, 1), jnp.float32)] * 2,
        name="contrastive_row_lse",
        interpret=interpret,
    )(x, y, inv_tau)
    row_lse = (rm + jnp.log(rs)).reshape(b)

    cm, cs = pl.pallas_call(
        _col_lse_kernel,
        grid=(nj, ni),
        in_specs=[
            pl.BlockSpec((bm, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
            _SCALAR,
        ],
        out_specs=[_cols(bn, "ji"), _cols(bn, "ji")],
        out_shape=[jax.ShapeDtypeStruct((1, b), jnp.float32)] * 2,
        name="contrastive_col_lse",
        interpret=interpret,
    )(x, y, inv_tau)
    col_lse = (cm + jnp.log(cs)).reshape(b)
    return row_lse, col_lse


def grads(x, y, inv_tau, row_lse, col_lse, *, bm=128, bn=128,
          interpret=False, b_norm=None, with_diag=True):
    """Two grid sweeps -> (dX, dY, dlog_tau), gradients in fp32 (legacy
    backward; ``b_norm``/``with_diag`` as in :func:`bwd_fused`)."""
    b, d = x.shape
    ni, nj = b // bm, b // bn
    inv_tau = _scalar(inv_tau)
    b_norm = b if b_norm is None else b_norm
    row_lse, col_lse = row_lse.reshape(b, 1), col_lse.reshape(1, b)

    dx, dtau = pl.pallas_call(
        functools.partial(_dx_kernel, bm=bm, bn=bn, b_norm=b_norm,
                          with_diag=with_diag),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            _SCALAR,
            _rows(bm),
            _cols(bn),
        ],
        out_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            _SCALAR,
        ],
        out_shape=[jax.ShapeDtypeStruct((b, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        name="contrastive_dx",
        interpret=interpret,
    )(x, y, inv_tau, row_lse, col_lse)

    dy = pl.pallas_call(
        functools.partial(_dy_kernel, bm=bm, bn=bn, b_norm=b_norm,
                          with_diag=with_diag),
        grid=(nj, ni),
        in_specs=[
            pl.BlockSpec((bm, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
            _SCALAR,
            _rows(bm, "ji"),
            _cols(bn, "ji"),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        name="contrastive_dy",
        interpret=interpret,
    )(x, y, inv_tau, row_lse, col_lse)
    return dx, dy, dtau[0, 0]
