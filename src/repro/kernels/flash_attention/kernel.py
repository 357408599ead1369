"""Pallas TPU flash attention: online-softmax forward + blockwise backward.

Forward grid: (batch*q_heads, q_blocks, k_blocks) — k innermost so the output
block and the running (max, sum) scratch persist across the reduction. The
forward also emits the per-row LSE (m + log l) consumed by the backward
kernels. Causal, sliding-window and *bidirectional* masks are applied from
global indices (the BASIC encoder towers run causal=False); an optional
additive key bias (one row per batch*head, e.g. -inf on padded text
positions) rides in as a (1, block_k) tile. GQA is handled by the ops.py
wrapper mapping each q head to its kv group.

Per-row vectors (key bias, LSE, delta) live in HBM as (bh, 1, n) arrays
read through (None, 1, block) blocks: lane-dense, and legal for Mosaic's
(8, 128) rule on the last two block dims. The running max/sum scratch is
(block_q, 1), and LSE/delta cross between the two orientations with a
2-D transpose.

Backward is the standard two-kernel flash split over the same tiles:
  dq  grid (bh, q_blocks, k_blocks), k innermost — dQ accumulates in VMEM
  dkv grid (bh, k_blocks, q_blocks), q innermost — dK/dV accumulate in VMEM
Both recompute the probability tile from (q, k, lse) instead of loading a
stored (s, t) matrix, so no attention matrix ever exists in HBM in either
direction. All tiles accumulate in fp32 regardless of input dtype
(bf16-in/fp32-accum, matching the PR-1 kernel conventions).

Block shapes are (block_q, head_dim) / (block_k, head_dim) — MXU-aligned
multiples of 128 for real TPU shapes; head_dim is kept whole. Every query
row must attend to at least one key (guaranteed by causal self-attention
and by ≥1-valid-token padding masks); fully-masked rows would produce
garbage rather than NaN-safe zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _vec(x):
    """(bh, n) per-row vector -> the (bh, 1, n) fp32 kernel layout."""
    if x is None:
        return None
    return x.astype(jnp.float32).reshape(x.shape[0], 1, x.shape[1])


def _vec_spec(block, axis, kv_major=False):
    """(1, block) tile of a (bh, 1, n) vector, indexed by the q-block
    (``axis="i"``) or k-block (``"j"``) grid coordinate. The forward and
    dq grids are (bh, i, j); the dkv grid is (bh, j, i) (``kv_major``)."""
    if kv_major:
        pick = (lambda b, j, i: (b, 0, i)) if axis == "i" else \
            (lambda b, j, i: (b, 0, j))
    else:
        pick = (lambda b, i, j: (b, 0, i)) if axis == "i" else \
            (lambda b, i, j: (b, 0, j))
    return pl.BlockSpec((None, 1, block), pick)


def _tile_mask(shape, qi, ki, block_q, block_k, causal, window, seq_k):
    """Boolean validity mask of one (block_q, block_k) score tile from the
    tile's global row/col offsets."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + qi * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + ki * block_k
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= (rows - cols) < window
    mask &= cols < seq_k
    return mask


def _fwd_kernel(*refs, scale, block_q, block_k, causal, window, seq_k,
                has_bias):
    if has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        b_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale               # (bq, d)
    k = k_ref[0].astype(jnp.float32)                       # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if b_ref is not None:
        s = s + b_ref[...]                                 # (1, bk)
    mask = _tile_mask(s.shape, qi, ki, block_q, block_k, causal, window,
                      seq_k)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T          # (1, bq)


def flash_fwd_bh(q, k, v, bias=None, *, causal=True, window=None,
                 block_q=128, block_k=128, interpret=False):
    """Forward pass on flattened heads. q: (bh, s, d); k/v: (bh, t, d);
    bias: optional (bh, t) fp32 additive key bias. Returns (out (bh, s, d)
    in q.dtype, lse (bh, s) fp32)."""
    bh, s, d = q.shape
    t = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    grid = (bh, s // block_q, t // block_k)
    scale = d ** -0.5

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_vec_spec(block_k, "j"))
        args.append(_vec(bias))

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          seq_k=t, has_bias=bias is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _vec_spec(block_q, "i"),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out, lse.reshape(bh, s)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _recompute_p_ds(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, d_ref,
                    qi, ki, scale, block_q, block_k, causal, window, seq_k):
    """Shared tile recomputation for both backward kernels: rebuild the
    probability tile p from (q·k, lse) and form ds = p * (do·v - delta).
    Returns q already scaled by d^-1/2 (so dsᵀ·q IS dk)."""
    q = q_ref[0].astype(jnp.float32) * scale               # (bq, d)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if b_ref is not None:
        s = s + b_ref[...]                                 # (1, bk)
    mask = _tile_mask(s.shape, qi, ki, block_q, block_k, causal, window,
                      seq_k)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_ref[...].T)                        # (bq, bk)
    do = do_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_ref[...].T)
    return q, p, do, ds


def _dq_kernel(*refs, scale, block_q, block_k, causal, window, seq_k,
               has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, d_ref, dq_ref,
         acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_scr = refs
        b_ref = None
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _, _, _, ds = _recompute_p_ds(q_ref, k_ref, v_ref, b_ref, do_ref,
                                  lse_ref, d_ref, qi, ki, scale, block_q,
                                  block_k, causal, window, seq_k)
    acc_scr[...] += jax.lax.dot_general(
        ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, block_q, block_k, causal, window, seq_k,
                has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
         dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
         dk_scr, dv_scr) = refs
        b_ref = None
    ki, qi = pl.program_id(1), pl.program_id(2)   # grid = (bh, nk, nq)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q, p, do, ds = _recompute_p_ds(q_ref, k_ref, v_ref, b_ref, do_ref,
                                   lse_ref, d_ref, qi, ki, scale, block_q,
                                   block_k, causal, window, seq_k)
    # q arrives pre-scaled by d^-1/2, so dsᵀ·q IS dk (no extra scale)
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd_bh(q, k, v, bias, out, lse, dout, *, causal=True, window=None,
                 block_q=128, block_k=128, interpret=False):
    """Backward pass on flattened heads: returns (dq, dk, dv) in the input
    dtypes. Recomputes probability tiles from (q, k, lse); ``delta`` —
    rowsum(dout·out) — is formed in XLA (one fused elementwise+reduce)."""
    bh, s, d = q.shape
    t = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = d ** -0.5
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # (bh, s)

    has_bias = bias is not None
    common = dict(scale=scale, block_q=block_q, block_k=block_k,
                  causal=causal, window=window, seq_k=t, has_bias=has_bias)

    lse, delta, bias = _vec(lse), _vec(delta), _vec(bias)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec_j = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    row_spec = _vec_spec(block_q, "i")

    dq_in_specs = [q_spec, kv_spec_j, kv_spec_j]
    dq_args = [q, k, v]
    if has_bias:
        dq_in_specs.append(_vec_spec(block_k, "j"))
        dq_args.append(bias)
    dq_in_specs += [q_spec, row_spec, row_spec]
    dq_args += [dout, lse, delta]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, s // block_q, t // block_k),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*dq_args)

    # dkv grid: (bh, k_blocks, q_blocks) — index_map args are (b, j, i)
    q_spec_i = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row_spec_i = _vec_spec(block_q, "i", kv_major=True)

    dkv_in_specs = [q_spec_i, kv_spec, kv_spec]
    dkv_args = [q, k, v]
    if has_bias:
        dkv_in_specs.append(_vec_spec(block_k, "j", kv_major=True))
        dkv_args.append(bias)
    dkv_in_specs += [q_spec_i, row_spec_i, row_spec_i]
    dkv_args += [dout, lse, delta]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, t // block_k, s // block_q),
        in_specs=dkv_in_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(*dkv_args)
    return dq, dk, dv


def flash_attention_bh(q, k, v, *, causal=True, window=None, block_q=128,
                       block_k=128, interpret=False):
    """Forward-only convenience (the pre-backward public entry point):
    q: (bh, s, d); k/v: (bh, t, d) — heads already broadcast/flattened."""
    out, _ = flash_fwd_bh(q, k, v, None, causal=causal, window=window,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out
