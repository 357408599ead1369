"""Jitted public op: fused contrastive loss with custom VJP.

``fused_contrastive_loss(x, y, log_tau)`` matches ``ref.loss_ref`` and its
gradients match ``ref.contrastive_grads_ref`` (asserted over shape/dtype
sweeps in tests/test_kernels.py) while keeping the B×B similarity matrix out
of HBM. The forward is ONE Pallas sweep (row+col LSE together) and the
backward is ONE sweep (dX, dY, dτ together) — see DESIGN.md §2.3.

Block sizes are chosen by ``pick_blocks`` — a VMEM-footprint-model autotuner
(DESIGN.md §2.4) preferring (bm, bn) ∈ {128, 256, 512}×{128, 256} — and can
be overridden explicitly via the ``bm``/``bn`` arguments, e.g. with a pair
returned by the optional timed sweep ``autotune_blocks(..., timed=True)``.
bf16 inputs are fed straight to the kernels (fp32 accumulation inside).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro.kernels.contrastive_loss import kernel

# Candidate block edges, largest first. {128, 256, 512}×{128, 256} are the
# MXU-friendly preferred pairs; smaller powers of two keep tiny (test-sized)
# batches on the blockwise path.
_BM_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)
_BN_CANDIDATES = (256, 128, 64, 32, 16, 8)

# Per-step VMEM budget for the block-dependent working set. Real TPU cores
# have ~16 MB of VMEM; 8 MiB leaves headroom for the full-kernel residents
# (col accumulators 2·B·4 bytes in fwd, the dY carrier B·D·4 bytes in bwd —
# see DESIGN.md §2.4 for the capacity discussion).
DEFAULT_VMEM_BUDGET = 8 * 2**20

_AUTOTUNE_CACHE: dict = {}

# Approximate compiled-mode VMEM capacity per core, minus slack. The fused
# backward keeps a (B, D) fp32 dY carrier resident for the whole sweep
# (DESIGN.md §2.3); when carrier + block working set can't fit, the compiled
# path falls back to the legacy two-sweep backward (3 launches total).
# Checked against Mosaic for TPU v5e (tests/test_tpu_compile.py): every
# shape this rule accepts compiles, and the first refusals (D=512 fp32
# B=6144, D=1024 B=3072) are rejected by it.
_VMEM_TOTAL_APPROX = 14 * 2**20


def bwd_fits_fused(b: int, d: int, bm: int, bn: int, itemsize: int) -> bool:
    """True when the single-pass backward's VMEM residency is compilable:
    the (B, D) fp32 dY carrier plus the per-step block working set."""
    return block_bytes(bm, bn, d, itemsize) + b * d * 4 <= _VMEM_TOTAL_APPROX


def backward_sweep(b: int, d: int, itemsize: int, *, bm: int | None = None,
                   bn: int | None = None, interpret: bool = False) -> str:
    """Which backward the loss runs at this shape: ``"fused"`` (one sweep,
    resident dY carrier) or ``"legacy"`` (two sweeps). Interpret mode has
    no VMEM limit and always takes the fused sweep."""
    bm, bn = pick_blocks(b, d, itemsize, bm=bm, bn=bn)
    if interpret or bwd_fits_fused(b, d, bm, bn, itemsize):
        return "fused"
    return "legacy"


def block_bytes(bm: int, bn: int, d: int, itemsize: int) -> int:
    """Block-dependent VMEM bytes per grid step (worst pass = backward):
    double-buffered X/Y tiles, ~4 fp32 tile temporaries (A, p_row, p_col,
    dA), the streamed dX block, and the per-block LSE slices."""
    stream = 2 * (bm + bn) * d * itemsize
    tiles = 4 * bm * bn * 4
    dx_out = 2 * bm * d * 4
    lse = (bm + bn) * 4
    return stream + tiles + dx_out + lse


def pick_blocks(b: int, d: int, itemsize: int = 4, *,
                bm: int | None = None, bn: int | None = None,
                vmem_budget: int = DEFAULT_VMEM_BUDGET) -> tuple[int, int]:
    """Pick (bm, bn) by the VMEM footprint model; explicit overrides win.

    Raises ValueError when B is not a multiple of 8 — a 1×1 grid would
    silently defeat the blockwise design (pad the batch instead).
    """
    if b % 8 != 0:
        raise ValueError(
            f"contrastive kernel batch size must be a multiple of 8, got "
            f"B={b}; pad the batch to {-(-b // 8) * 8} (the blockwise grid "
            f"needs sublane-aligned tiles; see DESIGN.md §2.4)")
    if bm is not None and (b % bm != 0 or bm % 8 != 0):
        raise ValueError(f"bm={bm} must divide B={b} and be a multiple of 8")
    if bn is not None and (b % bn != 0 or bn % 8 != 0):
        raise ValueError(f"bn={bn} must divide B={b} and be a multiple of 8")
    if bm is not None and bn is not None:
        return bm, bn

    bms = (bm,) if bm is not None else \
        tuple(c for c in _BM_CANDIDATES if b % c == 0)
    bns = (bn,) if bn is not None else \
        tuple(c for c in _BN_CANDIDATES if b % c == 0)

    best = None
    for cm in bms:
        for cn in bns:
            fits = block_bytes(cm, cn, d, itemsize) <= vmem_budget
            # prefer: fits with the largest tile area (widest lanes as the
            # tie-break); if nothing fits, the smallest footprint wins
            score = (fits, cm * cn if fits else -cm * cn, cn)
            if best is None or score > best[0]:
                best = (score, (cm, cn))
    return best[1]


def autotune_blocks(b: int, d: int, dtype=jnp.float32, *, timed: bool = False,
                    interpret: bool = False, iters: int = 2,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET) -> tuple[int, int]:
    """Return (bm, bn) for the fused kernels at shape (B, D).

    With ``timed=False`` this is just the VMEM model (``pick_blocks``). With
    ``timed=True`` every model-feasible candidate pair is benchmarked
    (jit-compiled fwd+bwd on random data) and the fastest wins; results are
    cached per (B, D, dtype, interpret, backend).
    """
    itemsize = jnp.dtype(dtype).itemsize
    if not timed:
        return pick_blocks(b, d, itemsize, vmem_budget=vmem_budget)

    key = (b, d, jnp.dtype(dtype).name, interpret, jax.default_backend(),
           vmem_budget, iters)
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]

    model_pick = pick_blocks(b, d, itemsize,
                             vmem_budget=vmem_budget)  # raises on bad B
    cands = [(cm, cn) for cm in _BM_CANDIDATES if b % cm == 0
             for cn in _BN_CANDIDATES if b % cn == 0
             if block_bytes(cm, cn, d, itemsize) <= vmem_budget]
    if not cands:
        cands = [model_pick]
    k1, k2 = jax.random.split(jax.random.key(0))
    x = jax.random.normal(k1, (b, d), jnp.float32).astype(dtype)
    y = jax.random.normal(k2, (b, d), jnp.float32).astype(dtype)
    log_tau = jnp.asarray(-1.0)

    best = None
    for cm, cn in cands:
        fn = jax.jit(jax.grad(
            lambda x, y, t, cm=cm, cn=cn: fused_contrastive_loss(
                x, y, t, interpret, cm, cn)))
        jax.block_until_ready(fn(x, y, log_tau))     # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(x, y, log_tau))
        dt = (time.perf_counter() - t0) / iters
        if best is None or dt < best[0]:
            best = (dt, (cm, cn))
    _AUTOTUNE_CACHE[key] = best[1]
    return best[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_contrastive_loss(x, y, log_tau, interpret=False, bm=None, bn=None):
    """Paper Eq. 3 contrastive loss via the single-pass fused kernels.

    x, y: (B, D) fp32/bf16 unit-norm embeddings (B % 8 == 0); log_tau:
    scalar fp32. Returns the scalar fp32 loss; differentiable via a
    custom VJP whose backward is one more Pallas sweep (dX/dY in the
    input dtype, dlog_tau fp32). interpret/bm/bn are static overrides
    (see module docstring)."""
    loss, _ = _fwd(x, y, log_tau, interpret, bm, bn)
    return loss


def _fwd(x, y, log_tau, interpret, bm, bn):
    b, d = x.shape
    bm, bn = pick_blocks(b, d, x.dtype.itemsize, bm=bm, bn=bn)
    inv_tau = jnp.exp(-log_tau)
    row_lse, col_lse = kernel.fwd_fused(x, y, inv_tau, bm=bm, bn=bn,
                                        interpret=interpret)
    diag = jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32),
                   axis=1) * inv_tau
    loss = 0.5 * (jnp.mean(row_lse - diag) + jnp.mean(col_lse - diag))
    return loss, (x, y, log_tau, row_lse, col_lse)


def _bwd(interpret, bm, bn, res, g):
    x, y, log_tau, row_lse, col_lse = res
    b, d = x.shape
    bm, bn = pick_blocks(b, d, x.dtype.itemsize, bm=bm, bn=bn)
    inv_tau = jnp.exp(-log_tau)
    if backward_sweep(b, d, x.dtype.itemsize, bm=bm, bn=bn,
                      interpret=interpret) == "fused":
        dx, dy, dtau = kernel.bwd_fused(x, y, inv_tau, row_lse, col_lse,
                                        bm=bm, bn=bn, interpret=interpret)
    else:
        dx, dy, dtau = kernel.grads(x, y, inv_tau, row_lse, col_lse,
                                    bm=bm, bn=bn, interpret=interpret)
    return ((g * dx).astype(x.dtype), (g * dy).astype(y.dtype), g * dtau)


fused_contrastive_loss.defvjp(_fwd, _bwd)


def fused_loss_and_lse(x, y, log_tau, interpret=False, bm=None, bn=None):
    """Non-VJP entry returning (loss, row_lse, col_lse) for diagnostics.

    x, y: (B, D) fp32/bf16 unit-norm embeddings; log_tau: scalar fp32.
    Returns (scalar fp32 loss, (B,) fp32 row LSE, (B,) fp32 col LSE)."""
    loss, (_, _, _, row_lse, col_lse) = _fwd(x, y, log_tau, interpret, bm, bn)
    return loss, row_lse, col_lse


def chunk_row_col_lse(x, y_chunk, inv_tau, interpret=False, bm=None, bn=None):
    """Blockwise row/col LSE of one square similarity chunk X·Y_chunkᵀ/τ.

    The streaming unit of the cross-shard chunked-negatives loss
    (core/distributed_loss.py, DESIGN.md §7.2): ``x`` is the shard's local
    (B_local, D) block, ``y_chunk`` one remote shard's (B_local, D) block.
    Returns ((B_local,) fp32 partial row LSE over this chunk's columns,
    (B_local,) fp32 partial col LSE over this chunk's rows); the caller
    logaddexp-combines row partials across chunks and psum-combines col
    partials across shards. One Pallas launch, no (B, B) materialization."""
    b, d = x.shape
    bm, bn = pick_blocks(b, d, x.dtype.itemsize, bm=bm, bn=bn)
    return kernel.fwd_fused(x, y_chunk, inv_tau, bm=bm, bn=bn,
                            interpret=interpret)


def chunk_grads(x, y_chunk, inv_tau, row_lse, col_lse_chunk, *, b_norm,
                with_diag=False, interpret=False, bm=None, bn=None):
    """dX/dY/dτ contribution of one square chunk of the cross-shard loss.

    x, y_chunk: (B_local, D); row_lse: (B_local,) GLOBAL row LSE of the
    local rows; col_lse_chunk: (B_local,) GLOBAL col LSE of this chunk's
    columns; b_norm: the GLOBAL batch size (1/(2·B_global) normalization).
    ``with_diag`` is True only for the shard-diagonal chunk, where the
    positive pairs live. Returns ((B_local, D) fp32 dX partial,
    (B_local, D) fp32 dY partial for this chunk's columns, scalar fp32
    dlog_tau partial). Uses the single-pass fused backward when its VMEM
    residency fits, else the legacy two-sweep kernels (same fallback rule
    as the square loss, DESIGN.md §2.3)."""
    b, d = x.shape
    bm, bn = pick_blocks(b, d, x.dtype.itemsize, bm=bm, bn=bn)
    if backward_sweep(b, d, x.dtype.itemsize, bm=bm, bn=bn,
                      interpret=interpret) == "fused":
        return kernel.bwd_fused(x, y_chunk, inv_tau, row_lse, col_lse_chunk,
                                bm=bm, bn=bn, interpret=interpret,
                                b_norm=b_norm, with_diag=with_diag)
    return kernel.grads(x, y_chunk, inv_tau, row_lse, col_lse_chunk,
                        bm=bm, bn=bn, interpret=interpret,
                        b_norm=b_norm, with_diag=with_diag)


def fused_loss_and_lse_4pass(x, y, log_tau, interpret=False, bm=None,
                             bn=None):
    """Legacy 2-launch forward (separate row and col LSE sweeps), kept as
    the comparison baseline for benchmarks/kernel_bench.py. Returns
    (loss, row_lse, col_lse)."""
    b, d = x.shape
    bm, bn = pick_blocks(b, d, x.dtype.itemsize, bm=bm, bn=bn)
    inv_tau = jnp.exp(-log_tau)
    row_lse, col_lse = kernel.row_col_lse(x, y, inv_tau, bm=bm, bn=bn,
                                          interpret=interpret)
    diag = jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32),
                   axis=1) * inv_tau
    loss = 0.5 * (jnp.mean(row_lse - diag) + jnp.mean(col_lse - diag))
    return loss, row_lse, col_lse


def fused_contrastive_loss_4pass(x, y, log_tau, interpret=False,
                                 bm=None, bn=None):
    """Legacy 4-launch path (2 fwd + 2 bwd sweeps), kept as the comparison
    baseline for benchmarks/kernel_bench.py. Not differentiable; returns
    (loss, dx, dy, dtau) directly."""
    b, d = x.shape
    bm, bn = pick_blocks(b, d, x.dtype.itemsize, bm=bm, bn=bn)
    loss, row_lse, col_lse = fused_loss_and_lse_4pass(x, y, log_tau,
                                                      interpret, bm, bn)
    inv_tau = jnp.exp(-log_tau)
    dx, dy, dtau = kernel.grads(x, y, inv_tau, row_lse, col_lse,
                                bm=bm, bn=bn, interpret=interpret)
    return loss, dx, dy, dtau
