"""Stage A: mesh-sharded similarity→top-k (DESIGN.md §13.1).

The PR-2 fused kernel never materializes the (b, n) logit matrix but is
single-device: at planet scale (10M+ gallery/class rows) one device can
neither hold the class matrix in HBM nor sweep it at interactive latency.
This module shards the class axis over the mesh's data axes (reusing the
``core/sharding`` axis conventions) and runs the fused kernel PER SHARD
inside ``shard_map``, each shard sweeping only its n/S rows:

  1. per shard: ``ops.similarity_topk`` over the local (n_local, d) block
     with a TRACED ``n_valid`` mask (the last shard's zero-padded tail is
     only known from the shard index), emitting (b, k) local winners whose
     indices are lifted to GLOBAL ids by the shard's row offset;
  2. combine: all-gather of the (b, k) per-shard candidates along the data
     axes — a psum-free top-k-of-top-k — then one ``ops.merge_topk``
     select-max-retire pass over the (b, S·k) pool.

Exactness argument (pinned by tests/distributed_checks.py ``retrieval``
against the stable-argsort oracle): every logit is a single fp32-accumulated
dot of one query row with one class row — identical arithmetic whichever
shard computes it — and a global top-k winner is necessarily inside its own
shard's top-k (at most k-1 better rows exist anywhere). The merge rule
(descending value, ties to the LOWER global id, retire-by-id) is the
kernel's own and is order-independent, so merging per-shard top-ks is
bit-identical to the single-device sweep, duplicates and ties included.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sharding as shd
from repro.kernels.similarity_topk import ops as topk_ops
from repro.kernels.similarity_topk.kernel import IDX_PAD, NEG
from repro.launch.mesh import make_mesh


def default_data_mesh(n_devices: Optional[int] = None):
    """A 1-D ('data',) mesh over the first ``n_devices`` local devices
    (all of them by default) — the serving-side default when no training
    mesh is passed in."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    return make_mesh((n,), (shd.DATA,), devices=devs[:n])


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _linear_index(axes):
    """Row-major linear shard index over the (possibly multi-) data axes —
    the same composition ``jax.lax.all_gather`` uses for a tuple axis, so
    gathered blocks land at this index."""
    if isinstance(axes, str):
        return jax.lax.axis_index(axes)
    idx = jnp.int32(0)
    for name in axes:
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


@dataclasses.dataclass(frozen=True)
class ShardedMatrix:
    """A device-resident class/gallery matrix, row-sharded over the mesh's
    data axes and padded so every shard holds ``n_local`` rows (the tail
    shard's padding is masked at query time via the kernel's ``n_valid``).
    Build once via ``shard_matrix``; every ``sharded_similarity_topk`` call
    against it then pays zero host→device transfer and zero resharding."""
    array: jax.Array     # (S * n_local, d), sharded P(axes) on dim 0
    n: int               # real (unpadded) row count
    n_local: int         # rows per shard (>= MAX_K)
    mesh: object
    axes: tuple          # data axis names the rows are split over

    @property
    def n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))


def shard_matrix(matrix, mesh=None, *, data_axes=None) -> ShardedMatrix:
    """Pad ``matrix`` (n, d) to S·n_local rows and lay it over ``mesh``'s
    data axes (``n_local >= MAX_K`` so any legal k fits inside one shard).
    The zero padding is never scored: query-time masking via ``n_valid``
    keeps it at the NEG sentinel."""
    if mesh is None:
        mesh = default_data_mesh()
    if data_axes is None:
        data_axes = tuple(a for a in shd.data_axes(mesh) if a in mesh.shape)
    s = int(np.prod([mesh.shape[a] for a in data_axes]))
    n, d = np.shape(matrix)
    n_local = max(-(-n // s), topk_ops.MAX_K)
    n_pad = s * n_local
    m = jnp.asarray(matrix)
    if n_pad != n:
        m = jnp.pad(m, ((0, n_pad - n), (0, 0)))
    sharding = NamedSharding(mesh, P(data_axes))
    return ShardedMatrix(jax.device_put(m, sharding), int(n), int(n_local),
                         mesh, tuple(data_axes))


def sharded_similarity_topk(query_emb, class_emb, k: int, *, mesh=None,
                            inv_tau=1.0, data_axes=None,
                            bm: Optional[int] = None,
                            bc: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Mesh-sharded drop-in for ``ops.similarity_topk`` (bit-identical
    output, tests pin it): per-shard fused sweeps + the psum-free
    top-k-of-top-k combine.

    query_emb: (b, d) host or device array (replicated to every shard);
    class_emb: a ``ShardedMatrix`` (the no-per-call-upload path) or a raw
    (n, d) array (sharded here on the fly). Returns (values (b, k) fp32,
    indices (b, k) int32). A 1-extent data mesh degenerates to the
    single-device kernel.
    """
    if not isinstance(class_emb, ShardedMatrix):
        class_emb = shard_matrix(class_emb, mesh, data_axes=data_axes)
    sm = class_emb
    n, d = sm.n, sm.array.shape[1]
    b, dq = np.shape(query_emb)
    if dq != d:
        raise ValueError(f"embed dims differ: query {dq} vs class {d}")
    k = int(k)
    if not 1 <= k <= n:
        raise ValueError(f"k={k} must be in [1, n={n}]")
    if k > topk_ops.MAX_K:
        raise ValueError(f"k={k} > MAX_K={topk_ops.MAX_K}")
    s = sm.n_shards
    if s == 1:
        return topk_ops.similarity_topk(
            jnp.asarray(query_emb), sm.array[:n], k, inv_tau=inv_tau,
            bm=bm, bc=bc, interpret=interpret)

    axis = sm.axes if len(sm.axes) > 1 else sm.axes[0]
    n_local = sm.n_local

    def local_fn(x, c_l):
        r = _linear_index(axis)
        offset = r * n_local
        n_valid = jnp.clip(n - offset, 0, n_local)
        v, i = topk_ops.similarity_topk(x, c_l, k, inv_tau=inv_tau,
                                        bm=bm, bc=bc, n_valid=n_valid,
                                        interpret=interpret)
        # lift to global ids; a shard with < k valid rows emits NEG-valued
        # tail entries whose ids must not alias real rows in the combine
        gi = i + offset
        dead = v <= NEG / 2
        gi = jnp.where(dead, IDX_PAD, gi)
        v = jnp.where(dead, NEG, v)
        # psum-free combine: gather everyone's (b, k) winners, one
        # select-max-retire pass over the (b, S*k) pool on every shard
        vg = jax.lax.all_gather(v, axis, tiled=False)       # (S, b, k)
        ig = jax.lax.all_gather(gi, axis, tiled=False)
        pool_v = jnp.moveaxis(vg, 0, 1).reshape(v.shape[0], -1)
        pool_i = jnp.moveaxis(ig, 0, 1).reshape(v.shape[0], -1)
        return topk_ops.merge_topk(pool_v, pool_i, k)

    mapped = jax.shard_map(local_fn, mesh=sm.mesh,
                           in_specs=(P(), P(axis)), out_specs=(P(), P()),
                           check_vma=False)
    x = jnp.asarray(query_emb)
    with sm.mesh:
        vals, idx = jax.jit(mapped)(x, sm.array)
    return vals, idx


def shard_winner_shares(indices, sm: ShardedMatrix) -> np.ndarray:
    """Per-shard share of the final top-k winners — the load-skew signal
    the serving telemetry histograms (`serve/retrieval_shard_share`).
    Returns (S,) fp32 summing to 1 (uniform ≈ balanced shards)."""
    idx = np.asarray(indices).reshape(-1)
    shard_of = np.clip(idx // sm.n_local, 0, sm.n_shards - 1)
    counts = np.bincount(shard_of, minlength=sm.n_shards).astype(np.float64)
    total = max(counts.sum(), 1.0)
    return (counts / total).astype(np.float32)
