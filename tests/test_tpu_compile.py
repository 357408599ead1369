"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax; it compiles for a chip that is
described and not attached, so these tests run on a CPU-only host and
catch what interpret mode cannot: block shapes Mosaic refuses, layouts
XLA and Mosaic disagree on, and kernels that outgrow VMEM. Nothing runs.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and every test worker imports this
file). Each compiled program must contain the kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.contrastive_loss import kernel as cl_kernel
from repro.kernels.contrastive_loss import ops as cl_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.similarity_topk import ops as topk_ops


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compilation cache off
    (a compile for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


B, D = 1024, 512
EMB = ((B, D), jnp.bfloat16)
SCALAR = ((), jnp.float32)
VEC = ((B,), jnp.float32)


def test_contrastive_fwd_fused_compiles(one_chip):
    bm, bn = cl_ops.pick_blocks(B, D, 2)
    _compile(lambda x, y, t: cl_kernel.fwd_fused(x, y, t, bm=bm, bn=bn),
             EMB, EMB, SCALAR, sharding=one_chip)


def test_contrastive_bwd_fused_compiles(one_chip):
    bm, bn = cl_ops.pick_blocks(B, D, 2)
    _compile(lambda x, y, t, r, c: cl_kernel.bwd_fused(x, y, t, r, c,
                                                       bm=bm, bn=bn),
             EMB, EMB, SCALAR, VEC, VEC, sharding=one_chip)


def test_contrastive_legacy_kernels_compile(one_chip):
    bm, bn = cl_ops.pick_blocks(B, D, 2)
    _compile(lambda x, y, t: cl_kernel.row_col_lse(x, y, t, bm=bm, bn=bn),
             EMB, EMB, SCALAR, sharding=one_chip)
    _compile(lambda x, y, t, r, c: cl_kernel.grads(x, y, t, r, c,
                                                   bm=bm, bn=bn),
             EMB, EMB, SCALAR, VEC, VEC, sharding=one_chip)


@pytest.mark.parametrize("b,d,want", [(1024, 512, "fused"),
                                      (8192, 1024, "legacy")])
def test_backward_rule_picks_a_sweep_that_compiles(one_chip, b, d, want):
    """ops.backward_sweep: the fused sweep where its resident dY carrier
    fits VMEM, the legacy sweeps where it does not — both compile."""
    assert cl_ops.backward_sweep(b, d, 4) == want
    grad = jax.grad(lambda x, y, t: cl_ops.fused_contrastive_loss(
        x, y, t, False), argnums=(0, 1, 2))
    text = _compile(grad, ((b, d), jnp.float32), ((b, d), jnp.float32),
                    SCALAR, sharding=one_chip)
    # fused: fwd + bwd; legacy: fwd + dX sweep + dY sweep
    assert text.count("custom_call_target=\"tpu_custom_call\"") == \
        (2 if want == "fused" else 3)


def test_similarity_topk_compiles(one_chip):
    _compile(lambda q, c: topk_ops.similarity_topk(q, c, 5, interpret=False),
             ((64, D), jnp.float32), ((1000, D), jnp.float32),
             sharding=one_chip)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_fwd_and_bwd_compile(one_chip, causal):
    q = ((2, 4, 256, 128), jnp.bfloat16)
    _compile(lambda q, k, v: fa_ops.flash_attention(
        q, k, v, causal=causal, interpret=False), q, q, q, sharding=one_chip)

    def loss(q, k, v, m):
        out = fa_ops.flash_attention(q, k, v, causal=causal, key_mask=m,
                                     interpret=False)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q,
             ((2, 256), jnp.bool_), sharding=one_chip)
