"""Cross-shard global-batch loss == single-device fused loss (DESIGN.md §7).

The real multi-shard assertions live in tests/distributed_checks.py and run
in a SUBPROCESS with 8 simulated host devices (jax pins the device count at
first init; the tier-1 process must keep seeing the single real CPU device,
tests/conftest.py). Here we spawn them and additionally cover the pieces
that don't need a multi-device mesh in-process.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

_CHECKS = os.path.join(os.path.dirname(__file__), "distributed_checks.py")


def _run_checks(mode):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, _CHECKS, mode],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, (
        f"distributed_checks.py {mode} failed\n--- stdout ---\n"
        f"{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}")
    assert f"PASS {mode}" in proc.stdout


def test_distributed_loss_matches_single_device():
    """Acceptance: mesh with data-axis size >= 2 (up to 8), allgather AND
    chunked paths, loss + dX/dY/dtau within fp32 tolerance of the
    single-device fused loss at the same global batch."""
    _run_checks("loss")


def test_gradaccum_composes_with_distributed_loss():
    """Algorithm-1 GradAccum x data-parallel x tensor-parallel under one
    jit: weight grads match the single-device step."""
    _run_checks("gradaccum")


def test_make_global_loss_fn_single_extent_falls_back():
    """On a 1-device data extent the factory returns the plain fused loss
    (no shard_map) — values and grads still match the reference."""
    from repro.core import distributed_loss as dl
    from repro.core.contrastive import fused_kernel_loss

    mesh = make_mesh((1,), ("data",))
    kx, ky = jax.random.split(jax.random.key(3))
    x = jax.random.normal(kx, (32, 16))
    x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    y = jax.random.normal(ky, (32, 16))
    y = y / jnp.linalg.norm(y, axis=-1, keepdims=True)
    tau = jnp.asarray(0.5)

    loss_fn = dl.make_global_loss_fn(mesh, "chunked")
    got = jax.jit(lambda x, y, t: loss_fn(x, y, t)[0])(x, y, tau)
    want = fused_kernel_loss(x, y, tau, interpret=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_make_global_loss_fn_rejects_unknown_method():
    mesh = make_mesh((1,), ("data",))
    from repro.core import distributed_loss as dl
    with pytest.raises(ValueError, match="method"):
        dl.make_global_loss_fn(mesh, "ring")


def test_chunk_grads_nodiag_matches_manual():
    """ops.chunk_grads with with_diag=False + b_norm reproduces the manual
    no-diagonal softmax-gradient formula for a remote chunk."""
    from repro.kernels.contrastive_loss import ops

    b_l, d, b_g = 16, 8, 64
    kx, ky = jax.random.split(jax.random.key(11))
    x = jax.random.normal(kx, (b_l, d), jnp.float32)
    y = jax.random.normal(ky, (b_l, d), jnp.float32)
    inv_tau = jnp.asarray(2.0)
    a = (x @ y.T) * inv_tau
    # arbitrary (global-looking) LSE vectors: the kernel only consumes them
    row_lse = jax.nn.logsumexp(a, axis=1) + 0.3
    col_lse = jax.nn.logsumexp(a, axis=0) + 0.1

    da = (jnp.exp(a - row_lse[:, None]) + jnp.exp(a - col_lse[None, :])) \
        / (2.0 * b_g)
    want_dx, want_dy = da @ y * inv_tau, da.T @ x * inv_tau
    want_dtau = -jnp.sum(da * a)

    dx, dy, dtau = ops.chunk_grads(x, y, inv_tau, row_lse, col_lse,
                                   b_norm=b_g, with_diag=False,
                                   interpret=True)
    np.testing.assert_allclose(dx, want_dx, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dy, want_dy, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dtau, want_dtau, rtol=1e-5, atol=1e-6)
