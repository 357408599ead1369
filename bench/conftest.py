import os
import sys

# the benchmark's tests run on the CPU; the program is imported from src/
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


import pytest  # noqa: E402

_CACHE_KEYS = ("jax_compilation_cache_dir",)


@pytest.fixture(autouse=True)
def _keep_jax_cache_settings(monkeypatch, tmp_path):
    """A run turns JAX's persistent compile cache on for its process; in
    a test it goes to a temporary directory and is put back after."""
    import jax
    before = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    yield
    for k, v in before.items():
        jax.config.update(k, v)
