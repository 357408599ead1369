"""Records ``cpu_trace.xplane.pb``: three calls of a small jitted
function on the CPU, each after a 20 ms host sleep inside a
``bench/gap`` span, the whole inside a ``bench/window`` marker.

    JAX_PLATFORMS=cpu python bench/testdata/record_trace.py
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    a = jnp.ones((256, 256))
    f(a, a).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench/window"):
        pass
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/gap"):
            time.sleep(0.02)
        f(a, a).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "cpu_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
