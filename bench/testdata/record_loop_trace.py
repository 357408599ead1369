"""Records ``cpu_loop_trace.xplane.pb``: a traced 0.4-second window of the
``tiny.train`` cell on the CPU (the program's own training loop, with its
``repro/`` spans, under the benchmark's ``bench/window`` marker), as
``bench/run.py --trace 1`` captures it on the chip.

    JAX_PLATFORMS=cpu python bench/testdata/record_loop_trace.py
"""
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import run as R  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402

MANIFEST = {"end_to_end": [{"name": "pairs_per_s", "unit": "pairs/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def main():
    args = R.parse(["--workload", "tiny.train", "--seed", "2147483659",
                    "--seconds", "0.4", "--trace", "1"])
    R.run(args, require_tpu=False, workload_dirs=[HERE], config_dirs=[HERE],
          manifest=MANIFEST)
    src = TR.find_xplane(os.path.join(R.H.RUNS_DIR, "tiny.train", "trace"))
    shutil.copy(src, os.path.join(HERE, "cpu_loop_trace.xplane.pb"))


if __name__ == "__main__":
    main()
