"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases pass at a tiny size in interpret mode (the same code the chip runs
at published widths)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro.configs import get_arch, smoke_dual_variant  # noqa: E402


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phases_pass_at_smoke_size(tmp_path):
    cfg = smoke_dual_variant(get_arch("basic-s"))
    argv = ["--arch", "basic-s", "--smoke", "--batch", "32", "--num-micro",
            "2", "--seq", "16", "--loss", "chunked", "--log-every", "1",
            "--steps", "2"]
    failed = chip_smoke.run_phases([
        ("kernels", lambda: (
            chip_smoke.check_contrastive(64, 32, interpret=True),
            chip_smoke.check_topk(100, 8, 32, 5, interpret=True))),
        ("train", lambda: chip_smoke.check_train(argv, run_dir=str(tmp_path))),
        ("serve", lambda: chip_smoke.check_serve(cfg, n_classes=20, batch=8,
                                                 requests=2)),
        ("backends", lambda: chip_smoke.report_backends(
            cfg, train_batch=32, seq=16, interpret=True)),
    ])
    assert failed == []


def test_a_failed_phase_is_reported_and_the_rest_still_run():
    ran = []

    def boom():
        raise AssertionError("kernel mismatch")

    failed = chip_smoke.run_phases([("a", boom),
                                    ("b", lambda: ran.append("b"))])
    assert failed == ["a"] and ran == ["b"]
