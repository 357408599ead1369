"""The manifest, the files it names, and how a run refuses to start."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return H.load_json(H.MANIFEST)


def test_every_cell_and_metric_resolves_to_its_file(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(H.ROOT, c["file"]))
        assert H.load_config(c["name"])["name"] == c["name"]
    for w in manifest["workloads"]:
        wl = H.load_workload(w["name"])
        assert wl["config"] == w["config"] and w["config"] in configs
        assert wl["chips"] == w["chips"]
        assert callable(H.load_module("drivers", wl["kind"]).run)
        e2e, layer = H.cell_metrics(manifest, w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer
    for m in manifest["per_layer"]:
        assert callable(H.load_module("metrics", m["name"]).compute)


def test_manifest_keeps_to_its_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in manifest[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in manifest[k]}) == len(manifest[k])
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    pairs = {(w["config"], w["traffic"]) for w in manifest["workloads"]}
    assert len(pairs) == len(manifest["workloads"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    wl = H.load_json(os.path.join(H.BENCH, "workloads",
                                  "basic-s.train.json"))
    wl["name"] = "basic-s.train.unseen"
    (tmp_path / "basic-s.train.unseen.json").write_text(json.dumps(wl))
    got = H.load_workload("basic-s.train.unseen", dirs=[str(tmp_path)])
    assert got["traffic"] == wl["traffic"]
    (tmp_path / "unseen_share.train.py").write_text(
        "def compute(data, trace, peaks):\n    return 42.0\n")
    mod = H.load_module("metrics", "unseen_share.train",
                        dirs=[str(tmp_path)])
    assert mod.compute({}, None, None) == 42.0
    with pytest.raises(H.BenchError):
        H.load_workload("no-such-cell", dirs=[str(tmp_path)])


def test_cell_metrics_follow_the_manifest():
    man = {"end_to_end": [
        {"name": "a_s", "workloads": ["c1"]}, {"name": "setup_s"}],
        "per_layer": [{"name": "x", "moves": "a_s", "workloads": ["c1"]},
                      {"name": "y", "moves": "a_s"},
                      {"name": "z", "moves": "b_s"}]}
    e2e, layer = H.cell_metrics(man, "c1")
    assert [m["name"] for m in e2e] == ["a_s", "setup_s"]
    assert [m["name"] for m in layer] == ["x", "y"]
    e2e, layer = H.cell_metrics(man, "c2")
    assert [m["name"] for m in e2e] == ["setup_s"] and layer == []


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "basic-s.train",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu_and_names_it():
    p = _run(H.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(H.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(H.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "program under test is not in this checkout" in p.stderr
